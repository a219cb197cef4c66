"""What the program itself wrote into a profiler trace: its ``dpx:`` spans
(``obs/trace.py``: every span is also a ``jax.profiler.TraceAnnotation``)
and, for every device operation, the JAX name stack it was traced under
(``jit(local_step)/transpose(jvp(loss))/blocks/attn/qkv/dot_general``,
with the program's own ``jax.named_scope``s in it).

``jax.profiler.ProfileData`` does not show the name stack at all (it is
the stat ``tf_op`` of an event's *metadata*, not of the event), and costs
a Python string of the whole HLO text an event. So this module reads the
``.xplane.pb`` itself: a protobuf wire-format reader of the messages it
needs and no more (tensorflow/tsl ``xplane.proto``; field numbers beside
each use), with nothing but the standard library. It is the one reader
of a run's trace: the file is read once, only the planes and lines that
some reader uses are decoded, and ``trace_reduce.load`` builds its
``Trace`` from the same parse (``parsed``). Times are nanoseconds on the
one clock all planes share (a line's ``timestamp_ns`` plus the event's
``offset_ps``).

The readers under ``layer_metrics/`` get the run's ``trace_reduce.Trace``
and ask here for the rest: ``program_trace.of(cell)`` finds the trace
under ``cell.out_dir/trace`` and returns None where there is none."""

import functools
import os

from chipbench import trace_reduce

SPAN_PREFIX = "dpx:"


# -- protobuf wire format ---------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of one message: a varint's value, or the
    (start, end) of a length-delimited field's bytes. Fixed-width fields
    give their (start, end) too."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield key >> 3, val
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            yield key >> 3, (i, i + 8)
            i += 8
        elif wire == 5:
            yield key >> 3, (i, i + 4)
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(buf, span):
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf, span):
    """A ``map<int64, Message>`` entry: key (1), value (2)."""
    key = val = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _stat(buf, span, stat_names):
    """XStat -> (name, value): metadata_id (1), double (2, not read),
    uint64 (3), int64 (4), str (5), bytes (6), ref (7: the value is the
    NAME of another stat metadata)."""
    name = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v)
        elif f in (3, 4):
            value = v
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v)
    return name, value


def _events(buf, i, end, names=None):
    """One XLine: name (2), timestamp_ns (3), events (4) -> (name,
    timestamp, [(metadata id, offset_ps, duration_ps, where the event's
    stats start, where it ends)]). XEvent: metadata_id (1), offset_ps
    (2), duration_ps (3), stats (4). This loop sees every event of a
    trace, so its varints are read in place; it leans on what the
    writer (C++ protobuf) guarantees, fields in the order of their
    numbers: a line's name before its events (a line whose name is not
    in ``names`` is left unread), an event's stats after its three
    numbers. A field that breaks that order raises."""
    name, t0, out = "", 0, []
    add = out.append
    while i < end:
        key = buf[i]
        i += 1
        if key == 0x22:                             # events (4), bytes
            n = buf[i]
            i += 1
            if n >= 0x80:
                n &= 0x7F
                shift = 7
                while True:
                    c = buf[i]
                    i += 1
                    n |= (c & 0x7F) << shift
                    if c < 0x80:
                        break
                    shift += 7
            ev_end = i + n
            meta = off = dur = 0
            while i < ev_end:
                k = buf[i]
                if k == 0x22:                       # stats (4): the rest
                    break
                i += 1
                v = buf[i]
                i += 1
                if v >= 0x80:
                    v &= 0x7F
                    shift = 7
                    while True:
                        c = buf[i]
                        i += 1
                        v |= (c & 0x7F) << shift
                        if c < 0x80:
                            break
                        shift += 7
                if k == 0x08:
                    meta = v
                elif k == 0x10:
                    off = v
                elif k == 0x18:
                    dur = v
                elif k & 7:                         # no varint: no XEvent
                    raise ValueError(f"XEvent field key {k} at byte {i}")
            add((meta, off, dur, i, ev_end))
            i = ev_end
            continue
        if key >= 0x80:
            key, i = _varint(buf, i - 1)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            if key >> 3 == 3:
                t0 = val
        elif wire == 2:
            n, i = _varint(buf, i)
            if key >> 3 == 2:
                if out:
                    raise ValueError("an XLine's name after its events")
                name = _text(buf, (i, i + n))
                if names is not None and name not in names:
                    return name, t0, out
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
    return name, t0, out


class _Plane:
    """XPlane: name (2), lines (3), event_metadata (4), stat_metadata (5).
    XEventMetadata: id (1), name (2), stats (5). XStatMetadata: id (1),
    name (2). The metadata is decoded on first use: most planes of a
    trace are ones no reader uses."""

    def __init__(self, buf, span):
        self.buf, self.name, self.lines = buf, "", []
        self._metas, self._stat_metas = [], []
        for f, v in _fields(buf, *span):
            if f == 2:
                self.name = _text(buf, v)
            elif f == 3:
                self.lines.append(v)
            elif f == 4:
                self._metas.append(v)
            elif f == 5:
                self._stat_metas.append(v)

    @functools.cached_property
    def stat_names(self):
        out = {}
        for entry in self._stat_metas:
            key, val = _map_entry(self.buf, entry)
            for f, v in _fields(self.buf, *val):
                if f == 2:
                    out[key] = _text(self.buf, v)
        return out

    @functools.cached_property
    def _names_and_stats(self):
        names, stats = {}, {}
        for entry in self._metas:
            key, val = _map_entry(self.buf, entry)
            mine = stats[key] = []
            for f, v in _fields(self.buf, *val):
                if f == 2:
                    names[key] = _text(self.buf, v)
                elif f == 5:
                    mine.append(v)
        return names, stats

    @property
    def event_names(self):
        return self._names_and_stats[0]

    def meta_stat(self, meta_id, name):
        for span in self._names_and_stats[1].get(meta_id, ()):
            n, v = _stat(self.buf, span, self.stat_names)
            if n == name:
                return v
        return None

    def event_stats(self, start, end):
        """{name: value} of one event's stats (the bytes ``_events``
        left unread)."""
        return dict(_stat(self.buf, v, self.stat_names)
                    for f, v in _fields(self.buf, start, end) if f == 4)


# -- what the readers ask for -----------------------------------------------

class ProgramTrace:
    """``spans``: every host event named ``dpx:<name>`` as (name without
    the prefix, line, start ns, end ns, attrs), sorted by start; ``line``
    numbers the host thread it ran on (both Python threads' lines are
    called ``python`` in a v5e trace, so a thread is told by what it
    holds). ``ops[chip]``: (short name, start ns, end ns, name stack) of
    every ``XLA Ops`` event, containers included, in the trace's order.
    ``modules[chip]``: (name, start ns, end ns) of every executed
    program. ``stats[chip]``: beside ``ops[chip]``, each event's
    ``{"hlo": the instruction's text}`` as ``trace_reduce.Trace`` hands
    it to a matcher (one dict an instruction, shared by its executions).
    ``host``: (name, start ns, end ns) of the benchmark's own
    annotations (``trace_reduce.HOST_SPANS``)."""

    def __init__(self, spans, ops, modules, stats, host):
        self.spans = sorted(spans, key=lambda s: (s[2], -s[3]))
        self.ops, self.modules = ops, modules
        self.stats, self.host = stats, host
        self.memo = {}      # a reduction's result, shared by its readers
        self._leaf = {}

    def spans_named(self, name):
        return [s for s in self.spans if s[0] == name]

    def thread_of(self, name):
        """The line of the thread that holds spans ``name``, or None."""
        for s in self.spans:
            if s[0] == name:
                return s[1]
        return None

    def leaf_ops(self, chip):
        if chip not in self._leaf:
            self._leaf[chip] = [o for o in self.ops.get(chip, ())
                                if not trace_reduce.is_container(o[0])]
        return self._leaf[chip]


def parse(path):
    with open(path, "rb") as f:
        buf = f.read()
    spans, host, ops, modules, stats = [], [], {}, {}, {}
    device_lines = (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)
    n_line = 0
    for f, v in _fields(buf, 0, len(buf)):
        if f != 1:                          # XSpace: planes (1)
            continue
        plane = _Plane(buf, v)
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            names = plane.event_names
            seen = {}           # metadata id -> (short name, stack, stats)
            for span in plane.lines:
                name, t0, events = _events(buf, *span, names=device_lines)
                if name == trace_reduce.OPS_LINE:
                    out = ops.setdefault(chip, [])
                    beside = stats.setdefault(chip, [])
                    for meta, off, dur, _, _ in events:
                        if meta not in seen:
                            text = names.get(meta, "")
                            seen[meta] = (
                                trace_reduce.short_name(text),
                                plane.meta_stat(meta, "tf_op") or "",
                                {"hlo": text})
                        short, stack, st = seen[meta]
                        start = t0 + off // 1000
                        out.append((short, start, start + dur // 1000,
                                    stack))
                        beside.append(st)
                elif name == trace_reduce.MODULES_LINE:
                    out = modules.setdefault(chip, [])
                    for meta, off, dur, _, _ in events:
                        start = t0 + off // 1000
                        out.append((names.get(meta, ""), start,
                                    start + dur // 1000))
        elif plane.name.startswith("/host:"):
            ours = {k: n[len(SPAN_PREFIX):]
                    for k, n in plane.event_names.items()
                    if n.startswith(SPAN_PREFIX)}
            bench = {k: n for k, n in plane.event_names.items()
                     if n in trace_reduce.HOST_SPANS}
            if not ours and not bench:
                continue
            for span in plane.lines:
                n_line += 1
                _, t0, events = _events(buf, *span)
                for meta, off, dur, at, end in events:
                    if meta in ours:
                        start = t0 + off // 1000
                        spans.append((ours[meta], n_line, start,
                                      start + dur // 1000,
                                      plane.event_stats(at, end)))
                    elif meta in bench:
                        start = t0 + off // 1000
                        host.append((bench[meta], start,
                                     start + dur // 1000))
    return ProgramTrace(spans, ops, modules, stats, host)


_parsed = {}


def parsed(path):
    """The one parse of a run's trace, whoever asks first (``run.py``
    through ``trace_reduce.load``, then every reader through ``of``)."""
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if key not in _parsed:
        _parsed.clear()
        _parsed[key] = parse(path)
    return _parsed[key]


def of(cell):
    """The program's view of this run's trace, or None where the run
    wrote none."""
    root = os.path.join(cell.out_dir, "trace")
    path = None
    for base, _, files in os.walk(root):
        for f in files:
            if f.endswith(".xplane.pb"):
                path = os.path.join(base, f)
    return None if path is None else parsed(path)


# -- reductions the readers share ---------------------------------------------

#: every ``jax.named_scope`` the program puts into its name stacks
#: (PERF.md section 3 says where each is)
SCOPES = frozenset((
    "embed", "blocks", "attn", "qkv", "core", "out", "mlp", "norm", "ln_f",
    "head", "loss", "cast", "optimizer", "decode_attention", "page_gather",
    "page_write", "sample"))
#: those of them that are the model's own layers
MODEL_SCOPES = frozenset(("embed", "blocks", "ln_f", "head"))


@functools.lru_cache(maxsize=None)
def scopes(stack):
    """The names in a name stack, transforms taken off:
    ``jit(local_step)/transpose(jvp(loss))/blocks/attn/qkv/dot_general:``
    -> {local_step, loss, blocks, attn, qkv, dot_general}."""
    return frozenset(part[part.rfind("(") + 1:].rstrip(")")
                     for part in stack.rstrip(":").split("/"))


def _once(reduction):
    """A reduction of this run's trace, computed once however many
    readers ask (each per-layer metric is a reader of its own)."""
    @functools.wraps(reduction)
    def cached(*args):
        pt = of(args[-1])
        if pt is None:
            return None
        if reduction.__name__ not in pt.memo:
            pt.memo[reduction.__name__] = reduction(*args)
        return pt.memo[reduction.__name__]
    return cached


def span_mean_ms(cell, name):
    """Mean duration of the program's spans ``name`` in the traced part,
    in milliseconds; None where the trace holds none, or no device plane
    (a CPU run's host times stand under no metric's name)."""
    pt = of(cell)
    got = pt.spans_named(name) if pt and pt.ops else []
    if not got:
        return None
    return sum(e - s for _, _, s, e, _ in got) / len(got) / 1e6


@_once
def engine_idle_parts(trace, cell):
    """The device's idle time (the same ``busy()`` union over the same
    window as ``Trace.idle_share``) split by what the engine's thread
    was doing, in percent of the window: ``row_loop`` (inside a
    ``serve.decode.rows`` span), ``admit`` (inside a ``serve.admit``
    span), ``other_span`` (inside any other span of that thread) and
    ``unattributed`` (no span of that thread covers it). The four sum
    to the idle share. None where the trace has no engine spans."""
    pt = of(cell)
    line = pt.thread_of("serve.iter") if pt else None
    if line is None or not trace.chips:
        return None
    mine = [s for s in pt.spans if s[1] == line]
    cover = lambda name: trace_reduce.union(
        (s, e) for n, _, s, e, _ in mine if name is None or n == name)
    rows, admit, every = cover("serve.decode.rows"), cover("serve.admit"), \
        cover(None)
    other = trace_reduce.subtract(trace_reduce.subtract(every, rows), admit)
    w0, w1 = trace.window
    parts = dict.fromkeys(("row_loop", "admit", "other_span",
                           "unattributed"), 0.0)
    for chip in trace.chips:
        idle = trace_reduce.subtract([[w0, w1]], trace.busy(chip))
        total = trace_reduce.length(idle)
        inside = lambda cov: total - trace_reduce.length(
            trace_reduce.subtract(idle, cov))
        parts["row_loop"] += inside(rows)
        parts["admit"] += inside(admit)
        parts["other_span"] += inside(other)
        parts["unattributed"] += total - inside(every)
    scale = 100.0 / (len(trace.chips) * (w1 - w0))
    return {k: v * scale for k, v in parts.items()}


def self_time_share(cell, name):
    """Share of the spans ``name`` that none of their thread's other
    spans covers (their own time), over the traced part; None without
    such spans."""
    pt = of(cell)
    got = pt.spans_named(name) if pt else []
    if not got:
        return None
    line = got[0][1]
    outer = trace_reduce.union((s, e) for _, _, s, e, _ in got)
    inner = trace_reduce.union(
        (s, e) for n, l, s, e, _ in pt.spans
        if l == line and n != name and e > s)
    own = trace_reduce.length(trace_reduce.subtract(outer, inner))
    return own / trace_reduce.length(outer)


def ops_of_program(cell, is_program):
    """(stack, nanoseconds) of every leaf op that ran inside an
    execution of the programs ``is_program(name)`` accepts, and the
    number of those executions, first chip; (None, 0) without a trace,
    a device plane or such a program."""
    import bisect

    pt = of(cell)
    if pt is None or not pt.ops:
        return None, 0
    chip = min(pt.ops)
    runs = sorted((s, e) for n, s, e in pt.modules.get(chip, ())
                  if is_program(n))
    if not runs:
        return None, 0
    starts = [s for s, _ in runs]
    out = []
    for _, s, e, stack in pt.leaf_ops(chip):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            out.append((stack, e - s))
    return out, len(runs)


def step_program(cell):
    """The training step's ops and executions: the program that took
    most of the device's time in the traced part."""
    pt = of(cell)
    if pt is None or not pt.modules:
        return None, 0
    total = {}
    for n, s, e in pt.modules[min(pt.modules)]:
        key = n.split("(")[0]
        total[key] = total.get(key, 0) + e - s
    name = max(total, key=total.get)
    return ops_of_program(cell, lambda n: n.split("(")[0] == name)


def step_class(stack):
    """Which part of a training step an op belongs to, first match wins:
    JAX's own words for recompute, backward and forward, then the
    program's scopes for the update."""
    if "rematted_computation" in stack:
        return "remat"
    if "transpose(jvp" in stack:
        return "bwd"
    if "jvp(" in stack:
        return "fwd"
    if scopes(stack) & {"optimizer", "cast"}:
        return "optimizer"
    return "unscoped"


@_once
def step_split_ms(cell):
    """Device milliseconds a step by :func:`step_class`, plus
    ``head_loss`` (an overlay on forward, backward and recompute: the
    vocabulary projection, and what the loss closure does outside the
    model); None where no op of the step carries a scope of ours."""
    ops, steps = step_program(cell)
    if not ops:
        return None
    out = dict.fromkeys(("remat", "bwd", "fwd", "optimizer", "unscoped",
                         "head_loss"), 0.0)
    ours = False
    for stack, ns in ops:
        kind, names = step_class(stack), scopes(stack)
        ours |= bool(names & SCOPES)
        out[kind] += ns
        if kind in ("remat", "bwd", "fwd") and (
                "head" in names
                or ("loss" in names and not names & MODEL_SCOPES)):
            out["head_loss"] += ns
    if not ours:
        return None
    return {k: v / steps / 1e6 for k, v in out.items()}


def is_decode_program(name):
    return "decode" in name.lower()


@_once
def decode_split_ms(cell):
    """Device milliseconds a decode program: ``total``, ``attention``
    (ops under ``decode_attention``), ``page_gather`` (inside it),
    ``unscoped`` (ops under no scope of ours: what the compiler added
    on its own, such as copies of a donated argument); None where no op
    of the program carries a scope of ours."""
    ops, runs = ops_of_program(cell, is_decode_program)
    if not ops:
        return None
    out = dict.fromkeys(("total", "attention", "page_gather", "unscoped"),
                        0.0)
    for stack, ns in ops:
        names = scopes(stack)
        out["total"] += ns
        if "decode_attention" in names:
            out["attention"] += ns
        if "page_gather" in names:
            out["page_gather"] += ns
        if not names & SCOPES:
            out["unscoped"] += ns
    if out["unscoped"] == out["total"]:
        return None
    return {k: v / runs / 1e6 for k, v in out.items()}


def compile_marks(cell):
    """Number of ``dpx:xla.compile`` marks (``runtime/compile_cache.py``
    drops one after every program XLA built) in the traced part; None
    where the program wrote no spans at all, or on no device plane."""
    pt = of(cell)
    if pt is None or not pt.spans or not pt.ops:
        return None
    return len(pt.spans_named("xla.compile"))
