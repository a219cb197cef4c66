"""Why the chip waits between two decode programs: the device's idle
time between the end of one pass's decode (or block-step) program and the
start of the next pass's, cut at the boundaries of the engine thread's
spans into seven parts that sum to it, named by WHAT THE CHIP WAS WAITING
FOR and not by the span the thread happened to be in
(``program_trace.engine_idle_parts`` does the latter, and its split moves
by six points of the window between two runs of one program: PERF.md
section 7).

A pass ``i`` is one ``serve.decode.dispatch`` span of the engine's thread
and the program ``P_i`` it launched: the first decode program
(``program_trace.is_decode_program``) on the first chip's ``XLA Modules``
line whose middle lies after the span's start. Its gap is the chip's idle time
(the ``busy()`` union ``device_idle_share.serve`` is made of) between the
end of ``P_(i-1)`` and the start of ``P_i``; what the chip ran in between
(a chunk's prefill, an upload's own program, a sampler) is busy and in no
part. The cuts, in order, each clamped into the gap:

=============  =====================================  ======================
part           from                                   to
=============  =====================================  ======================
``wake``       end of ``P_(i-1)``                     end of that pass's
                                                      ``serve.decode.fetch``
``emit``       end of that ``serve.decode.fetch``     end of that pass's
                                                      ``serve.decode.rows``
``turnaround`` end of ``serve.decode.rows``           start of pass ``i``'s
                                                      dispatch span, less
                                                      ``admit``
``admit``      the idle time of the interval above that lies inside a
               ``serve.admit`` span
``upload``     start of ``serve.decode.dispatch``     end of the
                                                      ``serve.decode.upload``
                                                      inside it
``dispatch``   end of ``serve.decode.upload``         end of the dispatch
                                                      span, or the start of
                                                      ``P_i`` if that comes
                                                      first
``launch``     end of ``serve.decode.dispatch``       start of ``P_i``
=============  =====================================  ======================

In milliseconds an iteration: the mean over the traced part's passes that
have a pass before them. Beside the sum, ``under_submit`` (the part of
the gap during which a ``serve.submit`` span was open on another thread:
an overlay, not an eighth part) and ``fetch_wait`` (the mean duration of
``serve.decode.fetch``: near the program's own time the host is early and
the chip sets the pace, near 0 the chip is early and the host does).

**The two planes' clocks.** The profiler lays the device's plane against
the host's to within a millisecond, not better, and differently in every
run: in one traced run every decode program "starts" 0.9 ms BEFORE the
host's own call that launches it (the runtime's ``tpu::System::Execute``
event, on the host plane between the end of ``serve.decode.upload`` and the
end of ``serve.decode.dispatch``), in the next 0.1 ms before it. That, and
nothing in the engine, is why ``device_idle_in_row_loop.serve`` and
``_in_other_span`` trade six points of the window between two runs of one
program while their sum stands. So the device's times are first moved later
by ``shift``: the least shift that lets no pass's program start before that
call does. What is then left in ``wake`` is the most it can be, and
``dispatch + launch`` the least, to within the call's own 0.1-0.2 ms; the
gap and the other parts do not depend on the shift. Where the host plane
holds no such event the shift is 0 and ``parts`` says so (``shift`` None).

:data:`METRICS` names the ten numbers as ``BENCHMARK.json`` would;
:func:`read` is what a reader under ``layer_metrics/`` would return.
They are no metrics of the manifest yet (PERF.md section 7 says which
files of the benchmark a ``benchmark`` issue has to edit for that);
``engine_gap_dump.py`` prints them after a traced run."""

import bisect
import os

from chipbench import program_trace, trace_reduce

PARTS = ("wake", "emit", "turnaround", "admit", "upload", "dispatch",
         "launch")
#: metric name -> key of :func:`parts`
METRICS = {"decode_gap_ms": "gap",
           **{f"decode_gap_{p}_ms": p for p in PARTS},
           "decode_gap_under_submit_ms": "under_submit",
           "engine_fetch_wait_ms": "fetch_wait"}
#: what ``turnaround`` is made of: these spans of the engine's thread,
#: and the loop's own remainder
TURNAROUND = ("serve.sweep", "serve.decode.capacity", "serve.snapshot")
#: the runtime's own host event around the launch of a program
LAUNCH = "tpu::System::Execute"


def _inside(idle, cover):
    """Nanoseconds of ``idle`` (disjoint, sorted) that ``cover`` (the
    same) covers."""
    return trace_reduce.length(idle) - trace_reduce.length(
        trace_reduce.subtract(idle, cover))


def _clip(idle, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in idle
            if min(e, hi) > max(s, lo)]


def _first_from(spans, starts, at, before):
    """The first of ``spans`` (sorted by start) that starts in
    [``at``, ``before``), or None."""
    i = bisect.bisect_left(starts, at)
    return spans[i] if i < len(spans) and starts[i] < before else None


def passes_of(pt, chip):
    """The traced part's passes in order, each a dict of (start, end)
    pairs: ``dispatch``, ``upload``, ``fetch``, ``rows`` (the engine
    thread's spans of that pass; None where the trace was cut before
    one) and ``program``. None where the trace has no engine thread, or
    no ``serve.decode.upload`` or ``serve.decode.fetch`` at all (a
    program from before they were there)."""
    line = pt.thread_of("serve.iter")
    if line is None:
        return None
    by_name = {}
    for n, l, s, e, _ in pt.spans:      # sorted by start
        if l == line:
            by_name.setdefault(n, []).append((s, e))
    if not by_name.get("serve.decode.upload") \
            or not by_name.get("serve.decode.fetch"):
        return None
    programs = sorted((s, e) for n, s, e in pt.modules.get(chip, ())
                      if program_trace.is_decode_program(n))
    starts = {n: [s for s, _ in v] for n, v in by_name.items()}
    # a pass's program is told by its middle: the profiler may lay the
    # device's plane a millisecond early or late (the module's docstring)
    middles = [(s + e) // 2 for s, e in programs]
    dispatches = by_name.get("serve.decode.dispatch", [])
    out = []
    for d, nxt in zip(dispatches, dispatches[1:] + [(float("inf"),) * 2]):
        find = lambda name, at: _first_from(
            by_name.get(name, ()), starts.get(name, ()), at, nxt[0])
        upload = find("serve.decode.upload", d[0])
        out.append({
            "dispatch": d,
            "upload": upload if upload and upload[1] <= d[1] else None,
            "fetch": find("serve.decode.fetch", d[1]),
            "rows": find("serve.decode.rows", d[1]),
            "program": _first_from(programs, middles, d[0], nxt[0])})
    return out


def _launches(path):
    """Start times (ns) of the :data:`LAUNCH` events on the host planes of
    one ``.xplane.pb``."""
    with open(path, "rb") as f:
        buf = f.read()
    out = []
    for field, span in program_trace._fields(buf, 0, len(buf)):
        if field != 1:                      # XSpace: planes (1)
            continue
        plane = program_trace._Plane(buf, span)
        if not plane.name.startswith("/host:"):
            continue
        ids = {k for k, n in plane.event_names.items() if n == LAUNCH}
        for line in plane.lines if ids else ():
            _, t0, events = program_trace._events(buf, *line)
            out += [t0 + off // 1000 for meta, off, _, _, _ in events
                    if meta in ids]
    return out


def launches_of(cell):
    """The runtime's launches in this run's trace, sorted: the one thing
    read here that ``program_trace.parsed`` does not keep (it keeps the
    ``dpx:`` spans and the benchmark's own annotations). [] where the
    run left no trace or the trace holds none."""
    root = getattr(cell, "out_dir", None)
    if root is None:
        return []
    return sorted(t for base, _, files in os.walk(os.path.join(root, "trace"))
                  for f in files if f.endswith(".xplane.pb")
                  for t in _launches(os.path.join(base, f)))


def gap_ns(pt, busy, chip=0, launches=()):
    """The parts of the gap, in nanoseconds summed over the passes
    counted, from a ``ProgramTrace``, the chip's busy union and the
    host's :data:`LAUNCH` times: ``gap``, the seven :data:`PARTS`,
    ``under_submit``, ``passes`` (how many gaps were counted),
    ``turnaround_in`` ({span or ``"loop"``: ns}), ``fetch_wait`` (mean,
    ns), ``shift`` (what the device's times were moved later by; None
    without a launch to go by) and ``shift_most`` (the most they could
    be: no fetch returns before its program ends). None where
    :func:`passes_of` is, or where no pass of the trace has a whole pass
    before it."""
    passes = passes_of(pt, chip)
    if not passes:
        return None
    # the least shift that puts every program's start at or after the
    # host's call that launched it: the first launch after the upload
    early = []
    for p in passes:
        if p["upload"] and p["program"]:
            i = bisect.bisect_left(launches, p["upload"][1])
            if i < len(launches) and launches[i] <= p["dispatch"][1]:
                early.append(launches[i] - p["program"][0])
    shift = max([0] + early)
    ends = [e for _, e in busy]
    line = pt.thread_of("serve.iter")
    cover = lambda name, mine: trace_reduce.union(
        (s, e) for n, l, s, e, _ in pt.spans
        if n == name and (l == line) is mine)
    admit, submit = cover("serve.admit", True), cover("serve.submit", False)
    between = {n: cover(n, True) for n in TURNAROUND}
    out = dict.fromkeys(("gap", "under_submit") + PARTS, 0)
    out["turnaround_in"] = dict.fromkeys(TURNAROUND + ("loop",), 0)
    out["passes"], most = 0, []
    for prev, cur in zip(passes, passes[1:]):
        if None in prev.values() or cur["program"] is None \
                or cur["upload"] is None:
            continue            # the trace starts or ends inside the pass
        # the gap on the device's clock, its idle time on the host's
        lo, hi = prev["program"][1], cur["program"][0]
        i, j = bisect.bisect_right(ends, lo), bisect.bisect_left(ends, hi)
        idle = [[s + shift, e + shift] for s, e in trace_reduce.subtract(
            [[lo, hi]], busy[i:j + 1])] if hi > lo else []
        lo, hi = lo + shift, hi + shift
        most.append(shift + prev["fetch"][1] - lo)
        # the engine thread's boundaries, in order, inside the gap
        cuts = [lo]
        for at in (prev["fetch"][1], prev["rows"][1], cur["dispatch"][0],
                   cur["upload"][1], cur["dispatch"][1], hi):
            cuts.append(max(cuts[-1], min(at, hi)))
        piece = dict(zip(("wake", "emit", "turn", "upload", "dispatch",
                          "launch"),
                         (_clip(idle, a, b) for a, b in zip(cuts, cuts[1:]))))
        turn = piece.pop("turn")
        for name, at in piece.items():
            out[name] += trace_reduce.length(at)
        in_admit = _inside(turn, admit)
        out["admit"] += in_admit
        out["turnaround"] += trace_reduce.length(turn) - in_admit
        rest = trace_reduce.subtract(turn, admit)
        for name in TURNAROUND:
            out["turnaround_in"][name] += _inside(rest, between[name])
        out["gap"] += trace_reduce.length(idle)
        out["under_submit"] += _inside(idle, submit)
        out["passes"] += 1
    if not out["passes"]:
        return None
    out["turnaround_in"]["loop"] = out["turnaround"] - sum(
        out["turnaround_in"][n] for n in TURNAROUND)
    fetch = pt.spans_named("serve.decode.fetch")
    out["fetch_wait"] = sum(e - s for _, _, s, e, _ in fetch) / len(fetch)
    out["shift"], out["shift_most"] = (shift if early else None), min(most)
    return out


def parts(trace, cell):
    """:func:`gap_ns` of this run's trace in milliseconds an iteration
    (``passes`` stays a count, the shifts are milliseconds), made once a
    run however many readers ask; None where there is nothing to read,
    or no device plane (a CPU run's host times stand under no metric's
    name)."""
    pt = program_trace.of(cell)
    if pt is None or not pt.ops or not trace.chips:
        return None
    if "engine_gap" not in pt.memo:
        chip = trace.chips[0]
        got = gap_ns(pt, trace.busy(chip), chip, launches_of(cell))
        if got is not None:
            n = got["passes"]
            ms = lambda ns: ns / n / 1e6
            got = {**{k: ms(got[k]) for k in ("gap", "under_submit") + PARTS},
                   "turnaround_in": {k: ms(v) for k, v
                                     in got["turnaround_in"].items()},
                   "fetch_wait": got["fetch_wait"] / 1e6, "passes": n,
                   "shift": None if got["shift"] is None
                   else got["shift"] / 1e6,
                   "shift_most": got["shift_most"] / 1e6}
        pt.memo["engine_gap"] = got
    return pt.memo["engine_gap"]


def read(trace, cell, metric):
    """The value of one of :data:`METRICS`, or None."""
    got = parts(trace, cell)
    return None if got is None else got[METRICS[metric]]
