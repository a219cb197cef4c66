"""From a profiler trace (``.xplane.pb``) to numbers. The one place that
knows how the TPU's trace is laid out, so that every PR computes the same
number in the same way and a reviewer can read how.

Layout, as read by hand from a v5e trace (PERF.md, Findings, PR 23): one
plane per chip named ``/device:TPU:<n>``; on it the line ``XLA Ops`` holds
one event per executed HLO operation, named by the instruction's whole
text (``%fusion.12 = bf16[...] fusion(...), kind=...``; the short name is
what stands between ``%`` and `` =``; a Pallas/Mosaic kernel is a
``custom-call`` whose text holds ``custom_call_target="tpu_custom_call"``
and whose short name is the JAX scope it was traced in), the line
``XLA Modules`` one event per executed program (``jit_<fn>(<id>)``) and
``Async XLA Ops`` the copies in flight (not counted as busy: they overlap
the operations). ``jax.profiler.TraceAnnotation``s land on the host plane
``/host:CPU``, on the line ``python``. All planes share one clock.

The file itself is read by ``program_trace.parsed``, once a run; what is
reduced from it more than once (the operations that are no containers,
the busy union) is kept on the ``Trace``."""

import functools
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?[.\d]*$")
# parents that span their children: their time is their children's
CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")
is_container = functools.lru_cache(maxsize=None)(
    lambda name: CONTAINER.match(name) is not None)
#: the benchmark's own host annotations (kinds/*.py)
HOST_SPANS = ("make_batch", "step_call", "submit", "drain", "wait_for_due",
              "window")


def union(intervals):
    """Merge (start, end) pairs; returns the merged, sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(merged):
    return sum(e - s for s, e in merged)


def subtract(merged, holes):
    """The parts of ``merged`` (disjoint, sorted) not covered by ``holes``
    (disjoint, sorted)."""
    out, j = [], 0
    for s, e in merged:
        cur = s
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < e:
            if holes[k][0] > cur:
                out.append([cur, holes[k][0]])
            cur = max(cur, holes[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


class Trace:
    """``ops[chip]`` and ``modules[chip]``: lists of (name, start_ns,
    end_ns, stats). ``host``: list of (name, start_ns, end_ns) of the
    benchmark's annotations. ``window``: (start_ns, end_ns)."""

    def __init__(self, ops, modules, host, n_chips):
        self.ops, self.modules, self.host = ops, modules, host
        self.chips = sorted(ops)[:n_chips] if ops else []
        every = [(s, e) for c in self.chips for _, s, e, _ in ops[c]]
        self.window = (min(s for s, _ in every), max(e for _, e in every)) \
            if every else (0, 0)
        self._leaf, self._busy = {}, {}

    # -- busy and idle ------------------------------------------------------

    def leaf_ops(self, chip):
        if chip not in self._leaf:
            self._leaf[chip] = [o for o in self.ops[chip]
                                if not is_container(o[0])]
        return self._leaf[chip]

    def busy(self, chip):
        """Merged (start, end) of the chip's operations; callers read it
        and do not change it."""
        if chip not in self._busy:
            self._busy[chip] = union(
                (s, e) for _, s, e, _ in self.leaf_ops(chip))
        return self._busy[chip]

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(length(self.busy(c)) for c in self.chips) \
            / len(self.chips) / 1e9

    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def idle_share(self):
        w = self.window_s()
        return None if w <= 0 else 1.0 - self.busy_s() / w

    # -- operations by name -------------------------------------------------

    def op_seconds(self, match, chip=None):
        """Summed device time of the operations ``match(name, stats)``
        accepts, averaged over the chips (or on one)."""
        chips = self.chips if chip is None else [chip]
        if not chips:
            return 0.0
        return sum(e - s for c in chips for n, s, e, st in self.leaf_ops(c)
                   if match(n, st)) / len(chips) / 1e9

    def op_events(self, match):
        return [(c, n, s, e, st) for c in self.chips
                for n, s, e, st in self.leaf_ops(c) if match(n, st)]

    def exposed_s(self, match):
        """Seconds of the matched operations during which no other
        operation ran on that chip, averaged over the chips."""
        if not self.chips:
            return 0.0
        total = 0
        for c in self.chips:
            ops = self.leaf_ops(c)
            mine = union((s, e) for n, s, e, st in ops if match(n, st))
            rest = union((s, e) for n, s, e, st in ops if not match(n, st))
            total += length(subtract(mine, rest))
        return total / len(self.chips) / 1e9

    def module_events(self, match):
        return [(c, n, s, e) for c in self.chips
                for n, s, e, _ in self.modules.get(c, []) if match(n)]

    # -- breakdown ----------------------------------------------------------

    def top_ops(self, k):
        """[name, seconds] of the operations that took most device time
        (summed over executions, averaged over the chips)."""
        if not self.chips:
            return []
        tot = {}
        for c in self.chips:
            for n, s, e, _ in self.leaf_ops(c):
                tot[n] = tot.get(n, 0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / len(self.chips) / 1e9] for n, v in top]

    def idle_gaps(self, k):
        """[what the host was doing, seconds] of the longest gaps between
        operations on the first chip. A gap is named by the benchmark's
        own annotation that covers most of it; inside the program's own
        threads nothing is annotated yet."""
        if not self.chips:
            return []
        busy = self.busy(self.chips[0])
        gaps = sorted(((b[0] - a[1], a[1], b[0])
                       for a, b in zip(busy[:-1], busy[1:])),
                      reverse=True)[:k]
        out = []
        for dur, s, e in gaps:
            best, cover = "program threads, unannotated", 0
            for n, hs, he in self.host:
                if n in ("window", "wait_for_due"):
                    continue        # spans of waiting, not of work
                ov = min(e, he) - max(s, hs)
                if ov > cover:
                    best, cover = n, ov
            out.append([best, dur / 1e9])
        return out


def is_collective(name, stats=None):
    return bool(COLLECTIVE.match(name))


def is_mosaic(name, stats):
    """A Pallas/Mosaic kernel: an HLO custom call whose target is
    ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in stats.get("hlo", "")


def short_name(text):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)", text)
    return m.group(1) if m else text


def load(path, n_chips):
    """The ``Trace`` of an ``.xplane.pb``, from the run's one parse of it
    (``program_trace.parsed``: the standard library alone)."""
    from chipbench import program_trace      # it imports this module

    pt = program_trace.parsed(path)
    ops = {chip: [(n, s, e, st)
                  for (n, s, e, _), st in zip(evs, pt.stats[chip])]
           for chip, evs in pt.ops.items()}
    modules = {chip: [(n, s, e, {"hlo": n}) for n, s, e in evs]
               for chip, evs in pt.modules.items()}
    return Trace(ops, modules, pt.host, n_chips)


def from_records(records, n_chips=1):
    """A trace from the compact form the tests keep: ``{"ops": [[chip,
    name, start_ns, end_ns, hlo], ...], "modules": [[chip, name, start_ns,
    end_ns], ...], "host": [[name, start_ns, end_ns], ...]}``."""
    ops, modules = {}, {}
    for c, n, s, e, hlo in records["ops"]:
        ops.setdefault(c, []).append((n, s, e, {"hlo": hlo}))
    for c, n, s, e in records.get("modules", []):
        modules.setdefault(c, []).append((n, s, e, {}))
    return Trace(ops, modules, [tuple(h) for h in records.get("host", [])],
                 n_chips)
