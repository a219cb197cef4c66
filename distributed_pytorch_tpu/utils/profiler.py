"""Tracing / profiling subsystem.

The reference has none (SURVEY.md §5: its only observability is
per-iteration prints whose ``.cpu().item()`` calls incidentally serialize
the device pipeline, reference ``min_DDP.py:110-116``). A TPU framework
needs real instrumentation because the interesting time is inside one
compiled XLA program where host-side timers see nothing. Three layers:

- **Device traces**: :func:`trace` / :func:`start_trace` wrap
  ``jax.profiler`` and dump XPlane protos viewable in XProf/TensorBoard —
  per-op device timelines, HBM traffic, collective time on the ICI.
- **Step timing**: :class:`StepTimer` measures wall-clock per step with
  explicit ``block_until_ready`` fencing (without the fence you time the
  async dispatch, not the step) and reports percentiles + throughput.
- **Static cost**: :func:`compiled_stats` asks XLA's cost model for
  FLOPs/bytes of a jitted function, so kernels can be checked against
  roofline expectations without running them.
"""

from __future__ import annotations

import contextlib
import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..obs import trace as _dpxtrace


# ---------------------------------------------------------------------------
# device traces (XPlane / XProf)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a device+host profile into ``logdir``.

    View with TensorBoard's profile plugin or xprof. Works on TPU and on
    the CPU test mesh (the trace then contains host/XLA-CPU lanes only).
    """
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


start_trace = jax.profiler.start_trace
stop_trace = jax.profiler.stop_trace


def annotate(name: str, **attrs):
    """Named region that shows up on the trace timeline as
    ``dpx:<name>``: a dpxtrace span (``obs/trace.py`` — the one way to
    name a region; under ``DPX_TRACE`` it is recorded there too)::

        with profiler.annotate("data-load"):
            batch = next(it)
    """
    return _dpxtrace.span(name, **attrs)


class CommStats:
    """Per-process communication accounting: calls, wall seconds, and
    payload bytes on the wire, per collective op.

    The host front door's :class:`..runtime.native.HostComm` owns one and
    feeds every collective through :meth:`timed`, so a training loop can
    diff :meth:`snapshot` around a step to attribute per-step comm time
    and bytes (quantized-vs-f32 wire cost shows up directly — see
    ``benchmarks/step_breakdown.py``'s comm arms). Bytes are the WIRE
    payload this rank sends (e.g. the int8+scales framing for the
    quantized ring), not the logical tensor size.

    **Overlap accounting**: each op's wall seconds are additionally
    split into ``overlapped_s`` (the call was issued with ``hidden=True``
    — the overlapping train step had later gradient buckets' backward
    still outstanding on the device, so this comm hid behind compute)
    and ``exposed_s`` (comm the step actually blocked on: the final
    bucket, and everything in non-overlapped mode). The hidden fraction
    of comm is thereby a MEASURED number, not a claim — the dp8 bench
    arm reports ``exposed_ms`` with and without overlap.
    """

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.per_op: Dict[str, Dict[str, float]] = {}

    def record(self, op: str, nbytes: int, seconds: float,
               hidden: bool = False) -> None:
        d = self.per_op.setdefault(
            op, {"calls": 0, "seconds": 0.0, "bytes": 0,
                 "overlapped_s": 0.0, "exposed_s": 0.0})
        d["calls"] += 1
        d["seconds"] += seconds
        d["bytes"] += int(nbytes)
        d["overlapped_s" if hidden else "exposed_s"] += seconds

    @contextlib.contextmanager
    def timed(self, op: str, nbytes: int, hidden: bool = False):
        """Time a collective and record its wire bytes; also opens a
        dpxtrace span (obs/trace.py), so the op shows on XProf
        timelines as ``dpx:comm:<op>`` and — with ``DPX_TRACE`` on —
        EVERY comm op (quantized/hier ring legs, the disagg
        handoff_send/recv transport included) lands on the cross-rank
        timeline with its overlapped-vs-exposed attribution. ``hidden``
        routes the wall time into the overlapped (vs exposed) bucket."""
        t0 = time.perf_counter()
        try:
            with _dpxtrace.span(f"comm:{op}", bytes=int(nbytes),
                                hidden=hidden):
                yield
        finally:
            self.record(op, nbytes, time.perf_counter() - t0,
                        hidden=hidden)

    def snapshot(self) -> Dict[str, float]:
        """Totals so far: {calls, seconds, bytes, overlapped_s,
        exposed_s} summed over ops."""
        out = {"calls": 0, "seconds": 0.0, "bytes": 0,
               "overlapped_s": 0.0, "exposed_s": 0.0}
        for d in self.per_op.values():
            out["calls"] += d["calls"]
            out["seconds"] += d["seconds"]
            out["bytes"] += d["bytes"]
            out["overlapped_s"] += d.get("overlapped_s", 0.0)
            out["exposed_s"] += d.get("exposed_s", 0.0)
        return out

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-op totals (a copy; safe to serialize)."""
        return {op: dict(d) for op, d in self.per_op.items()}

    def monitor_metrics(self) -> Dict[str, float]:
        """Flat ``{metric name: number}`` view for the dpxmon registry
        (obs/metrics.py registers this as the ``comm`` provider —
        polled once per snapshot, so the comm hot path never pays for
        it): per-op calls/bytes plus the whole-stack totals with the
        overlapped-vs-exposed split in milliseconds."""
        out: Dict[str, float] = {}
        for op, d in self.per_op.items():
            out[f"comm.{op}.calls"] = d["calls"]
            out[f"comm.{op}.bytes"] = d["bytes"]
        tot = self.snapshot()
        out["comm.calls"] = tot["calls"]
        out["comm.bytes"] = tot["bytes"]
        out["comm.exposed_ms"] = round(tot["exposed_s"] * 1e3, 3)
        out["comm.overlapped_ms"] = round(tot["overlapped_s"] * 1e3, 3)
        return out


def device_memory_stats(device=None) -> Dict[str, Any]:
    """Per-device allocator stats (bytes in use, peak, limit) where the
    backend exposes them; empty dict otherwise (XLA-CPU has none)."""
    dev = device if device is not None else jax.devices()[0]
    stats = dev.memory_stats()
    return dict(stats) if stats else {}


# ---------------------------------------------------------------------------
# step timing
# ---------------------------------------------------------------------------


class StepTimer:
    """Wall-clock step timing with async-dispatch fencing.

    Use either as a context manager per step — the yielded holder takes
    the fence produced *inside* the block (``out`` does not exist yet on
    the first iteration, so it cannot be passed as the ``fence=`` arg)::

        timer = StepTimer(warmup=2)
        for batch in loader:
            with timer.step() as h:
                out = train_step(params, opt_state, batch)
                h["fence"] = out.loss          # fence forces completion

    or functionally via :meth:`measure`. The first ``warmup`` steps
    (compile + cache warming) are recorded separately and excluded from
    the summary statistics.
    """

    def __init__(self, warmup: int = 1, fetch: bool = False):
        self.warmup = warmup
        self.fetch = fetch
        self.times: List[float] = []
        self.warmup_times: List[float] = []

    def _fence(self, x: Any) -> None:
        if self.fetch:
            # host materialization — correct even where block_until_ready
            # resolves early (see fetch_fence); pass a scalar fence so the
            # transfer is free
            fetch_fence(x)
        else:
            jax.block_until_ready(x)

    @contextlib.contextmanager
    def step(self, fence: Any = None):
        t0 = time.perf_counter()
        holder = {}
        try:
            yield holder
        finally:
            f = holder.get("fence", fence)
            if f is not None:
                self._fence(f)
            self._record(time.perf_counter() - t0)

    def measure(self, fn: Callable, *args, n: int = 10,
                fence_of: Optional[Callable] = None, **kwargs):
        """Time ``n`` calls of ``fn`` (plus warmup), fencing each result.
        Returns the last result. Each call runs its own warmup block, so a
        reused timer never counts a fresh function's compile step as a
        timed sample.

        In fetch mode, pass ``fence_of`` to select a SCALAR from the
        output to materialize — fetching the whole output pytree of a
        large-output function would put the device-to-host transfer
        inside every timed sample and measure the copy instead of the
        compute."""
        out = None
        for i in range(self.warmup + n):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            self._fence(fence_of(out) if fence_of is not None else out)
            dt = time.perf_counter() - t0
            (self.warmup_times if i < self.warmup else self.times).append(dt)
        return out

    def _record(self, dt: float) -> None:
        if len(self.warmup_times) < self.warmup:
            self.warmup_times.append(dt)
        else:
            self.times.append(dt)

    @property
    def count(self) -> int:
        return len(self.times)

    def summary(self) -> Dict[str, float]:
        """mean/median/p10/p90 step seconds and steps/sec over the
        post-warmup samples."""
        if not self.times:
            return {}
        ts = sorted(self.times)
        n = len(ts)
        return {
            "steps": n,
            "mean_s": statistics.fmean(ts),
            "median_s": ts[n // 2],
            "p10_s": ts[max(0, int(0.10 * n) - 1)] if n >= 10 else ts[0],
            "p90_s": ts[min(n - 1, int(0.90 * n))],
            "steps_per_sec": n / sum(ts),
        }

    def throughput(self, items_per_step: int) -> float:
        """items/sec (samples, tokens, images) given a fixed per-step count."""
        s = self.summary()
        return s["steps_per_sec"] * items_per_step if s else 0.0


def fetch_fence(x: Any) -> None:
    """Materialize ``x``'s bytes on the host — the strongest fence.

    A device-to-host copy of the value cannot complete before the value
    exists, so fencing by fetching is correct on every backend, whatever
    its notion of "ready". (On the directly attached v5e
    ``jax.block_until_ready`` does wait for the work: it returned
    together with a host fetch of the result, long after the enqueue —
    chip run, PR 21.) Fetch a SCALAR (e.g. the loss) so the transfer
    itself costs nothing."""
    for leaf in jax.tree_util.tree_leaves(x):
        np.asarray(leaf)


def time_steps_amortized(step_fn: Callable, state: Any, n: int,
                         fence_of: Callable[[Any], Any]) -> Tuple[float, Any]:
    """Throughput timing that is honest on high-latency backends.

    Runs ``n`` data-dependent iterations ``state = step_fn(state)`` with
    NO per-step synchronization and ONE host materialization of
    ``fence_of(final_state)`` at the end. The device executes the steps
    back-to-back (each step's inputs are the previous step's outputs, so
    the final fence transitively waits for all n); per-call dispatch
    latency overlaps with device work instead of serializing it.

    ``step_fn`` must already be compiled/warmed on ``state``'s shapes
    (run one step and fence it first). Returns ``(seconds_per_step,
    final_state)``. Use for throughput; for per-step latency percentiles
    use :class:`StepTimer` with a fetch fence and subtract the measured
    round trip."""
    t0 = time.perf_counter()
    for _ in range(n):
        state = step_fn(state)
    fetch_fence(fence_of(state))
    return (time.perf_counter() - t0) / n, state


# ---------------------------------------------------------------------------
# static cost analysis
# ---------------------------------------------------------------------------


def compiled_memory(fn: Callable, *args,
                    static_argnums=(), **kwargs) -> Dict[str, float]:
    """XLA memory analysis for ``fn`` jitted on the example args, without
    executing it: argument/output/temp/generated-code sizes in bytes.
    ``temp_size_bytes`` is the compiler's buffer-allocation high water
    mark for intermediates — the number that separates schedules with
    O(T) activation footprints from O(S) ones (see parallel/pipeline.py).
    Returns {} when the backend exposes no memory analysis."""
    jitted = jax.jit(fn, static_argnums=static_argnums)
    compiled = jitted.lower(*args, **kwargs).compile()
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    if m is None:
        return {}
    out = {}
    for name in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(m, name, None)
        if v is not None:
            out[name.replace("_in_bytes", "_bytes")] = float(v)
    return out


def compiled_stats(fn: Callable, *args,
                   static_argnums=(), **kwargs) -> Dict[str, float]:
    """XLA cost-model stats (flops, bytes accessed, ...) for ``fn`` jitted
    on the given example args — without executing it.

    Keys come from XLA's ``cost_analysis`` (always includes ``flops``
    when the backend provides a cost model)."""
    jitted = jax.jit(fn, static_argnums=static_argnums)
    compiled = jitted.lower(*args, **kwargs).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax returns [dict]
        cost = cost[0] if cost else {}
    return dict(cost) if cost else {}
