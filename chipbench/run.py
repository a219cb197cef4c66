"""Run one cell of the benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up (set-up), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON
object as the last line of standard output (``correct``, ``attempted``,
``failed``, ``device``, ``metrics``, with ``--trace 1`` ``breakdown``,
and last ``checks``: every number compared beside its limit, which are
also the last lines of standard error). Everything else goes on earlier
lines, among them ``chipbench: phases ...``: where the run's wall time
went, in seconds, part by part and in total. A run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero and prints no result.

Nothing here names a cell, a configuration, a mix or a metric: a cell in
``BENCHMARK.json`` names its configuration file and its mix
(``traffic/<mix>.json``), brings the limits of its ``correct``
(``limits/<cell>.json``, each with the readings it was set from), the mix
names its kind (``kinds/<kind>.py``
drives it), and every per-layer metric is a reader of its own
(``layer_metrics/<metric>.py``)."""

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import importlib.util                                       # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import shutil                                               # noqa: E402
import sys                                                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
# started as a script, Python puts chipbench/ itself first on the path;
# its modules are reached as ``chipbench.<name>`` and nothing else
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]


def say(msg, file=None):
    print(f"chipbench: {msg}", file=file, flush=True)


def check_line(name, c):
    return (f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g}) "
            f"{'ok' if c['ok'] else 'FAILED'}")


class Phases:
    """Where a run's wall time went: consecutive stretches of the main
    thread, each closed and named by ``end``, so that the parts sum to the
    total. The driver's limit is on a run's wall time, which no metric
    reports; every run prints this as one line."""

    def __init__(self, t_start):
        self.t_start = self.t_last = t_start
        self.parts = []

    def end(self, name, at=None):
        """Close the stretch since the last call (at ``at``, or now)."""
        at = time.perf_counter() if at is None else at
        self.parts.append((name, at - self.t_last))
        self.t_last = at

    def line(self):
        self.end("result")
        return "phases " + " ".join(f"{n}={s:.2f}" for n, s in self.parts) \
            + f" total={self.t_last - self.t_start:.2f}"


class Cell:
    """Everything one run needs to know, read from data files."""

    def __init__(self, root, workload, seed, seconds, trace, t_start=None):
        self.root, self.seed, self.seconds, self.trace = \
            root, seed, seconds, bool(trace)
        self.phases = Phases(time.perf_counter() if t_start is None
                             else t_start)
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        by_name = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in by_name:
            raise SystemExit(f"no cell {workload!r} in BENCHMARK.json "
                             f"(have {sorted(by_name)})")
        self.entry = by_name[workload]
        self.name, self.chips = workload, self.entry["chips"]
        cfg = next(c for c in self.manifest["configs"]
                   if c["name"] == self.entry["config"])
        with open(os.path.join(root, cfg["file"])) as f:
            self.config = json.load(f)
        bench_dir = os.path.join(root, self.manifest["paths"][0])
        with open(os.path.join(bench_dir, "traffic",
                               self.entry["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        with open(os.path.join(bench_dir, "limits", workload + ".json")) as f:
            self.limits = {k: v["limit"] for k, v in json.load(f).items()}
        self.out_dir = os.path.join(root, ".chipbench_runs", workload)
        self.peaks = None           # filled once the device is known

    def metrics_of(self, section):
        """The metrics of ``section`` that this cell reports."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or self.name in m["workloads"]]


def find_devices(cell, require_chip):
    """The chips the cell runs on; exits non-zero where JAX has no TPU or
    too few (a CPU number must never stand under a device metric)."""
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        say(f"no TPU: JAX reports platform {devices[0].platform!r}")
        raise SystemExit(3)
    if len(devices) < cell.chips:
        say(f"cell {cell.name} asks for {cell.chips} chips, JAX has "
            f"{len(devices)}")
        raise SystemExit(3)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    kind = devices[0].device_kind
    if kind not in peaks:
        if require_chip:
            raise SystemExit(f"device kind {kind!r} is not in peaks.json")
        peaks[kind] = None
    cell.peaks = peaks[kind]
    return devices[:cell.chips]


def setup_compile_cache():
    """The program's own door places the cache (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``); the benchmark only asks JAX
    to store every program, however quickly it compiled, so that a second
    run in the same checkout compiles nothing."""
    import jax

    from distributed_pytorch_tpu.runtime import compile_cache

    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class Tracer:
    """``jax.profiler`` around a part of the window, into a fixed
    directory inside the checkout. Host spans come from the
    ``jax.profiler.TraceAnnotation``s the kinds put round their own calls."""

    def __init__(self, cell):
        self.dir = os.path.join(cell.out_dir, "trace")
        self.on = False
        self.stop_span = None       # (start, end) of stop(), host clock

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        # every program's HLO as a protobuf beside the events: no reader
        # uses it (an event carries its instruction's text and name stack
        # without it), and writing it out costs as much again as the
        # events do, more in a process that compiled (PERF.md section 2)
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def stop(self):
        """The device's part ends at once; converting and writing the
        trace out then takes seconds (3.6 s for the training cell's 92 k
        device operations, 30 s for the serving cell's 600 k), and three
        times as long from a thread that is not the process's main one
        (PERF.md Findings, PR 26): call it from the main thread."""
        import jax

        self.on = False
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_span = (t0, time.perf_counter())

    def xplane(self):
        for base, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(base, f)
        raise FileNotFoundError(f"no .xplane.pb under {self.dir}")


def read_layer_metrics(cell, trace, counters):
    """Each per-layer metric of this cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out of
    the result, and named on one line: a metric that ``BENCHMARK.json``
    lists for the cell and that no traced run reports has read nothing
    since some change took its events out of the traced part (two did,
    from PR 29 to PR 40)."""
    out, silent = {}, []
    for m in cell.metrics_of("per_layer"):
        path = os.path.join(HERE, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "chipbench_layer_metric", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(trace, counters, cell)
        if value is None:
            silent.append(m["name"])
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    say(f"per-layer metrics of {cell.name} with nothing to read in this "
        f"traced run: {', '.join(silent) or 'none'}")
    return out


def device_record(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def open_cell(root, workload, seed, seconds, trace, require_chip,
              reference=True, t_start=None):
    """The cell, the module that drives its kind, and its chips, in the
    order the chip allows: a kind's ``before_devices`` (the training
    reference's own process) runs before this process touches JAX."""
    for p in (root, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    cell = Cell(root, workload, seed, seconds, trace, t_start)
    os.makedirs(cell.out_dir, exist_ok=True)
    kind = importlib.import_module(f"chipbench.kinds.{cell.traffic['kind']}")
    cell.phases.end("imports")
    if reference and hasattr(kind, "before_devices"):
        kind.before_devices(cell, require_chip)
        cell.phases.end("reference")
    setup_compile_cache()
    devices = find_devices(cell, require_chip)
    cell.phases.end("devices")
    return cell, kind, devices


def run_cell(argv, root=REPO, require_chip=True):
    """Drive one run and return the result object (``main`` prints it).
    ``require_chip=False`` is the tests' entry: it skips the look for a
    TPU and nothing else."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, kind, devices = open_cell(root, args.workload, args.seed,
                                    args.seconds, args.trace, require_chip,
                                    t_start=T_START)
    say(f"cell {cell.name} seed {cell.seed} seconds {cell.seconds} trace "
        f"{int(cell.trace)} on {len(devices)} x {devices[0].device_kind}, "
        f"{(devices[0].memory_stats() or {}).get('bytes_limit')} bytes a chip")

    tracer = Tracer(cell)
    run = kind.run(cell, devices, tracer, T_START)

    # every number compared, beside its limit
    correct, compared = True, {}
    for c in run["checks"]:
        ok = bool(c["value"] <= c["limit"]) and c["value"] == c["value"]
        correct &= ok
        compared[c["name"]] = {"value": float(c["value"]),
                               "limit": float(c["limit"]), "ok": ok}
        say(check_line(c["name"], compared[c["name"]]))
    # the peak is the program's: the training reference ran in a process
    # of its own, the serving one walks a layer at a time after the engine
    # and its weights are freed
    device = device_record(devices)
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "device": device}
    if cell.trace:
        from chipbench import trace_reduce

        path = tracer.xplane()
        trace = trace_reduce.load(path, len(devices))
        cell.phases.end("trace_read")
        say(f"trace of {os.path.getsize(path)} bytes: "
            f"{sum(len(v) for v in trace.ops.values())} device operations "
            f"in {sum(len(v) for v in trace.modules.values())} programs")
        result["metrics"] = read_layer_metrics(cell, trace, run["counters"])
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s()
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.idle_gaps(10)}
        cell.phases.end("readers")
    else:
        want = cell.metrics_of("end_to_end")
        result["metrics"] = {
            m["name"]: {"value": float(run["end_to_end"][m["name"]]),
                        "unit": m["unit"]} for m in want}
    say(cell.phases.line())
    result["checks"] = compared         # last in the line
    return result


def main(argv=None, **where):
    """``where`` is the tests' (``run_cell``'s ``root`` and
    ``require_chip``)."""
    result = run_cell(sys.argv[1:] if argv is None else argv, **where)
    print(json.dumps(result), flush=True)
    # and as the last lines of standard error, where a record of a run
    # that was not correct keeps them
    for name, c in result["checks"].items():
        say(check_line(name, c), file=sys.stderr)


if __name__ == "__main__":
    main()
