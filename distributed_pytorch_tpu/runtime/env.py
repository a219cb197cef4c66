"""Typed environment-variable registry — the single front door for every
environment read the framework makes.

Before this module, 29 call sites read ``os.environ`` directly, each with
its own ad-hoc parse/default/fallback. That scatter had three costs: no
one place lists the knobs a deployment can set, a typo'd variable name
fails silently, and a malformed value blows up (or worse, doesn't) at a
different layer every time. This registry fixes all three:

* every variable the framework reads or writes is **declared** here with
  its name, type, default, and a docstring — ``docs/env_vars.md`` is
  generated from these declarations (``python -m tools.gen_env_docs``),
  so the docs cannot drift from the code;
* reads go through :func:`get` (typed, default-applying, tolerant of
  malformed values the way the comm deadline read always was) or
  :func:`raw`; an **unregistered name raises** ``KeyError`` immediately —
  the registry is closed, not advisory;
* the ``dpxlint`` DPX002 rule (:mod:`..analysis.lint`) flags any new raw
  ``os.environ`` access outside this module, so the scatter cannot grow
  back.

Writes: the framework legitimately exports a handful of variables to
itself and to child processes (``DPX_BACKEND`` in the worker shim,
``DPX_FAULT`` from :func:`..runtime.faults.install`, the elastic
attempt counter). Those go through :func:`set`/:func:`unset` (registered
names only). Child-process bootstrap paths that apply a *caller-supplied*
environment dict verbatim use :func:`apply_overrides` /
:func:`snapshot` / :func:`restore` — passthrough by design, documented
as such.

Variables marked ``external=True`` are owned by other systems (XLA, JAX,
the TPU runtime, torch's rendezvous convention); they are registered so
reads are typed and documented, but their semantics are defined
elsewhere.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Iterable, Mapping, Optional

__all__ = [
    "EnvVar", "REGISTRY", "register", "get", "raw", "is_set", "set",
    "unset", "apply_overrides", "snapshot", "restore", "environ_copy",
    "generate_docs",
]


@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One declared environment variable."""

    name: str
    type: str            # 'str' | 'int' | 'float' | 'bool'
    default: Any         # typed default returned when unset/malformed
    doc: str             # one-line description (docs/env_vars.md row)
    external: bool = False  # owned by XLA/JAX/TPU/torch, not this repo

    def parse(self, text: str) -> Any:
        if self.type == "int":
            return int(text)
        if self.type == "float":
            return float(text)
        if self.type == "bool":
            # accepted spellings mirror the repo's historical checks
            # (DPX_ELASTIC == "1", DPX_BENCH_SELFLOG != "0")
            return text.strip().lower() in ("1", "true", "yes", "on")
        return text


REGISTRY: Dict[str, EnvVar] = {}


def register(name: str, type: str = "str", default: Any = None,
             doc: str = "", external: bool = False) -> EnvVar:
    """Declare a variable. Idempotent for identical declarations; a
    conflicting re-declaration raises (two modules disagreeing about a
    knob's type/default is exactly the bug the registry exists to stop).
    """
    if type not in ("str", "int", "float", "bool"):
        raise ValueError(f"unsupported env var type {type!r} for {name}")
    var = EnvVar(name=name, type=type, default=default, doc=doc,
                 external=external)
    old = REGISTRY.get(name)
    if old is not None and old != var:
        raise ValueError(
            f"conflicting registration for {name}: {old} vs {var}")
    REGISTRY[name] = var
    return var


def _lookup(name: str) -> EnvVar:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"environment variable {name!r} is not registered in "
            f"runtime/env.py — declare it there (name, type, default, "
            f"docstring) before reading it") from None


def get(name: str) -> Any:
    """Typed value of ``name``: parsed when set, the declared default when
    unset **or malformed**. Malformed-falls-back is deliberate — it is
    the contract the comm deadline read always had (a garbage
    ``DPX_COMM_TIMEOUT_MS`` must degrade to the default, not crash a
    2000-host job at rendezvous)."""
    var = _lookup(name)
    text = os.environ.get(name)
    if text is None:
        return var.default
    try:
        return var.parse(text)
    except ValueError:
        return var.default


def raw(name: str) -> Optional[str]:
    """The unparsed string value (None when unset). For variables whose
    grammar is richer than one scalar (``DPX_CPU_DEVICES`` accepts an int
    or ``'all'``; ``DPX_FAULT`` has its own spec language)."""
    _lookup(name)
    return os.environ.get(name)


def is_set(name: str) -> bool:
    _lookup(name)
    return name in os.environ


def set(name: str, value: Any) -> None:
    """Export a registered variable (stringified) to this process and
    its future children."""
    _lookup(name)
    os.environ[name] = str(value)


def unset(name: str) -> None:
    _lookup(name)
    os.environ.pop(name, None)


def apply_overrides(mapping: Mapping[str, str]) -> None:
    """Apply a caller-supplied environment dict verbatim (child-process
    bootstrap: the elastic child env, the per-rank worker env). Keys are
    NOT required to be registered — these dicts legitimately carry
    user-provided passthrough variables."""
    os.environ.update({k: str(v) for k, v in mapping.items()})


def snapshot(keys: Iterable[str]) -> Dict[str, Optional[str]]:
    """Current raw values of ``keys`` (None = unset), for :func:`restore`."""
    return {k: os.environ.get(k) for k in keys}


def environ_copy() -> Dict[str, str]:
    """A mutable copy of the FULL process environment, for child-process
    construction (the benchmark subprocess runner builds each child's
    env from this plus explicit overrides).  Passthrough by design, like
    :func:`apply_overrides`: a child legitimately inherits variables the
    registry has never heard of — the registry's closedness governs what
    *this framework reads*, not what it forwards."""
    return dict(os.environ)


def restore(saved: Mapping[str, Optional[str]]) -> None:
    """Undo an :func:`apply_overrides` using a prior :func:`snapshot`."""
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def generate_docs() -> str:
    """The ``docs/env_vars.md`` content — one table row per declaration,
    framework-owned variables first. ``tools/gen_env_docs.py`` writes
    this; a tier-1 test asserts the committed file matches."""
    lines = [
        "# Environment variables",
        "",
        "Generated from the typed registry in "
        "`distributed_pytorch_tpu/runtime/env.py` by "
        "`python -m tools.gen_env_docs` — edit the registry, not this "
        "file. Every environment read the framework makes goes through "
        "the registry; the `dpxlint` rule DPX002 (`docs/analysis.md`) "
        "keeps it that way.",
        "",
        "## Framework-owned",
        "",
        "| Name | Type | Default | Description |",
        "|---|---|---|---|",
    ]
    own = [v for _, v in sorted(REGISTRY.items()) if not v.external]
    ext = [v for _, v in sorted(REGISTRY.items()) if v.external]
    for v in own:
        lines.append(f"| `{v.name}` | {v.type} | `{v.default!r}` | "
                     f"{v.doc} |")
    lines += [
        "",
        "## External (owned by XLA / JAX / TPU runtime / torch "
        "conventions)",
        "",
        "| Name | Type | Default | Description |",
        "|---|---|---|---|",
    ]
    for v in ext:
        lines.append(f"| `{v.name}` | {v.type} | `{v.default!r}` | "
                     f"{v.doc} |")
    lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# The registry. One declaration per variable the repo reads or writes;
# the doc string here IS the docs/env_vars.md row.
# ---------------------------------------------------------------------------

# -- runtime / comm ---------------------------------------------------------
register("DPX_BACKEND", "str", None,
         "Force the process-group backend; `host` selects the native TCP "
         "per-rank-process group (set by the multiprocess worker shim).")
register("DPX_MASTER_ADDR", "str", "127.0.0.1",
         "Rendezvous address of the native host process group (the "
         "MASTER_ADDR analog).")
register("DPX_MASTER_PORT", "int", None,
         "Rendezvous base port of the native host process group; rank r "
         "listens on port+r. Required in host-backend workers.")
register("DPX_COMM_TIMEOUT_MS", "int", 300_000,
         "Per-collective deadline in ms for the native host group "
         "(0 disables). A wedged peer becomes a typed `CommTimeout`, "
         "never an infinite hang (docs/failures.md).")
register("DPX_VISIBLE_DEVICES", "str", None,
         "Comma-separated accelerator device indices visible to this "
         "process — the `CUDA_VISIBLE_DEVICES` analog "
         "(runtime/context.py).")
register("DPX_CPU_DEVICES", "str", None,
         "Opt N virtual CPU XLA devices in as accelerators (`all` for "
         "every host device) — the virtual-mesh testing knob.")
register("DPX_MULTIPROC_ACCEL", "str", "",
         "Per-rank-process device ownership: `tpu` gives child rank r "
         "exclusive ownership of local chip r; empty/`cpu` keeps "
         "children on the CPU backend.")
register("DPX_NATIVE_LIB", "str", None,
         "Absolute path of a prebuilt libdpxhost.so to load instead of "
         "the default build — how the CI sanitizer jobs point the whole "
         "test suite at an ASan/UBSan/TSan-instrumented native library "
         "(docs/analysis.md).")
register("DPX_COMM_SANITIZE", "bool", False,
         "Arm the runtime collective sanitizer: every host-group "
         "collective first exchanges a fixed-size fingerprint (seq no, "
         "op, dtype, nbytes, call site) and a cross-rank divergence "
         "raises a typed `CollectiveMismatch` naming both ranks and "
         "ops within one exchange — instead of hanging for a full "
         "`DPX_COMM_TIMEOUT_MS` (comm/sanitizer.py, docs/analysis.md).")
register("DPX_SCHEDULE_WINDOW", "int", 64,
         "How many recent per-rank collective records the runtime "
         "schedule verifier keeps for divergence reports (0 disables "
         "recording; docs/analysis.md).")
register("DPX_WIRE_WIDTH", "str", "8",
         "Default wire width of the quantized collectives under "
         "`wire=\"quant\"`/`grad_reduce=\"quant\"`: `8` (block int8), "
         "`4` (nibble-packed, ~7.9x less traffic than f32), or "
         "`adaptive` (per-bucket WidthChooser with hysteresis; "
         "docs/comms.md).")
register("DPX_HIER_RING", "int", 0,
         "Ranks per host of the two-level hierarchical ring (0/1 = "
         "flat). When it divides the world, the quantized gradient "
         "reduce runs exact intra-host to one leader per host and the "
         "quantized ring only between leaders — each gradient byte "
         "crosses the slow hop once (comm/hier.py, docs/comms.md).")
register("DPX_COMM_OVERLAP", "bool", False,
         "Overlap gradient-bucket ring traffic with still-running "
         "backward compute in the host train step (bucketed issue + "
         "CommStats overlapped/exposed accounting; docs/comms.md).")
register("DPX_COMM_BUCKETS", "int", 4,
         "Gradient bucket count of the overlapped host train step "
         "(clamped to the leaf count; only read when the overlap path "
         "is active).")

# -- compute path (docs/compute.md) -----------------------------------------
register("DPX_FLASH_MIN_SEQ", "int", 1024,
         "Key count below which the flash attn_fn dispatches to the "
         "dense einsum instead of the pallas kernel "
         "(ops/flash_attention.py — numerics identical either way; "
         "the v5e crossover itself is not measured).")
register("DPX_MP_POLICY", "str", "off",
         "Default mixed-precision policy of `parallel.make_train_step`: "
         "`off` (f32 throughout) or `bf16` (bf16 compute-params/"
         "activations with the f32 master kept authoritative — "
         "docs/compute.md).")
register("DPX_DONATE", "bool", True,
         "Default whole-step buffer donation of the pjit front door "
         "(`parallel.front_door.make_step` and every builder shimmed "
         "over it): params + optimizer state are donated with "
         "out_shardings pinned equal to in_shardings, so the update "
         "runs in place instead of copying the full state every step "
         "(docs/front_door.md). Set 0 to force copying builds "
         "everywhere (debugging).")
register("DPX_REMAT", "str", "none",
         "Default per-layer remat policy of `models.TransformerLM"
         "(remat=None)`: `none` (save all activations), `full` "
         "(recompute each block in backward), or `dots_saveable` "
         "(save matmul outputs only, recompute elementwise — "
         "jax.checkpoint_policies; docs/compute.md).")

# -- observability ----------------------------------------------------------
register("DPX_METRICS_LOG", "str", None,
         "Line-JSON file receiving structured events (worker failures, "
         "ckpt saves, schedule digests) from every rank and supervisor.")
register("DPX_TRACE", "bool", False,
         "Enable dpxtrace span recording (obs/trace.py): comm ops, the "
         "host train step, the serve request lifecycle and ckpt phases "
         "emit trace_span events + feed the per-rank flight recorder "
         "(docs/observability.md). Off = near-zero overhead, gated in "
         "the bench smoke.")
register("DPX_TRACE_RING", "int", 256,
         "Flight-recorder capacity in spans: the bounded per-process "
         "ring whose last-N spans every typed failure path dumps as a "
         "flight_recorder event (0 disables the ring; drops are "
         "counted, never silent).")
register("DPX_TRACE_LOG", "str", None,
         "Span sink path for trace_span events (default: the "
         "DPX_METRICS_LOG stream, so spans ride the same multi-writer "
         "line-JSON channel as failure events; tools/dpxtrace.py "
         "merges and exports them).")
register("DPX_MON", "bool", True,
         "Enable the dpxmon live metrics registry (obs/metrics.py): "
         "counters/gauges/histograms record in-process and snapshots "
         "can be emitted. 0 makes every instrument a no-op costing one "
         "global read (<= 2 µs/increment, gated in the bench smoke). "
         "No IO happens either way until a snapshot sink is configured "
         "(DPX_METRICS_LOG or an explicit path).")
register("DPX_MON_EVERY", "int", 0,
         "Auto-emit a rank-attributed metrics_snapshot every N train "
         "steps from the instrumented step hooks (0 = no automatic "
         "cadence; explicit obs.metrics.emit_snapshot calls and the "
         "serve engine's log_every emission are unaffected).")
register("DPX_MON_RULES", "str", None,
         "Extra SLO health rules appended to obs/health.py's default "
         "set, in the rule grammar (docs/observability.md): e.g. "
         "`serve.ttft_ms.p99<=500;drift(train.steps_per_sec)@k=3`.")

# -- faults / elastic -------------------------------------------------------
register("DPX_FAULT", "str", None,
         "Deterministic fault-injection spec(s): "
         "`action@key=value,...` with actions kill|delay|drop_conn|"
         "diverge (grammar in runtime/faults.py, docs/failures.md).")
register("DPX_CHAOS", "str", None,
         "Declarative multi-fault chaos campaign: inline JSON, a path "
         "to a JSON spec, or `;`-joined `[leg:expect:]fault` clauses "
         "(grammar in runtime/chaos.py, docs/failures.md; driven by "
         "benchmarks/chaos_campaign.py, validated by tools/dpxchaos.py).")
register("DPX_RETRY_MAX", "int", 2,
         "Bounded retry budget for TRANSIENT comm faults — rendezvous "
         "connect and the handoff-transport hooks retry up to this many "
         "times (total attempts = 1 + budget) before raising the typed "
         "CommRetryExhausted. Collectives mid-flight never retry "
         "(docs/failures.md).")
register("DPX_RETRY_BACKOFF_MS", "float", 25.0,
         "Base backoff of the transient-fault retry path: attempt k "
         "sleeps base*2^(k-1) ms before re-entering; every retry emits "
         "a comm_retry event so flakiness is never silent.")
register("DPX_CHAOS_WORLD", "int", 4,
         "World size of the chaos-campaign train legs "
         "(benchmarks/chaos_campaign.py; the shrink-resume leg "
         "relaunches at half this).")
register("DPX_ELASTIC_ATTEMPT", "int", 0,
         "Restart attempt number exported to elastically supervised "
         "workers (0 = first launch).")
register("DPX_ELASTIC", "bool", False,
         "Set to 1 in workers supervised by `elastic_run`.")
register("DPX_PLATFORM", "str", None,
         "Platform the elastic child applies via jax.config before any "
         "backend use (the child has imported jax by the time its "
         "environment overrides are applied).")
register("DPX_WORKER_TAG", "str", None,
         "Per-launch tag stamped on spawned rank processes so "
         "`watchdog.kill_orphan_workers` can clean up after a crashed "
         "launcher.")
register("DPX_ELASTIC_TEST_LEAK", "str", None,
         "Test-only canary asserting elastic child env never leaks into "
         "the supervisor (tests/test_elastic.py).")
register("DPX_SOAK_WORLD", "int", 4,
         "World size of the composed soak arm (benchmarks/soak.py: "
         "hier two-level ring x adaptive wire x bucketed overlap x "
         "sharded elastic checkpointing under chaos + dpxmon gating).")
register("DPX_SOAK_STEPS", "int", 0,
         "Total train steps of the soak arm (0 = the mode default: "
         "the smoke's short step count, or time-bounded via "
         "DPX_SOAK_SECONDS for long runs).")
register("DPX_SOAK_SECONDS", "float", 0.0,
         "Wall-clock budget of a long soak run (0 = step-bounded "
         "only). The worker checks the budget at step granularity and "
         "exits cleanly once it is spent.")
register("DPX_SCALE_WORLDS", "str", None,
         "Comma-separated world sizes for the weak-scaling sweep "
         "(bench.py --stage scale_sweep); default derives "
         "2..max-sustainable from the host core count.")

# -- serving ----------------------------------------------------------------
register("DPX_SERVE_PAGE_LEN", "int", 16,
         "Tokens per KV page of the paged serving cache "
         "(serve/pages/; only full pages are prefix-shared — "
         "docs/serving.md).")
register("DPX_SERVE_N_PAGES", "int", 0,
         "Total pages of the paged serving KV pool (0 = derive "
         "n_slots*ceil(max_len/page_len): every slot's worst case at "
         "once).")
register("DPX_SERVE_PREFIX_SHARE", "bool", True,
         "Enable radix prefix sharing in the paged serving cache "
         "(refcounted reuse of resident full prompt pages; 0 = paged "
         "layout without sharing).")
register("DPX_SERVE_KV_DTYPE", "str", "f32",
         "Resident storage width of the paged serving KV pool: `f32` "
         "(exact pages — the bit-exact-tokens default contract), `q8` "
         "(block-int8 pages + per-page scales, ~3.9x resident tokens "
         "per byte) or `q4` (nibble-packed, ~7.5x). Dequant happens "
         "inside the one paged decode program (docs/serving.md "
         "\"Quantized resident pool\").")
register("DPX_SERVE_DISAGG", "bool", False,
         "Serve through the disaggregated prefill/decode split "
         "(serve/disagg/) where the front door supports it "
         "(examples/serve_lm.py honors it as the --disagg default; "
         "docs/serving.md).")
register("DPX_HANDOFF_WIDTH", "str", "f32",
         "Wire width of the disaggregated KV-page handoff frame: `f32` "
         "(exact — the bit-exact-tokens default contract), `q8` "
         "(block-int8 with per-page scales, ~4x fewer handoff bytes) "
         "or `q4` (nibble-packed, ~7.9x; serve/disagg/frames.py).")
register("DPX_HANDOFF_TIMEOUT_MS", "int", 30_000,
         "Deadline for a sent handoff frame to materialize in the "
         "decode pool; past it the request fails as a typed "
         "`HandoffTimeout` instead of waiting forever on a wedged "
         "prefill engine or transport (0 disables).")
register("DPX_FLEET_REPLICAS", "int", 2,
         "Default replica count of the multi-replica serving fleet "
         "(serve/fleet/FleetRouter; FleetConfig(n_replicas=) "
         "overrides — docs/serving.md \"Multi-replica fleet\").")
register("DPX_FLEET_SPILL_QUEUE", "int", 4,
         "Home-replica queue depth at which the fleet router "
         "proactively spills a request to the least-loaded replica "
         "instead of queueing behind known back-pressure (reactive "
         "spill on `queue_full`/`no_free_pages` rejection happens "
         "regardless; each spill is a from/to-attributed fleet_spill "
         "event).")
register("DPX_FLEET_MIN_REPLICAS", "int", 1,
         "Elasticity floor of the fleet autoscaler — sustained-ok "
         "drains never shrink the fleet below this many live replicas "
         "(serve/fleet/autoscale.py).")
register("DPX_FLEET_MAX_REPLICAS", "int", 4,
         "Elasticity ceiling of the fleet autoscaler — SLO-degraded "
         "scale-outs never grow the fleet past this many live "
         "replicas.")
register("DPX_FLEET_SCALE_RULES", "str", "",
         "SLO rule spec the fleet autoscaler evaluates (the "
         "obs/health.py rule grammar, e.g. "
         "`serve.ttft_ms.p99<=500;fleet.max_queue_depth<=8`); empty = "
         "serve/fleet/autoscale.py DEFAULT_FLEET_RULES (TTFT p99 "
         "ceiling + worst per-replica queue depth).")
register("DPX_FLEET_DRAIN_AFTER_OK", "int", 8,
         "Consecutive ok autoscaler evaluations required before a "
         "sustained-ok drain retires a replica — the scale-in half of "
         "the hysteresis (scale-out reacts on the first degraded "
         "verdict).")
register("DPX_SPEC_DECODE", "bool", False,
         "Enable speculative decoding in the serving engines "
         "(serve/spec/): a draft model proposes DPX_SPEC_DRAFT_LEN "
         "tokens per iteration, one batched verify program scores "
         "them, only accepted tokens commit. Requires "
         "EngineConfig(draft_model=, draft_params=); greedy requests "
         "only (docs/serving.md \"Speculative decoding\").")
register("DPX_SPEC_DRAFT_LEN", "int", 4,
         "Draft tokens proposed per speculative iteration (k); the "
         "verify program scores k+1 positions and emits between 1 and "
         "k+1 tokens. One verify compile per distinct k.")
register("DPX_SERVE_TENANT_MAX_INFLIGHT", "int", 0,
         "Per-tenant inflight-request quota of the serving front door "
         "(0 = unlimited): a tenant at its cap gets a synchronous "
         "typed AdmissionRejected(reason=\"tenant_quota\") with "
         "tenant attribution instead of queueing.")

# -- torch front door / benches --------------------------------------------
register("DPX_WEIGHT_UPDATE", "str", "replicated",
         "Default weight-update mode of `parallel.make_train_step`: "
         "`replicated` (every rank runs the full optimizer step) or "
         "`sharded` (ZeRO-1 reduce-scatter/local-step/all-gather on "
         "the quantized ring, docs/optimizer_sharding.md).")
register("DPX_GRAD_REDUCE", "str", "mean",
         "Default gradient-reduction wire of the torch-compat DDP "
         "wrapper: `mean` (exact) or `quant` (block-int8 ring, "
         "docs/comms.md).")
register("DPX_TORCH_THREADS", "int", 8,
         "Torch intra-op thread count pinned by bench.py for stable "
         "A/B comparisons.")
register("DPX_BENCH_SELFLOG", "bool", True,
         "bench.py appends its own records to the default results log "
         "(set 0 to disable).")
register("DPX_BENCH_TRIALS", "int", 5,
         "Repeated-trial count of the perfbench statistical policy "
         "(perfbench/stats.py; docs/benchmarking.md).")
register("DPX_BENCH_WARMUP", "int", 1,
         "Leading trials discarded as warmup before median/IQR "
         "aggregation (a cold first trial is far off the warm ones).")
register("DPX_BENCH_MAX_SPREAD", "float", 0.15,
         "Hard spread gate (IQR/median) above which trial stats are "
         "marked untrusted and vs_baseline ratios are structurally "
         "withheld (perfbench/stats.py).")
register("DPX_BENCH_AFFINITY", "int", 8,
         "Pin benchmark processes to the first N allowed CPUs for "
         "run-to-run comparability (0 = leave affinity alone; "
         "perfbench/stats.pin_process — the dp8 bench child reads this, "
         "so it actually governs the pinning it documents).")
register("DPX_BENCH_BUDGET_S", "float", 120.0,
         "Wall-clock budget of stats.measure_until's hunt for a "
         "stationary trial window on a contended host (perfbench/"
         "stats.py; the loopback dp8 smoke runs under it).")
register("DPX_BENCH_SHARDED_ELEMS", "int", 0,
         "Bucket elements of the dp8_sharded_adam bench arm (0 = the "
         "full-size default; the CI smoke sets a small bucket to stay "
         "seconds-scale — bench.py).")
register("DPX_BENCH_HIER_ELEMS", "int", 0,
         "Bucket elements of the dp8_hier_adaptive bench arm (0 = the "
         "full-size default; the CI smoke sets a small bucket to stay "
         "seconds-scale — bench.py).")
register("DPX_BENCH_MIN_DROP", "float", 0.10,
         "Regression-sensitivity floor of tools/benchdiff.py: changes "
         "smaller than this are never flagged even when spreads are "
         "tiny.")

# -- external ---------------------------------------------------------------
register("JAX_PLATFORMS", "str", None,
         "JAX platform selection, honoured as JAX defines it: `cpu` for "
         "tests and sandbox runs, unset on a TPU host.", external=True)
register("JAX_COMPILATION_CACHE_DIR", "str", None,
         "Directory of XLA's persistent compilation cache. When set, "
         "JAX's own handling places the cache and the code sets no "
         "directory; unset, `runtime/compile_cache.py` uses the fixed "
         "in-checkout `<repo>/.jax_cache`.", external=True)
register("MASTER_ADDR", "str", "localhost",
         "torch.distributed rendezvous address (torch-compat shim "
         "convention).", external=True)
register("MASTER_PORT", "int", 29_500,
         "torch.distributed rendezvous port (torch-compat shim "
         "convention).", external=True)
register("CUDA_VISIBLE_DEVICES", "str", None,
         "CUDA device visibility — consulted by the torch-compat shim's "
         "device-count fallback.", external=True)
register("TPU_VISIBLE_DEVICES", "str", None,
         "TPU chip visibility; the multiprocess front door sets it to "
         "give child rank r chip r.", external=True)
register("TPU_CHIPS_PER_PROCESS_BOUNDS", "str", None,
         "TPU runtime topology bound set for single-chip child "
         "processes.", external=True)
register("TPU_PROCESS_BOUNDS", "str", None,
         "TPU runtime process-grid bound set for single-chip child "
         "processes.", external=True)
register("TPU_WORKER_HOSTNAMES", "str", None,
         "Comma-separated pod worker hostnames (multi-host discovery).",
         external=True)
register("MEGASCALE_COORDINATOR_ADDRESS", "str", None,
         "Megascale/DCN coordinator address — its presence marks a "
         "multi-slice deployment.", external=True)
register("PYTHONPATH", "str", None,
         "Python module search path; the benchmark subprocess runner "
         "prepends the repo root for every child "
         "(perfbench/runner.py).", external=True)
