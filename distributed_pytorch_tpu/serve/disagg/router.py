"""The disaggregated serving front door (``serve/disagg/``).

:class:`DisaggEngine` keeps the PR 3 contract — ``submit(prompt,
SamplingParams, rng=...) → RequestHandle`` with a future, streaming
callbacks, and per-request SLO metrics — while running prefill and
decode as SEPARATE ENGINES connected by the quantized KV-page handoff:

    submit() → AdmissionScheduler → PrefillEngine (radix reuse, tail
    prefill, extract pages, encode frame) → transport (block-q8/q4 wire
    or exact f32; DPX_HANDOFF_WIDTH) → DecodeEngine (integrity check,
    adopt pages via the alloc/refcount path, sample token 0, decode
    loop) → future / streaming

The router owns the pieces both engines need one authority for: the
admission queue, the request registry, the handoff-in-flight set (the
decode loop sweeps it against ``DPX_HANDOFF_TIMEOUT_MS``), and the ONE
completion path — every retirement and every typed failure funnels
through :meth:`finish_ok` / :meth:`fail` under a lock, so a request can
never resolve twice no matter which engine observed its fate first.

Failure containment (the reason the subsystem exists, chaos-tested):
:meth:`on_prefill_dead` fails ONLY the requests still on the prefill
side — queued, mid-prefill, or sent-but-unreceived — each as a typed
``PrefillEngineDied`` with request + engine attribution, and flips the
front door to reject new submissions; every decode-resident stream
keeps producing tokens bit-identical to ``generate()``.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from ...models.generate import (_check_attn_compatible, _model_window,
                                refuse_mixed, refuse_mixers)
from ...obs import metrics as dpxmon
from ...obs import trace as dpxtrace
from ...runtime import env as dpxenv
from ...utils.logging import MetricsLogger
from ..engine import _default_buckets
from ..metrics import emit_request_trace, request_record
from ..scheduler import AdmissionScheduler
from ..spec import SpecConfig
from ..types import (FAILED, FINISHED, AdmissionRejected, EngineStopped,
                     HandoffCorrupt, HandoffTimeout, PrefillEngineDied,
                     Request, RequestDeadlineExceeded, RequestHandle,
                     SamplingParams)
from .decode import DecodeEngine
from .prefill import PrefillEngine
from .transport import LocalTransport


@dataclass
class DisaggConfig:
    """Shape and policy of the disaggregated split. ``n_slots`` ×
    ``max_len`` budgets the DECODE pool (the monolithic
    ``EngineConfig`` semantics); the prefill pool only ever holds
    prompts (``prefill_pages``, default 4x one max-bucket prompt, so
    the radix index has residency to hit). ``handoff_width`` selects
    the frame wire (``f32`` exact — the bit-exact default — or
    ``q8``/``q4``); None knobs default from the typed env registry
    (``DPX_HANDOFF_WIDTH`` / ``DPX_HANDOFF_TIMEOUT_MS`` /
    ``DPX_SERVE_PAGE_LEN`` / ``DPX_SERVE_N_PAGES`` /
    ``DPX_SERVE_PREFIX_SHARE``)."""

    n_slots: int = 4
    max_len: int = 256
    buckets: Optional[Tuple[int, ...]] = None
    max_queue: int = 64
    metrics: Optional[MetricsLogger] = None
    log_every: int = 16
    allow_custom_attn: bool = False
    page_len: Optional[int] = None
    n_pages: Optional[int] = None          # decode pool
    prefill_pages: Optional[int] = None    # prefill pool
    prefix_share: Optional[bool] = None
    handoff_width: Optional[str] = None    # "f32" | "q8" | "q4"
    handoff_timeout_ms: Optional[int] = None
    # resident storage width of BOTH pools ("f32" | "q8" | "q4"; None =
    # DPX_SERVE_KV_DTYPE). When it matches handoff_width the frame
    # carries the prefill pool's resident bits verbatim and the decode
    # pool adopts them verbatim — no dequant→requant double hop
    # (docs/serving.md "Quantized resident pool").
    kv_dtype: Optional[str] = None
    # speculative decoding on the DECODE side (serve/spec/;
    # docs/serving.md "Speculative decoding"): same semantics as the
    # monolithic EngineConfig — the draft loop lives in the
    # DecodeEngine, which owns token cadence. None spec_decode /
    # draft_len default from DPX_SPEC_DECODE / DPX_SPEC_DRAFT_LEN.
    spec_decode: Optional[bool] = None
    draft_model: Any = None
    draft_params: Any = None
    draft_len: Optional[int] = None


class DisaggEngine:
    """Disaggregated prefill/decode serving over ``TransformerLM``
    params — the drop-in for :class:`~..engine.InferenceEngine` when a
    long prefill must never stall decode cadence.

    >>> eng = DisaggEngine(model, params, DisaggConfig(n_slots=4))
    >>> eng.start()
    >>> h = eng.submit(prompt_ids, SamplingParams(max_new_tokens=32))
    >>> tokens = h.result(timeout=60)
    >>> eng.shutdown()
    """

    def __init__(self, model, params,
                 config: Optional[DisaggConfig] = None, *,
                 transport=None):
        from . import frames
        self.config = cfg = config or DisaggConfig()
        if cfg.n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {cfg.n_slots}")
        _check_attn_compatible(model, cfg.allow_custom_attn)
        refuse_mixed(model, "the disaggregated hand-off (serve/disagg)")
        refuse_mixers(model, "the disaggregated hand-off (serve/disagg)")
        if _model_window(model) is not None:
            raise ValueError(
                "disaggregated serving runs on the paged KV cache and "
                "hands no sliding-window model off: the monolithic "
                "InferenceEngine serves one told its windows "
                "(TransformerLM(layer_windows=...))")
        if (getattr(model, "pos", None) is not None
                and cfg.max_len > model.max_seq):
            raise ValueError(
                f"max_len {cfg.max_len} exceeds the model's max_seq "
                f"({model.max_seq})")
        self.model = model
        self.params = params
        self.buckets = tuple(sorted(cfg.buckets)) if cfg.buckets \
            else _default_buckets(cfg.max_len)
        if max(self.buckets) > cfg.max_len:
            raise ValueError(
                f"largest prefill bucket ({max(self.buckets)}) exceeds "
                f"max_len ({cfg.max_len}) — the decode pool cannot "
                f"hold it")
        width = cfg.handoff_width if cfg.handoff_width is not None \
            else dpxenv.get("DPX_HANDOFF_WIDTH")
        self.handoff_width = width
        bits = frames.resolve_handoff_bits(width)
        self.handoff_timeout_ms = (
            cfg.handoff_timeout_ms if cfg.handoff_timeout_ms is not None
            else dpxenv.get("DPX_HANDOFF_TIMEOUT_MS"))
        page_len = (cfg.page_len if cfg.page_len is not None
                    else dpxenv.get("DPX_SERVE_PAGE_LEN"))
        n_pages = (cfg.n_pages if cfg.n_pages is not None
                   else dpxenv.get("DPX_SERVE_N_PAGES"))
        if not n_pages:
            n_pages = cfg.n_slots * (-(-cfg.max_len // page_len))
        share = (cfg.prefix_share if cfg.prefix_share is not None
                 else dpxenv.get("DPX_SERVE_PREFIX_SHARE"))
        prefill_pages = cfg.prefill_pages or \
            4 * (-(-max(self.buckets) // page_len))
        self.metrics = cfg.metrics
        self.scheduler = AdmissionScheduler(cfg.max_queue)
        self.transport = transport if transport is not None \
            else LocalTransport()
        if not getattr(self.transport, "pollable", True):
            # the decode loop drains the transport BETWEEN tokens with
            # recv(0) polls; a transport whose recv can only block
            # (HostCommTransport — a broadcast cannot return "nothing
            # yet") would stall cadence on the channel and misread an
            # idle prefill peer as dead, so it is refused up front
            raise ValueError(
                f"{type(self.transport).__name__} is not pollable — "
                f"the DisaggEngine decode loop needs a non-blocking "
                f"recv; drive a blocking cross-process transport from "
                f"a dedicated receiver instead (see "
                f"serve/disagg/transport.py)")
        kv_dtype = (cfg.kv_dtype if cfg.kv_dtype is not None
                    else dpxenv.get("DPX_SERVE_KV_DTYPE"))
        self.kv_dtype = kv_dtype
        self.prefill = PrefillEngine(
            model, params, self, self.transport, buckets=self.buckets,
            page_len=page_len, n_pages=prefill_pages,
            prefix_share=bool(share), bits=bits, kv_dtype=kv_dtype)
        spec_on = (cfg.spec_decode if cfg.spec_decode is not None
                   else dpxenv.get("DPX_SPEC_DECODE"))
        spec = None
        if spec_on:
            if cfg.draft_model is None or cfg.draft_params is None:
                raise ValueError(
                    "spec_decode=True requires draft_model and "
                    "draft_params (DisaggConfig) — there is nothing "
                    "to propose with")
            draft_len = (cfg.draft_len if cfg.draft_len is not None
                         else dpxenv.get("DPX_SPEC_DRAFT_LEN"))
            spec = SpecConfig(draft_model=cfg.draft_model,
                              draft_params=cfg.draft_params,
                              draft_len=int(draft_len))
        self.decode = DecodeEngine(
            model, params, self, self.transport, n_slots=cfg.n_slots,
            max_len=cfg.max_len, page_len=page_len, n_pages=n_pages,
            kv_dtype=kv_dtype, spec=spec, buckets=self.buckets)
        # per-tenant admission quota (DPX_SERVE_TENANT_MAX_INFLIGHT;
        # 0 = unlimited): inflight counts move under _lock, released
        # in the one exactly-once completion path (_resolve)
        self._tenant_max = int(dpxenv.get("DPX_SERVE_TENANT_MAX_INFLIGHT"))
        self._tenant_inflight: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._handoff: Dict[int, Request] = {}   # sent, not yet adopted
        self._requests: Dict[int, Request] = {}  # all in-flight
        self._next_id = 0
        self._completed = 0
        self._failed = 0
        self._stop = False
        self._started = False
        self._prefill_dead_cause: Optional[Exception] = None
        self._crash: Optional[Exception] = None

    # -- front door --------------------------------------------------------

    def submit(self, prompt, params: Optional[SamplingParams] = None, *,
               rng=None, on_token=None,
               tenant: Optional[str] = None) -> RequestHandle:
        """Enqueue one request; same contract as
        ``InferenceEngine.submit`` (synchronous typed
        ``AdmissionRejected`` when it can never be served, bounded
        queue, per-request PRNG split schedule identical to
        ``generate()``, per-tenant inflight quota via ``tenant``)."""
        sp = params or SamplingParams()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self._lock:
            if self._stop:
                raise EngineStopped("engine is shut down")  # dpxlint: disable=DPX004 pre-admission, no request id assigned yet
            rid = self._next_id
            self._next_id += 1
        self._validate(prompt, sp, rid)
        if rng is None:
            rng = jax.random.PRNGKey(rid)
        rngs = np.asarray(jax.random.split(rng, sp.max_new_tokens))
        now = time.monotonic()
        req = Request(request_id=rid, prompt=prompt, params=sp,
                      rngs=rngs, submit_t=now,
                      deadline_t=(now + sp.deadline_ms / 1e3
                                  if sp.deadline_ms is not None
                                  else None),
                      on_token=on_token, tenant=tenant,
                      stage="prefill_queue",
                      trace_id=dpxtrace.new_trace_id())
        req.handle = RequestHandle(req)
        with self._lock:
            if self._stop:
                raise EngineStopped("engine is shut down",
                                    request_id=rid)
            if self._prefill_dead_cause is not None:
                exc = AdmissionRejected(
                    f"request {rid}: the prefill engine is dead — "
                    f"decode-resident streams continue, new admissions "
                    f"are refused", reason="prefill_dead",
                    request_id=rid)
                exc.__cause__ = self._prefill_dead_cause
                raise exc
            if (tenant is not None and self._tenant_max > 0
                    and self._tenant_inflight.get(tenant, 0)
                    >= self._tenant_max):
                dpxmon.inc("serve.rejected")
                dpxmon.inc(f"serve.rejected.tenant.{tenant}")
                raise AdmissionRejected(
                    f"request {rid}: tenant {tenant!r} already has "
                    f"{self._tenant_inflight[tenant]} inflight "
                    f"request(s) (DPX_SERVE_TENANT_MAX_INFLIGHT="
                    f"{self._tenant_max})", reason="tenant_quota",
                    tenant=tenant, request_id=rid)
            self.scheduler.submit(req)   # may raise AdmissionRejected
            self._requests[rid] = req
            if tenant is not None:
                self._tenant_inflight[tenant] = \
                    self._tenant_inflight.get(tenant, 0) + 1
        self.prefill.wake()
        return req.handle

    def _validate(self, prompt, sp: SamplingParams, rid: int) -> None:
        s = int(prompt.shape[0])
        if s < 1 or sp.max_new_tokens < 1:
            raise AdmissionRejected(
                f"request {rid}: empty prompt or max_new_tokens < 1",
                reason="invalid", request_id=rid)
        if s > max(self.buckets):
            raise AdmissionRejected(
                f"request {rid}: prompt length {s} exceeds the largest "
                f"prefill bucket ({max(self.buckets)})",
                reason="prompt_too_long", request_id=rid)
        if s + sp.max_new_tokens > self.config.max_len:
            raise AdmissionRejected(
                f"request {rid}: prompt ({s}) + max_new_tokens "
                f"({sp.max_new_tokens}) exceeds the decode pool "
                f"({self.config.max_len})",
                reason="too_long", request_id=rid)
        L = self.decode.pool.page_len
        worst = -(-(s + sp.max_new_tokens - 1) // L)
        if worst > self.decode.pool.n_pages:
            raise AdmissionRejected(
                f"request {rid}: worst-case page need ({worst}) exceeds "
                f"the decode page pool ({self.decode.pool.n_pages} "
                f"pages of {L})", reason="no_free_pages",
                request_id=rid)
        if -(-s // self.prefill.pool.page_len) > self.prefill.pool.n_pages:
            raise AdmissionRejected(
                f"request {rid}: prompt needs "
                f"{-(-s // self.prefill.pool.page_len)} page(s), more "
                f"than the whole prefill pool "
                f"({self.prefill.pool.n_pages})",
                reason="no_free_pages", request_id=rid)

    def start(self) -> "DisaggEngine":
        if self._started:
            raise RuntimeError("engine already started")
        self._started = True
        self.decode.start()
        self.prefill.start()
        return self

    def shutdown(self, wait: bool = True) -> None:
        with self._lock:
            self._stop = True
        self.prefill.stop(wait=wait)
        self.decode.stop(wait=wait)
        self._drain_on_stop()

    def __enter__(self) -> "DisaggEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- the one completion path ------------------------------------------

    def _resolve(self, req: Request) -> bool:
        """Claim the right to resolve ``req`` (exactly-once, under the
        lock); False if another path already did."""
        with self._lock:
            if req.done:
                return False
            self._requests.pop(req.request_id, None)
            self._handoff.pop(req.request_id, None)
            if req.tenant is not None:
                # the tenant's inflight credit returns at ANY terminal
                # transition — this gate is the one place both paths
                # (retire and typed failure) funnel through exactly once
                n = self._tenant_inflight.get(req.tenant, 0)
                if n <= 1:
                    self._tenant_inflight.pop(req.tenant, None)
                else:
                    self._tenant_inflight[req.tenant] = n - 1
            return True

    def finish_ok(self, req: Request) -> None:
        if not self._resolve(req):
            return
        req.state = FINISHED
        with self._lock:
            self._completed += 1
        rec = request_record(req, "ok")
        req.handle.metrics = rec
        # dpxmon SLO instruments (obs/metrics.py): same window
        # histograms as the monolithic engine, so the p99-ceiling
        # health rules cover both front doors
        dpxmon.inc("serve.completed")
        if rec["ttft_ms"] is not None:
            dpxmon.observe("serve.ttft_ms", rec["ttft_ms"])
            if req.tenant is not None:
                dpxmon.observe(f"serve.ttft_ms.tenant.{req.tenant}",
                               rec["ttft_ms"])
        if rec["tpot_ms"] is not None:
            dpxmon.observe("serve.tpot_ms", rec["tpot_ms"])
            if req.tenant is not None:
                dpxmon.observe(f"serve.tpot_ms.tenant.{req.tenant}",
                               rec["tpot_ms"])
        if self.metrics is not None:
            self.metrics.event("serve_request", **rec)
        emit_request_trace(req, "ok")
        req.handle.future.set_result(
            np.asarray(req.out_tokens, np.int32))

    def fail(self, req: Request, exc: Exception, outcome: str) -> None:
        if not self._resolve(req):
            return
        req.state = FAILED
        with self._lock:
            self._failed += 1
        rec = request_record(req, outcome)
        req.handle.metrics = rec
        dpxmon.inc("serve.failed")
        dpxmon.inc(f"serve.outcome.{outcome}")
        if self.metrics is not None:
            self.metrics.event("serve_request", **rec)
        emit_request_trace(req, outcome)
        from ..types import HandoffError, PagePoolExhausted
        if isinstance(exc, (HandoffError, PagePoolExhausted)):
            # infra-failure postmortem (obs/trace.py): the split's
            # recent span timeline rides out with the typed error
            dpxtrace.on_typed_failure(exc)
        req.handle.future.set_exception(exc)

    def fail_queued_deadline(self, req: Request) -> None:
        self.fail(req, RequestDeadlineExceeded(
            f"request {req.request_id} missed its deadline "
            f"({req.params.deadline_ms} ms) while queued for prefill",
            deadline_ms=req.params.deadline_ms, stage="queued",
            request_id=req.request_id,
            iteration=self.prefill.iterations),
            outcome="deadline_queued")

    def fail_handoff_corrupt(self, exc: HandoffCorrupt,
                             iteration: int) -> None:
        """Route a corrupt frame to its request when the header named
        one; unattributable damage (bad magic, truncated header) means
        the channel itself cannot be trusted — treated as prefill-side
        death, decode residents unaffected."""
        req = None
        if exc.request_id is not None:
            with self._lock:
                req = self._requests.get(exc.request_id)
        if req is not None:
            exc.iteration = iteration
            self.fail(req, exc, outcome="handoff_corrupt")
        else:
            self.on_prefill_dead(exc)

    # -- handoff bookkeeping ----------------------------------------------

    def enter_handoff(self, req: Request) -> None:
        with self._lock:
            self._handoff[req.request_id] = req

    def take_handoff(self, request_id: int) -> Optional[Request]:
        with self._lock:
            return self._handoff.pop(request_id, None)

    def handoff_count(self) -> int:
        with self._lock:
            return len(self._handoff)

    def sweep_handoff_timeouts(self, now: float, iteration: int) -> None:
        """Fail (typed ``HandoffTimeout``) every sent frame that outran
        ``DPX_HANDOFF_TIMEOUT_MS`` — called by the decode loop each
        iteration, so a wedged prefill engine or transport cannot park
        a request forever."""
        tmo = self.handoff_timeout_ms
        if not tmo:
            return
        with self._lock:
            late = [r for r in self._handoff.values()
                    if r.handoff_send_t is not None
                    and (now - r.handoff_send_t) * 1e3 >= tmo]
        for req in late:
            self.fail(req, HandoffTimeout(
                f"request {req.request_id}: handoff frame not "
                f"materialized within {tmo} ms of send",
                deadline_ms=float(tmo), engine="transport",
                request_id=req.request_id, iteration=iteration),
                outcome="handoff_timeout")

    # -- failure domains ---------------------------------------------------

    def on_prefill_dead(self, cause: Exception) -> None:
        """The prefill engine is gone (crash, severed transport,
        injected kill). Fail ONLY its side of the handoff — queued,
        mid-prefill, sent-but-unreceived — typed and attributed; flip
        the front door to reject new work; leave every decode-resident
        stream running."""
        with self._lock:
            if self._prefill_dead_cause is not None:
                return
            self._prefill_dead_cause = cause
            victims = list(self._handoff.values())
        victims += self.prefill.drain_requests()
        victims += self.scheduler.drain()
        for req in victims:
            exc = PrefillEngineDied(
                f"request {req.request_id} lost in stage "
                f"{req.stage}: the prefill engine died "
                f"({cause!r}) — decode-resident streams continue",
                request_id=req.request_id, engine="prefill",
                iteration=self.prefill.iterations)
            exc.__cause__ = cause
            self.fail(req, exc, outcome="prefill_died")

    def on_decode_crash(self, cause: Exception) -> None:
        """A decode-loop crash strands every future — fail them all
        typed with the cause chained, then stop serving (the monolithic
        engine's crash-drain contract)."""
        self._crash = cause
        with self._lock:
            self._stop = True
        self.prefill.stop(wait=False)
        self.transport.abort()
        self._drain_on_stop()

    def _drain_on_stop(self) -> None:
        cause = f" (engine crashed: {self._crash!r})" \
            if self._crash is not None else ""
        victims = self.scheduler.drain() + self.prefill.drain_requests() \
            + self.decode.drain_requests()
        with self._lock:
            victims += list(self._handoff.values())
        for req in victims:
            exc = EngineStopped(
                f"engine stopped with request {req.request_id} in "
                f"stage {req.stage}{cause}",
                request_id=req.request_id,
                iteration=self.decode.iterations)
            exc.__cause__ = self._crash
            self.fail(req, exc, outcome="engine_stopped")

    # -- introspection -----------------------------------------------------

    def stats(self) -> Dict:
        """Split-aware engine stats. The compile-discipline gates live
        here: ``decode.decode_compiles == 1`` and
        ``prefill.decode_compiles == 0`` after any workload — the split
        must not multiply programs (asserted in tests + CI smoke)."""
        tstats = self.transport.stats.summary()
        return {
            "completed": self._completed,
            "failed": self._failed,
            "queue_depth": len(self.scheduler),
            "buckets": self.buckets,
            "handoff_width": self.handoff_width,
            "prefill": self.prefill.stats(),
            "decode": self.decode.stats(),
            "handoff": {
                "in_flight": self.handoff_count(),
                "frames_sent": self.transport.frames_sent,
                "frames_recv": self.transport.frames_recv,
                "bytes_sent": int(tstats.get("handoff_send", {})
                                  .get("bytes", 0)),
                "bytes_recv": int(tstats.get("handoff_recv", {})
                                  .get("bytes", 0)),
            },
        }

    def periodic_metrics(self, iteration: int) -> None:
        """Emit the periodic engine snapshot (decode-loop cadence)
        through the ONE dpxmon registry path (obs/metrics.py) — the
        ad-hoc ``kind="serve_disagg_engine"`` step records are gone;
        the split's queue/occupancy/handoff gauges ride the same
        rank-attributed ``metrics_snapshot`` stream the health rules
        and ``tools/dpxmon.py`` read."""
        if self.metrics is None or iteration % self.config.log_every:
            return
        if not dpxmon.enabled():
            return
        d = self.decode.stats()
        dpxmon.set_gauge("serve.queue_depth", len(self.scheduler))
        dpxmon.set_gauge("serve.handoff_in_flight",
                         self.handoff_count())
        dpxmon.set_gauge("serve.active_slots", d["active_slots"])
        dpxmon.set_gauge("serve.pending_handoffs",
                         d["pending_handoffs"])
        dpxmon.set_gauge("serve.tokens_emitted", d["tokens_emitted"])
        dpxmon.set_gauge("serve.pool_occupancy",
                         d["pages"]["pool_occupancy"])
        dpxmon.set_gauge("serve.kv_bits", d["pages"]["kv_bits"])
        dpxmon.set_gauge("serve.kv_pool_bytes",
                         d["pages"]["kv_pool_bytes"])
        dpxmon.set_gauge("serve.bytes_per_resident_token",
                         d["pages"]["bytes_per_resident_token"])
        dpxmon.set_gauge("serve.handoff_bytes_sent", int(
            self.transport.stats.summary()
            .get("handoff_send", {}).get("bytes", 0)))
        if self.decode.spec_proposed:
            dpxmon.set_gauge(
                "serve.spec_acceptance_rate",
                self.decode.spec_accepted / self.decode.spec_proposed)
            dpxmon.set_gauge(
                "serve.spec_tokens_per_iteration",
                self.decode.spec_tokens / self.decode.spec_iters
                if self.decode.spec_iters else 0.0)
        dpxmon.emit_snapshot(path=self.metrics.path, step=iteration,
                             source="serve_disagg_engine")
