"""The continuous-batching inference engine (Orca-style iteration-level
scheduling over a shared decode batch).

One engine thread runs the iteration loop; each iteration

1. fires the fault-injection hooks (``DPX_FAULT`` — docs/serving.md),
2. sweeps deadlines (queued AND running requests; a miss surfaces as a
   typed ``RequestDeadlineExceeded`` on that request's future, other
   slots untouched),
3. prefills at most ONE chunk of one prompt (at most
   ``serve.pages.cache.chunk_tokens`` tokens, right-padded to a length
   bucket — one compile per bucket), so that an admitted prompt stalls
   the running rows for a chunk and not for its whole prefill, and
4. advances EVERY active slot one token through the single jitted
   decode program (``serve.pages.PagedSlotPool``), retiring slots that hit
   ``max_new_tokens`` / ``eos_token`` so the next iteration can refill
   them. ONE decode pass is kept in flight (``_decode_all``): an
   iteration dispatches the next pass, whose tokens argument is the
   pass before's output still on the device, and only then reads the
   pass before and emits it, so the chip does not wait for the host
   between two passes. A prompt's first token is taken after that read.

Determinism contract: each request's token stream is identical to a
standalone ``models.generate.generate`` call with the same params/rng
(same per-request ``jax.random.split`` schedule, same ``_sample``;
asserted in tests/test_serve.py). Logits agree with the standalone
pipeline to ~1 ulp — XLA fuses differently at different batch shapes —
which is why the contract is over token streams, not logit bits.

**A model that generates by blocks** (``TransformerLM(gen_block=L)``;
docs/serving.md "Generation by blocks") takes step 4 as a BLOCK STEP
(``_block_all``): every running row holds a block of ``L`` positions,
one program an iteration runs one pass of every row's block, whatever
pass each row is in, and fills positions of it on the device; a block's
tokens are streamed when its last position is filled; prefill emits
nothing. The blocks, tokens and masks, stay on the device from one
program to the next, and ONE block pass is kept in flight as a decode
pass is: how many positions a pass fills is fixed at admission, so the
host counts what each row's next pass is (a fill, its commit, none)
without reading the last, dispatches it, and only then reads the pass
before: which positions were filled, with what, and the blocks that
became clean, which it streams. Greedy requests on the exact paged pool
only: everything else refuses such a model by name
(``nn.paged.block_unsupported``).

SLO metrics (TTFT/TPOT/queue depth/slot occupancy, defined in
``serve.metrics``) flow into the line-JSON ``MetricsLogger`` stream.

Where the engine thread's time goes is on two instruments
(docs/observability.md): dpxtrace spans ``serve.*`` around every phase
of an iteration down to its one token fetch and each row's emit — profiler
annotations, so a ``jax.profiler`` session lays them against the device
ops — and the always-on ``stats()["host_ns"]`` counters, cumulative
nanoseconds a phase, read twice an iteration and never per row.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import numpy as np

from ..models.generate import (_check_attn_compatible, _model_window,
                               block_unsupported, refuse_mixed)
from ..obs import metrics as dpxmon
from ..obs import trace as dpxtrace
from ..runtime import compile_cache
from ..runtime import env as dpxenv
from ..runtime import faults
from ..utils.logging import MetricsLogger
from .metrics import emit_request_trace, request_record
from .pages import PagedSlotPool, chunk_tokens
from .sampling import RowSampler, fill_counts
from .scheduler import AdmissionScheduler
from .spec import SpecConfig, SpecState, accept_greedy
from .types import (FAILED, FINISHED, PREFILLING, QUEUED, RUNNING,
                    AdmissionRejected, EngineStopped, PagePoolExhausted,
                    Request, RequestDeadlineExceeded, RequestHandle,
                    SamplingParams, SpecDecodeError)


class _Pass(NamedTuple):
    """A decode pass dispatched and not yet read."""

    #: the iteration whose ``serve.decode.dispatch`` launched it
    iteration: int
    #: (n_slots,) int32 on the device: every row's token of this pass,
    #: after the samplers' merge; the next pass's argument as it stands
    tokens: Any
    #: slot -> the REQUEST that held it at dispatch: by the read the
    #: slot (and its pages) may belong to another
    rows: Dict[int, Request]


class _BlockPass(NamedTuple):
    """A block pass dispatched and not yet read: the block path's
    :class:`_Pass`."""

    iteration: int
    #: (n_slots, 3, L) int32 on the device: the rows' blocks after this
    #: pass, the positions it filled and the positions still masked. The
    #: next pass runs over this same array (``pool.blocks``, not donated)
    out: Any
    #: slot -> (the REQUEST that held it at dispatch; its pass of its
    #: block, -1 the commit pass; whether the pass fills the block's
    #: last masked position, so that the read streams the block)
    rows: Dict[int, Tuple[Request, int, bool]]


def _default_buckets(cap: int) -> Tuple[int, ...]:
    """Power-of-two prefill buckets up to ``cap`` (inclusive) — a
    bounded set of compile variants covering every admissible prompt."""
    out, b = [], 8
    while b < cap:
        out.append(b)
        b *= 2
    out.append(cap)
    return tuple(sorted(set(out)))


@dataclass
class EngineConfig:
    """Engine shape and policy. ``n_slots`` requests run at once, each
    of at most ``max_len`` positions; the KV memory budget is the page
    pool's, ``n_pages`` pages of ``page_len`` positions (fixed at
    startup — serving never reallocates; by default what ``n_slots``
    rows of ``max_len`` need). ``buckets`` are the padded lengths of a
    prefill chunk (None = powers of two up to ``max_len``); ``max_queue``
    bounds admission; ``metrics`` is an optional line-JSON
    ``MetricsLogger`` receiving per-request SLO events and periodic
    occupancy records."""

    n_slots: int = 4
    max_len: int = 256
    buckets: Optional[Tuple[int, ...]] = None
    max_queue: int = 64
    metrics: Optional[MetricsLogger] = None
    log_every: int = 16
    allow_custom_attn: bool = False
    # not an option: the engine's one pool is the page pool, and False
    # raises. The word is kept only until a ``benchmark`` PR drops the
    # key ``"paged": true`` from the five traffic files under
    # chipbench/traffic/, which build ``EngineConfig(**engine)``.
    paged: bool = True
    # the page pool (serve/pages/; docs/serving.md): a refcounted block
    # pool in which identical prompt prefixes are computed once; the
    # None knobs default from the typed env registry (DPX_SERVE_PAGE_LEN
    # / DPX_SERVE_N_PAGES / DPX_SERVE_PREFIX_SHARE).
    page_len: Optional[int] = None
    n_pages: Optional[int] = None
    prefix_share: Optional[bool] = None
    # resident KV storage width (docs/serving.md "Quantized resident
    # pool"): "f32" exact (default) | "q8" | "q4". None defaults from
    # DPX_SERVE_KV_DTYPE.
    kv_dtype: Optional[str] = None
    # speculative decoding (serve/spec/; docs/serving.md "Speculative
    # decoding"): a draft model proposes draft_len tokens per
    # iteration, one batched verify program scores them, only accepted
    # tokens commit. None spec_decode/draft_len default from the typed
    # env registry (DPX_SPEC_DECODE / DPX_SPEC_DRAFT_LEN); enabling
    # spec without a draft model+params raises at construction. Only
    # greedy (temperature 0) requests speculate; others share the same
    # batch non-speculatively.
    spec_decode: Optional[bool] = None
    draft_model: Any = None
    draft_params: Any = None
    draft_len: Optional[int] = None
    # reshard-free admit (docs/front_door.md): the params handed to the
    # engine must ALREADY carry these shardings — typically a train
    # step's ``out_shardings["params"]`` (parallel.handoff_shardings).
    # Admission then never copies or reshards the weights; a mismatch
    # raises a typed HandoffMismatch at construction instead of pjit
    # silently resharding on the first prefill.
    param_shardings: Optional[Any] = None


class InferenceEngine:
    """Threaded serving front door over ``TransformerLM`` params.

    >>> eng = InferenceEngine(model, params, EngineConfig(n_slots=4))
    >>> eng.start()
    >>> h = eng.submit(prompt_ids, SamplingParams(max_new_tokens=32))
    >>> tokens = h.result(timeout=60)   # np (n,) int32
    >>> eng.shutdown()
    """

    def __init__(self, model, params, config: Optional[EngineConfig] = None):
        self.config = cfg = config or EngineConfig()
        compile_cache.enable()
        if cfg.n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {cfg.n_slots}")
        _check_attn_compatible(model, cfg.allow_custom_attn)
        if not cfg.paged:
            raise ValueError(
                "EngineConfig(paged=False): the contiguous slot pool is "
                "gone, the engine's one pool is the page pool "
                "(serve.pages.PagedSlotPool), which chunks a prompt of "
                "any length the slot holds; a sliding-window model is "
                "served through TransformerLM(layer_windows=...), a ring "
                "of O(window) a slot and window layer")
        if _model_window(model) is not None:
            # a width the model's attn_fn bakes in for every layer; a
            # model TOLD its layers' windows is served
            raise ValueError(
                "the engine does not serve a sliding-window model whose "
                "width only its attn_fn carries: tell the model its "
                "windows (TransformerLM(layer_windows=(W,) * n_layers)) "
                "and the page pool keeps each window layer a ring of "
                "O(window) a slot")
        self.model = model
        if cfg.param_shardings is not None:
            # the train -> serve-admit half of the reshard-free
            # pjit-to-pjit handoff contract: assert, never copy
            from ..parallel.front_door import verify_handoff
            params = verify_handoff(params, cfg.param_shardings,
                                    what="serve-admit params")
        self.params = params
        if (getattr(model, "pos", None) is not None
                and cfg.max_len > model.max_seq):
            raise ValueError(
                f"max_len {cfg.max_len} exceeds the model's max_seq "
                f"({model.max_seq}): learned position embeddings cannot "
                "address slots past their table")
        self.buckets = tuple(sorted(cfg.buckets)) if cfg.buckets \
            else _default_buckets(cfg.max_len)
        if max(self.buckets) > cfg.max_len:
            raise ValueError(
                f"largest prefill bucket ({max(self.buckets)}) exceeds "
                f"max_len ({cfg.max_len}) — the slot row cannot hold it")
        # L where the model generates by blocks of L positions, else None
        self._block = getattr(model, "gen_block", None)
        if self._block and any(b % self._block for b in self.buckets):
            raise ValueError(
                f"prefill buckets {self.buckets} must be multiples of the "
                f"model's gen_block ({self._block}): a prompt is prefilled "
                "in whole blocks")
        page_len = (cfg.page_len if cfg.page_len is not None
                    else dpxenv.get("DPX_SERVE_PAGE_LEN"))
        n_pages = (cfg.n_pages if cfg.n_pages is not None
                   else dpxenv.get("DPX_SERVE_N_PAGES"))
        if not n_pages:
            # what n_slots rows of max_len positions need, unshared
            n_pages = cfg.n_slots * (-(-cfg.max_len // page_len))
        share = (cfg.prefix_share if cfg.prefix_share is not None
                 else dpxenv.get("DPX_SERVE_PREFIX_SHARE"))
        kv_dtype = (cfg.kv_dtype if cfg.kv_dtype is not None
                    else dpxenv.get("DPX_SERVE_KV_DTYPE"))
        chunk_tokens(self.buckets, page_len)    # refuses what it cannot chunk
        self.pool = PagedSlotPool(model, cfg.n_slots, cfg.max_len,
                                  page_len=page_len, n_pages=n_pages,
                                  prefix_share=bool(share),
                                  kv_dtype=kv_dtype)
        spec_on = (cfg.spec_decode if cfg.spec_decode is not None
                   else dpxenv.get("DPX_SPEC_DECODE"))
        self._spec: Optional[SpecState] = None
        if spec_on:
            if self._block:
                raise block_unsupported("speculative decoding (serve/spec)")
            self.pool.require("commit")
            refuse_mixed(model, "speculative decoding (serve/spec)")
            if cfg.draft_model is None or cfg.draft_params is None:
                raise ValueError(
                    "spec_decode=True requires draft_model and "
                    "draft_params (EngineConfig) — there is nothing "
                    "to propose with")
            draft_len = (cfg.draft_len if cfg.draft_len is not None
                         else dpxenv.get("DPX_SPEC_DRAFT_LEN"))
            self._spec = SpecState(
                SpecConfig(draft_model=cfg.draft_model,
                           draft_params=cfg.draft_params,
                           draft_len=int(draft_len)),
                cfg.n_slots, cfg.max_len, page_len)
        # cumulative speculation accounting (gauges + bench record)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_iters = 0      # spec row-iterations
        self._spec_tokens = 0     # tokens emitted via spec commits
        # per-tenant admission quota (DPX_SERVE_TENANT_MAX_INFLIGHT;
        # 0 = unlimited): inflight counts move under _cond
        self._tenant_max = int(dpxenv.get("DPX_SERVE_TENANT_MAX_INFLIGHT"))
        self._tenant_inflight: Dict[str, int] = {}
        self.metrics = cfg.metrics
        self._scheduler = AdmissionScheduler(cfg.max_queue)
        self._sampler = RowSampler(cfg.n_slots, self.pool.compiles)
        self._running: Dict[int, Request] = {}     # slot -> request
        # the one request between the pool's begin and its last chunk:
        # it owns its slot and pages and is no row of the decode program
        self._prefilling: Optional[Request] = None
        self._free: List[int] = list(range(cfg.n_slots))[::-1]
        # one pass is kept in flight (_decode_all, _block_all): the pass
        # dispatched and not yet read, a _Pass or a _BlockPass. The
        # token path besides: the rows' current tokens
        # on the device (the newest pass's output with the first tokens
        # of rows admitted since put in: the next pass's argument; None
        # before the first); requests whose first token is sampled on
        # the device and not yet read, each with that (1,) array
        self._inflight: Optional[Union[_Pass, _BlockPass]] = None
        self._dev_tokens = None
        self._awaiting: Dict[int, Tuple[Request, Any]] = {}   # by slot
        if self._block:
            # the blocks are on the device (pool.blocks). What the host
            # keeps of every slot's, COUNTED AHEAD over the passes it
            # has dispatched, read or not: the positions still masked,
            # the passes of the block, and the tokens the request will
            # have been streamed when this block has been;
            self._blk_left = np.zeros(cfg.n_slots, np.int32)
            self._blk_passes = np.zeros(cfg.n_slots, np.int32)
            self._blk_owed = np.zeros(cfg.n_slots, np.int32)
            # and what it learns at the read: the pass that filled each
            # position (-1: it came with the prompt). The tokens a
            # request's first block opens with wait here for its first
            # pass, by slot
            self._blk_fill_pass = np.zeros((cfg.n_slots, self._block),
                                           np.int32)
            self._opened: Dict[int, np.ndarray] = {}
        self._block_passes = 0      # row-passes run
        self._block_commits = 0     # of them over a clean block
        self._block_fills = 0       # positions filled
        self._blocks_emitted = 0
        self._iteration = 0
        # cumulative engine-thread nanoseconds by phase, and what they
        # bought (stats(); the serve.host_share.* gauges). Two of them
        # are parts of another: decode_fetch, the wait for the program,
        # of row_loop; decode_upload, the pass's copies to the device
        # (the pool counts them, _host_times), of decode_dispatch
        self._host_ns = dict.fromkeys(
            ("idle", "admit", "decode_dispatch", "decode_fetch",
             "row_loop", "iter"), 0)
        self._admitted = 0
        self._prefill_chunks = 0            # chunk programs run
        self._prefill_chunk_iterations = 0  # iterations: a chunk AND a decode
        self._rows_decoded = 0
        self._decode_fetches = 0  # device-to-host token reads, decode path
        self._passes_ahead = 0    # passes dispatched before the last was read
        self._rows_dropped = 0    # row-steps read for a row no longer there
        self._tokens_emitted = 0
        self._completed = 0
        self._failed = 0
        self._next_id = 0
        self._cond = threading.Condition()
        self._stop = False
        self._crash: Optional[Exception] = None
        self._thread: Optional[threading.Thread] = None

    # -- front door --------------------------------------------------------

    def submit(self, prompt, params: Optional[SamplingParams] = None, *,
               rng=None, on_token=None,
               tenant: Optional[str] = None) -> RequestHandle:
        """Enqueue one request; returns immediately with a handle.

        ``prompt``: (S,) int token ids. ``rng``: the request's PRNG key
        (defaults to ``PRNGKey(request_id)``) — the engine consumes it
        with exactly ``generate()``'s split schedule, so the same key
        reproduces the same stream standalone. ``tenant`` attributes
        the request for quota (``DPX_SERVE_TENANT_MAX_INFLIGHT``) and
        per-tenant latency histograms. Raises a typed
        :class:`AdmissionRejected` synchronously when the request can
        never be served (or the bounded queue / the tenant's inflight
        quota is full)."""
        with dpxtrace.span("serve.submit"):
            return self._submit(prompt, params, rng, on_token, tenant)

    def _submit(self, prompt, params, rng, on_token, tenant):
        sp = params or SamplingParams()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self._cond:
            if self._stop:
                # pre-admission: no request id exists yet to attribute
                raise EngineStopped("engine is shut down")  # dpxlint: disable=DPX004 pre-admission, no request id assigned yet
            rid = self._next_id
            self._next_id += 1
        try:
            self._validate(prompt, sp, rid)
        except AdmissionRejected:
            # synchronous rejections are a first-class health signal
            # (the back-pressure rate a quota/saturation rule watches)
            dpxmon.inc("serve.rejected")
            raise
        if rng is None:
            rng = jax.random.PRNGKey(rid)
        rngs = np.asarray(jax.random.split(rng, sp.max_new_tokens))
        now = time.monotonic()
        req = Request(request_id=rid, prompt=prompt, params=sp, rngs=rngs,
                      submit_t=now,
                      deadline_t=(now + sp.deadline_ms / 1e3
                                  if sp.deadline_ms is not None else None),
                      on_token=on_token, tenant=tenant,
                      trace_id=dpxtrace.new_trace_id())
        req.handle = RequestHandle(req)
        # enqueue under the same lock the stop flag lives behind: a
        # submit that races shutdown either sees _stop and raises, or
        # lands the request BEFORE the engine thread's final drain —
        # never in a dead scheduler with a forever-pending future
        with self._cond:
            if self._stop:
                raise EngineStopped("engine is shut down",
                                    request_id=rid)
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
            try:
                self._scheduler.submit(req)  # may raise AdmissionRejected
            except AdmissionRejected:
                dpxmon.inc("serve.rejected")
                raise
            if tenant is not None:
                self._tenant_inflight[tenant] = \
                    self._tenant_inflight.get(tenant, 0) + 1
            self._cond.notify_all()
        return req.handle

    def _validate(self, prompt, sp: SamplingParams, rid: int) -> None:
        s = int(prompt.shape[0])
        if s < 1 or sp.max_new_tokens < 1:
            raise AdmissionRejected(
                f"request {rid}: empty prompt or max_new_tokens < 1",
                reason="invalid", request_id=rid)
        if sp.denoise_steps is not None and not self._block:
            raise AdmissionRejected(
                f"request {rid}: denoise_steps is for a model that "
                "generates by blocks", reason="invalid", request_id=rid)
        if self._block:
            if sp.temperature != 0.0:
                raise block_unsupported(
                    f"request {rid}: sampling (temperature "
                    f"{sp.temperature}; a block is filled greedily by "
                    "confidence)")
            if sp.denoise_steps is not None \
                    and not 1 <= sp.denoise_steps <= self._block:
                raise AdmissionRejected(
                    f"request {rid}: denoise_steps {sp.denoise_steps} not "
                    f"in [1, {self._block}]", reason="invalid",
                    request_id=rid)
        # the positions written: a block generator writes the whole of
        # its last block, whatever of it is streamed
        need = s + sp.max_new_tokens
        if self._block:
            need = -(-need // self._block) * self._block
        if need > self.config.max_len:
            raise AdmissionRejected(
                f"request {rid}: prompt ({s}) + max_new_tokens "
                f"({sp.max_new_tokens}) exceeds the slot cache "
                f"({self.config.max_len})",
                reason="too_long", request_id=rid)
        # the LAST sampled token retires without a KV write (decode
        # writes positions s .. s+max_new-2), so the true worst case is
        # ceil((s + max_new - 1) / page_len) pages
        worst = -(-(need if self._block else need - 1)
                  // self.pool.page_len)
        if worst > self.pool.n_pages:
            # the request could NEVER hold its pages even with the
            # whole pool to itself — reject synchronously rather than
            # let it starve in the queue
            raise AdmissionRejected(
                f"request {rid}: worst-case page need ({worst}) "
                f"exceeds the page pool ({self.pool.n_pages} pages "
                f"of {self.pool.page_len})",
                reason="no_free_pages", request_id=rid)

    def start(self) -> "InferenceEngine":
        if self._thread is not None:
            raise RuntimeError("engine already started")
        self._thread = threading.Thread(target=self._loop,
                                        name="dpx-serve-engine", daemon=True)
        self._thread.start()
        return self

    def shutdown(self, wait: bool = True) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if wait and self._thread is not None:
            # dpxlint: disable=DPX003 loop exits at its next iteration boundary once _stop is set; per-request deadlines bound the iterations
            self._thread.join()
            self._thread = None

    def crash(self, exc: Exception, wait: bool = True) -> None:
        """Hard-stop the engine AS IF its loop crashed with ``exc``:
        every in-flight request fails a typed ``EngineStopped`` with
        ``exc`` chained as the cause — exactly the real crash-drain
        path. This is the chaos seam the fleet router's replica kill
        (``serve/fleet/router.py``) rides; an orderly stop is
        :meth:`shutdown`."""
        with self._cond:
            self._crash = exc
            self._stop = True
            self._cond.notify_all()
        if wait:
            t = self._thread
            if t is not None:
                t.join(timeout=60.0)
                self._thread = None
            else:
                # never started: no loop exists to run the drain
                self._drain_on_stop()

    def __enter__(self) -> "InferenceEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    def stats(self) -> Dict:
        c = self.pool.compiles
        out = {"iterations": self._iteration,
               "completed": self._completed, "failed": self._failed,
               "tokens_emitted": self._tokens_emitted,
               # block generation (0 for a token-a-step model)
               "block_passes": self._block_passes,
               "block_commits": self._block_commits,
               "block_fills": self._block_fills,
               "blocks_emitted": self._blocks_emitted,
               "admitted": self._admitted,
               "prefill_chunks": self._prefill_chunks,
               "prefill_chunk_iterations": self._prefill_chunk_iterations,
               "rows_decoded": self._rows_decoded,
               "decode_fetches": self._decode_fetches,
               # one pass in flight: passes dispatched while the pass
               # before was not yet read (over decode_fetches, the share
               # of passes the chip did not wait for), and row-steps run
               # ahead for a row that had finished or failed by the read
               "decode_passes_ahead": self._passes_ahead,
               "decode_rows_dropped": self._rows_dropped,
               "sample_dispatches": self._sampler.dispatches,
               "host_ns": self._host_times(),
               "queue_depth": len(self._scheduler),
               "active_slots": len(self._running),
               "n_slots": self.config.n_slots,
               "decode_compiles": c.decode,
               "decode_attention_kernel_layers": c.decode_kernel_layers,
               "prefill_compiles": dict(c.prefill),
               "sample_compiles": c.sample,
               "place_compiles": c.place,
               # every program XLA built in this process, whoever asked
               "xla_compiles": compile_cache.compile_events(),
               "buckets": self.buckets,
               "spec_decode": self._spec is not None,
               "pages": self.pool.page_stats()}
        moe = self.pool.moe_stats()
        mixers = self.pool.mixer_stats()
        if moe is not None or mixers is not None:
            # the one place the expert layers' and the sparse layers'
            # device counters are read; the mark puts a reading on the
            # profiler's clock, so that a traced part can be told by two
            # of them
            out.update(moe or {})
            out.update(mixers or {})
            also = ("decode_passes_ahead", "decode_rows_dropped")
            if self._block:
                # a block generator's marks carry its own counters
                also += ("block_passes", "block_commits", "block_fills",
                         "blocks_emitted", "tokens_emitted")
            mixed = {}
            if self.pool.window_layers or mixers is not None:
                # more than one kind of store in one cache: what each
                # kind keeps and how long the contexts are (whole
                # numbers: a reader of the trace takes no doubles from a
                # mark)
                mixed = {k: int(round(out["pages"][k])) for k in (
                    "kv_resident_bytes_global",
                    "kv_resident_bytes_window", "pages_in_use",
                    "context_tokens_max", "context_tokens_mean")}
                mixed["active_slots"] = out["active_slots"]
            with dpxtrace.span("serve.stats", **(moe or {}),
                               **(mixers or {}), **mixed,
                               **{k: out[k] for k in also}):
                pass
        if self._spec is not None:
            out["spec"] = {
                "draft_len": self._spec.cfg.draft_len,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "acceptance_rate": (
                    self._spec_accepted / self._spec_proposed
                    if self._spec_proposed else None),
                "tokens_per_iteration": (
                    self._spec_tokens / self._spec_iters
                    if self._spec_iters else None),
                "spec_tokens": self._spec_tokens,
                "verify_compiles": dict(c.verify),
                "commit_compiles": dict(c.commit),
                "draft_decode_compiles": self._spec.pool.compiles.decode}
        return out

    # -- engine loop -------------------------------------------------------

    def _loop(self) -> None:
        clock, host = time.perf_counter_ns, self._host_ns
        while True:
            t_idle = clock()
            with self._cond:
                # untimed wait is safe: both transitions out of idle
                # (submit enqueue, shutdown stop flag) notify under
                # this lock, and no deadline can be pending while the
                # queue AND the running set are empty
                while (not self._stop and not self._running
                       and self._prefilling is None
                       and not len(self._scheduler)):
                    with dpxtrace.span("serve.idle"):
                        # dpxlint: disable=DPX003 untimed wait safe per the invariant above: every idle-exit transition notifies under this lock
                        self._cond.wait()
                if self._stop:
                    break
            t_iter = clock()
            host["idle"] += t_iter - t_idle
            self._iteration += 1
            try:
                with dpxtrace.span("serve.iter",
                                   iteration=self._iteration) as it:
                    faults.on_serve_iteration(self._iteration)
                    now = time.monotonic()
                    with dpxtrace.span("serve.sweep",
                                       iteration=self._iteration):
                        self._sweep_deadlines(now)
                    t_admit = clock()
                    chunks = self._admit_from_queue()
                    host["admit"] += clock() - t_admit
                    it.set(rows=len(self._running))
                    if self._running and chunks:
                        self._prefill_chunk_iterations += 1
                    if self._running or self._inflight is not None:
                        if self._block:
                            self._block_all()
                        else:
                            self._decode_all()
            except Exception as e:  # noqa: BLE001
                # an engine-loop crash (XLA error, bad params) must not
                # strand every future unresolved: fail them typed, with
                # the cause chained, then stop serving
                with self._cond:
                    self._stop = True
                self._crash = e
                break
            if (self.metrics is not None
                    and self._iteration % self.config.log_every == 0):
                self._emit_snapshot()
            host["iter"] += clock() - t_iter
        self._drain_on_stop()

    def _host_times(self) -> Dict[str, int]:
        """``stats()["host_ns"]``: the loop's own counters and the
        pool's, which makes the copies (``serve.cache.upload_pass``)."""
        return dict(self._host_ns, decode_upload=self.pool.upload_ns)

    def _emit_snapshot(self) -> None:
        """The ONE periodic-metrics emission path (obs/metrics.py):
        engine gauges land in the dpxmon registry and the registry
        emits a rank-attributed ``metrics_snapshot`` event into this
        engine's metrics log — the ad-hoc ``kind="serve_engine"`` step
        records (and their duplicate field plumbing) are gone; dpxmon
        and the SLO health rules read the same stream."""
        if not dpxmon.enabled():
            return
        with dpxtrace.span("serve.snapshot", iteration=self._iteration):
            dpxmon.set_gauge("serve.queue_depth", len(self._scheduler))
            dpxmon.set_gauge("serve.active_slots", len(self._running))
            dpxmon.set_gauge("serve.slot_occupancy",
                             len(self._running) / self.config.n_slots)
            dpxmon.set_gauge("serve.tokens_emitted", self._tokens_emitted)
            # where the engine thread's time went so far: each phase's
            # share of the loop's working time, the wait's of all of it.
            # decode_fetch near the program's share of an iteration: the
            # chip sets the pace; near 0: the host does
            host = self._host_times()
            for phase in ("admit", "decode_dispatch", "decode_upload",
                          "row_loop", "decode_fetch"):
                dpxmon.set_gauge(f"serve.host_share.{phase}",
                                 host[phase] / max(host["iter"], 1))
            dpxmon.set_gauge(
                "serve.host_share.idle",
                host["idle"] / max(host["idle"] + host["iter"], 1))
            ps = self.pool.page_stats()
            dpxmon.set_gauge("serve.pool_occupancy", ps["pool_occupancy"])
            dpxmon.set_gauge("serve.free_pages", ps["free_pages"])
            dpxmon.set_gauge("serve.prefix_hit_rate",
                             ps["prefix_hit_rate"] or 0.0)
            dpxmon.set_gauge("serve.page_evictions", ps["evictions"])
            # resident-KV capacity gauges (gauges are plain floats, so
            # the storage width rides as numeric bits: 32 / 8 / 4)
            dpxmon.set_gauge("serve.kv_bits", ps["kv_bits"])
            dpxmon.set_gauge("serve.kv_pool_bytes", ps["kv_pool_bytes"])
            dpxmon.set_gauge("serve.bytes_per_resident_token",
                             ps["bytes_per_resident_token"])
            if self._spec is not None and self._spec_proposed:
                dpxmon.set_gauge("serve.spec_acceptance_rate",
                                 self._spec_accepted / self._spec_proposed)
                dpxmon.set_gauge("serve.spec_tokens_per_iteration",
                                 self._spec_tokens / max(self._spec_iters, 1))
            dpxmon.emit_snapshot(path=self.metrics.path,
                                 step=self._iteration,
                                 source="serve_engine")

    def _sweep_deadlines(self, now: float) -> None:
        for req in self._scheduler.expired(now):
            self._fail(req, RequestDeadlineExceeded(
                f"request {req.request_id} missed its deadline "
                f"({req.params.deadline_ms} ms) while queued",
                deadline_ms=req.params.deadline_ms, stage="queued",
                request_id=req.request_id, iteration=self._iteration),
                outcome="deadline_queued")
        req = self._prefilling
        if (req is not None and req.deadline_t is not None
                and now >= req.deadline_t):
            self._fail(req, RequestDeadlineExceeded(
                f"request {req.request_id} missed its deadline "
                f"({req.params.deadline_ms} ms) with "
                f"{self.pool.prefilling[req.slot].done} of "
                f"{req.prompt.shape[0]} prompt tokens prefilled",
                deadline_ms=req.params.deadline_ms, stage="prefilling",
                request_id=req.request_id, iteration=self._iteration),
                outcome="deadline_prefilling")
        for slot, req in list(self._running.items()):
            if req.deadline_t is not None and now >= req.deadline_t:
                self._fail(req, RequestDeadlineExceeded(
                    f"request {req.request_id} missed its deadline "
                    f"({req.params.deadline_ms} ms) mid-decode after "
                    f"{len(req.out_tokens)} tokens",
                    deadline_ms=req.params.deadline_ms, stage="running",
                    request_id=req.request_id, iteration=self._iteration),
                    outcome="deadline_running")

    def _admit_from_queue(self) -> int:
        """This iteration's prefill work; returns the chunk programs it
        ran. The request mid-prefill gets its next chunk, else the next
        queued one is begun and gets its first: ONE chunk while rows are
        running (they decode next, behind it), chunks back to back while
        none is (an empty engine has nothing to protect)."""
        chunks = 0
        while True:
            if self._prefilling is None and not self._begin_next():
                return chunks
            self._prefill_chunk()
            chunks += 1
            if self._running:
                return chunks

    def _admitted_now(self, req: Request) -> None:
        req.admit_t = time.monotonic()
        req.admit_iteration = self._iteration
        self._admitted += 1

    def _begin_next(self) -> bool:
        """Give the next queued request a slot and ALL its prompt's
        pages (``pool.begin``: no program runs). False when nothing can
        be begun now: no free slot, an empty queue, or a pool without
        the pages, which requeues the request until a retirement frees
        some (or fails it, where no running request ever could)."""
        while self._free:
            req = self._scheduler.pop()
            if req is None:
                return False
            # claim the slot BEFORE the pool is asked: whatever raises,
            # the crash drain finds the request and fails its future
            # instead of stranding it half-admitted
            slot = req.slot = self._free.pop()
            head = req.prompt
            if self._block:
                # the prompt's whole blocks are prefilled; the remainder
                # opens the first block (a prompt shorter than a block
                # has nothing to prefill and runs at once)
                head = head[:len(head) - len(head) % self._block]
                if not len(head):
                    self._admitted_now(req)
                    self._run_blocks(req)
                    continue
            req.state = PREFILLING
            self._prefilling = req
            try:
                req.prefix_hit_pages, req.prefill_tokens_saved = \
                    self.pool.begin(head, slot, self.buckets)
            except PagePoolExhausted as e:
                # typed back-pressure into the scheduler: unwind the slot
                # claim and retry after a retirement frees pages — or
                # fail NOW when no running request could ever free them
                # (permanent exhaustion)
                self._prefilling = None
                self._free.append(slot)
                req.slot = None
                if self._running:
                    req.state = QUEUED
                    self._scheduler.requeue(req)
                    return False
                exc = AdmissionRejected(
                    f"request {req.request_id}: page pool exhausted at "
                    f"admission ({e.needed} page(s) needed, "
                    f"{e.free_pages} free) with no running request to "
                    f"release pages", reason="no_free_pages",
                    request_id=req.request_id, iteration=self._iteration)
                exc.__cause__ = e
                self._fail(req, exc, outcome="no_free_pages")
                continue
            self._admitted_now(req)
            return True
        return False

    def _prefill_chunk(self) -> None:
        """One chunk of the request mid-prefill, dispatched and not
        waited for: the decode program queues behind it. Nothing of a
        chunk that is not its prompt's last is fetched. Behind the last
        one the first token's sampler is dispatched too
        (``_sample_first``): the request is a running row from this
        iteration's decode pass on, and its first token is read and
        emitted once the loop has read the pass in flight
        (``_emit_first``)."""
        req = self._prefilling
        with dpxtrace.span("serve.admit", iteration=self._iteration,
                           trace_id=req.trace_id, request_id=req.request_id,
                           prompt_len=int(req.prompt.shape[0]),
                           n_hit=req.prefix_hit_pages) as adm:
            with dpxtrace.span("serve.admit.prefill",
                               iteration=self._iteration):
                ch = self.pool.chunk(self.params, req.slot)
            self._prefill_chunks += 1
            adm.set(chunk=ch.index, offset=ch.offset, bucket=ch.bucket)
            if ch.logits is not None:
                self._prefilling = None
                if self._block:
                    self._run_blocks(req)       # prefill emits nothing
                    return
                req.state = RUNNING
                self._running[req.slot] = req
                self._sample_first(req, ch.logits)

    def _sample_first(self, req: Request, logits) -> None:
        """Dispatch, behind ``req``'s prefill, what gives it a row of
        the next decode pass without the host having seen its first
        token: the one-row sampler on the prefill's ``logits``, and the
        program that puts that token among the rows' tokens on the
        device. Nothing is waited for: :meth:`_emit_first` reads it."""
        if self._spec is not None and req.params.temperature == 0.0:
            # greedy requests speculate: prefill the draft's own slot
            # too
            self._spec.admit(req.prompt, req.slot, self.buckets)
        first = self._sampler.first(req, logits)
        self._dev_tokens = self._sampler.place(self._dev_tokens, first,
                                               req.slot)
        self._awaiting[req.slot] = (req, first)

    def _emit_first(self, req: Request, first) -> None:
        """Read and emit ``req``'s first token: where the host waits
        for the prefill. After the read of the decode pass in flight,
        which a chunk dispatched behind that pass must not hold back."""
        with dpxtrace.span("serve.admit.first_token",
                           iteration=self._iteration):
            self._emit(req, int(np.asarray(first)[0]))

    def _decode_all(self) -> None:
        """The token path's step, one decode pass kept in flight: with
        pass ``k`` still running, dispatch pass ``k + 1`` over the rows
        that have a token after ``k`` (its argument is ``k``'s output,
        on the device), and only then read ``k`` and emit it. What the
        host knows ahead it decides ahead: a row whose token in flight
        is its last (``max_new_tokens``; ``_validate`` keeps that inside
        ``max_len``) is not in ``k + 1``, asks for no page and writes
        nothing. What only the token tells (``eos_token``), a deadline
        or a failure leaves a row in ``k + 1`` that is gone when it is
        read: its token is dropped there (``decode_rows_dropped``).
        Speculating rows decide on the host what they keep
        (``_spec_step``), so an iteration that has any runs nothing
        ahead: it reads what is in flight, then its own pass. Last, the
        first token of a prompt whose prefill this iteration finished
        dispatching (``_awaiting``): its row is in pass ``k + 1``
        already, the sampler having put the token there."""
        spec_slots: List[int] = []
        if self._spec is not None:
            spec_slots = [s for s in sorted(self._running)
                          if self._spec.active[s]]
        speculating = set(spec_slots)
        if spec_slots and self._inflight is not None:
            self._read_pass()
        prev, self._inflight = self._inflight, None
        # (slot, request, index of the token this pass gives it): a
        # token on the device and not yet read counts as had, be it the
        # pass in flight's or the first, a prefill's (_awaiting)
        rows: List[Tuple[int, Request, int]] = []
        for slot in sorted(self._running):
            req = self._running[slot]
            step = len(req.out_tokens) + (slot in self._awaiting) + (
                prev is not None and prev.rows.get(slot) is req)
            if slot not in speculating \
                    and step < req.params.max_new_tokens:
                rows.append((slot, req, step))
        # grow page tables at page boundaries BEFORE the decode write; an
        # exhausted pool fails the victim request typed (request +
        # iteration attributed) and frees its pages — co-resident slots
        # decode on, untouched. Spec rows don't take part: their pages
        # grow AFTER acceptance is known (ensure_spec_capacity), so
        # rejected drafts never demand a page
        with dpxtrace.span("serve.decode.capacity",
                           iteration=self._iteration):
            for row in list(rows):
                slot, req, _ = row
                try:
                    self.pool.ensure_decode_capacity(slot)
                except PagePoolExhausted as e:
                    self._fail(req, PagePoolExhausted(
                        f"request {req.request_id}: page pool "
                        f"exhausted mid-decode after "
                        f"{len(req.out_tokens)} tokens ({e.needed} "
                        f"page(s) needed, {e.free_pages} free — every "
                        f"page held by a live reader)",
                        needed=e.needed, free_pages=e.free_pages,
                        request_id=req.request_id,
                        iteration=self._iteration),
                        outcome="no_free_pages")
                    rows.remove(row)
        if rows:
            self._dispatch_pass(rows, ahead=prev is not None)
        if prev is not None:
            self._read_pass(prev)
        t0 = time.perf_counter_ns()
        for req, first in self._awaiting.values():
            if not req.done:        # the capacity check above may fail it
                self._emit_first(req, first)
        self._awaiting.clear()
        self._host_ns["admit"] += time.perf_counter_ns() - t0
        if spec_slots and self._inflight is not None:
            self._read_pass()
        spec_slots = [s for s in spec_slots if s in self._running]
        if spec_slots:
            self._spec_step(spec_slots)
        if self._inflight is not None and not self._running:
            # every row of the pass in flight has gone (eos, deadline,
            # failure): nothing would wake the loop to read it, so it
            # is read before the engine goes idle
            self._read_pass()

    def _dispatch_pass(self, rows: List[Tuple[int, Request, int]],
                       ahead: bool) -> None:
        """Dispatch one decode pass over ``rows`` and the samplers of
        those that sample; it is ``_inflight`` from here on. Its tokens
        argument is already on the device. ``ahead``: the pass before is
        not yet read."""
        clock = time.perf_counter_ns
        it = self._iteration
        active = np.zeros(self.config.n_slots, bool)
        active[[slot for slot, _, _ in rows]] = True
        t0 = clock()
        with dpxtrace.span("serve.decode.dispatch", iteration=it,
                           rows=len(rows)):
            tokens, logits = self.pool.decode(
                self.params, self._dev_tokens, active, iteration=it)
        t1 = clock()
        # a greedy row's token is the decode program's own; the rows
        # that sample get theirs from one program a setting, each with
        # the key of the token this pass gives it. What is left of a
        # row's sample is host work of about a microsecond (joining its
        # group); its span stays for the metric that reads it (PERF.md
        # section 3)
        groups = {}
        for slot, req, step in rows:
            with dpxtrace.span("serve.row.sample", iteration=it, slot=slot,
                               trace_id=req.trace_id,
                               request_id=req.request_id):
                self._sampler.join(groups, slot, req, step)
        if groups:
            with dpxtrace.span("serve.decode.sample", iteration=it,
                               groups=len(groups)):
                tokens = self._sampler.merge(tokens, logits, groups)
        self._passes_ahead += ahead
        self._inflight = _Pass(it, tokens, {s: r for s, r, _ in rows})
        self._dev_tokens = tokens
        self._host_ns["decode_dispatch"] += t1 - t0
        self._host_ns["row_loop"] += clock() - t1
        self._rows_decoded += len(rows)

    def _read_pass(self, p: Optional[_Pass] = None) -> None:
        """Read a dispatched pass (default: the one in flight, which
        then is none) and emit it: the pass's one read, where the host
        waits for the decode program that the dispatch span of iteration
        ``p.iteration`` launched. A row whose request has finished or
        failed since is dropped: never emitted, never passed to
        ``on_token``."""
        if p is None:
            p, self._inflight = self._inflight, None
        clock = time.perf_counter_ns
        it, rows = self._iteration, len(p.rows)
        t1 = clock()
        with dpxtrace.span("serve.decode.rows", iteration=it,
                           dispatched=p.iteration, rows=rows):
            t2 = clock()
            with dpxtrace.span("serve.decode.fetch", iteration=it,
                               dispatched=p.iteration, rows=rows):
                tokens = np.asarray(p.tokens)
            self._host_ns["decode_fetch"] += clock() - t2
            self._decode_fetches += 1
            for slot, req in p.rows.items():
                if req.done:
                    self._rows_dropped += 1
                    continue
                ids = dict(iteration=it, slot=slot, trace_id=req.trace_id,
                           request_id=req.request_id)
                with dpxtrace.span("serve.row.fetch", **ids):
                    tok = int(tokens[slot])
                with dpxtrace.span("serve.row.emit", **ids):
                    self._emit(req, tok)
        self._host_ns["row_loop"] += clock() - t1

    # -- generation by blocks ------------------------------------------------

    def _run_blocks(self, req: Request) -> None:
        """``req`` becomes a running row of the block step: its first
        block opens, in its first pass, with what the prompt's whole
        blocks left over."""
        slot, L = req.slot, self._block
        req.state = RUNNING
        self._running[slot] = req
        req.fill_schedule = fill_counts(
            L, req.params.denoise_steps or L)
        head = req.prompt[len(req.prompt) - len(req.prompt) % L:]
        self._opened[slot] = head
        self._blk_left[slot] = self._blk_owed[slot] = L - len(head)
        self._blk_passes[slot] = 0
        # a later block needs no such reset: every position of it is
        # filled, and its pass recorded, before it is streamed
        self._blk_fill_pass[slot] = -1

    def _block_all(self) -> None:
        """The block path's step, one block pass kept in flight: with
        pass ``k`` still running, dispatch pass ``k + 1`` over the rows
        that have one, and only then read ``k``, record which pass
        filled which position and stream the blocks ``k`` cleaned (in
        position order, before their commit pass is read). A pass fills
        ``min(n_fill, masked)`` positions of a row's block and
        ``n_fill`` is the row's ``fill_schedule``, so the host knows
        ahead what ``k + 1`` is for every row: a fill; the commit pass
        of a block ``k`` cleaned; or nothing, where that block streams
        the request's last token (``max_new_tokens``: no commit pass,
        nobody reads its keys). What only the tokens tell (an
        ``eos_token`` inside a block), a deadline or a failure leaves a
        row in ``k + 1`` that is gone when it is read: it is dropped
        there (``decode_rows_dropped``)."""
        L, it = self._block, self._iteration
        prev, self._inflight = self._inflight, None
        slots = [slot for slot, req in sorted(self._running.items())
                 if self._blk_left[slot]
                 or self._blk_owed[slot] < req.params.max_new_tokens]
        with dpxtrace.span("serve.decode.capacity", iteration=it):
            for slot in list(slots):
                req = self._running[slot]
                try:
                    # the block's L positions lie inside one page
                    self.pool.ensure_spec_capacity(slot, L)
                except PagePoolExhausted as e:
                    self._fail(req, PagePoolExhausted(
                        f"request {req.request_id}: page pool exhausted "
                        f"mid-block after {len(req.out_tokens)} tokens "
                        f"({e.needed} page(s) needed, {e.free_pages} free "
                        f"— every page held by a live reader)",
                        needed=e.needed, free_pages=e.free_pages,
                        request_id=req.request_id, iteration=it),
                        outcome="no_free_pages")
                    slots.remove(slot)
        if slots:
            self._dispatch_blocks(slots, ahead=prev is not None)
        if prev is not None:
            self._read_blocks(prev)
        if self._inflight is not None and not self._running:
            # as _decode_all: nothing else would wake the loop to read it
            self._read_blocks()

    def _dispatch_blocks(self, slots: List[int], ahead: bool) -> None:
        """Dispatch one block pass over the rows in ``slots``, each
        advanced one pass in the host's count; it is ``_inflight`` from
        here on. ``ahead``: the pass before is not yet read."""
        clock = time.perf_counter_ns
        L, it, n = self._block, self._iteration, self.config.n_slots
        active = np.zeros(n, bool)
        n_fill = np.zeros(n, np.int32)
        given = np.zeros((n, L), np.int32)
        n_given = np.full(n, -1, np.int32)
        rows: Dict[int, Tuple[Request, int, bool]] = {}
        for slot in slots:
            req = self._running[slot]
            active[slot] = True
            head = self._opened.get(slot)
            if head is not None:
                given[slot, :len(head)] = head
                n_given[slot] = len(head)
            left = self._blk_left[slot]
            if not left:
                # its commit pass; the program opens its next block
                rows[slot] = (req, -1, False)
                self._blk_left[slot] = L
                self._blk_owed[slot] += L
                self._blk_passes[slot] = 0
                continue
            at = self._blk_passes[slot]
            sched = req.fill_schedule
            n_fill[slot] = sched[min(at, len(sched) - 1)]
            left = self._blk_left[slot] = max(left - n_fill[slot], 0)
            self._blk_passes[slot] = at + 1
            rows[slot] = (req, at, not left)
        # a request that opened a block and has no row of this pass has
        # failed since (the capacity check)
        self._opened.clear()
        t0 = clock()
        with dpxtrace.span("serve.decode.dispatch", iteration=it,
                           rows=len(rows)):
            out = self.pool.block_step(self.params, given, n_given, n_fill,
                                       active, iteration=it)
        self._host_ns["decode_dispatch"] += clock() - t0
        self._passes_ahead += ahead
        self._inflight = _BlockPass(it, out, rows)
        self._rows_decoded += len(rows)
        self._block_passes += len(rows)
        self._block_commits += sum(at < 0 for _, at, _ in rows.values())

    def _read_blocks(self, p: Optional[_BlockPass] = None) -> None:
        """Read a dispatched block pass (default: the one in flight,
        which then is none): the pass's one read, where the host waits
        for the block-step program that the dispatch span of iteration
        ``p.iteration`` launched. Then every row's record of the pass,
        and the stream of each block it cleaned. A row whose request has
        finished or failed since is dropped."""
        if p is None:
            p, self._inflight = self._inflight, None
        clock = time.perf_counter_ns
        it, rows = self._iteration, len(p.rows)
        commits = sum(at < 0 for _, at, _ in p.rows.values())
        t1 = clock()
        with dpxtrace.span("serve.decode.rows", iteration=it,
                           dispatched=p.iteration, rows=rows):
            t2 = clock()
            with dpxtrace.span("serve.decode.fetch", iteration=it,
                               dispatched=p.iteration, rows=rows,
                               commits=commits) as fetch:
                out = np.asarray(p.out)
                filled = out[:, 1].astype(bool)
                # a dropped row's fills are nobody's: not counted
                fills = int(filled[[slot for slot, (req, _, _)
                                    in p.rows.items()
                                    if not req.done]].sum())
                fetch.set(fills=fills)
            self._host_ns["decode_fetch"] += clock() - t2
            self._decode_fetches += 1
            emitted = 0
            with dpxtrace.span("serve.block.advance", iteration=it) as adv:
                for slot, (req, at, cleans) in p.rows.items():
                    if req.done:
                        self._rows_dropped += 1
                    elif at >= 0:
                        self._blk_fill_pass[slot, filled[slot]] = at
                        if cleans:
                            # the host's count against the device's mask
                            if out[slot, 2].any():
                                raise RuntimeError(
                                    f"request {req.request_id}: slot "
                                    f"{slot}'s block counted clean at pass "
                                    f"{at} holds masked positions")
                            self._emit_block(req, out[slot, 0])
                            emitted += 1
                adv.set(blocks=emitted, commits=commits)
        self._host_ns["row_loop"] += clock() - t1
        self._block_fills += fills
        self._blocks_emitted += emitted

    def _emit_block(self, req: Request, tokens: np.ndarray) -> None:
        """Stream ``req``'s finished block, ``tokens`` (L,), in position
        order: the positions the prompt gave are not output, and what
        lies past ``max_new_tokens`` (or an ``eos_token``) is dropped
        with the request's retirement."""
        for tok, at in zip(tokens.tolist(),
                           self._blk_fill_pass[req.slot].tolist()):
            if at < 0:
                continue
            req.fill_pass.append(at)
            self._emit(req, tok)
            if req.done:
                return

    def _spec_fail(self, slots: List[int], cause: Exception,
                   stage: str) -> None:
        """Fail the speculating victims of a propose/verify/commit
        fault, typed and stage-attributed; non-spec co-residents are
        untouched (the target pool was not written for this iteration,
        so their streams stay bit-exact)."""
        for slot in slots:
            req = self._running.get(slot)
            if req is None:
                continue
            exc = SpecDecodeError(
                f"request {req.request_id}: speculative {stage} failed "
                f"after {len(req.out_tokens)} tokens: {cause!r}",
                stage=stage, request_id=req.request_id,
                iteration=self._iteration)
            exc.__cause__ = cause
            self._fail(req, exc, outcome="spec_decode")

    def _spec_step(self, spec_slots: List[int]) -> None:
        """One speculative iteration for the speculating slots: draft
        proposes k tokens each, ONE batched verify program scores all
        k+1 positions, the longest matching prefix (+ the free bonus
        token) is emitted, and only accepted positions commit — the
        rejected suffix was never written anywhere, so rollback is pure
        host bookkeeping (the draft's length rewind)."""
        spec = self._spec
        k = spec.cfg.draft_len
        cur = np.asarray([self._running[s].out_tokens[-1]
                          for s in spec_slots], np.int32)
        ids = dict(iteration=self._iteration, rows=len(spec_slots),
                   draft_len=k)
        try:
            faults.on_comm_op("draft_propose")
            with dpxtrace.span("serve.spec.propose", **ids):
                drafts = spec.propose(spec_slots, cur)
        except Exception as e:  # noqa: BLE001 — victim containment
            self._spec_fail(spec_slots, e, "propose")
            return
        tokens = np.zeros((self.config.n_slots, k + 1), np.int32)
        tokens[spec_slots, 0] = cur
        tokens[spec_slots, 1:] = drafts
        try:
            faults.on_comm_op("spec_verify")
            with dpxtrace.span("serve.spec.verify", **ids):
                logits, sk, sv = self.pool.spec_verify(self.params, tokens)
                logits_np = np.asarray(logits)
        except Exception as e:  # noqa: BLE001 — victim containment
            self._spec_fail(spec_slots, e, "verify")
            return
        commit = np.zeros(self.config.n_slots, np.int32)
        emits: Dict[int, List[int]] = {}
        for i, slot in enumerate(spec_slots):
            req = self._running[slot]
            sp = req.params
            out, e = accept_greedy(
                drafts[i], logits_np[slot],
                sp.max_new_tokens - len(req.out_tokens), sp.eos_token)
            req.spec_proposed += k
            req.spec_accepted += e - 1
            self._spec_proposed += k
            self._spec_accepted += e - 1
            self._spec_iters += 1
            commit[slot] = e
            emits[slot] = out
        # accepted counts are known — only NOW may pages be demanded;
        # exhaustion fails THAT victim typed (its commit zeroes, nothing
        # of its iteration lands)
        for slot in list(emits):
            req = self._running[slot]
            try:
                self.pool.ensure_spec_capacity(slot, int(commit[slot]))
            except PagePoolExhausted as e:
                n_acc = int(commit[slot])
                commit[slot] = 0
                del emits[slot]
                self._fail(req, PagePoolExhausted(
                    f"request {req.request_id}: page pool exhausted "
                    f"committing {n_acc} accepted "
                    f"token(s) after {len(req.out_tokens)} tokens "
                    f"({e.needed} page(s) needed, {e.free_pages} "
                    f"free)", needed=e.needed,
                    free_pages=e.free_pages,
                    request_id=req.request_id,
                    iteration=self._iteration),
                    outcome="no_free_pages")
        try:
            with dpxtrace.span("serve.spec.commit", **ids):
                self.pool.spec_commit(sk, sv, commit)
        except Exception as e:  # noqa: BLE001 — victim containment
            self._spec_fail(list(emits), e, "commit")
            return
        alive = [s for s in emits if s in self._running]
        spec.rollback(alive, commit[alive])
        self._spec_tokens += int(commit[alive].sum()) if alive else 0
        for slot in alive:
            req = self._running[slot]
            for tok in emits[slot]:
                self._emit(req, tok)
                if req.done:
                    break

    def _emit(self, req: Request, tok: int) -> None:
        now = time.monotonic()
        i = len(req.out_tokens)
        req.out_tokens.append(tok)    # handle.tokens aliases this list
        if req.first_token_t is None:
            req.first_token_t = now
        req.last_token_t = now
        self._tokens_emitted += 1
        if req.on_token is not None:
            try:
                req.on_token(tok, i)
            except Exception:  # noqa: BLE001 — a user callback must
                pass           # never take down the engine loop
        sp = req.params
        if (len(req.out_tokens) >= sp.max_new_tokens
                or (sp.eos_token is not None and tok == sp.eos_token)):
            self._retire(req)

    def _free_slot(self, req: Request) -> None:
        if req.slot is not None:
            # every exit path (retire, deadline, crash drain) runs
            # through here, for a running row and for a request still
            # prefilling. Page refcounts can never leak: private pages
            # free immediately, indexed prompt pages stay resident for
            # future prefix hits; the slot's length zeroes, so the
            # blockwise decode's max(lengths) trip count stops charging
            # for a request that no longer exists.
            self.pool.release(req.slot)
            if self._spec is not None:
                # draft state exits through the same funnel — retire,
                # typed failure, crash drain alike (serve/spec/)
                self._spec.release(req.slot)
            self._running.pop(req.slot, None)
            if self._prefilling is req:
                self._prefilling = None
            self._free.append(req.slot)
            req.slot = None

    def _retire(self, req: Request) -> None:
        req.state = FINISHED
        req.retire_iteration = self._iteration
        self._free_slot(req)
        self._completed += 1
        rec = request_record(req, "ok")
        req.handle.metrics = rec
        # dpxmon SLO instruments: TTFT/TPOT window histograms (the
        # p99-ceiling health rules read their snapshot summaries) and
        # the completion counter
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
        self._tenant_release(req)
        if self.metrics is not None:
            self.metrics.event("serve_request", **rec)
        emit_request_trace(req, "ok")
        req.handle.future.set_result(
            np.asarray(req.out_tokens, np.int32))

    def _fail(self, req: Request, exc: Exception, outcome: str) -> None:
        req.state = FAILED
        req.retire_iteration = self._iteration
        self._free_slot(req)
        self._failed += 1
        rec = request_record(req, outcome)
        req.handle.metrics = rec
        self._tenant_release(req)
        dpxmon.inc("serve.failed")
        dpxmon.inc(f"serve.outcome.{outcome}")
        if self.metrics is not None:
            self.metrics.event("serve_request", **rec)
        emit_request_trace(req, outcome)
        if isinstance(exc, PagePoolExhausted):
            # infra-failure postmortem: ship the engine's recent span
            # timeline with the typed error (obs/trace.py, best-effort)
            dpxtrace.on_typed_failure(exc)
        req.handle.future.set_exception(exc)

    def _tenant_release(self, req: Request) -> None:
        """Give the tenant its inflight credit back at ANY terminal
        transition (retire or typed failure, queued or running)."""
        if req.tenant is None:
            return
        with self._cond:
            n = self._tenant_inflight.get(req.tenant, 0)
            if n <= 1:
                self._tenant_inflight.pop(req.tenant, None)
            else:
                self._tenant_inflight[req.tenant] = n - 1

    def _drain_on_stop(self) -> None:
        cause = f" (engine loop crashed: {self._crash!r})" \
            if self._crash is not None else ""
        held = [self._prefilling] if self._prefilling is not None else []
        for req in (self._scheduler.drain() + held
                    + list(self._running.values())):
            exc = EngineStopped(
                f"engine stopped with request {req.request_id} "
                f"{req.state}{cause}", request_id=req.request_id,
                iteration=self._iteration)
            exc.__cause__ = self._crash
            self._fail(req, exc, outcome="engine_stopped")
        # a pass in flight is not read: its requests have just failed
        self._inflight = None
