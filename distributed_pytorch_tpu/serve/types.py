"""Request-level types of the serving engine: sampling parameters, the
request lifecycle, and the typed failure vocabulary.

The error hierarchy follows the PR-2 comm design
(``runtime.native.CommError``): every failure is a TYPED exception that
carries enough to *attribute* it — which request, which engine
iteration, which stage of the lifecycle — so callers never parse
message strings, and the same names flow into the line-JSON metrics
log. ``RequestDeadlineExceeded`` mirrors ``CommTimeout``'s
``deadline_ms`` field on purpose: a per-request SLO miss and a
per-collective deadline miss are the same failure shape at two layers.
"""

from __future__ import annotations

from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional


@dataclass(frozen=True)
class SamplingParams:
    """Per-request generation knobs.

    ``temperature``/``top_k``/``top_p`` have exactly the semantics of
    ``models.generate.make_generate_fn`` (temperature 0 is greedy) —
    the engine compiles one tiny sampler per DISTINCT (temperature,
    top_k, top_p) triple, so a serving mix should draw from a bounded
    set of configs. ``eos_token`` stops generation early (the token is
    included in the output); ``deadline_ms`` is a wall-clock SLO from
    submit time, enforced while queued AND while decoding; lower
    ``priority`` runs sooner (FCFS within a priority class)."""

    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token: Optional[int] = None
    deadline_ms: Optional[float] = None
    priority: int = 0
    denoise_steps: Optional[int] = None

    @property
    def sampler_key(self):
        return (self.temperature, self.top_k, self.top_p)


class ServeError(RuntimeError):
    """A serving-engine failure. Base of the typed hierarchy (mirrors
    ``runtime.native.CommError``): carries the request id and the
    engine iteration at which the failure was observed."""

    def __init__(self, msg: str, *, request_id: Optional[int] = None,
                 iteration: Optional[int] = None):
        super().__init__(msg)
        self.request_id = request_id
        self.iteration = iteration


class AdmissionRejected(ServeError):
    """The front door refused the request outright — bounded queue
    full, prompt longer than the largest prefill bucket (the
    disaggregated router; the engine prefills in chunks), or a
    prompt+max_new that cannot fit the slot cache. Raised
    synchronously from ``submit`` with ``reason`` set."""

    def __init__(self, msg: str, *, reason: str = "rejected",
                 tenant: Optional[str] = None, **kw):
        super().__init__(msg, **kw)
        self.reason = reason
        #: which tenant's quota refused it (``reason="tenant_quota"``
        #: only) — attribution for multi-tenant dashboards
        self.tenant = tenant


class RequestDeadlineExceeded(ServeError):
    """The request's ``deadline_ms`` SLO elapsed before completion —
    while still queued (``stage='queued'``), between two chunks of its
    prefill (``stage='prefilling'``) or mid-decode (``stage='running'``).
    Field names mirror
    ``runtime.native.CommTimeout`` (PR 2's typed-failure vocabulary)."""

    def __init__(self, msg: str, *, deadline_ms: float = 0.0,
                 stage: str = "running", **kw):
        super().__init__(msg, **kw)
        self.deadline_ms = deadline_ms
        self.stage = stage


class EngineStopped(ServeError):
    """The engine shut down while the request was still in flight."""


class HandoffError(ServeError):
    """A disaggregated-serving KV-page handoff failed (``serve/disagg/``).

    Base of the handoff failure vocabulary: carries the request, the
    iteration at which the failure was observed, and ``engine`` — which
    side of the split is BLAMED (``"prefill"`` / ``"decode"`` /
    ``"transport"``). The attribution matters operationally: a dead
    prefill engine must fail ONLY its in-flight requests, typed, while
    decode-resident streams keep producing bit-exact tokens — so a
    supervisor restarting the prefill side needs to know no decode
    state was lost (docs/serving.md)."""

    def __init__(self, msg: str, *, engine: str = "transport", **kw):
        super().__init__(msg, **kw)
        self.engine = engine


class PrefillEngineDied(HandoffError):
    """The prefill engine died (crash, injected kill, severed
    transport) with this request still on its side of the handoff —
    queued for prefill, mid-prefill, or sent-but-never-received. Only
    those requests fail; every decode-resident stream continues."""


class HandoffTimeout(HandoffError):
    """A sent handoff frame did not materialize in the decode pool
    within ``DPX_HANDOFF_TIMEOUT_MS`` — the transport or the prefill
    side is wedged but nothing closed. Mirrors
    ``runtime.native.CommTimeout``'s ``deadline_ms`` field (the same
    failure shape at the serving layer)."""

    def __init__(self, msg: str, *, deadline_ms: float = 0.0, **kw):
        super().__init__(msg, **kw)
        self.deadline_ms = deadline_ms


class HandoffCorrupt(HandoffError):
    """A handoff frame failed its integrity check (magic/version/CRC).
    ``page`` names the first page tensor whose CRC32C mismatched (−1 =
    the header or logits section) — corruption must never reach the
    decode pool as silently wrong KV."""

    def __init__(self, msg: str, *, page: int = -1, **kw):
        super().__init__(msg, **kw)
        self.page = page


class SpecDecodeError(ServeError):
    """A speculative-decoding step (``serve/spec/``) failed for THIS
    request: the draft proposal loop, the batched verify program, or
    the accepted-prefix commit raised. ``stage`` attributes which —
    ``"propose"`` / ``"verify"`` / ``"commit"`` — so an operator can
    tell a diverging/broken draft model from a verify-side fault
    (chaos-injected or real) at a glance. Containment mirrors the
    paged-growth contract: only the speculating victim fails; the
    target pool was not yet written for the iteration (verify is
    read-only, rollback is simply not-committing), so co-resident
    non-spec streams keep producing bit-exact tokens."""

    def __init__(self, msg: str, *, stage: str = "verify", **kw):
        super().__init__(msg, **kw)
        self.stage = stage


class PagePoolExhausted(ServeError):
    """The paged KV pool (``serve/pages/``) could not supply a page:
    every page is either free-list-empty or held by a live reader
    (refcount > 0), and nothing refcount-zero is LRU-evictable. Raised
    by the pool with ``needed``/``free_pages`` attribution; the engine
    re-raises with the victim request and iteration attached (a
    mid-decode growth failure fails THAT request only — co-resident
    streams are untouched). At admission the same condition surfaces as
    back-pressure instead: the request stays queued while other
    requests hold pages, or fails typed
    ``AdmissionRejected(reason="no_free_pages")`` when the exhaustion
    is permanent."""

    def __init__(self, msg: str, *, needed: int = 0, free_pages: int = 0,
                 **kw):
        super().__init__(msg, **kw)
        self.needed = needed
        self.free_pages = free_pages


#: Request lifecycle states (host-side bookkeeping only). PREFILLING: the
#: paged engine has given the request its slot and pages and is
#: prefilling its prompt a chunk an iteration; it decodes from RUNNING on.
QUEUED, RUNNING, FINISHED, FAILED = "queued", "running", "finished", "failed"
PREFILLING = "prefilling"


@dataclass
class Request:
    """One in-flight generation request (engine-internal)."""

    request_id: int
    prompt: Any                      # np.ndarray (S,) int32
    params: SamplingParams
    rngs: Any                        # (max_new, 2) uint32 split keys
    submit_t: float                  # monotonic
    deadline_t: Optional[float]      # monotonic, or None
    on_token: Optional[Callable[[int, int], None]] = None
    handle: Any = None               # RequestHandle (set by the engine)
    state: str = QUEUED
    slot: Optional[int] = None
    out_tokens: List[int] = field(default_factory=list)
    #: block generation: for each output token, the pass of its block
    #: (0-based) in which its position was filled
    fill_pass: List[int] = field(default_factory=list)
    #: block generation: positions each pass of a block fills, by pass
    fill_schedule: Any = None
    admit_t: Optional[float] = None
    admit_iteration: Optional[int] = None
    # paged-KV accounting (serve/pages/): how many full prefix pages the
    # radix index supplied at admission, and the prefill tokens that
    # reuse saved (0/0 for cold or unpaged requests)
    prefix_hit_pages: int = 0
    prefill_tokens_saved: int = 0
    retire_iteration: Optional[int] = None
    first_token_t: Optional[float] = None
    last_token_t: Optional[float] = None
    # disaggregated serving (serve/disagg/): the handoff timeline and
    # wire accounting. ``handoff_send_t`` is stamped when the prefill
    # engine finishes the tail prefill and hands the frame to the
    # transport; ``handoff_recv_t`` when the decode engine materializes
    # the pages into its pool. Together with submit_t/admit_t/
    # first_token_t they decompose TTFT into queue → prefill → handoff
    # → decode-admission spans (serve/metrics.py); all None for
    # monolithic engines.
    handoff_send_t: Optional[float] = None
    handoff_recv_t: Optional[float] = None
    handoff_bytes: Optional[int] = None
    #: coarse lifecycle location for the disagg router's failure
    #: attribution: "prefill_queue" | "prefill" | "handoff" | "decode"
    stage: Optional[str] = None
    #: multi-tenant attribution (None = untenanted): checked against
    #: ``DPX_SERVE_TENANT_MAX_INFLIGHT`` at submit, dimensioned onto
    #: the TTFT/TPOT histograms at retirement
    tenant: Optional[str] = None
    #: speculative decoding accounting (serve/spec/): drafted tokens
    #: offered to verify, and how many of them were accepted (the +1
    #: bonus token verify emits for free is counted in NEITHER —
    #: acceptance_rate = accepted/proposed is a pure draft-quality
    #: measure). 0/0 for non-spec requests.
    spec_proposed: int = 0
    spec_accepted: int = 0
    #: dpxtrace lineage (obs/trace.py): ONE trace id assigned at submit
    #: that every lifecycle span carries — across the monolithic engine
    #: thread AND across the disagg prefill→handoff→decode split, so a
    #: request renders as one connected timeline (docs/observability.md)
    trace_id: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.state in (FINISHED, FAILED)


class RequestHandle:
    """The caller's view of a submitted request: a future for the final
    token array, the streamed tokens so far, and (after completion)
    the per-request SLO metrics."""

    def __init__(self, request: Request):
        self._request = request
        self.future: Future = Future()
        # the ONE token list, shared with the engine-side Request —
        # appends are GIL-atomic, so mid-stream reads see a consistent
        # prefix of the stream
        self.tokens: List[int] = request.out_tokens
        # block generation: the pass of its block that filled each token
        # (same list as the engine's; empty for a token-a-step model)
        self.fill_pass: List[int] = request.fill_pass
        self.metrics: dict = {}       # filled at completion

    @property
    def request_id(self) -> int:
        return self._request.request_id

    @property
    def state(self) -> str:
        return self._request.state

    def result(self, timeout: Optional[float] = None):
        """Block for the final (n_tokens,) int32 array; raises the
        request's typed ``ServeError`` on failure."""
        return self.future.result(timeout)
