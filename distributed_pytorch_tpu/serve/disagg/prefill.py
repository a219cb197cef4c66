"""The prefill half of the disaggregated split (``serve/disagg/``).

One loop, one job: pop an admitted request, compute its prompt's KV —
with the PR 8 radix prefix reuse, so a shared system prompt is computed
once and every later request only prefills its tail — then EXTRACT the
resident pages, encode the handoff frame at the configured wire width,
and hand it to the transport. Prefill never decodes: a 4k-token prompt
monopolizes THIS engine's accelerator time, and the decode loop's token
cadence (TPOT) is structurally out of its blast radius.

The engine owns a single-slot :class:`~..pages.PagedSlotPool` whose
prefix index PERSISTS across requests: pages released after extraction
stay resident at refcount zero, so the radix hit accounting
(``prefix_hit_pages`` / ``prefill_tokens_saved``) works exactly as in
the monolithic paged engine. Compile discipline is inherited: one
jitted prefill program per TAIL bucket, zero decode programs.

Failure containment is the point of the split: a transport severed
mid-handoff, an injected ``drop_conn@op=handoff_send``, or a crash in
this loop reaches :meth:`~.router.DisaggEngine.on_prefill_dead` — which
fails ONLY the requests still on the prefill side of the handoff
(queued / prefilling / sent-but-unreceived), typed
``PrefillEngineDied`` with request + engine attribution. Decode-resident
streams never hear about it.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from ..pages import PagedSlotPool
from ..types import RUNNING, AdmissionRejected, PagePoolExhausted
from . import frames
from .transport import TransportSevered


class PrefillEngine:
    """The prefill loop: admit → tail prefill (radix reuse) → extract
    pages → encode frame → send. Driven by the router's scheduler."""

    def __init__(self, model, params, router, transport, *, buckets,
                 page_len: int, n_pages: int, prefix_share: bool,
                 bits: Optional[int], kv_dtype: str = "f32"):
        self.model = model
        self.params = params
        self.router = router
        self.transport = transport
        self.buckets = buckets
        self.bits = bits
        # single prefill slot: the loop processes one prompt at a time
        # (admission IS the work); the pool's radix index carries the
        # cross-request prefix residency
        self.pool = PagedSlotPool(model, 1, max(buckets),
                                  page_len=page_len, n_pages=n_pages,
                                  prefix_share=prefix_share,
                                  kv_dtype=kv_dtype)
        self.pool.require("export")
        self.iterations = 0
        self._cond = threading.Condition()
        self._stop = False
        self._active = None           # the request being prefilled
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop,
                                        name="dpx-serve-prefill",
                                        daemon=True)
        self._thread.start()

    def wake(self) -> None:
        with self._cond:
            self._cond.notify_all()

    def stop(self, wait: bool = True) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if wait and self._thread is not None:
            # dpxlint: disable=DPX003 loop exits at its next iteration boundary once _stop is set; every blocking step inside is deadline-bounded
            self._thread.join()
            self._thread = None

    # -- the loop ----------------------------------------------------------

    def _loop(self) -> None:
        sched = self.router.scheduler
        while True:
            with self._cond:
                while not self._stop and not len(sched):
                    # dpxlint: disable=DPX003 untimed wait safe: submit enqueue and stop both notify under this lock
                    self._cond.wait()
                if self._stop:
                    return
            self.iterations += 1
            try:
                for req in sched.expired(time.monotonic()):
                    self.router.fail_queued_deadline(req)
                req = sched.pop()
                if req is None:
                    continue
                self._active = req
                req.state = RUNNING
                req.stage = "prefill"
                try:
                    self._prefill_one(req)
                finally:
                    self._active = None
            # dpxlint: disable=DPX010 prefill death is fail-fast by design: decode's deadline-bounded recv observes severance typed, not a hang
            except TransportSevered as e:
                self.router.on_prefill_dead(e)
                return
            # dpxlint: disable=DPX010 prefill death is fail-fast by design: decode's deadline-bounded recv observes severance typed, not a hang
            except Exception as e:  # noqa: BLE001 — a prefill-loop
                # crash (XLA error, codec bug) fails ONLY prefill-side
                # requests, typed; the decode loop keeps serving
                self.router.on_prefill_dead(e)
                return

    def _prefill_one(self, req) -> None:
        prompt = req.prompt
        # admission stamp BEFORE the prefill compute: queue_ms ends
        # when the prompt is claimed, and the prefill compute itself
        # lands in the decomposition's prefill_ms span (serve/metrics)
        req.admit_t = time.monotonic()
        req.admit_iteration = self.iterations
        try:
            logits, n_hit, offset = self.pool.admit(
                self.params, prompt, 0, self.buckets)
        except PagePoolExhausted as e:
            # single-slot pool with LRU-evictable index residency: only
            # a pool smaller than the prompt itself lands here (submit
            # validation bounds it, but a shrunken config must still
            # fail typed, never corrupt)
            exc = AdmissionRejected(
                f"request {req.request_id}: prefill page pool exhausted "
                f"({e.needed} page(s) needed, {e.free_pages} free)",
                reason="no_free_pages", request_id=req.request_id)
            exc.__cause__ = e
            self.router.fail(req, exc, outcome="no_free_pages")
            return
        req.prefix_hit_pages = n_hit
        req.prefill_tokens_saved = offset
        if (self.pool.quant_bits is not None
                and self.pool.quant_bits == self.bits):
            # matched pool/wire width: the frame carries the pool's
            # resident bits verbatim — no dequant→requant double hop
            length, kqs, vqs = self.pool.extract_quantized(0)
            self.pool.release(0)
            frame, kv_bytes = frames.encode_frame_quantized(
                req.request_id, length, np.asarray(logits)[0], kqs, vqs,
                self.bits)
        else:
            length, ks, vs = self.pool.extract(0)
            self.pool.release(0)
            frame, kv_bytes = frames.encode_frame(
                req.request_id, length, np.asarray(logits)[0], ks, vs,
                self.bits)
        req.handoff_bytes = kv_bytes
        # enter the handoff stage BEFORE the send: if the transport
        # dies inside send, the victim is already attributable as
        # in-flight (on_prefill_dead finds it in the handoff set), and
        # the decode-side timeout sweep has a start timestamp
        req.stage = "handoff"
        req.handoff_send_t = time.monotonic()
        self.router.enter_handoff(req)
        self.transport.send(frame, kv_bytes)

    def drain_requests(self):
        """The requests currently on this engine's side (the active
        prefill, if any) — the router folds them into the prefill-death
        victim set."""
        req = self._active
        return [req] if req is not None else []

    def stats(self) -> dict:
        c = self.pool.compiles
        return {"iterations": self.iterations,
                "prefill_compiles": dict(c.prefill),
                "decode_compiles": c.decode,   # must stay 0
                "pages": self.pool.page_stats()}
