"""The fleet router: R replica engines behind one front door.

``FleetRouter`` keeps the PR 3 engine contract — ``submit() -> future
+ streaming tokens`` — over R :class:`~..engine.InferenceEngine`
replicas sharing one (model, params). Routing is prefix-affine
(``placement.py``): the prompt's first-page chunk rendezvous-hashes to
a home replica so shared prefixes land where their pages already live;
capacity back-pressure (``queue_full`` / ``no_free_pages`` rejection,
or a home queue already past ``DPX_FLEET_SPILL_QUEUE``) spills the
request to the least-loaded replica instead — a typed, logged
``fleet_spill`` event with request + from/to attribution. When EVERY
replica rejects, the caller gets a synchronous
``AdmissionRejected(reason="fleet_exhausted")`` with the last replica
rejection chained.

Failure isolation is the headline contract: :meth:`kill_replica` (the
in-process analogue of a replica host dying — also the ``drop_conn``
target of the ``op=fleet_submit`` DPX_FAULT hook) fails ONLY that
replica's in-flight requests, each as a typed replica-attributed
``ReplicaFailed``; placement immediately re-homes its prefix shard
over the survivors, and a ``replica_failed`` event (rank = replica id)
degrades the fleet HealthMonitor stream until a later fleet snapshot
naming the replica live again clears it (obs/health.py). Drain is the
graceful opposite: stop admitting, finish in-flight, release pages —
never kill mid-stream.

Per-request determinism survives routing: the router stamps every
request with an explicit fleet-level PRNG key (``PRNGKey(fleet id)``
when the caller passes none), so a request's token stream is
bit-identical to a standalone ``generate()`` call REGARDLESS of which
replica — and which engine-local request id — served it.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from ...obs import metrics as dpxmon
from ...runtime import env as dpxenv
from ...runtime import faults
from ...utils.logging import append_event
from ..engine import EngineConfig, InferenceEngine
from ..types import AdmissionRejected, EngineStopped, SamplingParams
from . import placement
from .types import (REPLICA_DRAINING, REPLICA_FAILED, REPLICA_LIVE,
                    REPLICA_RETIRED, FleetConfig, FleetHandle, Replica,
                    ReplicaFailed)

#: The op name routed submits fire through the fault-injection hook —
#: ``drop_conn@op=fleet_submit[,rank=R|,call=N]`` kills the targeted
#: request's home replica in-process (the fleet chaos leg).
FLEET_OP = "fleet_submit"

#: Engine rejection reasons that mean CAPACITY (spillable) rather than
#: an invalid request (a too-long prompt is rejected identically by
#: every replica — spilling it would only burn the walk).
_SPILL_REASONS = ("queue_full", "no_free_pages")


class _ReplicaAbort:
    """``drop_conn`` target for the ``fleet_submit`` fault hook:
    "aborting the connection" to a replica kills that replica
    in-process (``kill`` in the DPX_FAULT grammar is ``os._exit`` —
    whole-process, subprocess chaos only)."""

    def __init__(self, router: "FleetRouter", rid: int):
        self._router = router
        self._rid = rid

    def abort(self) -> None:
        self._router.kill_replica(self._rid, reason="fault_injected")


class FleetRouter:
    """Multi-replica serving front door.

    >>> fleet = FleetRouter(model, params, FleetConfig(n_replicas=2))
    >>> fleet.start()
    >>> h = fleet.submit(prompt_ids, SamplingParams(max_new_tokens=32))
    >>> tokens = h.result(timeout=60)    # np (n,) int32, bit-exact
    >>> fleet.shutdown()
    """

    def __init__(self, model, params,
                 config: Optional[FleetConfig] = None):
        self.config = cfg = config or FleetConfig()
        self.model = model
        self.params = params
        self._engine_cfg = cfg.engine or EngineConfig()
        n = (cfg.n_replicas if cfg.n_replicas is not None
             else dpxenv.get("DPX_FLEET_REPLICAS"))
        if n < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n}")
        self._spill_queue = (cfg.spill_queue if cfg.spill_queue is not None
                             else dpxenv.get("DPX_FLEET_SPILL_QUEUE"))
        self.metrics = cfg.metrics or self._engine_cfg.metrics
        # the placement chunk length mirrors the replicas' prefix-index
        # chunking so fleet affinity and in-replica page sharing agree
        self._page_len = (self._engine_cfg.page_len
                          if self._engine_cfg.page_len is not None
                          else dpxenv.get("DPX_SERVE_PAGE_LEN"))
        self._lock = threading.RLock()
        self._replicas: Dict[int, Replica] = {}
        self._next_rid = 0
        self._next_fid = 0
        self._routes = 0
        self._affinity_hits = 0
        self._spills = 0
        self._started = False
        for _ in range(n):
            self._build_replica()

    # -- lifecycle ----------------------------------------------------------

    def _build_replica(self) -> Replica:
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            eng = InferenceEngine(self.model, self.params,
                                  self._engine_cfg)
            rep = Replica(rid=rid, engine=eng)
            self._replicas[rid] = rep
            if self._started:
                eng.start()
        return rep

    def start(self) -> "FleetRouter":
        with self._lock:
            if self._started:
                raise RuntimeError("fleet already started")
            self._started = True
            for rep in self._replicas.values():
                if rep.state == REPLICA_LIVE:
                    rep.engine.start()
        dpxmon.register_provider("fleet", self._provider)
        return self

    def shutdown(self) -> None:
        """Orderly fleet stop: every live/draining replica's engine
        shuts down (in-flight requests fail ``EngineStopped``, NOT
        ``ReplicaFailed`` — the caller asked for this)."""
        dpxmon.unregister_provider("fleet")
        with self._lock:
            reps = list(self._replicas.values())
            self._started = False
        for rep in reps:
            if rep.state in (REPLICA_LIVE, REPLICA_DRAINING):
                rep.engine.shutdown(wait=True)
                rep.state = REPLICA_RETIRED

    def __enter__(self) -> "FleetRouter":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- placement views ----------------------------------------------------

    def _admitting(self) -> List[int]:
        with self._lock:
            return [r.rid for r in self._replicas.values()
                    if r.state == REPLICA_LIVE]

    def _loads(self, rids: List[int]) -> Dict[int, tuple]:
        out = {}
        for rid in rids:
            rep = self._replicas.get(rid)
            if rep is None:
                continue
            st = rep.engine.stats()
            occ = st["pages"]["pool_occupancy"]
            out[rid] = (st["queue_depth"], occ)
        return out

    def home_of(self, prompt) -> Optional[int]:
        """The CURRENT home replica of a prompt (None when nothing
        admits) — placement is live state, so a drain or failure
        re-homes the prefix shard on the next call."""
        key = placement.prefix_key(
            np.asarray(prompt, np.int32).reshape(-1), self._page_len)
        admitting = self._admitting()
        return placement.rendezvous(key, admitting) if admitting else None

    # -- front door ---------------------------------------------------------

    def submit(self, prompt, params: Optional[SamplingParams] = None, *,
               rng=None, on_token=None,
               tenant: Optional[str] = None) -> FleetHandle:
        """Route one request; returns immediately with a
        :class:`FleetHandle` (same streaming contract as the engine's,
        ``tenant`` passed through for the per-replica inflight quota).
        Raises ``AdmissionRejected`` synchronously — with
        ``reason="fleet_exhausted"`` when EVERY replica refused."""
        sp = params or SamplingParams()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self._lock:
            fid = self._next_fid
            self._next_fid += 1
        key = placement.prefix_key(prompt, self._page_len)
        admitting = self._admitting()
        if admitting:
            home = placement.rendezvous(key, admitting)
            # the fleet chaos seam: a drop_conn@op=fleet_submit spec
            # "severs the connection" to this request's home replica —
            # i.e. kills it in-process via _ReplicaAbort
            faults.on_comm_op(FLEET_OP, rank=home,
                              comm=_ReplicaAbort(self, home))
            admitting = self._admitting()   # the hook may have killed it
        if not admitting:
            dpxmon.inc("fleet.rejected")
            raise AdmissionRejected(
                f"fleet request {fid}: no live replica admits traffic",
                reason="fleet_exhausted", request_id=fid)
        home = placement.rendezvous(key, admitting)
        if rng is None:
            # fleet-level determinism: the engine would default to
            # PRNGKey(engine-local id), which depends on WHICH replica
            # serves — stamp the fleet id instead so the stream is
            # bit-exact regardless of routing
            rng = jax.random.PRNGKey(fid)
        order = placement.spill_order(key, home, self._loads(admitting),
                                      self._spill_queue)
        last_reject: Optional[Exception] = None
        for rid in order:
            rep = self._replicas.get(rid)
            if rep is None or rep.state != REPLICA_LIVE:
                continue
            try:
                inner = rep.engine.submit(prompt, sp, rng=rng,
                                          on_token=on_token,
                                          tenant=tenant)
            except AdmissionRejected as e:
                if e.reason in _SPILL_REASONS:
                    last_reject = e       # capacity — walk the fleet
                    continue
                raise                     # invalid everywhere — no walk
            except EngineStopped as e:
                last_reject = e           # died between checks
                continue
            return self._routed(fid, home, rid, rep, inner)
        dpxmon.inc("fleet.rejected")
        exc = AdmissionRejected(
            f"fleet request {fid}: every live replica "
            f"({len(admitting)}) rejected admission — fleet exhausted",
            reason="fleet_exhausted", request_id=fid)
        exc.__cause__ = last_reject
        raise exc

    def _routed(self, fid: int, home: int, rid: int, rep: Replica,
                inner) -> FleetHandle:
        spilled = rid != home
        with self._lock:
            self._routes += 1
            routes = self._routes
            if spilled:
                self._spills += 1
            else:
                self._affinity_hits += 1
        dpxmon.inc("fleet.routed")
        if spilled:
            dpxmon.inc("fleet.spills")
            append_event("fleet_spill", path=self._path(),
                         request_id=fid, from_replica=home,
                         to_replica=rid,
                         engine_request_id=inner.request_id)
        append_event("fleet_route", path=self._path(), request_id=fid,
                     replica=rid, home=home, spilled=spilled,
                     engine_request_id=inner.request_id)
        if routes % max(self.config.log_every, 1) == 0:
            self.emit_snapshot(step=routes)
        return FleetHandle(fid, rep, inner)

    # -- failure / elasticity ----------------------------------------------

    def kill_replica(self, rid: int, *, reason: str = "killed") -> None:
        """Hard-kill one replica IN-PROCESS (the chaos analogue of its
        host dying): its in-flight requests fail typed
        ``ReplicaFailed`` (replica + request attributed, engine crash
        chained), its prefix shard re-homes over the survivors on the
        very next ``submit``, and a rank-attributed ``replica_failed``
        event degrades the fleet health stream. Idempotent."""
        with self._lock:
            rep = self._replicas.get(rid)
            if rep is None or rep.state in (REPLICA_FAILED,
                                            REPLICA_RETIRED):
                return
            rep.state = REPLICA_FAILED
        st = rep.engine.stats()
        inflight = st["queue_depth"] + st["active_slots"]
        rep.engine.crash(
            ReplicaFailed(f"replica {rid} {reason}", replica=rid))
        dpxmon.inc("fleet.replica_failures")
        append_event("replica_failed", path=self._path(), rank=rid,
                     replica=rid, reason=reason, inflight=inflight)
        self.emit_snapshot()

    def drain_replica(self, rid: int, *, timeout_s: float = 120.0,
                      rule: str = "", reason: str = "drain") -> bool:
        """Graceful retire: stop admitting (placement re-homes the
        shard NOW), let the engine finish every in-flight request —
        never kill mid-stream — then shut it down and release its
        pages. Returns False (replica back to live) if in-flight work
        outlasts ``timeout_s``; refuses to drain the last live
        replica."""
        with self._lock:
            rep = self._replicas.get(rid)
            if rep is None or rep.state != REPLICA_LIVE:
                return False
            if len(self._admitting()) <= 1:
                raise ValueError(
                    f"cannot drain replica {rid}: it is the last live "
                    f"replica (the fleet would admit nothing)")
            rep.state = REPLICA_DRAINING
        eng = rep.engine
        deadline = time.monotonic() + timeout_s
        drained = False
        while time.monotonic() < deadline:
            st = eng.stats()
            if st["queue_depth"] == 0 and st["active_slots"] == 0:
                drained = True
                break
            time.sleep(0.01)
        if not drained:
            with self._lock:
                rep.state = REPLICA_LIVE     # drain aborted, not killed
            return False
        eng.shutdown(wait=True)
        with self._lock:
            rep.state = REPLICA_RETIRED
        append_event("replica_drained", path=self._path(), rank=rid,
                     replica=rid, rule=rule, reason=reason,
                     completed=st["completed"])
        append_event("fleet_scale", path=self._path(), action="drain",
                     rank=rid, replica=rid, rule=rule, reason=reason,
                     replicas=len(self._admitting()))
        dpxmon.inc("fleet.replicas_drained")
        self.emit_snapshot()
        return True

    def add_replica(self, *, rule: str = "",
                    reason: str = "scale_out") -> int:
        """Scale out by one replica (a fresh engine over the shared
        params, started if the fleet is). Every call is a scaling
        decision: a rank-attributed ``fleet_scale`` event."""
        rep = self._build_replica()
        append_event("fleet_scale", path=self._path(), action="add",
                     rank=rep.rid, replica=rep.rid, rule=rule,
                     reason=reason, replicas=len(self._admitting()))
        dpxmon.inc("fleet.scale_events")
        self.emit_snapshot()
        return rep.rid

    def revive_replica(self, rid: int, *, backoff_s: float = 0.0) -> int:
        """Relaunch a FAILED replica under the SAME id — stable ids are
        what make the health recovery attributable (the replica's
        ``replica_failed`` stream is keyed on rank=rid; the next fleet
        snapshot naming rid live clears it). Mirrors the
        ``runtime/elastic.py`` relaunch discipline: a per-slot attempt
        counter and doubling backoff between attempts."""
        with self._lock:
            rep = self._replicas.get(rid)
            if rep is None or rep.state != REPLICA_FAILED:
                raise ValueError(
                    f"replica {rid} is not failed — revive relaunches "
                    f"failed replicas only (add_replica scales out)")
            rep.attempt += 1
            attempt = rep.attempt
        if backoff_s > 0:
            time.sleep(min(backoff_s * (2 ** (attempt - 1)), 30.0))
        eng = InferenceEngine(self.model, self.params, self._engine_cfg)
        with self._lock:
            rep.engine = eng
            rep.state = REPLICA_LIVE
            if self._started:
                eng.start()
        append_event("fleet_scale", path=self._path(), action="revive",
                     rank=rid, replica=rid, attempt=attempt,
                     reason="relaunch", replicas=len(self._admitting()))
        dpxmon.inc("fleet.scale_events")
        self.emit_snapshot()
        return rid

    # -- observability ------------------------------------------------------

    def _path(self) -> Optional[str]:
        return self.metrics.path if self.metrics is not None else None

    def stats(self) -> Dict:
        with self._lock:
            reps = list(self._replicas.values())
            routes, hits, spills = (self._routes, self._affinity_hits,
                                    self._spills)
        per = {}
        completed = failed = 0
        for rep in reps:
            st = rep.engine.stats()
            per[rep.rid] = {"state": rep.state, "attempt": rep.attempt,
                            "queue_depth": st["queue_depth"],
                            "active_slots": st["active_slots"],
                            "completed": st["completed"],
                            "failed": st["failed"]}
            completed += st["completed"]
            failed += st["failed"]
        return {"replicas": per,
                "live": sum(1 for r in reps
                            if r.state == REPLICA_LIVE),
                "routes": routes, "spills": spills,
                "affinity_hits": hits,
                "route_affinity_hit_rate": (hits / routes) if routes
                else None,
                "completed": completed, "failed": failed}

    def _provider(self) -> Dict[str, float]:
        """dpxmon snapshot provider: fleet-level gauges plus the
        per-replica queue/occupancy dimensions the SLO scale rules and
        ``tools/dpxmon.py`` replay read."""
        with self._lock:
            reps = [r for r in self._replicas.values()
                    if r.state in (REPLICA_LIVE, REPLICA_DRAINING)]
            routes, hits = self._routes, self._affinity_hits
        out: Dict[str, float] = {
            "fleet.replicas": float(sum(1 for r in reps
                                        if r.state == REPLICA_LIVE)),
            "fleet.route_affinity_hit_rate":
                (hits / routes) if routes else 0.0,
        }
        depths = []
        for rep in reps:
            st = rep.engine.stats()
            occ = st["pages"]["pool_occupancy"]
            out[f"fleet.r{rep.rid}.queue_depth"] = float(
                st["queue_depth"])
            out[f"fleet.r{rep.rid}.pool_occupancy"] = float(occ)
            depths.append(st["queue_depth"])
        out["fleet.max_queue_depth"] = float(max(depths, default=0))
        return out

    def emit_snapshot(self, step: Optional[int] = None) -> None:
        """One fleet-attributed ``metrics_snapshot``: the registry
        (including the fleet provider's per-replica gauges) plus a
        ``replicas`` field naming the CURRENT admitting set — the clean
        observation that recovers each named replica's failure stream
        in ``obs/health.py``."""
        if not dpxmon.enabled():
            return
        dpxmon.emit_snapshot(path=self._path(),
                             step=step if step is not None
                             else self._routes,
                             source="serve_fleet",
                             replicas=self._admitting())
