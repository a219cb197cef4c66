"""Per-rank-process front door — the ``mp.spawn`` equivalent.

The reference's execution model is one OS process per device with rank
injection and join-based error propagation (``mp.spawn(worker_fn,
args=(world_size, *args), nprocs=world_size, join=True)``, reference
``distributed.py:51-52``). The SPMD path doesn't need it (one controller
drives all chips), but the capability is part of the surface: this module
spawns ``worker_fn(rank, world_size, *args)`` in ``nprocs`` OS processes,
wired to the NATIVE host process group (native/dpxhost.cpp) for
collectives — the c10d/gloo replacement — and propagates child failures to
the parent like ``join=True``.

Device ownership: by default children are forced onto the CPU XLA
backend — the accelerator belongs to the single-controller SPMD front
door (two processes cannot share one TPU chip), so per-rank host
processes are the CPU-fallback execution model (reference
``distributed.py:57-58``/gloo). On a MULTI-chip host the torch-style
one-process-per-chip model is available by opt-in:
``DPX_MULTIPROC_ACCEL=tpu`` gives child rank r exclusive ownership of
chip r (``TPU_VISIBLE_DEVICES=r``, the TPU analog of the reference's
``CUDA_VISIBLE_DEVICES`` remapping, reference ``distributed.py:88-91``:
rank i owns local device i). Run on a four-chip v5e host:
``DPX_MULTIPROC_ACCEL=tpu python examples/min_ddp_multiprocess.py
--nprocs 4`` trained with rank r on chip r (PR 21).

The parent only spawns and supervises: it never initializes a JAX
backend, so every chip is free for the child that owns it.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import sys
import time
import traceback
from typing import Callable

from . import env as _env
from .launcher import find_free_port
from .watchdog import (WORKER_TAG_ENV, ProcessSupervisor, WorkerFailure,
                       register_active_tag, unregister_active_tag)

_CHILD_ENV = {
    # host processes are CPU-backed: the chip belongs to one process
    "JAX_PLATFORMS": "cpu",
}

MULTIPROC_ACCEL_ENV = "DPX_MULTIPROC_ACCEL"


def _child_env_for_rank(rank: int) -> dict:
    """Per-rank child environment: CPU by default; with
    ``DPX_MULTIPROC_ACCEL=tpu`` rank r owns LOCAL chip r exclusively.
    Unknown values raise — a typo must not silently demote a multi-chip
    run to CPU."""
    accel = _env.get(MULTIPROC_ACCEL_ENV).strip().lower()
    if accel == "tpu":
        return {"JAX_PLATFORMS": "tpu",
                "TPU_VISIBLE_DEVICES": str(rank),
                # each single-chip process is its own one-proc runtime
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}
    if accel not in ("", "cpu"):
        raise ValueError(
            f"{MULTIPROC_ACCEL_ENV}={accel!r} not supported (use 'tpu', "
            "'cpu', or unset)")
    return dict(_CHILD_ENV)


def _worker_shim(rank: int, world_size: int, master_port: int,
                 worker_fn: Callable, args: tuple, err_q) -> None:
    try:
        _env.set("DPX_BACKEND", "host")
        _env.set("DPX_MASTER_PORT", master_port)
        _env.set("DPX_MASTER_ADDR", "127.0.0.1")
        worker_fn(rank, world_size, *args)
    except Exception as e:
        # typed comm failures carry structured attribution (which op,
        # which peer) — ship it so the supervisor can name the dead rank
        # even when that rank itself never reported (hard kill)
        from .native import CommError
        if isinstance(e, CommError):
            err_q.put((rank, traceback.format_exc(),
                       {"kind": type(e).__name__, "op": e.op,
                        "peer": e.peer}))
        else:
            err_q.put((rank, traceback.format_exc()))
        raise


def launch_multiprocess(worker_fn: Callable, nprocs: int, *args,
                        master_port: int = None,
                        grace_s: float = 5.0) -> None:
    """Spawn ``worker_fn(rank, nprocs, *args)`` in ``nprocs`` processes.

    Worker functions must be picklable (module-level), as with torch's
    ``mp.spawn``. Raises ``RuntimeError`` carrying the first failing
    child's traceback (the ``join=True`` contract) — but fail-FAST: the
    first abnormal exit terminates the surviving workers after
    ``grace_s`` instead of leaving them hung in a collective (the orphan
    scenario the reference handles with a manual kill command,
    ``README.md:121-125``). Workers carry a per-launch tag in
    ``DPX_WORKER_TAG`` so :func:`watchdog.kill_orphan_workers` can clean
    up after a crashed launcher."""
    if nprocs < 1:
        raise ValueError(f"nprocs must be >= 1, got {nprocs}")
    port = master_port if master_port is not None else find_free_port()
    tag = f"{os.getpid()}-{int(time.time() * 1e6)}"

    ctx = mp.get_context("spawn")
    err_q = ctx.Queue()
    procs = []
    register_active_tag(tag)
    try:
        try:
            for rank in range(nprocs):
                child_env = {**_child_env_for_rank(rank),
                             WORKER_TAG_ENV: tag}
                saved = _env.snapshot(child_env)
                try:
                    _env.apply_overrides(child_env)
                    p = ctx.Process(
                        target=_worker_shim,
                        args=(rank, nprocs, port, worker_fn, args, err_q),
                        daemon=False)
                    p.start()
                    procs.append(p)
                finally:
                    _env.restore(saved)
        except BaseException:
            # a failed start must not leave earlier ranks hanging in the
            # rendezvous waiting for peers that never launched
            ProcessSupervisor(procs, err_q, grace_s=grace_s).terminate_all()
            raise

        try:
            # dpxlint: disable=DPX003 supervisor join polls children with its own settle/grace escalation
            ProcessSupervisor(procs, err_q, grace_s=grace_s).join()
        except WorkerFailure as e:
            # failure events land in the line-JSON metrics log (path via
            # DPX_METRICS_LOG) so post-mortems see WHAT died, not just
            # that the run ended
            from ..utils.logging import append_event
            append_event("worker_failure", rank=e.rank, op=e.op,
                         kind=e.kind, exitcode=e.exitcode, world=nprocs,
                         tag=tag)
            # flight recorder (obs/trace.py): if this supervisor process
            # traced any spans, ship them with the failure — no-op when
            # the ring is empty (the common supervisor case; each rank
            # process ships its own timeline from its typed error path)
            from ..obs import trace as _dpxtrace
            _dpxtrace.on_typed_failure(e)
            # schedule verifier: when the dying ranks flushed divergent
            # collective schedules, name the odd rank/op/seq alongside
            # the timeout instead of leaving a bare CommTimeout
            # (analysis/schedule.py; logs a schedule_divergence event).
            # Best-effort by contract: the diagnosis must never replace
            # the typed WorkerFailure it annotates.
            try:
                from ..analysis.schedule import report_divergence
                report = report_divergence(tag=tag)
                if report:
                    print(f"# {report}", file=sys.stderr, flush=True)
            except Exception:
                pass
            raise
    finally:
        unregister_active_tag(tag)
