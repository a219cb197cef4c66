"""Elastic training: restart-from-checkpoint supervision.

The reference's failure story ends at detection (its ``join=True`` spawn
surfaces child errors; recovery is the user re-running the command —
reference ``README.md:121-125``). :mod:`watchdog` automates the
detection half (fail-fast supervision, heartbeats, orphan cleanup); this
module closes the loop with *recovery*: run the training entrypoint in a
supervised subprocess and, when it dies — crash, OOM-kill, watchdog
fail-fast — relaunch it up to ``max_restarts``
times with exponential backoff. Workers make this correct by being
resume-idempotent: start from ``utils.checkpoint.latest_step`` when a
checkpoint directory is non-empty (exactly what
``examples/train_transformer_lm.py --save DIR --resume`` does), so a
relaunch repeats no optimizer step and the loss trajectory continues
bit-exactly (tests/test_elastic.py pins this).

The child runs in a fresh OS process (spawn context by default): a
segfaulted or OOM-killed worker cannot take the supervisor down, and a
fresh process re-initializes the accelerator runtime cleanly. The chip
belongs to one process at a time, so the supervisor itself never
initializes a JAX backend: only the child touches the device.

The restart attempt number is exported to the child as
``DPX_ELASTIC_ATTEMPT`` (0 on the first launch); ``DPX_ELASTIC=1`` marks
the child as elastically supervised.

Topology shrink: a relaunch is not forced back onto the dead topology.
The ``reconfigure`` hook of :func:`elastic_run` rewrites the worker's
arguments between attempts (e.g. halving the world size after a host
loss), and the sharded checkpoint subsystem (:mod:`..ckpt`) reshards the
restore onto whatever mesh the relaunched worker builds — a checkpoint
written at ``dp=N`` resumes at ``dp=M`` (tests/test_ckpt_sharded.py
covers kill → shrink → resume end to end).
"""

from __future__ import annotations

import multiprocessing as mp
import time
from typing import Callable, NamedTuple, Optional, Sequence

from . import env as _env
from .watchdog import WorkerFailure

ATTEMPT_ENV = "DPX_ELASTIC_ATTEMPT"
ELASTIC_ENV = "DPX_ELASTIC"


class ElasticResult(NamedTuple):
    restarts: int          # how many times the worker was relaunched
    exitcodes: tuple       # exit code of every attempt (last one is 0)


def _child_bootstrap(target, args, child_env):
    """Module-level (spawn-picklable) child entry. Exports the elastic
    bookkeeping + caller env IN THE CHILD (the parent's environment must
    not be mutated — a leaked DPX_ELASTIC would make the supervisor
    itself claim to be supervised), then applies ``DPX_PLATFORM``
    (+ ``DPX_CPU_DEVICES`` for cpu) via jax.config before any backend
    use: unpickling this function has already imported jax, which read
    ``JAX_PLATFORMS`` at import, so a platform named in ``child_env``
    must go through the config."""
    _env.apply_overrides(child_env)
    plat = _env.get("DPX_PLATFORM")
    if plat:
        import jax
        jax.config.update("jax_platforms", plat)
        n = _env.raw("DPX_CPU_DEVICES")
        if plat == "cpu" and n:
            jax.config.update("jax_num_cpu_devices", int(n))
    target(*args)


def elastic_run(target: Callable, args: Sequence = (), *,
                max_restarts: int = 3, backoff_s: float = 1.0,
                ctx_method: str = "spawn",
                env: Optional[dict] = None,
                reconfigure: Optional[Callable] = None) -> ElasticResult:
    """Run ``target(*args)`` in a subprocess; relaunch on failure.

    ``target`` must be picklable (module-level) and resume-idempotent:
    on restart it is called with the SAME arguments and is expected to
    pick up from its latest checkpoint. Returns once an attempt exits 0;
    raises :class:`watchdog.WorkerFailure` when ``max_restarts``
    relaunches are exhausted. ``backoff_s`` doubles per restart (a
    crashing-on-start worker must not busy-loop the host). ``env``
    entries are exported to the child (on top of the parent's
    environment).

    ``reconfigure(attempt, exitcode, args) -> args | None`` runs before
    each relaunch (``attempt`` = the upcoming attempt number, ``exitcode``
    = the failed attempt's exit code) and may return NEW arguments for the
    next attempt — the topology-shrink hook: after a host dies, relaunch
    the worker on a smaller world and let the sharded checkpoint
    subsystem (:mod:`..ckpt`) reshard the restore onto it, instead of
    demanding the original world size back (docs/failures.md). Returning
    None keeps the previous arguments.
    """
    from ..obs import metrics as _dpxmon
    from ..utils.logging import append_event

    ctx = mp.get_context(ctx_method)
    codes = []
    args = tuple(args)
    for attempt in range(max_restarts + 1):
        if attempt > 0 and reconfigure is not None:
            new_args = reconfigure(attempt, codes[-1], args)
            if new_args is not None and tuple(new_args) != args:
                args = tuple(new_args)
                append_event("elastic_reconfigured", attempt=attempt,
                             args=[str(a) for a in args])
        child_env = {ATTEMPT_ENV: str(attempt), ELASTIC_ENV: "1"}
        if env:
            child_env.update({k: str(v) for k, v in env.items()})
        p = ctx.Process(target=_child_bootstrap,
                        args=(target, tuple(args), child_env))
        p.start()
        try:
            # dpxlint: disable=DPX003 the supervisor's whole job is waiting out the worker; watchdog deadlines live inside it
            p.join()
        except BaseException:
            # supervisor interrupted (KeyboardInterrupt, an exception in
            # our own machinery): the child must not outlive us as an
            # orphan still holding ports/checkpoint locks
            if p.is_alive():
                p.terminate()
                p.join(5)
                if p.is_alive():
                    p.kill()
                    p.join()  # dpxlint: disable=DPX003 post-SIGKILL reap returns promptly
            raise
        codes.append(p.exitcode)
        # dpxmon gauges (obs/metrics.py): relaunch churn is alertable
        # BEFORE giveup — a monitor rule on elastic.attempts catches a
        # crash-looping worker while restarts are still being burned
        _dpxmon.set_gauge("elastic.attempts", attempt + 1)
        _dpxmon.set_gauge("elastic.last_exit_code", p.exitcode)
        if p.exitcode == 0:
            if attempt > 0:
                append_event("elastic_recovered", restarts=attempt,
                             exitcodes=codes)
            return ElasticResult(restarts=attempt, exitcodes=tuple(codes))
        append_event("elastic_worker_exit", attempt=attempt,
                     exitcode=p.exitcode,
                     restarts_left=max_restarts - attempt)
        if attempt < max_restarts:
            sleep = backoff_s * (2 ** attempt)
            print(f"# elastic: attempt {attempt} exited "
                  f"{p.exitcode}; relaunching in {sleep:.1f}s "
                  f"({max_restarts - attempt} restart(s) left)", flush=True)
            time.sleep(sleep)
    append_event("elastic_giveup", attempts=max_restarts + 1,
                 exitcodes=codes)
    raise WorkerFailure(
        f"worker failed {max_restarts + 1} times "
        f"(exit codes {codes}); giving up", exitcode=codes[-1])


def elastic_attempt() -> int:
    """The current process's restart attempt number (0 = first launch,
    also when not running under :func:`elastic_run`)."""
    return _env.get(ATTEMPT_ENV)


def is_elastic() -> bool:
    """Whether this process is supervised by :func:`elastic_run`."""
    return _env.get(ELASTIC_ENV)
