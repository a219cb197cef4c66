"""Launch: the framework's entry point (reference ``distributed.py:40-58``).

The reference launches one OS process per GPU via ``mp.spawn`` after a
free-port rendezvous scramble (``distributed.py:32-52``). On TPU neither is
needed: a single controller process owns every chip and XLA compiles the
collectives into the step, so "launch" degenerates to device discovery plus
one call of the worker body — while preserving the reference's three-branch
contract exactly:

* ``world > 1``  — distributed: ``worker_fn(rank, world, *args)`` with the
  mesh available for :func:`init_process_group`. Under SPMD the worker runs
  once per *controller process* (one per host), not once per chip; ``rank``
  is the process index. (The per-rank-process front door lives in
  :mod:`distributed_pytorch_tpu.runtime.multiprocess` backed by the native
  host collectives — the gloo/c10d path.)
* ``world == 1`` — single accelerator: ``worker_fn(0, 1, *args)`` in-process,
  no group (reference ``distributed.py:54-55``).
* ``world == 0`` — CPU-only host: ``worker_fn(0, 0, *args)``
  (reference ``distributed.py:57-58``).

Like the reference's spawn-with-``join=True`` (``distributed.py:51-52``),
worker exceptions propagate to the caller.
"""

from __future__ import annotations

import os
from typing import Callable

import jax

from . import compile_cache, context


def launch(worker_fn: Callable, *args) -> None:
    """Run ``worker_fn(rank, world_size, *args)`` per the visible topology.

    TPU-native analog of ``launch`` (reference ``distributed.py:40-58``).
    The ``CUDA_VISIBLE_DEVICES``-must-be-set guard (``distributed.py:44-45``)
    has no analog: TPU topology is discovered from the runtime, so there is
    no footgun of silently grabbing every GPU on a shared box.
    """
    compile_cache.enable()
    world_size = context.device_count()

    if world_size > 1:
        # Multi-host SPMD: each controller process calls launch; jax gives
        # each a process index. Single host: process_index() == 0.
        rank = jax.process_index()
        worker_fn(rank, world_size, *args)
    elif world_size == 1:
        worker_fn(0, world_size, *args)
    else:
        worker_fn(0, world_size, *args)


def find_free_port() -> int:
    """Return a kernel-assigned free TCP port.

    Kept for API parity with the reference (``distributed.py:32-37``), where
    it seeds the ``MASTER_PORT`` rendezvous. The SPMD runtime needs no port;
    the native multiprocess front door uses it for its TCP store. Same
    inherent TOCTOU caveat as the reference: the port is released before the
    consumer binds it.
    """
    import socket
    from contextlib import closing

    with closing(socket.socket(socket.AF_INET, socket.SOCK_STREAM)) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("", 0))
        return s.getsockname()[1]
