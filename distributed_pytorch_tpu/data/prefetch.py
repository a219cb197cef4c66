"""Background-thread device prefetching for the input pipeline.

The reference's DataLoader blocks the training loop on both batch
assembly and the H2D copy every step (``min_DDP.py:95-96``). Both are
fully overlappable: a worker thread assembles the
next batches and starts their device transfers while the current step
runs, keeping the accelerator fed.

``device_prefetch`` wraps any batch iterator (e.g. ``data.DataLoader``)
and yields batches that are already on device (or in flight —
``device_put`` is async; by the time the step consumes them the transfer
has overlapped with the previous step's compute).
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Optional

import jax


class _Stop:
    pass


_STOP = _Stop()


def device_prefetch(iterable: Iterable, size: int = 2,
                    place: Optional[Callable] = None) -> Iterator:
    """Iterate ``iterable`` with ``size`` batches prefetched onto device.

    ``place`` maps a host batch to device (default:
    ``runtime.context.shard_batch`` — dp-sharded axis 0, replicated at
    world 1). Exceptions from the source iterator or placement propagate
    to the consumer at the matching position. The worker is a daemon
    thread; when the consumer abandons the iterator, every queue
    interaction the worker makes is abandonment-aware (timeout + flag
    polls), so the thread exits as soon as the source yields control —
    only a source blocked forever inside ``next()`` can pin it, which no
    queue design can interrupt.
    """
    if size < 1:
        raise ValueError(f"size must be >= 1, got {size}")
    if place is None:
        from ..runtime.context import shard_batch
        place = shard_batch

    q: "queue.Queue" = queue.Queue(maxsize=size)
    abandoned = threading.Event()

    def put_or_abandon(item) -> bool:
        """Bounded put that gives up when the consumer is gone."""
        while not abandoned.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for batch in iterable:
                if abandoned.is_set():
                    return
                if not put_or_abandon(place(batch)):
                    return
            put_or_abandon(_STOP)
        except BaseException as e:  # noqa: BLE001 — repropagated below
            put_or_abandon(e)

    t = threading.Thread(target=worker, daemon=True,
                         name="dpx-prefetch")
    t.start()

    try:
        while True:
            # dpxlint: disable=DPX003 producer is in-process and always lands _STOP or the exception before exiting
            item = q.get()
            if item is _STOP:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        abandoned.set()


class PrefetchLoader:
    """A DataLoader wrapper yielding device-resident batches each epoch.

    Keeps the loader's epoch/len surface (``set_epoch``, ``len``) so it
    drops into the ladder examples in place of the bare loader::

        loader = PrefetchLoader(DataLoader(ds, batch_size, sampler=s))
        for epoch ...:
            loader.set_epoch(epoch)
            for batch in loader:   # already on device
                ...
    """

    def __init__(self, loader, size: int = 2,
                 place: Optional[Callable] = None):
        self.loader = loader
        self.size = size
        self.place = place

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def __iter__(self):
        return device_prefetch(self.loader, self.size, self.place)

    def __len__(self):
        return len(self.loader)
