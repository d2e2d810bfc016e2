"""Async data plane: a background feed thread + bounded device queue.

The reference's ``DataProvider`` owns an async double-buffer queue so the
next batch is converted and staged while the trainer computes
(paddle/gserver/dataproviders/DataProvider.h DoubleBuffer, and
PyDataProvider2.cpp's background load thread).  The TPU-native equivalent:
``DevicePrefetcher`` runs the host-side feed — python converters, sharding,
``jax.device_put`` — on a worker thread, so batch N+1's host→device transfer
overlaps step N's device compute.  JAX dispatch is already asynchronous; the
piece that would otherwise serialize on the main thread is exactly this
host-side conversion + transfer issue, which the worker hides.

Queue depth 2 = the reference's double buffer: one batch in flight on the
device path, one staged.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional

__all__ = ["DevicePrefetcher", "prefetch"]


class _Failure:
    def __init__(self, exc: BaseException):
        self.exc = exc


_DONE = object()


class DevicePrefetcher:
    """Iterate ``prepare(item)`` for each item of ``source``, with the
    prepare calls running ahead on a background thread.

    ``prepare`` does the host-side feed work (DataFeeder conversion +
    shard_batch/device_put); the returned batches come out in order.
    ``wait_s`` accumulates main-thread time spent blocked on the queue —
    ~0 means the data plane fully hides behind compute; large means the
    reader/transfer is the bottleneck.  ``prefetch()`` hides this object,
    so no trainer path reads the field (only ``bench.py`` does): what
    ``trainer.SGD.train`` waits for its next batch is its ``feed_wait``
    span (trainer/sgd.py), the same wait seen from the consumer's side.
    """

    def __init__(
        self,
        source: Iterable,
        prepare: Optional[Callable] = None,
        depth: int = 2,
    ):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._prepare = prepare if prepare is not None else (lambda x: x)
        self._source = source
        self._stop = threading.Event()
        self._terminal = None  # sticky: _DONE or _Failure once seen
        self.wait_s = 0.0
        self._thread = threading.Thread(
            target=self._run, name="paddle-feed", daemon=True
        )
        self._thread.start()

    # -- worker ----------------------------------------------------------
    def _put(self, item) -> bool:
        """Bounded put that stays responsive to close(); False = stopping."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self) -> None:
        try:
            for item in self._source:
                if self._stop.is_set() or not self._put(self._prepare(item)):
                    return
        except BaseException as e:  # re-raised on the consuming thread
            self._put(_Failure(e))
        else:
            self._put(_DONE)

    # -- consumer --------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        # terminal states are sticky: the worker is gone, so a consumer that
        # keeps calling next() (retry loops, second iteration) must keep
        # getting StopIteration / the error instead of blocking forever
        if self._terminal is not None:
            if self._terminal is _DONE:
                raise StopIteration
            raise self._terminal.exc
        t0 = time.perf_counter()
        got = self._q.get()
        self.wait_s += time.perf_counter() - t0
        if got is _DONE:
            self._terminal = got
            raise StopIteration
        if isinstance(got, _Failure):
            self._terminal = got
            raise got.exc
        return got

    def close(self) -> None:
        """Stop the worker (early loop exit); safe to call repeatedly."""
        self._stop.set()
        while True:  # unblock a worker stuck in put()
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def prefetch(source: Iterable, prepare: Optional[Callable] = None, depth: int = 2):
    """Generator face over DevicePrefetcher with guaranteed worker teardown
    even when the consumer abandons the loop early."""
    pf = DevicePrefetcher(source, prepare, depth)
    try:
        for item in pf:
            yield item
    finally:
        pf.close()
