"""Double-buffered host pipeline: stage chunk i+1 while chunk i trains.

The old server loop serialized host work (numpy batch assembly, rng draws,
host->device transfer) with device work every round.  ``HostPrefetcher``
moves all of it onto one background thread that walks the chunk schedule
in order — a single thread, because the data rng stream must advance in
exactly the per-round order of the reference loop for sampled clients and
batches to match it bit for bit — and hands staged, device-resident chunks
to the consumer through a bounded queue (default depth 2: one chunk being
consumed, one in flight).

``StagingPool`` keeps the big stacked host arrays a chunk builder fills
(batches/cids/sizes) alive across chunks: steady-state staging re-fills
the same buffers instead of re-allocating tens of MB per chunk, which is
what makes them pinnable on accelerator backends.  The builder must
guarantee the previous transfer out of a buffer has completed before
re-filling it — the engine does so by blocking the PREFETCH thread (never
the dispatch thread) on the staged device arrays before handing the chunk
over.

Exceptions raised inside the builder are re-raised at the consuming
``__iter__`` site; ``close()`` unblocks and retires the worker if the
consumer stops early.  ``wait_s`` accumulates the time the CONSUMER spent
blocked on the queue — the host-side stall the pipeline exists to remove;
the engine surfaces it in ``ServerResult.stats``.

``WritebackLane`` is the pipeline's reverse direction: a single serialized
worker draining device results back to host state (the cohort-paged EF
store writes each chunk's updated rows back through one — see
``repro.engine.efstore``).  A completion counter + condition variable let
producers wait for a PREFIX of the submitted work ("writebacks through
chunk j-2 done") without ever blocking on the device themselves.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Dict, Iterable, Iterator, Tuple

import numpy as np


class StagingPool:
    """Reusable host staging buffers, keyed by name, matched on shape/dtype.

    ``take(name, shape, dtype)`` returns a writable ndarray; the same name
    returns the SAME memory as long as shape/dtype are stable (chunk
    shapes only change at schedule tails).  Callers own the discipline of
    not re-taking a name while its previous contents are still being
    transferred.

    Accelerator backends only: ``jax.device_put`` there is a real
    host->device DMA and ``block_until_ready`` fences it, after which the
    buffer is refillable.  The CPU backend may alias or lazily read the
    numpy buffer PAST that fence (the "device" is the host), so the engine
    disables reuse on CPU — refilling would corrupt in-flight chunks.
    """

    def __init__(self):
        self._bufs: Dict[str, np.ndarray] = {}

    def take(self, name: str, shape, dtype) -> np.ndarray:
        buf = self._bufs.get(name)
        if buf is None or buf.shape != tuple(shape) \
                or buf.dtype != np.dtype(dtype):
            buf = np.empty(shape, dtype)
            self._bufs[name] = buf
        return buf


class WritebackLane:
    """Single-worker serialized write-back queue with a completion counter.

    ``submit(fn)`` enqueues a thunk; one daemon worker runs them strictly
    in submission order (the thunks typically ``jax.device_get`` a chunk
    result and fold it into host state, so ordering IS the consistency
    model).  ``wait_done(n)`` blocks the CALLING thread until at least
    ``n`` submitted thunks have completed — the EF pager's staging thread
    uses it to order host gathers after the write-backs they depend on —
    and returns False instead of blocking forever once ``close()`` has
    been called.  ``stall_s`` accumulates producer time spent inside
    ``wait_done``.

    A thunk exception is captured (the worker keeps counting so waiters
    never deadlock) and re-raised at the next ``wait_done``/``flush``;
    ``close()`` drains the remaining queue through the worker before
    joining, so a post-close ``flush`` still sees everything completed.
    """

    def __init__(self, *, name: str = "engine-writeback", runlog=None):
        from repro.obs.runlog import as_runlog
        self._runlog = as_runlog(runlog)
        self._q: queue.Queue = queue.Queue()
        self._cv = threading.Condition()
        self._done = 0
        self._submitted = 0
        self._stop = False
        self._closed = False
        self.error = None
        self.stall_s = 0.0      # producer time blocked in wait_done
        self._thread = threading.Thread(target=self._worker, name=name,
                                        daemon=True)
        self._thread.start()

    @property
    def submitted(self) -> int:
        return self._submitted

    @property
    def done(self) -> int:
        with self._cv:
            return self._done

    def submit(self, fn: Callable) -> None:
        self._submitted += 1
        self._q.put(fn)

    def _worker(self):
        while True:
            fn = self._q.get()
            if fn is None:
                return
            try:
                fn()
            except BaseException as e:   # surfaced at the next wait/flush
                with self._cv:
                    if self.error is None:
                        self.error = e
            finally:
                # count even a failed thunk: waiters must wake either way
                with self._cv:
                    self._done += 1
                    self._cv.notify_all()

    def _raise_error(self):
        if self.error is not None:
            err, self.error = self.error, None
            raise err

    def wait_done(self, n: int) -> bool:
        """Block until ``n`` submitted thunks completed; False if the lane
        was closed first (the shutdown path — callers abort their work)."""
        t0 = time.perf_counter()
        with self._cv:
            while self._done < n and not self._stop:
                self._cv.wait(0.05)
            ok = self._done >= n
        self.stall_s += time.perf_counter() - t0
        self._raise_error()
        return ok

    def flush(self) -> None:
        """Wait for everything submitted so far to complete."""
        self.wait_done(self._submitted)

    def close(self) -> None:
        """Drain the queue through the worker, then retire it (idempotent).

        Pending thunks still RUN (a checkpoint's final flush may follow),
        but ``wait_done`` callers blocked on never-submitted work are woken
        immediately.  Never raises — shutdown runs from ``finally`` blocks;
        a captured error is emitted as a runlog warning instead.
        """
        if self._closed:
            return
        self._closed = True
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._q.put(None)
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            self._runlog.warning("writeback.join_timeout")
        if self.error is not None:
            self._runlog.warning("writeback.error", error=repr(self.error))


class HostPrefetcher:
    """Iterate ``(r0, r1, build_chunk(r0, r1))`` over ``schedule``.

    With ``enabled=False`` the chunks are built synchronously on the
    consumer thread (same iteration contract, no overlap) — the debugging
    / fallback path.
    """

    def __init__(self, build_chunk: Callable, schedule: Iterable[Tuple[int,
                 int]], *, depth: int = 2, enabled: bool = True,
                 runlog=None):
        from repro.obs.runlog import as_runlog
        self._build = build_chunk
        self._schedule = list(schedule)
        self._enabled = enabled
        self._runlog = as_runlog(runlog)
        self.wait_s = 0.0       # consumer time blocked on staging
        self.error = None       # builder exception the consumer never saw
        self._closed = False
        if enabled:
            self._q: queue.Queue = queue.Queue(maxsize=depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._worker, name="engine-prefetch", daemon=True)
            self._thread.start()

    def _put(self, item) -> bool:
        """Blocking put that stays responsive to close()."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            for r0, r1 in self._schedule:
                if self._stop.is_set():
                    return
                # the span runs on THIS thread — RunLog's nesting stacks
                # are thread-local, so staging intervals interleave
                # correctly with the dispatch thread's chunk spans
                with self._runlog.span("prefetch.stage", r0=r0, r1=r1):
                    staged = self._build(r0, r1)
                if not self._put((r0, r1, staged)):
                    return
            self._put(None)
        except BaseException as e:  # surfaced at the consumer
            if not self._put(e):
                # the consumer is already gone (stopped early / closing):
                # the queue put was refused, so park the exception on the
                # prefetcher for close() to surface instead of letting it
                # die silently with this daemon thread
                self.error = e

    def __iter__(self) -> Iterator:
        if not self._enabled:
            for r0, r1 in self._schedule:
                t0 = time.perf_counter()
                with self._runlog.span("prefetch.stage", r0=r0, r1=r1):
                    staged = self._build(r0, r1)
                self.wait_s += time.perf_counter() - t0
                yield r0, r1, staged
            return
        while True:
            t0 = time.perf_counter()
            item = self._q.get()
            self.wait_s += time.perf_counter() - t0
            if item is None:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def _drain_queue(self):
        """Drop staged chunks; keep the FIRST builder exception found."""
        try:
            while True:
                item = self._q.get_nowait()
                if isinstance(item, BaseException) and self.error is None:
                    self.error = item
        except queue.Empty:
            pass

    def close(self):
        """Stop the worker and drop any staged chunks (idempotent).

        A builder exception the consumer never iterated far enough to see
        — it stopped early, or the failure raced the shutdown — is
        captured on ``self.error`` and emitted as a structured runlog
        warning rather than dying silently with the daemon thread.
        ``close`` never raises it: the engine closes from a ``finally``
        block, where raising would mask the error already unwinding.
        """
        if not self._enabled or self._closed:
            return
        self._closed = True
        self._stop.set()
        self._drain_queue()             # unblock a worker stuck in _put
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            self._runlog.warning("prefetch.join_timeout")
        self._drain_queue()             # anything parked while joining
        if self.error is not None:
            self._runlog.warning("prefetch.error", error=repr(self.error))
