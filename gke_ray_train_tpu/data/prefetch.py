"""Asynchronous input pipeline: background prefetch + sharded placement.

The reference delegates input to HF ``datasets`` over a GCS FUSE mount
and eats the host-side stall every step — tokenize/pack and the
host→device transfer run serially with the train step, so the TPU idles
whenever the host is the bottleneck (the packed-4k and SFT regimes).
Production JAX stacks (MaxText's multihost dataloading, tf.data-style
pipelined ETL) hide this by prefetching N batches ahead on background
threads and landing them pre-sharded on device.

Two things must overlap to fix the input-bound regime:

1. **device compute vs. host work** — jax's asynchronous dispatch
   already gives the loop ~one step of lookahead, but only while the
   host keeps dispatching; any host stall (slow FUSE read, tokenizer
   hiccup) lands directly in the step cadence.
2. **host production with itself** — when producing one batch
   (read+tokenize+pack+place) costs more than a step, the pipeline is
   host-bound and lookahead cannot help; the only fix is overlapping
   the production of batch N+1..N+k with batch N. The worker pool here
   parallelizes the ``place_fn`` stage: ``workers`` threads pull from
   the iterator (serialized under a lock — Python iterators admit no
   concurrent ``next``), run ``place_fn`` concurrently, and deliver
   **in ticket order**, so the consumed stream is byte-identical to the
   serial one. ``place_fn`` is therefore where expensive per-batch work
   must live to parallelize: the sharded form-up of
   ``parallel.placement.make_place_batch`` (batches land distributed
   over the mesh, never staged replicated) and any read/tokenize/pack
   stage routed into it (the iterator then yields cheap work descriptors,
   tf.data ``map``-style). GIL-releasing work (FUSE/network reads,
   ``device_put``, HF fast tokenizers) genuinely parallelizes; work
   left inside the iterator gains only overlap #1.

Backpressure: a worker may not start placing ticket T until
``T < consumed + depth``, bounding device-resident prefetched batches at
``depth`` (plus the ≤ ``workers`` currently being placed).

Shared contract of :class:`Prefetcher` and :class:`SyncBatchSource`
(the ``prefetch=0`` inline path — one iteration shape in the loop):

- **resume fast-forward skip**: the first ``skip`` batches are consumed
  from the iterator but NEVER transferred (``place_fn`` not called) —
  replaying a resumed epoch costs tokenize time only, no device traffic.
- **wait accounting**: ``consume_wait()`` returns host seconds the
  consumer spent blocked since the last call — the loop books it into
  :meth:`train.metrics.ThroughputMeter.data_wait`, surfacing the
  data-stall fraction per log window.
- **exception propagation**: an iterator/placement error re-raises at
  the consumer's ``next()`` (type preserved), after every batch that
  preceded it — exactly like the inline path.
- **clean shutdown**: ``close()`` stops the workers and joins them;
  epoch-boundary exhaustion drains and joins automatically.

Both stages are regions of obs/trace.py (``batch_next``: the epoch
iterator's ``next``; ``batch_place``: ``place_fn``), on whichever thread
runs them, so a profiler window shows what the input pipeline did while
the device waited.

Determinism: ticket-ordered delivery means a prefetched run consumes
the identical batch stream — losses are bitwise identical to the
synchronous path (pinned by tests/test_prefetch.py).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Iterable, Optional

from gke_ray_train_tpu.obs import trace


class _Failure:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class SyncBatchSource:
    """The inline (no-thread) batch source: pull → skip-or-place → yield.

    Counters after iteration: ``yielded`` = batches pulled from the
    underlying iterator (skipped included), ``skipped`` = resume
    fast-forward batches consumed without placement.
    """

    def __init__(self, iterable: Iterable[Dict], *,
                 place_fn: Optional[Callable] = None, skip: int = 0):
        self._it = iter(iterable)
        self._place = place_fn
        self._skip = max(int(skip), 0)
        self.yielded = 0
        self.skipped = 0
        self._wait = 0.0

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        try:
            while True:
                with trace.region("batch_next"):
                    batch = next(self._it)
                self.yielded += 1
                if self.skipped < self._skip:
                    self.skipped += 1
                    continue
                if self._place is not None:
                    with trace.region("batch_place"):
                        batch = self._place(batch)
                return batch
        finally:
            self._wait += time.perf_counter() - t0

    def consume_wait(self) -> float:
        w, self._wait = self._wait, 0.0
        return w

    def close(self) -> None:
        pass


class Prefetcher:
    """Bounded multi-worker prefetch with on-thread device placement and
    deterministic (ticket-ordered) delivery."""

    def __init__(self, iterable: Iterable[Dict], *,
                 place_fn: Optional[Callable] = None, depth: int = 2,
                 skip: int = 0, workers: Optional[int] = None,
                 name: str = "batch-prefetch"):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._it = iter(iterable)
        self._place = place_fn
        self._skip = max(int(skip), 0)
        self.yielded = 0
        self.skipped = 0
        self._wait = 0.0
        self.depth = depth
        # default: one placement worker per queue slot (backpressure
        # bounds useful concurrency at `depth` anyway), capped at 8 so a
        # deep queue does not spawn a thread horde; explicit `workers`
        # still clamps to depth — extra producers would only park
        self.workers = max(1, min(int(workers) if workers
                                  else min(depth, 8), depth))
        self._src_lock = threading.Lock()   # iterator pull + ticketing
        self._cond = threading.Condition()  # results / backpressure
        self._results: Dict[int, object] = {}
        self._next_ticket = 0   # next ticket a worker will take
        self._next_out = 0      # next ticket the consumer will deliver
        self._end_ticket: Optional[int] = None  # tickets == stream length
        self._exhausted = False
        self._stop = threading.Event()
        self._done = False
        self._threads = [
            threading.Thread(target=self._work, name=f"{name}-{i}",
                             daemon=True)
            for i in range(self.workers)]
        for t in self._threads:
            t.start()

    # -- worker side ---------------------------------------------------
    def _work(self) -> None:
        while not self._stop.is_set():
            # pull + ticket under one lock: tickets follow iterator order
            with self._src_lock:
                if self._exhausted:
                    return
                try:
                    with trace.region("batch_next"):
                        batch = next(self._it)
                except StopIteration:
                    self._exhausted = True
                    self._finish(self._next_ticket)
                    return
                except BaseException as e:  # noqa: BLE001 - consumer raises
                    self._exhausted = True
                    ticket = self._next_ticket
                    self._next_ticket += 1
                    self._deliver(ticket, _Failure(e))
                    self._finish(ticket + 1)
                    return
                self.yielded += 1
                if self.skipped < self._skip:
                    # resume fast-forward: consumed, never transferred
                    self.skipped += 1
                    continue
                ticket = self._next_ticket
                self._next_ticket += 1
            # backpressure: at most `depth` placed-but-undelivered batches
            with self._cond:
                while not self._stop.is_set() and \
                        ticket >= self._next_out + self.depth:
                    self._cond.wait(0.05)
            if self._stop.is_set():
                return
            try:
                if self._place is not None:
                    with trace.region("batch_place"):
                        item = self._place(batch)
                else:
                    item = batch
            except BaseException as e:  # noqa: BLE001 - consumer raises
                item = _Failure(e)
            self._deliver(ticket, item)

    def _deliver(self, ticket: int, item) -> None:
        with self._cond:
            self._results[ticket] = item
            self._cond.notify_all()

    def _finish(self, end_ticket: int) -> None:
        with self._cond:
            if self._end_ticket is None:
                self._end_ticket = end_ticket
            self._cond.notify_all()

    # -- consumer side -------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        t0 = time.perf_counter()
        item = None
        ended = dead = False
        try:
            with self._cond:
                while True:
                    if self._next_out in self._results:
                        item = self._results.pop(self._next_out)
                        self._next_out += 1
                        self._cond.notify_all()  # open the window
                        break
                    if self._end_ticket is not None and \
                            self._next_out >= self._end_ticket:
                        self._done = ended = True
                        break
                    if not any(t.is_alive() for t in self._threads):
                        self._done = dead = True
                        break
                    self._cond.wait(0.1)
        finally:
            self._wait += time.perf_counter() - t0
        # joins happen OUTSIDE the condition lock (a worker parked on it
        # could never exit otherwise)
        if ended:
            for t in self._threads:
                t.join(timeout=10.0)
            raise StopIteration
        if dead:
            raise RuntimeError("prefetch workers exited without a result "
                               "(killed thread?)")
        if isinstance(item, _Failure):
            self._done = True
            self.close()
            raise item.exc
        return item

    def consume_wait(self) -> float:
        w, self._wait = self._wait, 0.0
        return w

    def close(self) -> None:
        """Stop the workers and reclaim the threads. Safe to call twice,
        and after normal exhaustion (then it is a no-op join)."""
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=10.0)
        self._done = True


def make_batch_source(iterable: Iterable[Dict], *,
                      place_fn: Optional[Callable] = None, depth: int = 0,
                      skip: int = 0, workers: Optional[int] = None):
    """``depth >= 1`` → background :class:`Prefetcher`; ``depth <= 0`` →
    inline :class:`SyncBatchSource`. One call site, one iteration shape."""
    if depth and depth > 0:
        return Prefetcher(iterable, place_fn=place_fn, depth=depth,
                          skip=skip, workers=workers)
    return SyncBatchSource(iterable, place_fn=place_fn, skip=skip)
