"""Asynchronous input pipeline: prefetch-rendered, pre-transferred batches.

The reference overlaps I/O and compute by construction — its multithreaded
minibatch SGD keeps pulling rows while other worker threads grind batches
(word2vec.h:475-547 spawns one thread per core over AsynExec's bounded
queue).  The JAX port's training loops used to render every batch
(Python/native stencil batcher + ``np.stack``) and ``device_put`` its
arrays *inline on the dispatch thread*, so the device idled through
host-side rendering and H2D transfer between fused-scan groups — the
devices-starved failure mode Parallax (1808.02621) identifies for sparse
data-parallel training.

:class:`PrefetchIterator` is the one producer/consumer primitive every
loop shares:

* a **producer thread** walks the source iterator ``depth`` items ahead
  into a bounded FIFO queue.  Rendering (batcher ``next``, ``np.stack``)
  and the optional ``transfer`` hook (eager ``device_put`` with the
  step's committed input sharding, so H2D DMA overlaps the previous
  group's compute) both run on the producer's clock;
* the **consumer** iterates as usual.  Order is exactly the source
  iterator's order — single producer, FIFO queue — and the producer owns
  NO RNG (key splitting stays in the consumer, in consumption order), so
  a pipelined run is bit-identical to the synchronous one;
* time the consumer spends blocked on an empty queue is recorded as
  **host stall** (``stats().stall_s``) — the quantity the pipeline
  exists to drive to zero.  ``utils.timers.Throughput`` reports it as
  ``host_stall_ms``.

Bounding the *output* side (in-flight dispatches the consumer issues
against prefetched inputs) is the consumer's job — see
``utils.pipeline.DispatchWindow`` and ``resolve_dispatch_bound``; the
two bounds compose into the ``[worker] pipeline: K`` /
``dispatch_depth: D`` watermark pair so async dispatch never outruns
HBM: at most K rendered+transferred groups and D undispatched-result
programs are in flight at once.

Failure semantics: a producer exception is captured and re-raised at the
consumer's next ``__next__`` (training crash paths — fault injection,
flaky batchers — behave as if the loop were synchronous).  ``close()``
(also the context-manager exit and the GC hook) unblocks and joins the
producer, so a consumer that dies mid-epoch never leaks a thread that
keeps rendering into a dead queue.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, Iterable, Iterator, Optional

from swiftmpi_tpu import obs

_DONE = object()          # producer sentinel: source exhausted
_CLOSED = object()        # close() sentinel: wake a blocked consumer


class PipelineError(RuntimeError):
    """Producer-side failure, re-raised on the consumer thread with the
    original exception chained (``__cause__``)."""


class _Unobserved:
    """What an ``observed=False`` iterator opens in a span's place."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def drop(self):
        pass


_UNOBSERVED = _Unobserved()


class PrefetchIterator:
    """Iterate ``source`` through a ``depth``-bounded background queue.

    ``transfer`` (optional) maps each item on the producer thread —
    the eager ``device_put`` hook.  ``depth`` counts fully rendered and
    transferred items the producer may run ahead; the queue slot the
    producer is rendering *into* is not yet visible to the consumer, so
    peak host memory is ``depth + 1`` items.

    ``observed=False`` is for a read-ahead that is not the training
    loop's input pipeline (a batcher's own, consumed inside the loop's
    ``input_wait``): it opens no span and feeds no ``pipeline/*`` series,
    so the loop's phases are told once.
    """

    def __init__(self, source: Iterable, depth: int = 2,
                 transfer: Optional[Callable[[Any], Any]] = None,
                 name: str = "input-pipeline", observed: bool = True):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self._span = obs.span if observed else (lambda _name: _UNOBSERVED)
        self._observed = bool(observed)
        self._source = iter(source)
        self._transfer = transfer
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._done = False
        # observability — read via stats()
        self._produced = 0
        self._consumed = 0
        self._stall_s = 0.0
        self._transfer_s = 0.0
        self._peak_depth = 0
        self._thread = threading.Thread(
            target=self._produce, name=name, daemon=True)
        self._thread.start()

    # -- producer ----------------------------------------------------------
    def _produce(self) -> None:
        try:
            src = self._source
            while True:
                if self._stop.is_set():
                    return
                # "render" / "h2d" phase spans: the producer thread is
                # exactly where batch rendering and eager H2D transfer
                # happen, so the telemetry phases are measured here (the
                # concurrent-write side of the registry's thread-safety
                # contract)
                with self._span("render"):
                    try:
                        item = next(src)
                    except StopIteration:
                        break
                if self._transfer is not None:
                    t0 = time.monotonic()
                    with self._span("h2d"):
                        item = self._transfer(item)
                    self._transfer_s += time.monotonic() - t0
                # bounded put that stays responsive to close(): a plain
                # blocking put on a full queue would deadlock the join
                # when the consumer is already gone
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        self._produced += 1
                        self._peak_depth = max(self._peak_depth,
                                               self._q.qsize())
                        reg = obs.get_registry()
                        if reg.enabled and self._observed:
                            reg.counter("pipeline/produced").inc()
                            reg.gauge("pipeline/queue_depth").set(
                                self._q.qsize())
                        break
                    except queue.Full:
                        continue
        except BaseException as e:            # noqa: BLE001 — re-raised
            self._error = e                   # on the consumer thread
        finally:
            self._done = True
            # land _DONE AFTER every real item (never displace one —
            # a full queue means we wait for the consumer to drain a
            # slot), unless close() already took over wake-up duty
            while not self._stop.is_set():
                try:
                    self._q.put(_DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue

    # -- consumer ----------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        t0 = time.monotonic()
        with self._span("input_wait") as wait:
            item = self._q.get()
            if item is _DONE or item is _CLOSED:
                wait.drop()     # one `input_wait` sample per item
        self._stall_s += time.monotonic() - t0
        if item is _DONE or item is _CLOSED:
            # drain-order guarantee: _DONE lands after every real item
            if self._error is not None:
                err, self._error = self._error, None
                self.close()
                raise PipelineError(
                    f"input-pipeline producer failed: {err!r}") from err
            self.close()
            raise StopIteration
        self._consumed += 1
        reg = obs.get_registry()
        if reg.enabled and self._observed:
            reg.counter("pipeline/consumed").inc()
        return item

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        """Stop the producer and join it.  Idempotent; safe to call from
        ``finally`` around a consumer loop that may have crashed."""
        if self._stop.is_set():
            return
        self._stop.set()
        # unblock a producer stuck on a full queue
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
        try:
            self._q.put_nowait(_CLOSED)       # wake any blocked consumer
        except queue.Full:
            pass

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # pragma: no cover — GC backstop
        try:
            self.close()
        except Exception:
            pass

    # -- observability -----------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Counters for train metrics / bench detail fields."""
        return {"depth": self.depth,
                "produced": self._produced,
                "consumed": self._consumed,
                "peak_queue_depth": self._peak_depth,
                "stall_s": self._stall_s,
                "transfer_s": self._transfer_s}


def device_put_transfer(sharding) -> Callable[[Any], Any]:
    """Producer ``transfer`` hook: eagerly ``device_put`` every array
    leaf of a work item with the step's committed input ``sharding`` (a
    ``jax.sharding.Sharding`` or a ``jax.Device``), so H2D DMA issues
    from the producer thread and overlaps the previous group's compute.

    Non-array leaves (ints, strings, item-kind tags) pass through.  The
    sharding is captured by the CONSUMER at pipeline build time —
    ``jax.default_device`` is thread-local context, so the producer
    thread must never rely on it.
    """
    import jax
    import numpy as np

    def put(item):
        def leaf(x):
            if isinstance(x, (np.ndarray, jax.Array)):
                return jax.device_put(x, sharding)
            return x
        return jax.tree_util.tree_map(leaf, item)

    return put
