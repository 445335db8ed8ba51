"""Host -> device input pipeline: double-buffered batch prefetch.

The whole-epoch scan (`Scheme.make_epoch`, `launch/steps.make_scan_train_step`)
turns an epoch into ONE dispatch — which moves the bottleneck to the
host->device transfer of the epoch's stacked batches.  This module overlaps
that transfer with the previous epoch's compute: a producer THREAD pulls the
iterator up to ``size`` items ahead and `jax.device_put`s each immediately
(async on accelerators), so by the time the consumer asks for epoch e+1 its
buffers are already resident — and already laid out with the batch sharding
when a mesh is in play (`shardings`), so the jitted epoch never re-shards its
inputs.

Failure containment: an exception anywhere in the producer (the source
iterator, host-side batch assembly, `device_put`) is captured and RE-RAISED
on the consumer side at the next pull — the consumer never hangs on a dead
producer, and the traceback points at the real data-pipeline fault rather
than a queue timeout.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Iterable, Iterator

import jax

from repro import tracing

# queue sentinels: exhaustion vs producer fault (the exception rides along)
_DONE = object()


def nbytes(item) -> int:
    """Bytes of a pytree of arrays (host or device)."""
    return sum(x.nbytes for x in jax.tree.leaves(item))


class _Failure:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def prefetch_to_device(iterator: Iterable, *, size: int = 2,
                       shardings: Any = None) -> Iterator:
    """Yield items from `iterator`, keeping up to `size` device transfers in
    flight ahead of the consumer (double-buffered at the default size=2).

    Each item is a pytree of host arrays; it is moved with `jax.device_put`
    before being buffered.  `shardings` is None (default device placement),
    one `jax.sharding.Sharding` applied to every leaf, or a pytree of
    shardings matching the item structure — the layout the jitted consumer
    expects, so no resharding happens at dispatch.

    The producer runs in a daemon thread, overlapping host-side batch
    assembly (index/stack) AND the device transfer with device compute of
    the current item.  If the producer raises, the exception is re-raised
    here — from the generator, on the consumer's thread — instead of the
    consumer blocking forever on an empty queue.  Dropping the generator
    early (``close()``/GC) signals the producer to stop.
    """
    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")

    def _put(item):
        # device_put returns before the bytes reach the device; the span
        # does not wait for them, as waiting would end the overlap
        with tracing.span("prefetch.put", bytes=nbytes(item)):
            if shardings is None:
                return jax.device_put(item)
            return jax.device_put(item, shardings)

    # maxsize bounds host+device memory: at most `size` items buffered plus
    # the one the producer is transferring
    buf: "queue.Queue" = queue.Queue(maxsize=size)
    stop = threading.Event()

    def _offer(item) -> bool:
        """put() that gives up when the consumer dropped the generator."""
        while not stop.is_set():
            try:
                buf.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _producer():
        try:
            for item in iterator:
                if not _offer(_put(item)):
                    return
            _offer(_DONE)
        except BaseException as exc:  # re-raised consumer-side, never lost
            _offer(_Failure(exc))

    def _drain():
        """Release every buffered item (each pins a device buffer until
        dropped) and unblock a producer stuck in put()."""
        while True:
            try:
                buf.get_nowait()
            except queue.Empty:
                return

    thread = threading.Thread(target=_producer, name="prefetch_to_device",
                              daemon=True)
    thread.start()
    try:
        while True:
            with tracing.span("prefetch.wait"):
                got = buf.get()
            if got is _DONE:
                return
            if isinstance(got, _Failure):
                raise got.exc
            yield got
    finally:
        # A consumer that drops the generator early (close()/GC) used to
        # leave the producer thread alive and up to `size` device_put items
        # queued, pinning their device buffers until GC.  Drain + join: the
        # producer observes `stop` within its 0.1 s put timeout, so the
        # bounded join only trips if an item's device_put itself hangs —
        # in which case the daemon thread cannot block interpreter exit.
        stop.set()
        _drain()
        thread.join(timeout=5.0)
        _drain()
