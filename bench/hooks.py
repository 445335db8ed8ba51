"""What the training drivers share: ending the program's own loop when the
window closes, timing its waits on the prefetcher, and the leaf-norm
comparison their `correct` rests on."""
from __future__ import annotations

import contextlib
import gc
import threading
import time

import numpy as np


@contextlib.contextmanager
def patched(owner, name, make):
    """`owner.name` replaced by `make(original)` for the duration of a
    context: how a driver plants a fault in the program's timed path."""
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


class WindowClosed(Exception):
    """Raised from a driver's hook on the program's step call to end the
    program's own loop when the window closes."""


def timed_prefetch(run, prefetch_to_device):
    """`prefetch_to_device` with the bench span `bench.input_wait` around
    each pull: the time the consumer was blocked on the host pipeline."""
    def wrapped(iterator, **kw):
        gen = prefetch_to_device(iterator, **kw)

        def items():
            try:
                while True:
                    with run.span("bench.input_wait"):
                        item = next(gen, None)
                    if item is None:
                        return
                    yield item
            finally:
                gen.close()
        return items()
    return wrapped


def join_prefetchers(timeout: float = 60.0) -> None:
    """Wait for the prefetcher threads a closed window left behind."""
    gc.collect()
    deadline = time.time() + timeout
    for t in threading.enumerate():
        if t.name == "prefetch_to_device":
            t.join(max(0.0, deadline - time.time()))


def leaf_norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def leaf_gaps(prog: list, ref: list) -> list:
    """Each leaf's |norm(prog) - norm(ref)| over the larger of the
    reference leaf's norm and the median leaf's."""
    med = float(np.median(ref))
    return [abs(a - b) / max(b, med, 1e-30) for a, b in zip(prog, ref)]


def kept_leaves(first_grad_norms: list) -> list:
    """The leaves compared: those whose first gradient in the reference is
    not nought to rounding (at least a thousandth of the median leaf's).
    The others, such as a conv bias under BatchNorm, move under Adam by
    round-off alone."""
    med = float(np.median(first_grad_norms))
    return [g >= 1e-3 * med for g in first_grad_norms]
