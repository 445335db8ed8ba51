"""Host spans of the program, on the profiler's clock.

`span(name, **stats)` is `jax.profiler.TraceAnnotation("repro." + name,
**stats)`: a named interval, with its stats, in the same trace as the
device's ops, so an idle stretch of the device can be put down to what a
host thread was doing in it.  Nothing is recorded, and next to nothing
spent, while no profiler runs.  Stats known only at the span's end are
added with the returned annotation's `set_metadata(**stats)`.

Device work is named with `jax.named_scope` where it is traced; the
scopes reach the compiled program's op metadata (`op_name`).
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)
