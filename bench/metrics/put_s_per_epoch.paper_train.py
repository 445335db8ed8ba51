"""Seconds the prefetcher's producer thread spends in `jax.device_put` of
one epoch's input (`repro.prefetch.put` in data/prefetch.py, which does not
wait for the transfer to end), the mean over the spans the trace holds
whole."""


def read(run, out):
    p = getattr(run, "program", None)
    return p and p.mean_s("repro.prefetch.put")
