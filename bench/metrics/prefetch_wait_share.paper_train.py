"""Share of the window in which the runner was blocked on the device
prefetcher, from the program's own span `repro.prefetch.wait` around each
pull (data/prefetch.py), on the profiler's clock: the in-program twin of
input_wait_share."""


def read(run, out):
    p = getattr(run, "program", None)
    return p and p.window_share("repro.prefetch.wait")
