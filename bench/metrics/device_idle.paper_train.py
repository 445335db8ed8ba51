"""Share of the traced window in which no op ran on the device (1 - busy /
window, from the profiler's trace, averaged over the chips used)."""


def read(run, out):
    r = run.reduced
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
