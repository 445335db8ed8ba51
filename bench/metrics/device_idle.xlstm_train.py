"""Share of the traced span in which no op ran on the device (1 - busy /
span, from the profiler's trace, averaged over the chips used).  The span
is the scan group(s) that bench/drivers/llm_train.py records right after
the timed window (TRACED_GROUPS), not the window: a stall inside the
window does not show here."""


def read(run, out):
    r = run.reduced
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
