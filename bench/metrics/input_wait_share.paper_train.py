"""Share of the window the runner spent blocked on the device prefetcher
(the bench's span around each pull from `data/prefetch.prefetch_to_device`):
the host pipeline's gather and transfer not hidden behind device work."""


def read(run, out):
    f = out.facts
    return 100.0 * f["input_wait_s"] / f["window_s"]
