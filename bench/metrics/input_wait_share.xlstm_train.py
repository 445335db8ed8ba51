"""Share of the window the training loop spent blocked on the device
prefetcher (the bench span around each pull of a scan group from
`launch/train.device_groups`): the token stream's stacking and transfer
not hidden behind device work."""


def read(run, out):
    f = out.facts
    return 100.0 * f["input_wait_s"] / f["window_s"]
