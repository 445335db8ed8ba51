"""Share of the window in which device 0 was idle while the producer
thread assembled an epoch's input on the host (`repro.runner.assemble`):
the mean over the spans in the trace of the device's idle time inside
one, times the epochs in the window, over the window."""


def read(run, out):
    p = getattr(run, "program", None)
    return p and p.idle_share("repro.runner.assemble",
                              out.facts.get("epochs_in_window"))
