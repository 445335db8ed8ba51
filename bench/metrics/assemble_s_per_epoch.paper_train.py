"""Seconds the prefetcher's producer thread spends assembling one epoch's
input on the host (`repro.runner.assemble` in core/schemes/runner.py: the
epoch's index matrix, the gather of views and labels, the moveaxis), the
mean over the spans the trace holds whole."""


def read(run, out):
    p = getattr(run, "program", None)
    return p and p.mean_s("repro.runner.assemble")
