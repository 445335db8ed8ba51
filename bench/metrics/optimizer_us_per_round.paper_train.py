"""Device time of the optimizer update per training round: the epoch
program's leaf ops under the `optimizer` named scope (core/inl.py), over
the traced rounds."""


def read(run, out):
    p = getattr(run, "program", None)
    return p and p.us_per_round(("optimizer",), out.facts.get("rounds_traced"))
