"""Device time of the J encoders per training round: the epoch program's
leaf ops under the `encoder` named scope (core/inl.py), forward and
backward, over the traced rounds."""


def read(run, out):
    p = getattr(run, "program", None)
    return p and p.us_per_round(("encoder",), out.facts.get("rounds_traced"))
