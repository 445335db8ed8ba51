"""Device time of the fusion node per training round: the epoch program's
leaf ops under the `decoder` and `loss` named scopes (core/inl.py),
forward and backward, over the traced rounds."""


def read(run, out):
    p = getattr(run, "program", None)
    return p and p.us_per_round(("decoder", "loss"),
                                out.facts.get("rounds_traced"))
