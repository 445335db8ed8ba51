"""Device time of the fused cut layer per training round: its forward and
backward Pallas kernels (the `tpu_custom_call`s that the program's
`_cutlayer_call` lowers to) summed over the traced window, over the rounds
in it."""

KERNELS = r'_cutlayer_call_.*custom_call_target="tpu_custom_call"'


def read(run, out):
    r, f = run.reduced, out.facts
    if r is None or not f.get("rounds_traced"):
        return None
    spent = r.matching(KERNELS)
    if spent <= 0:
        return None
    return 1e6 * spent / f["rounds_traced"]
