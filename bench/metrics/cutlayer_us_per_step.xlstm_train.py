"""Device time of the fused cut layer per optimizer step: its forward and
backward Pallas kernels (the `tpu_custom_call`s that the program's
`_cutlayer_call` lowers to), here at 4,096 rows of 192, summed over the
traced group(s) after the window, over the steps in them."""

KERNELS = r'_cutlayer_call_.*custom_call_target="tpu_custom_call"'


def read(run, out):
    r, steps = run.reduced, out.facts.get("steps_traced")
    if r is None or not steps:
        return None
    spent = r.matching(KERNELS)
    if spent <= 0:
        return None
    return 1e6 * spent / steps
