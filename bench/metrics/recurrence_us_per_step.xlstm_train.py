"""Device time of the mLSTM and sLSTM time scans per optimizer step: the
leaf ops of the train program under the `mlstm` and `slstm` named scopes
(models/ssm.py), forward, backward and recomputed, over the steps of the
traced group(s) after the window.
The driver names each op from the compiled program's HLO; a `while`,
`call` or `conditional` op is not counted, as its time is its children's."""


def read(run, out):
    f = out.facts
    spent, steps = f.get("recurrence_s_traced"), f.get("steps_traced")
    if not spent or not steps:
        return None
    return 1e6 * spent / steps
