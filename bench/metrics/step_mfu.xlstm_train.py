"""The whole training step's share of the chips' bf16 peak: the model's
forward and backward FLOPs per trained token (bench/flops_llm.py, the
mLSTM readout and state update included, recomputation not counted) times
tokens per second, over chips times peak."""


def read(run, out):
    if run.peaks is None:
        return None
    f = out.facts
    return 100.0 * f["train_flops_per_token"] * f["tokens_per_s"] / (
        len(run.devices) * run.peaks["bf16_flops_per_s"])
