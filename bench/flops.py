"""Operations the algorithm needs, computed from shapes.

These count the same work whatever implements it: a faster program cannot
move them, only the time they are divided by.  Matrix products and
convolutions count 2 operations per multiply-add; elementwise work is left
out of a model's count (the usual convention for model FLOP utilization).
Recomputation is never counted.
"""
from __future__ import annotations


# ---------------------------------------------------------------------------
# The paper's model (Fig. 4): J conv encoders, a cut, a fusion MLP
# ---------------------------------------------------------------------------

def paper_conv_flops(cfg: dict) -> list:
    """Forward FLOPs of each 3x3 SAME conv of one encoder, per example."""
    H, _, C = cfg["image_shape"]
    chans = [C] + list(cfg["conv_channels"])
    out, h = [], H
    for cin, cout in zip(chans[:-1], chans[1:]):
        out.append(2 * h * h * 9 * cin * cout)
        h //= 2
    return out


def paper_feat_dim(cfg: dict) -> int:
    h = cfg["image_shape"][0] // 2 ** len(cfg["conv_channels"])
    return h * h * cfg["conv_channels"][-1]


def paper_forward_flops(cfg: dict) -> dict:
    """Forward FLOPs per example, by part: the J encoders (convs and the
    (mu, logvar) head), the fusion decoder and the J branch heads."""
    J, d, C = cfg["num_clients"], cfg["d_bottleneck"], cfg["num_classes"]
    enc = sum(paper_conv_flops(cfg)) + 2 * 2 * paper_feat_dim(cfg) * d
    dims = [J * d] + list(cfg["dense_units"]) + [C]
    dec = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    return {"encoders": J * enc, "decoder": dec, "branch_heads": J * 2 * d * C}


def paper_train_flops(cfg: dict) -> int:
    """Forward and backward FLOPs per training example: every product runs
    forward, for its weight gradient and for its input gradient, except
    the first conv, whose input (the data) needs no gradient."""
    fwd = paper_predict_flops(cfg)
    first_conv = cfg["num_clients"] * paper_conv_flops(cfg)[0]
    return 3 * fwd - first_conv


def paper_predict_flops(cfg: dict) -> int:
    return sum(paper_forward_flops(cfg).values())
