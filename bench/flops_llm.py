"""Operations per trained token of the xLSTM INL split, from shapes.

As in bench/flops.py: 2 operations per multiply-add of every product,
elementwise work left out, recomputation never counted, and training
counted as three times the forward products (forward, weight gradient,
input gradient).  The mLSTM's recurrence is counted with its products: the
readout C_t q_t and the outer-product update k_t v_t^T into the decayed
state, each H x dh x dh multiply-adds per token, and the normaliser's
n_t . q_t; the sLSTM's head-wise recurrent matrices, H x dh x 4 dh.
"""
from __future__ import annotations


def mlstm_macs(conf: dict) -> int:
    """Multiply-adds of one mLSTM block per token."""
    d, H, W = conf["d_model"], conf["num_heads"], conf["mlstm"]["conv_width"]
    d_in = conf["mlstm"]["proj_factor"] * d
    dh = d_in // H
    proj = d * 2 * d_in + 3 * d_in * d_in + d_in * 2 * H + d_in * d
    recurrence = 2 * H * dh * dh + H * dh
    return proj + W * d_in + recurrence


def slstm_macs(conf: dict) -> int:
    """Multiply-adds of one sLSTM block per token: input gates, head-wise
    recurrence, gated FFN."""
    d, H = conf["d_model"], conf["num_heads"]
    dh = d // H
    ff = conf["slstm"]["ffn_dim"]
    return d * 4 * d + H * dh * 4 * dh + 3 * d * ff


def period_macs(conf: dict) -> int:
    kinds = conf["block_pattern"]
    return sum(mlstm_macs(conf) if k == "mlstm" else slstm_macs(conf)
               for k in kinds)


def forward_macs(conf: dict) -> dict:
    """Forward multiply-adds per token, by part."""
    d, V = conf["d_model"], conf["vocab_size"]
    inl = conf["inl"]
    J, db = inl["num_nodes"], inl["d_bottleneck"]
    periods = conf["num_layers"] // len(conf["block_pattern"])
    dec_periods = periods - inl["encoder_periods"]
    return {
        "encoders": J * (inl["encoder_periods"] * period_macs(conf)
                         + 2 * d * db),
        "decoder": J * db * d + dec_periods * period_macs(conf),
        "lm_head": d * V,
        "branch_heads": J * db * V,
    }


def train_flops_per_token(conf: dict) -> int:
    """Forward and backward FLOPs per trained token (the embeddings are a
    lookup, so every counted product has an input gradient)."""
    return 3 * 2 * sum(forward_macs(conf).values())

