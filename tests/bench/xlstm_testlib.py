"""The xLSTM INL cell at a tiny size for tests on the CPU: a configuration
and the cell's own traffic cut to a few tokens, for `bench_testlib`'s tiny
benchmark root."""
from __future__ import annotations

import json
import os

from bench_testlib import ROOT

# the xLSTM INL split at a tiny size: one (mLSTM, sLSTM) period per node
# and one at the fusion node, 2 nodes with 32-wide cuts, time scans remat'd
# every 16 steps
TINY_XLSTM = {
    "name": "tiny_xlstm", "source": "test", "reference": "xlstm_inl",
    "arch": "xlstm-125m", "num_layers": 4,
    "block_pattern": ["mlstm", "slstm"], "d_model": 64, "num_heads": 2,
    "vocab_size": 256, "norm_eps": 1e-5,
    "mlstm": {"proj_factor": 2, "inner_dim": 128, "head_dim": 64,
              "conv_width": 4, "chunk_size": 16},
    "slstm": {"head_dim": 32, "ffn_dim": 128},
    "inl": {"num_nodes": 2, "encoder_periods": 1, "d_bottleneck": 32,
            "s": 0.01, "link_bits": 32},
    "precision": {"params": "bfloat16", "activations": "bfloat16",
                  "state": "float32", "matmul_precision": "default"},
    "context": 32, "batch": 1}


def tiny_xlstm(dtype: str = "bfloat16") -> dict:
    """TINY_XLSTM with its parameters and activations in `dtype`."""
    conf = json.loads(json.dumps(TINY_XLSTM))
    conf["precision"].update(params=dtype, activations=dtype)
    return conf


def tiny_llm_traffic(**limits):
    """The xLSTM cell's traffic at 1 x 32 tokens, held to the limits that
    the cell's own traffic file commits."""
    with open(os.path.join(ROOT, "bench", "traffic",
                           "llm_inl_train_1x1024.json")) as f:
        tr = json.load(f)
    tr["limits"].update(limits)
    tr.update(seq=32, schedule_steps=200)
    tr["optimizer"].update(warmup_steps=20, total_steps=200)
    return tr
