"""Pallas TPU flash attention (blockwise online-softmax), causal + GQA +
sliding window.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * Tiling is BlockSpec-driven: q tiles (BLOCK_Q x Dh) live in VMEM; the kv
    loop walks (BLOCK_K x Dh) tiles.  BLOCK_Q/BLOCK_K default to 128 — the
    MXU systolic dim — so every partial matmul is 128-aligned.
  * GQA is handled with a ZERO-COPY index map: the kv BlockSpec maps query
    head h to kv head h // group, so grouped keys are never materialised.
  * The causal early-exit (skipping kv tiles fully above the diagonal) is a
    grid-size reduction per q tile via the kv upper bound, not warp-level
    control flow.

Target: TPU (MXU 128x128, VMEM ~16 MB).  Validated in interpret mode on CPU
against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, seq_k: int,
                 causal: bool, window: int, scale: float, q_offset: int):
    """Grid: (batch*heads, num_q_blocks).  Refs:
    q_ref (block_q, Dh), k_ref/v_ref (seq_k, Dh) full-row VMEM views,
    o_ref (block_q, Dh)."""
    block_q, dh = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0) \
        + q_offset

    nk = seq_k // block_k

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.dslice(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.dslice(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window:
            mask = mask & (q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, dh), jnp.float32)
    if causal:
        # causal early exit: kv tiles strictly above the diagonal are skipped
        hi = jnp.minimum(
            (qi + 1) * block_q + q_offset + block_k - 1, seq_k) // block_k
    else:
        hi = nk
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K, interpret: bool = None):
    """q: (B, Sq, H, Dh); k, v: (B, Sk, KV, Dh).  Returns (B, Sq, H, Dh).

    Sq % block_q == 0 and Sk % block_k == 0 are required (pad upstream).
    interpret=None compiles on TPU and interprets elsewhere.
    """
    from repro.kernels import ops          # lazy: ops imports this module
    interpret = ops.resolve_interpret(interpret)
    B, Sq, H, Dh = q.shape
    _, Sk, KV, _ = k.shape
    g = H // KV
    scale = 1.0 / np.sqrt(Dh)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dh)
    kt = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, Dh)
    vt = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, Dh)

    grid = (B * H, Sq // block_q)

    def q_map(bh, qi):
        return (bh, qi, 0)

    def kv_map(bh, qi):
        # zero-copy GQA: query head -> its kv head
        b = bh // H
        h = bh % H
        return (b * KV + h // g, 0, 0)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, block_k=block_k, seq_k=Sk,
                          causal=causal, window=window, scale=scale,
                          q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, Dh), q_map),
            pl.BlockSpec((None, Sk, Dh), kv_map),
            pl.BlockSpec((None, Sk, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((None, block_q, Dh), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, Dh), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3)
