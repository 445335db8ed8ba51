"""Pallas TPU kernel for the chunked Mamba2/SSD selective scan.

TPU adaptation (vs the CUDA selective-scan): instead of a warp-parallel
linear recurrence, the sequence is chunked so that *within* a chunk all work
is dense matmuls on the MXU (decay-weighted (C B^T) attention-like matrix and
state outer products), and the only sequential dependency is the (N x P)
state carried BETWEEN chunks — held in a VMEM scratch across the innermost
(sequential) grid dimension.  This is the SSD block-decomposition of Mamba2,
mapped onto Pallas's sequential-grid + scratch-carry idiom.

Grid: (B*H, num_chunks) — the chunk axis is the sequential innermost axis.
The per-head state (N, P) persists in scratch; chunk 0 zeroes it.

Validated in interpret mode against kernels/ref.py's sequential scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
                *, chunk: int):
    """Refs (per grid step): x (chunk, P), dt (chunk, 1), a (1, 1),
    b/c (chunk, N), d (1, 1), y (chunk, P); scratch state (N, P) fp32."""
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)                    # (cs, P)
    dt = dt_ref[...].astype(jnp.float32)                  # (cs, 1)
    a = a_ref[0, 0].astype(jnp.float32)                   # scalar (negative)
    bm = b_ref[...].astype(jnp.float32)                   # (cs, N)
    cm = c_ref[...].astype(jnp.float32)                   # (cs, N)
    dskip = d_ref[0, 0].astype(jnp.float32)

    dA = dt * a                                           # (cs, 1), <= 0
    cum = jnp.cumsum(dA, axis=0)                          # (cs, 1)

    # intra-chunk: att[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j, j <= i
    seg = cum - cum.reshape(1, chunk)                     # (cs, cs) = cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(jj <= ii, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    att = cb * decay * dt.reshape(1, chunk)
    y = jax.lax.dot(att, x, preferred_element_type=jnp.float32)

    # inter-chunk: y += (C * exp(cum)) @ state
    state = state_ref[...]
    y = y + jax.lax.dot(cm * jnp.exp(cum), state,
                        preferred_element_type=jnp.float32)

    # state update: state' = exp(cum[-1]) * state + B^T diag(exp(cum[-1]-cum)*dt) X
    gamma = jnp.exp(cum[chunk - 1, 0])
    w = jnp.exp(cum[chunk - 1, 0] - cum) * dt             # (cs, 1)
    upd = jax.lax.dot_general(bm * w, x, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_ref[...] = gamma * state + upd

    y_ref[...] = (y + dskip * x).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bm, cm, dskip, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = None):
    """Chunked SSD scan.

    x: (B, S, H, P); dt: (B, S, H) post-softplus; a: (H,) negative;
    bm, cm: (B, S, N) shared across heads (ngroups=1); dskip: (H,).
    Returns y: (B, S, H, P).  S % chunk == 0 required.  interpret=None
    compiles on TPU and interprets elsewhere.
    """
    from repro.kernels import ops          # lazy: ops imports this module
    interpret = ops.resolve_interpret(interpret)
    B, S, H, P = x.shape
    N = bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    xt = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtt = dt.transpose(0, 2, 1).reshape(B * H, S, 1)
    at = a.reshape(H, 1, 1)
    dt_skip = dskip.reshape(H, 1, 1)

    grid = (B * H, nc)

    def bh_map(bh, ci):
        return (bh, ci, 0)

    def b_shared_map(bh, ci):
        return (bh // H, ci, 0)

    def head_map(bh, ci):
        return (bh % H, 0, 0)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, P), bh_map),       # x
            pl.BlockSpec((None, chunk, 1), bh_map),       # dt
            pl.BlockSpec((None, 1, 1), head_map),         # a
            pl.BlockSpec((None, chunk, N), b_shared_map),  # B
            pl.BlockSpec((None, chunk, N), b_shared_map),  # C
            pl.BlockSpec((None, 1, 1), head_map),         # D
        ],
        out_specs=pl.BlockSpec((None, chunk, P), bh_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, at, bm, cm, dt_skip)
    return out.reshape(B, H, S, P).transpose(0, 2, 1, 3)
