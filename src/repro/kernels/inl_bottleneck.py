"""Fused cut-layer megakernel for in-network learning (the paper's hot loop).

Per node j the cut layer is  (mu, logvar) -> u = Q(mu + exp(logvar/2)*eps)
-> per-row rate  forward, and the eq.-(8c)/(10) error-vector split backward.
Unfused that is three HBM-bound passes (reparametrised sample, link
quantizer, rate term) plus vanilla AD; here it is ONE Pallas pass per
direction:

  forward   `_cut_fwd_kernel`: each (block_t, d) tile of mu/logvar/eps is
            read into VMEM once and produces BOTH the quantized transmission
            u and the per-row rate (sampled estimator of eq. 6 evaluated at
            the quantized latent, the analytic Gaussian KL, or zero for the
            deterministic "none" mode split learning's non-stochastic cut
            uses).
  backward  `_cut_bwd_kernel`: given the decoder cotangent chunk delta[j]
            (straight-through through the quantizer) and the rate cotangent,
            recomputes sigma/u from the saved inputs and emits
            (dmu, dlogvar, deps) in a single fused pass — the paper's
            error-vector + local-rate-gradient split, eq. (10).

A second kernel pair (`_cut_prior_fwd_kernel` / `_cut_prior_bwd_kernel`)
evaluates the eq.-(6) rate against LEARNED diagonal-Gaussian priors
Q_psi_j = N(prior_mu_j, exp(prior_logvar_j)): the grid becomes
(J, row-blocks) so each node's (d,)-vector prior is read once per block, and
the backward additionally emits (dpmu, dplv), accumulated across each node's
row blocks inside the kernel (the grid is sequential, so `+=` into the
per-node output block is exact).  Learned priors therefore run the SAME
one-pass-per-direction fused path as the standard-normal case — no fallback
to the unfused 3-pass estimator.

All directions hang off `jax.custom_vjp` wrappers (`cutlayer_fused`), so
training never differentiates through `pallas_call` (interpret-mode AD was
the seed's CPU bottleneck).  The J client nodes are BATCHED into one kernel
launch: callers pass (J, ..., d) and the leading axes are folded into the
row grid — no `jax.vmap` over per-node calls.

Contract:
  * arbitrary leading dims; rows padded to a block_t multiple (no assert),
    outputs sliced back.  With learned priors, priors are (d,) shared or
    (J, d) per-node with mu shaped (J, ..., d).
  * Mosaic tiling: inside the pallas_calls the per-row rate is laid out
    lane-dense as (1, R) (learned priors: (J, 1, T)) and the priors as
    (J, 1, d), so every block's last two dims are (8, 128)-divisible or
    full; the wrappers restore the (...,) rate and (J, d) prior shapes.
    Compiled on TPU, a block_t below the row count must be a multiple of
    128 (the default 256 is; fewer rows use one full-size block).
  * codewords are built as int32 (TPU has no float<->uint32 cast; a
    codeword is below 2^16) and packed with lane masks and lane
    reductions, no minor-dim reshape; the uint32 lanes are an XLA
    bitcast outside the kernels.
  * `impl="reference"` routes the same custom VJP through the jnp oracle
    (kernels/ref.py), which XLA compiles to one fused pass on CPU — CI and
    TPU run identical code paths.
  * `interpret=None` auto-detects via the kernels/ops.py backend resolver
    (compiled on TPU, interpret elsewhere); never silently interprets on
    TPU.
  * quantizer semantics (clip to +-QUANT_RANGE, uniform midtread,
    straight-through) are shared with core/linkmodel.py via
    ref.quantize_value.

`bottleneck_fused` (sample + analytic KL, no quantizer) is kept as the
seed-compatible entry point on top of the same kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref

DEFAULT_BLOCK_T = 256

MODES = ("sample", "analytic", "none")


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _quantize(pre, bits: int):
    """In-kernel uniform quantizer value map; identical math to
    ref.quantize_value (bits is a compile-time constant)."""
    if bits >= 32:
        return pre
    r = ref.QUANT_RANGE
    scale = ((1 << bits) - 1) / (2.0 * r)
    return jnp.round((jnp.clip(pre, -r, r) + r) * scale) / scale - r


def _rate(u, mu, lv, mode: str):
    """Per-row rate of one (rows, d) tile as a lane-dense (1, rows) row."""
    if mode == "sample":
        rate = 0.5 * jnp.sum(u * u - (u - mu) ** 2 * jnp.exp(-lv) - lv,
                             axis=-1)
    elif mode == "analytic":
        rate = 0.5 * jnp.sum(jnp.exp(lv) + mu * mu - 1.0 - lv, axis=-1)
    else:
        rate = jnp.zeros(u.shape[:-1], jnp.float32)
    return rate[None, :]


def _cut_fwd_kernel(mu_ref, lv_ref, eps_ref, u_ref, rate_ref, *,
                    bits: int, mode: str):
    mu = mu_ref[...].astype(jnp.float32)
    lv = lv_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    sigma = jnp.exp(0.5 * lv)
    u = _quantize(mu + sigma * eps, bits)
    u_ref[...] = u.astype(u_ref.dtype)
    rate_ref[...] = _rate(u, mu, lv, mode).astype(rate_ref.dtype)


def _codewords(x, bits: int):
    """Codeword index of the link quantizer as int32 (same value as
    ref.quantize_index, which is below 2^16)."""
    r = ref.QUANT_RANGE
    scale = ((1 << bits) - 1) / (2.0 * r)
    return jnp.round((jnp.clip(x, -r, r) + r) * scale).astype(jnp.int32)


def _dequantize(idx, bits: int):
    r = ref.QUANT_RANGE
    scale = ((1 << bits) - 1) / (2.0 * r)
    return idx.astype(jnp.float32) / scale - r


def _lane_group(lane, w: int, bits: int):
    """Which lanes of a d-vector lane iota hold codewords of packed word w,
    and each one's bit offset inside that word."""
    vpw = 32 // bits
    lo = w * vpw
    grp = (lane >= lo) & (lane < lo + vpw)
    return grp, jnp.where(grp, (lane - lo) * bits, 0)


def _pack_lanes(idx, W: int, bits: int):
    """(rows, d) int32 codewords -> (rows, W) int32 words, in-kernel.

    Same little-endian lane layout as ref.pack_indices (the bits of the
    int32 words are the uint32 lanes).  Each word is a masked lane
    reduction of its shifted codewords; the bit fields are disjoint, so the
    sum is their OR."""
    rows, d = idx.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 1)
    word = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    packed = jnp.zeros((rows, W), jnp.int32)
    for w in range(W):
        grp, shift = _lane_group(lane, w, bits)
        col = jnp.sum(jnp.where(grp, idx << shift, 0), axis=-1,
                      keepdims=True)
        packed = jnp.where(word == w, col, packed)
    return packed


def _unpack_lanes(packed, d: int, bits: int):
    """(rows, W) int32 words -> (rows, d) int32 codewords, in-kernel."""
    rows, W = packed.shape
    mask = (1 << bits) - 1
    word = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 1)
    idx = jnp.zeros((rows, d), jnp.int32)
    for w in range(W):
        col = jnp.sum(jnp.where(word == w, packed, 0), axis=-1,
                      keepdims=True)
        grp, shift = _lane_group(lane, w, bits)
        idx = jnp.where(grp, jax.lax.shift_right_logical(col, shift) & mask,
                        idx)
    return idx


def _cut_fwd_pack_kernel(mu_ref, lv_ref, eps_ref, u_ref, pk_ref, rate_ref, *,
                         bits: int, mode: str):
    """Pack-emitting fused forward: the codeword index is the shared
    intermediate, so u, the packed lanes and the rate all come out of ONE
    read of (mu, logvar, eps) — the wire buffer costs no extra pass."""
    mu = mu_ref[...].astype(jnp.float32)
    lv = lv_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    sigma = jnp.exp(0.5 * lv)
    idx = _codewords(mu + sigma * eps, bits)
    u = _dequantize(idx, bits)
    u_ref[...] = u.astype(u_ref.dtype)
    pk_ref[...] = _pack_lanes(idx, pk_ref.shape[-1], bits)
    rate_ref[...] = _rate(u, mu, lv, mode).astype(rate_ref.dtype)


def _pack_kernel(u_ref, pk_ref, *, bits: int):
    """Standalone pack: quantized values -> codeword lanes (used for paths
    whose forward kernel does not emit packed output, e.g. learned priors)."""
    idx = _codewords(u_ref[...].astype(jnp.float32), bits)
    pk_ref[...] = _pack_lanes(idx, pk_ref.shape[-1], bits)


def _unpack_dequant_kernel(pk_ref, u_ref, *, bits: int):
    """Fusion-center side: packed lanes -> dense quantized values."""
    idx = _unpack_lanes(pk_ref[...], u_ref.shape[-1], bits)
    u_ref[...] = _dequantize(idx, bits).astype(u_ref.dtype)


def _cut_bwd_kernel(mu_ref, lv_ref, eps_ref, gu_ref, gr_ref,
                    dmu_ref, dlv_ref, deps_ref, *, bits: int, mode: str):
    mu = mu_ref[...].astype(jnp.float32)
    lv = lv_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    gu = gu_ref[...].astype(jnp.float32)
    gr = gr_ref[0].astype(jnp.float32)[:, None]          # (1, rows) row
    sigma = jnp.exp(0.5 * lv)
    if mode == "sample":
        u = _quantize(mu + sigma * eps, bits)
        w = (u - mu) * jnp.exp(-lv)
        g_pre = gu + gr * (u - w)
        dmu = gu + gr * u
        dlv = g_pre * (0.5 * sigma * eps) + gr * 0.5 * (w * (u - mu) - 1.0)
        deps = g_pre * sigma
    elif mode == "analytic":
        dmu = gu + gr * mu
        dlv = gu * (0.5 * sigma * eps) + gr * 0.5 * (jnp.exp(lv) - 1.0)
        deps = gu * sigma
    else:
        dmu = gu
        dlv = gu * (0.5 * sigma * eps)
        deps = gu * sigma
    dmu_ref[...] = dmu.astype(dmu_ref.dtype)
    dlv_ref[...] = dlv.astype(dlv_ref.dtype)
    deps_ref[...] = deps.astype(deps_ref.dtype)


def _rate_spec(block_t):
    """The lane-dense (1, R) per-row rate, one (1, block_t) block per row
    block."""
    return pl.BlockSpec((1, block_t), lambda i: (0, i))


def _fwd_pallas(mu, logvar, eps, bits, mode, block_t, interpret):
    R, d = mu.shape
    grid = (R // block_t,)
    spec = pl.BlockSpec((block_t, d), lambda i: (i, 0))
    u, rate = pl.pallas_call(
        functools.partial(_cut_fwd_kernel, bits=bits, mode=mode),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, _rate_spec(block_t)],
        out_shape=[jax.ShapeDtypeStruct((R, d), mu.dtype),
                   jax.ShapeDtypeStruct((1, R), jnp.float32)],
        interpret=interpret,
    )(mu, logvar, eps)
    return u, rate[0]


def _bwd_pallas(mu, logvar, eps, gu, grate, bits, mode, block_t,
                interpret):
    R, d = mu.shape
    grid = (R // block_t,)
    spec = pl.BlockSpec((block_t, d), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_cut_bwd_kernel, bits=bits, mode=mode),
        grid=grid,
        in_specs=[spec, spec, spec, spec, _rate_spec(block_t)],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((R, d), mu.dtype),
                   jax.ShapeDtypeStruct((R, d), logvar.dtype),
                   jax.ShapeDtypeStruct((R, d), eps.dtype)],
        interpret=interpret,
    )(mu, logvar, eps, gu, grate.reshape(1, R))


def _fwd_pack_pallas(mu, logvar, eps, bits, mode, block_t, interpret):
    R, d = mu.shape
    W = ref.packed_width(d, bits)
    grid = (R // block_t,)
    spec = pl.BlockSpec((block_t, d), lambda i: (i, 0))
    u, words, rate = pl.pallas_call(
        functools.partial(_cut_fwd_pack_kernel, bits=bits, mode=mode),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, pl.BlockSpec((block_t, W), lambda i: (i, 0)),
                   _rate_spec(block_t)],
        out_shape=[jax.ShapeDtypeStruct((R, d), mu.dtype),
                   jax.ShapeDtypeStruct((R, W), jnp.int32),
                   jax.ShapeDtypeStruct((1, R), jnp.float32)],
        interpret=interpret,
    )(mu, logvar, eps)
    return u, jax.lax.bitcast_convert_type(words, jnp.uint32), rate[0]


def _pack_pallas(u, bits, block_t, interpret):
    R, d = u.shape
    W = ref.packed_width(d, bits)
    grid = (R // block_t,)
    words = pl.pallas_call(
        functools.partial(_pack_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_t, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, W), jnp.int32),
        interpret=interpret,
    )(u)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _unpack_pallas(packed, d, bits, dtype, block_t, interpret):
    R, W = packed.shape
    grid = (R // block_t,)
    return pl.pallas_call(
        functools.partial(_unpack_dequant_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, W), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), dtype),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(packed, jnp.int32))


# ---------------------------------------------------------------------------
# Learned-prior kernels: grid (J, row-blocks), per-node (d,) prior vectors
# ---------------------------------------------------------------------------

def _cut_prior_fwd_kernel(mu_ref, lv_ref, eps_ref, pmu_ref, plv_ref,
                          u_ref, rate_ref, *, bits: int, mode: str):
    mu = mu_ref[0].astype(jnp.float32)           # (block_t, d)
    lv = lv_ref[0].astype(jnp.float32)
    eps = eps_ref[0].astype(jnp.float32)
    pmu = pmu_ref[0].astype(jnp.float32)         # (1, d) broadcasts over rows
    plv = plv_ref[0].astype(jnp.float32)
    sigma = jnp.exp(0.5 * lv)
    u = _quantize(mu + sigma * eps, bits)
    u_ref[0] = u.astype(u_ref.dtype)
    if mode == "sample":
        rate = 0.5 * jnp.sum((u - pmu) ** 2 * jnp.exp(-plv) + plv
                             - (u - mu) ** 2 * jnp.exp(-lv) - lv, axis=-1)
    else:                                        # "analytic"
        rate = 0.5 * jnp.sum(plv - lv + (jnp.exp(lv) + (mu - pmu) ** 2)
                             * jnp.exp(-plv) - 1.0, axis=-1)
    rate_ref[0] = rate[None, :].astype(rate_ref.dtype)


def _cut_prior_bwd_kernel(mu_ref, lv_ref, eps_ref, pmu_ref, plv_ref,
                          u_ref, gu_ref, gr_ref, dmu_ref, dlv_ref,
                          deps_ref, dpmu_ref, dplv_ref, *, bits: int,
                          mode: str):
    mu = mu_ref[0].astype(jnp.float32)
    lv = lv_ref[0].astype(jnp.float32)
    eps = eps_ref[0].astype(jnp.float32)
    pmu = pmu_ref[0].astype(jnp.float32)         # (1, d)
    plv = plv_ref[0].astype(jnp.float32)
    gu = gu_ref[0].astype(jnp.float32)
    gr = gr_ref[0, 0].astype(jnp.float32)[:, None]
    sigma = jnp.exp(0.5 * lv)
    if mode == "sample":
        u = u_ref[0].astype(jnp.float32)         # saved forward output
        w = (u - mu) * jnp.exp(-lv)
        wq = (u - pmu) * jnp.exp(-plv)
        g_pre = gu + gr * (wq - w)
        dmu = g_pre + gr * w
        dlv = g_pre * (0.5 * sigma * eps) + gr * 0.5 * (w * (u - mu) - 1.0)
        deps = g_pre * sigma
        dpmu = jnp.sum(-gr * wq, axis=0, keepdims=True)
        dplv = jnp.sum(gr * 0.5 * (1.0 - wq * (u - pmu)), axis=0,
                       keepdims=True)
    else:                                        # "analytic"
        dm = (mu - pmu) * jnp.exp(-plv)
        dmu = gu + gr * dm
        dlv = gu * (0.5 * sigma * eps) + gr * 0.5 * (jnp.exp(lv - plv) - 1.0)
        deps = gu * sigma
        dpmu = jnp.sum(-gr * dm, axis=0, keepdims=True)
        dplv = jnp.sum(gr * 0.5 * (1.0 - (jnp.exp(lv) + (mu - pmu) ** 2)
                                   * jnp.exp(-plv)), axis=0, keepdims=True)
    dmu_ref[0] = dmu.astype(dmu_ref.dtype)
    dlv_ref[0] = dlv.astype(dlv_ref.dtype)
    deps_ref[0] = deps.astype(deps_ref.dtype)
    # per-node prior grads: accumulate across this node's row blocks (the
    # grid is sequential with the row dimension innermost, so the first
    # block initialises and the rest add)
    @pl.when(pl.program_id(1) == 0)
    def _init():
        dpmu_ref[...] = jnp.zeros(dpmu_ref.shape, dpmu_ref.dtype)
        dplv_ref[...] = jnp.zeros(dplv_ref.shape, dplv_ref.dtype)
    dpmu_ref[0] += dpmu.astype(dpmu_ref.dtype)
    dplv_ref[0] += dplv.astype(dplv_ref.dtype)


# the prior grid's blocks: per-node priors travel as (J, 1, d) and the rate
# as (J, 1, T), so each block's last two dims are full or lane-aligned
def _prior_specs(block_t, d):
    row = pl.BlockSpec((1, block_t, d), lambda j, i: (j, i, 0))
    prior = pl.BlockSpec((1, 1, d), lambda j, i: (j, 0, 0))
    rate = pl.BlockSpec((1, 1, block_t), lambda j, i: (j, 0, i))
    return row, prior, rate


def _prior_fwd_pallas(mu, logvar, eps, pmu, plv, bits, mode, block_t,
                      interpret):
    J, T, d = mu.shape
    row, prior, rate_spec = _prior_specs(block_t, d)
    u, rate = pl.pallas_call(
        functools.partial(_cut_prior_fwd_kernel, bits=bits, mode=mode),
        grid=(J, T // block_t),
        in_specs=[row, row, row, prior, prior],
        out_specs=[row, rate_spec],
        out_shape=[jax.ShapeDtypeStruct((J, T, d), mu.dtype),
                   jax.ShapeDtypeStruct((J, 1, T), jnp.float32)],
        interpret=interpret,
    )(mu, logvar, eps, pmu.reshape(J, 1, d), plv.reshape(J, 1, d))
    return u, rate.reshape(J, T)


def _prior_bwd_pallas(mu, logvar, eps, pmu, plv, u, gu, grate, bits, mode,
                      block_t, interpret):
    J, T, d = mu.shape
    row, prior, rate_spec = _prior_specs(block_t, d)
    dmu, dlv, deps, dpmu, dplv = pl.pallas_call(
        functools.partial(_cut_prior_bwd_kernel, bits=bits, mode=mode),
        grid=(J, T // block_t),
        in_specs=[row, row, row, prior, prior, row, row, rate_spec],
        out_specs=[row, row, row, prior, prior],
        out_shape=[jax.ShapeDtypeStruct((J, T, d), mu.dtype),
                   jax.ShapeDtypeStruct((J, T, d), logvar.dtype),
                   jax.ShapeDtypeStruct((J, T, d), eps.dtype),
                   jax.ShapeDtypeStruct((J, 1, d), pmu.dtype),
                   jax.ShapeDtypeStruct((J, 1, d), plv.dtype)],
        interpret=interpret,
    )(mu, logvar, eps, pmu.reshape(J, 1, d), plv.reshape(J, 1, d), u, gu,
      grate.reshape(J, 1, T))
    return dmu, dlv, deps, dpmu.reshape(J, d), dplv.reshape(J, d)


# ---------------------------------------------------------------------------
# Shared custom VJPs (pallas and reference impls run the same wrappers)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _cutlayer(mu, logvar, eps, bits, mode, impl, block_t, interpret):
    if impl == "pallas":
        return _fwd_pallas(mu, logvar, eps, bits, mode, block_t, interpret)
    return ref.cutlayer_fwd_ref(mu, logvar, eps, bits, mode)


def _cutlayer_fwd(mu, logvar, eps, bits, mode, impl, block_t, interpret):
    out = _cutlayer(mu, logvar, eps, bits, mode, impl, block_t, interpret)
    return out, (mu, logvar, eps)


def _cutlayer_bwd(bits, mode, impl, block_t, interpret, res, cts):
    mu, logvar, eps = res
    gu, grate = cts
    if impl == "pallas":
        return _bwd_pallas(mu, logvar, eps, gu, grate, bits, mode,
                           block_t, interpret)
    return ref.cutlayer_bwd_ref(mu, logvar, eps, gu, grate, bits, mode)


_cutlayer.defvjp(_cutlayer_fwd, _cutlayer_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _cutlayer_prior(mu, logvar, eps, pmu, plv, bits, mode, impl, block_t,
                    interpret):
    if impl == "pallas":
        return _prior_fwd_pallas(mu, logvar, eps, pmu, plv, bits, mode,
                                 block_t, interpret)
    return ref.cutlayer_prior_fwd_ref(mu, logvar, eps, pmu, plv, bits, mode)


def _cutlayer_prior_fwd(mu, logvar, eps, pmu, plv, bits, mode, impl,
                        block_t, interpret):
    out = _cutlayer_prior(mu, logvar, eps, pmu, plv, bits, mode, impl,
                          block_t, interpret)
    # u (out[0]) rides along as a residual: it is a live output buffer
    # anyway, and the backward reading it (instead of recomputing the
    # exp/quantize chain) keeps the prior-grad reductions' dependency cone
    # minimal — without this, XLA's reduction fusions re-derive u and the
    # learned-prior backward regresses ~1.4x vs standard-normal on CPU.
    return out, (mu, logvar, eps, pmu, plv, out[0])


def _cutlayer_prior_bwd(bits, mode, impl, block_t, interpret, res, cts):
    mu, logvar, eps, pmu, plv, u = res
    gu, grate = cts
    if impl == "pallas":
        return _prior_bwd_pallas(mu, logvar, eps, pmu, plv, u, gu, grate,
                                 bits, mode, block_t, interpret)
    return ref.cutlayer_prior_bwd_ref(mu, logvar, eps, pmu, plv, u, gu,
                                      grate, bits, mode)


_cutlayer_prior.defvjp(_cutlayer_prior_fwd, _cutlayer_prior_bwd)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def _resolve_interpret(interpret):
    from repro.kernels import ops          # lazy: ops imports this module
    return ops.resolve_interpret(interpret)


@functools.partial(jax.jit, static_argnames=("link_bits", "rate_estimator",
                                             "impl", "block_t", "interpret"))
def _cutlayer_call(mu, logvar, eps, link_bits, rate_estimator, impl,
                   block_t, interpret):
    shape = mu.shape
    d = shape[-1]
    R = 1
    for s in shape[:-1]:
        R *= s
    mu2 = mu.reshape(R, d)
    lv2 = logvar.reshape(R, d)
    eps2 = eps.reshape(R, d)
    bt = min(block_t or DEFAULT_BLOCK_T, R)
    pad = (-R) % bt
    if pad:
        mu2 = jnp.pad(mu2, ((0, pad), (0, 0)))
        lv2 = jnp.pad(lv2, ((0, pad), (0, 0)))
        eps2 = jnp.pad(eps2, ((0, pad), (0, 0)))
    u, rate = _cutlayer(mu2, lv2, eps2, link_bits, rate_estimator, impl,
                        bt, interpret)
    if pad:
        u, rate = u[:R], rate[:R]
    return u.reshape(shape), rate.reshape(shape[:-1])


@functools.partial(jax.jit, static_argnames=("link_bits", "rate_estimator",
                                             "impl", "block_t", "interpret"))
def _cutlayer_prior_call(mu, logvar, eps, pmu, plv, link_bits,
                         rate_estimator, impl, block_t, interpret):
    shape = mu.shape
    d = shape[-1]
    if pmu.ndim == 1:                       # shared prior: one node group
        J, lead = 1, shape[:-1]
        pmu2, plv2 = pmu[None], plv[None]
    else:                                   # per-node (J, d) priors
        J, lead = pmu.shape[0], shape[1:-1]
        if shape[0] != J:
            raise ValueError(f"per-node prior J={J} vs mu leading axis "
                             f"{shape[0]}")
        pmu2, plv2 = pmu, plv
    T = 1
    for s in lead:
        T *= s
    mu3 = mu.reshape(J, T, d)
    lv3 = logvar.reshape(J, T, d)
    eps3 = eps.reshape(J, T, d)
    bt = min(block_t or DEFAULT_BLOCK_T, T)
    pad = (-T) % bt
    if pad:
        mu3 = jnp.pad(mu3, ((0, 0), (0, pad), (0, 0)))
        lv3 = jnp.pad(lv3, ((0, 0), (0, pad), (0, 0)))
        eps3 = jnp.pad(eps3, ((0, 0), (0, pad), (0, 0)))
    u, rate = _cutlayer_prior(mu3, lv3, eps3, pmu2, plv2, link_bits,
                              rate_estimator, impl, bt, interpret)
    if pad:
        u, rate = u[:, :T], rate[:, :T]
    return u.reshape(shape), rate.reshape(shape[:-1])


def cutlayer_fused(mu, logvar, eps, *, link_bits: int = 32,
                   rate_estimator: str = "analytic", impl: str = "pallas",
                   prior_mu=None, prior_logvar=None,
                   block_t: int = None, interpret: bool = None):
    """One fused pass over the cut layer, all J nodes in one launch.

    mu/logvar/eps: (..., d) — fold any leading axes (J clients, batch,
    sequence) in; they become the row grid.  Returns
    (u (..., d) in mu.dtype, rate (...,) fp32).

    link_bits >= 32 disables the quantizer; rate_estimator selects the
    paper's sampled eq.-(6) estimator (evaluated at the quantized latent),
    the analytic Gaussian KL, or "none" (rate == 0, the deterministic cut
    split learning uses with eps == 0).  prior_mu/prior_logvar — (d,)
    shared or (J, d) per-node with mu shaped (J, ..., d) — switch the rate
    to a learned Gaussian prior Q_psi; the fused backward then also yields
    the prior gradients.  Gradients always flow through the hand-written
    fused backward (eq. 10), never through AD of the kernel body."""
    if rate_estimator not in MODES:
        raise ValueError(f"unknown rate_estimator {rate_estimator!r}")
    interpret = _resolve_interpret(interpret)
    if prior_mu is None:
        return _cutlayer_call(mu, logvar, eps, link_bits, rate_estimator,
                              impl, block_t, interpret)
    if rate_estimator == "none":            # prior irrelevant when rate == 0
        return _cutlayer_call(mu, logvar, eps, link_bits, rate_estimator,
                              impl, block_t, interpret)
    return _cutlayer_prior_call(mu, logvar, eps, prior_mu, prior_logvar,
                                link_bits, rate_estimator, impl, block_t,
                                interpret)


# ---------------------------------------------------------------------------
# Packed wire format: non-VJP building blocks (core/wirefmt.py owns the
# straight-through custom_vjp that spans pack -> collective -> unpack)
# ---------------------------------------------------------------------------

def _rows(x):
    R = 1
    for s in x.shape[:-1]:
        R *= s
    return R


@functools.partial(jax.jit, static_argnames=("link_bits", "rate_estimator",
                                             "impl", "block_t", "interpret"))
def _pack_fwd_call(mu, logvar, eps, link_bits, rate_estimator, impl, block_t,
                   interpret):
    shape = mu.shape
    d = shape[-1]
    R = _rows(mu)
    W = ref.packed_width(d, link_bits)
    mu2, lv2, eps2 = (x.reshape(R, d) for x in (mu, logvar, eps))
    bt = min(block_t or DEFAULT_BLOCK_T, R)
    pad = (-R) % bt
    if pad:
        mu2, lv2, eps2 = (jnp.pad(x, ((0, pad), (0, 0)))
                          for x in (mu2, lv2, eps2))
    if impl == "pallas":
        u, packed, rate = _fwd_pack_pallas(mu2, lv2, eps2, link_bits,
                                           rate_estimator, bt, interpret)
    else:
        u, packed, rate = ref.cutlayer_pack_fwd_ref(mu2, lv2, eps2,
                                                    link_bits, rate_estimator)
    if pad:
        u, packed, rate = u[:R], packed[:R], rate[:R]
    return (u.reshape(shape), packed.reshape(shape[:-1] + (W,)),
            rate.reshape(shape[:-1]))


def cutlayer_pack_forward(mu, logvar, eps, *, link_bits: int,
                          rate_estimator: str = "sample",
                          impl: str = "pallas", block_t: int = None,
                          interpret: bool = None):
    """Pack-emitting fused forward: (u (..., d), packed (..., W) uint32,
    rate (...,) fp32) in one kernel pass.  NO gradient rule — callers wrap
    it in their own custom_vjp (core/wirefmt.py) whose backward is
    `cutlayer_backward`.  Bit-identical to `cutlayer_fused` on (u, rate)."""
    if rate_estimator not in MODES:
        raise ValueError(f"unknown rate_estimator {rate_estimator!r}")
    return _pack_fwd_call(mu, logvar, eps, link_bits, rate_estimator, impl,
                          block_t, _resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("link_bits", "rate_estimator",
                                             "impl", "block_t", "interpret"))
def _bwd_call(mu, logvar, eps, gu, grate, link_bits, rate_estimator, impl,
              block_t, interpret):
    shape = mu.shape
    d = shape[-1]
    R = _rows(mu)
    mu2, lv2, eps2, gu2 = (x.reshape(R, d) for x in (mu, logvar, eps, gu))
    gr2 = grate.reshape(R)
    bt = min(block_t or DEFAULT_BLOCK_T, R)
    pad = (-R) % bt
    if pad:
        mu2, lv2, eps2, gu2 = (jnp.pad(x, ((0, pad), (0, 0)))
                               for x in (mu2, lv2, eps2, gu2))
        gr2 = jnp.pad(gr2, (0, pad))
    if impl == "pallas":
        dmu, dlv, deps = _bwd_pallas(mu2, lv2, eps2, gu2, gr2, link_bits,
                                     rate_estimator, bt, interpret)
    else:
        dmu, dlv, deps = ref.cutlayer_bwd_ref(mu2, lv2, eps2, gu2, gr2,
                                              link_bits, rate_estimator)
    if pad:
        dmu, dlv, deps = dmu[:R], dlv[:R], deps[:R]
    return tuple(x.reshape(shape) for x in (dmu, dlv, deps))


def cutlayer_backward(mu, logvar, eps, gu, grate, *, link_bits: int,
                      rate_estimator: str = "sample", impl: str = "pallas",
                      block_t: int = None, interpret: bool = None):
    """The fused eq.-(10) backward as a plain dispatch (same kernels the
    `cutlayer_fused` custom VJP runs), for wrappers that own their VJP."""
    return _bwd_call(mu, logvar, eps, gu, grate, link_bits, rate_estimator,
                     impl, block_t, _resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("link_bits", "impl", "block_t",
                                             "interpret"))
def _pack_call(u, link_bits, impl, block_t, interpret):
    shape = u.shape
    d = shape[-1]
    R = _rows(u)
    W = ref.packed_width(d, link_bits)
    u2 = u.reshape(R, d)
    bt = min(block_t or DEFAULT_BLOCK_T, R)
    pad = (-R) % bt
    if pad:
        u2 = jnp.pad(u2, ((0, pad), (0, 0)))
    if impl == "pallas":
        packed = _pack_pallas(u2, link_bits, bt, interpret)
    else:
        packed = ref.pack_values_ref(u2, link_bits)
    if pad:
        packed = packed[:R]
    return packed.reshape(shape[:-1] + (W,))


def pack_values(u, *, link_bits: int, impl: str = "pallas",
                block_t: int = None, interpret: bool = None):
    """Quantized values -> packed codeword lanes ((..., d) -> (..., W)
    uint32).  Lossless on values already on the link_bits quantizer grid.

    bf16 storage can only address grids up to 8 bits exactly (coarser than
    the bf16 mantissa); wider codes would decode to different values, so
    they are rejected rather than silently corrupted."""
    if jnp.dtype(u.dtype).itemsize < 4 and link_bits > 8:
        raise ValueError(f"cannot re-encode {u.dtype} values at "
                         f"{link_bits}-bit codes (> 8 bits exceeds the "
                         "half-precision mantissa); pack from the kernel's "
                         "fp32 internals via cutlayer_pack_forward instead")
    return _pack_call(u, link_bits, impl, block_t,
                      _resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("d", "link_bits", "dtype",
                                             "impl", "block_t", "interpret"))
def _unpack_call(packed, d, link_bits, dtype, impl, block_t, interpret):
    shape = packed.shape
    W = shape[-1]
    R = _rows(packed)
    pk2 = packed.reshape(R, W)
    bt = min(block_t or DEFAULT_BLOCK_T, R)
    pad = (-R) % bt
    if pad:
        pk2 = jnp.pad(pk2, ((0, pad), (0, 0)))
    if impl == "pallas":
        u = _unpack_pallas(pk2, d, link_bits, dtype, bt, interpret)
    else:
        u = ref.unpack_dequant_ref(pk2, d, link_bits, dtype=dtype)
    if pad:
        u = u[:R]
    return u.reshape(shape[:-1] + (d,))


def unpack_dequant(packed, d: int, *, link_bits: int, dtype=jnp.float32,
                   impl: str = "pallas", block_t: int = None,
                   interpret: bool = None):
    """Fusion-center unpack: (..., W) uint32 lanes -> (..., d) quantized
    values, one fused extract+dequantize pass."""
    if packed.shape[-1] != ref.packed_width(d, link_bits):
        raise ValueError(f"packed width {packed.shape[-1]} does not match "
                         f"d={d} at {link_bits} bits "
                         f"(want {ref.packed_width(d, link_bits)})")
    return _unpack_call(packed, d, link_bits, jnp.dtype(dtype), impl,
                        block_t, _resolve_interpret(interpret))


def bottleneck_fused(mu, logvar, eps, *, block_t: int = DEFAULT_BLOCK_T,
                     interpret: bool = None):
    """Seed-compatible entry: u = mu + exp(logvar/2)*eps (no quantizer) and
    the per-row analytic KL.  mu/logvar/eps: (T, d); returns (u, kl).

    T need not divide block_t (rows are padded internally); interpret=None
    auto-detects the backend."""
    return cutlayer_fused(mu, logvar, eps, link_bits=32,
                          rate_estimator="analytic", impl="pallas",
                          block_t=block_t, interpret=interpret)
