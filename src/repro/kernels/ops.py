"""Dispatching wrappers for the Pallas kernels.

On TPU the compiled Pallas kernels run natively (interpret=False); on CPU
(this container) they execute in interpret mode for correctness, and the
model code defaults to its jnp formulations (models/attention.py's blockwise
scan, models/ssm.py's chunked SSD) which XLA compiles efficiently.  The
`backend` argument makes the choice explicit and testable:

    backend="pallas"     pallas_call, interpret on CPU / compiled on TPU
    backend="reference"  kernels/ref.py jnp oracle
    backend="auto"       pallas on TPU, reference elsewhere

`cutlayer` is the fused cut-layer megakernel (inl_bottleneck.py): sample +
link-quantize + rate in one forward pass, the paper's eq.-(10) error-vector
split in one backward pass, under a single shared `jax.custom_vjp`.  Both
backends run that same VJP wrapper — "reference" swaps the kernel bodies
for the jnp oracle so CPU CI exercises the training code path exactly.

`resolve_backend` / `resolve_interpret` are the canonical resolvers; kernel
modules use the latter for their `interpret=None` auto-detection (a kernel
must never silently interpret on TPU, nor compile Mosaic on CPU).
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import inl_bottleneck as _bn
from repro.kernels import ref
from repro.kernels import ssm_scan as _ssd


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret):
    """A kernel's `interpret=None`: compiled on TPU, interpret mode
    elsewhere."""
    return (not on_tpu()) if interpret is None else interpret


def resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "pallas" if on_tpu() else "reference"
    if backend not in ("pallas", "reference"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


def attention(q, k, v, *, causal=True, window=0, q_offset=0,
              backend: str = "auto", **block_kw):
    if resolve_backend(backend) == "pallas":
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, **block_kw)
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)


def bottleneck(mu, logvar, eps, *, backend: str = "auto", **block_kw):
    """Seed-compatible fused sample + analytic KL (no quantizer)."""
    if resolve_backend(backend) == "pallas":
        return _bn.bottleneck_fused(mu, logvar, eps, **block_kw)
    return ref.bottleneck_ref(mu, logvar, eps)


def cutlayer(mu, logvar, eps, *, link_bits: int = 32,
             rate_estimator: str = "sample", prior_mu=None,
             prior_logvar=None, backend: str = "auto",
             block_t: int = None):
    """Fused cut layer: (u_quantized, per-row rate) in one kernel pass,
    custom-VJP backward.  mu/logvar/eps: (..., d) with all leading axes
    (clients, batch, sequence) folded into the row grid — one launch for
    all J nodes.  rate_estimator "none" zeroes the rate (split learning's
    deterministic cut); prior_mu/prior_logvar — (d,) shared or (J, d)
    per-node — evaluate the rate against a learned Gaussian prior, still
    in one fused pass per direction (prior grads included).

    Dtype contract (the mixed-precision policy depends on it): u comes back
    in mu.dtype — a bf16 latent stays bf16 end to end, with only the
    kernels' INTERNAL arithmetic and the rate accumulation in fp32.  The
    dispatch enforces it here so a kernel regression cannot silently widen
    the hot path back to fp32."""
    u, rate = _bn.cutlayer_fused(mu, logvar, eps, link_bits=link_bits,
                                 rate_estimator=rate_estimator,
                                 prior_mu=prior_mu, prior_logvar=prior_logvar,
                                 impl=resolve_backend(backend),
                                 block_t=block_t, interpret=None)
    if u.dtype != mu.dtype:
        raise TypeError(f"cutlayer kernel changed the latent dtype: "
                        f"{mu.dtype} in, {u.dtype} out")
    if rate.dtype != jax.numpy.float32:
        raise TypeError(f"cutlayer rate must accumulate in fp32, got "
                        f"{rate.dtype}")
    return u, rate


def ssd_scan(x, dt, a, bm, cm, dskip, *, backend: str = "auto", **block_kw):
    if resolve_backend(backend) == "pallas":
        return _ssd.ssd_scan(x, dt, a, bm, cm, dskip, **block_kw)
    return ref.ssd_scan_ref(x, dt, a, bm, cm, dskip)
