"""Stochastic bottlenecks for in-network learning.

Each edge node j parametrises P_theta_j(u_j | x_j) as a diagonal Gaussian
(regression/continuous latents; the paper's choice via the reparametrization
trick of Kingma & Welling) whose (mu, log sigma^2) come from the node's NN.
The prior Q_psi_j(u_j) is a standard normal by default or a learned diagonal
Gaussian marginal.

The rate term of eq. (6), log(P(u|x)/Q(u)), is provided both as the paper's
per-sample ESTIMATE (evaluated at the sampled u) and as the ANALYTIC KL
between the two Gaussians — the estimator the paper trains with is the
sampled one; both are tested against each other in expectation.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers

LOG2PI = float(np.log(2.0 * np.pi))


def head_init(key, d_in: int, d_bottleneck: int, dtype=jnp.float32):
    """Projection from encoder features to (mu, logvar)."""
    ks = jax.random.split(key, 2)
    return {"mu": layers.dense_init(ks[0], d_in, d_bottleneck, bias=True,
                                    dtype=dtype),
            "logvar": layers.dense_init(ks[1], d_in, d_bottleneck, bias=True,
                                        dtype=dtype, scale=1e-2)}


def head_apply(p, h) -> Tuple[jnp.ndarray, jnp.ndarray]:
    mu = layers.dense(p["mu"], h)
    logvar = jnp.clip(layers.dense(p["logvar"], h), -8.0, 8.0)
    return mu, logvar


def sample(key, mu, logvar):
    """Reparametrised draw u = mu + sigma * eps.

    Computed in fp32, returned in mu.dtype — bf16 inputs must not silently
    upcast the latent (the kernels' dtype-preservation contract,
    tests/test_cutlayer_vjp.py)."""
    eps = jax.random.normal(key, mu.shape, jnp.float32)
    u = mu.astype(jnp.float32) \
        + jnp.exp(0.5 * logvar.astype(jnp.float32)) * eps
    return u.astype(mu.dtype)


def fused_sample_rate(key, mu, logvar, *, link_bits: int = 32,
                      rate_estimator: str = "sample", prior: dict = None,
                      backend: str = "auto", block_t: int = None,
                      eps=None):
    """The cut-layer hot path in ONE fused kernel pass: draws eps and
    returns

        u    = quantize_st(mu + exp(logvar/2) * eps)   (..., d)
        rate = eq.-(6) rate term per row                (...,)  fp32

    with mu/logvar read from HBM once (kernels/inl_bottleneck.py via
    kernels/ops.py dispatch).  The backward pass is the hand-written
    eq.-(10) split, not AD through three unfused ops.  Leading axes —
    including the J client axis — fold into the kernel row grid, so all
    nodes share one launch.

    key=None runs the DETERMINISTIC cut (eps == 0 -> u == quantize(mu)):
    split learning's non-stochastic activation exchange and the inference
    path, still through the same kernel.  Pair it with
    rate_estimator="none" to skip the rate entirely.

    prior — a {"mu", "logvar"} dict of (d,) shared or (J, d) per-node
    learned-Gaussian-prior params — switches the eq.-(6) rate to Q_psi and
    stays on the fused path (the kernel also emits the prior gradients);
    there is no fallback to the unfused 3-pass estimator any more.

    eps — the standard-normal draw itself, in place of key's (for a caller
    that puts the draw under a scope of its own)."""
    from repro.kernels import ops
    if eps is None:
        eps = jnp.zeros(mu.shape, jnp.float32) if key is None \
            else jax.random.normal(key, mu.shape, jnp.float32)
    prior = prior or {}
    return ops.cutlayer(mu, logvar, eps, link_bits=link_bits,
                        rate_estimator=rate_estimator,
                        prior_mu=prior.get("mu"),
                        prior_logvar=prior.get("logvar"),
                        backend=backend, block_t=block_t)


def gaussian_logpdf(u, mu, logvar):
    lv = logvar.astype(jnp.float32)
    d = (u - mu).astype(jnp.float32)
    return -0.5 * jnp.sum(lv + LOG2PI + d * d * jnp.exp(-lv), axis=-1)


def prior_init(d_bottleneck: int, learned: bool = False,
               num_nodes: int = None):
    """Learned diagonal-Gaussian prior params; {} = standard normal.

    num_nodes=J stacks one independent prior per node ((J, d) leaves) —
    the shape the fused cut-layer kernel's per-node prior grid expects."""
    if not learned:
        return {}
    shape = (d_bottleneck,) if num_nodes is None \
        else (num_nodes, d_bottleneck)
    return {"mu": jnp.zeros(shape, jnp.float32),
            "logvar": jnp.zeros(shape, jnp.float32)}


def prior_logpdf(prior, u):
    if prior:
        return gaussian_logpdf(u, prior["mu"], prior["logvar"])
    uf = u.astype(jnp.float32)
    return -0.5 * jnp.sum(uf * uf + LOG2PI, axis=-1)


def rate_sampled(u, mu, logvar, prior=None):
    """The paper's per-sample rate term log(P(u|x) / Q(u)), eq. (6)."""
    return gaussian_logpdf(u, mu, logvar) - prior_logpdf(prior or {}, u)


def rate_analytic(mu, logvar, prior=None):
    """KL( N(mu, sigma^2) || prior ) in closed form (variance-reduced)."""
    lv = logvar.astype(jnp.float32)
    muf = mu.astype(jnp.float32)
    if prior:
        plv = prior["logvar"]
        pmu = prior["mu"]
        return 0.5 * jnp.sum(plv - lv + (jnp.exp(lv) + (muf - pmu) ** 2)
                             / jnp.exp(plv) - 1.0, axis=-1)
    return 0.5 * jnp.sum(jnp.exp(lv) + muf * muf - 1.0 - lv, axis=-1)
