"""Plain reference of the paper's INL model (Fig. 4 and eq. 6), in
straightforward jax.numpy, independent of the program under test.

J conv encoders (3x3 SAME conv, BatchNorm, ReLU, 2x2 max-pool per block;
then a dense (mu, logvar) head with logvar clipped to [-8, 8]), the cut
u = mu + exp(logvar / 2) * eps on a full-precision link, the fusion MLP
(ReLU, dropout 0.3 after each hidden layer) on the concatenated latents,
one linear branch head per node, and the eq.-(6) loss

    CE(joint) + s * sum_j (CE(branch_j) + mean_rows rate_j),
    rate = log N(u; mu, sigma^2) - log N(u; 0, I)  at the sampled u.

Adam as the paper trains: global-norm clipping at 1, b1 0.9, b2 0.95, eps
1e-8, constant learning rate, bias-corrected moments.

`dtype=float32` runs the network in float32, each matrix product and
convolution at the precision the configuration states (`matmul_precision`:
"default", one bfloat16 pass per float32 product on a TPU, or "highest");
`dtype=bfloat16` runs the whole network in bfloat16.
Randomness follows the seed's conventions: a round key splits into the
cut's key (eps ~ N(0, 1) of shape (J, B, d)) and the decoder's key, which
splits once per hidden layer for its keep mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5
DROP = 0.3
LV_CLIP = 8.0


def _prec(cfg: dict, dtype):
    """The precision of every product: the configuration's for float32."""
    if dtype != jnp.float32:
        return jax.lax.Precision.DEFAULT
    return {"default": jax.lax.Precision.DEFAULT,
            "highest": jax.lax.Precision.HIGHEST}[cfg["matmul_precision"]]


def init(cfg: dict, key):
    """Seeded weights in the reference's own layout, plus BatchNorm running
    statistics.  Biases, BN affine parameters and running statistics are
    random too, so that no part of the model reads as the identity."""
    J, d, C = cfg["num_clients"], cfg["d_bottleneck"], cfg["num_classes"]
    chans = [cfg["image_shape"][-1]] + list(cfg["conv_channels"])
    h = cfg["image_shape"][0] // 2 ** len(cfg["conv_channels"])
    feat = h * h * chans[-1]
    dims = [J * d] + list(cfg["dense_units"]) + [C]
    ks = iter(jax.random.split(key, 64))

    def nrm(shape, scale):
        return scale * jax.random.normal(next(ks), shape, jnp.float32)

    p = {"conv": [], "bn": [], "dense": []}
    stats = []
    for cin, cout in zip(chans[:-1], chans[1:]):
        p["conv"].append({"w": nrm((J, 3, 3, cin, cout),
                                   np.sqrt(2.0 / (9 * cin))),
                          "b": nrm((J, cout), 0.1)})
        p["bn"].append({"scale": 1.0 + nrm((J, cout), 0.1),
                        "bias": nrm((J, cout), 0.1)})
        stats.append({"mean": nrm((J, cout), 0.1),
                      "var": jnp.exp(nrm((J, cout), 0.2))})
    p["mu"] = {"w": nrm((J, feat, d), 1.0 / np.sqrt(feat)),
               "b": nrm((J, d), 0.1)}
    p["lv"] = {"w": nrm((J, feat, d), 1e-2), "b": nrm((J, d), 0.1)}
    for a, b in zip(dims[:-1], dims[1:]):
        p["dense"].append({"w": nrm((a, b), 1.0 / np.sqrt(a)),
                           "b": nrm((b,), 0.1)})
    p["branch"] = {"w": nrm((J, d, C), 1.0 / np.sqrt(d)),
                   "b": nrm((J, C), 0.1)}
    return p, stats


def _encoder(p, stats, x, *, train: bool, dtype, prec):
    """One node: x (B, H, W, C) -> (mu, logvar) (B, d), in `dtype`."""
    h = x.astype(dtype)
    for i, conv in enumerate(p["conv"]):
        h = jax.lax.conv_general_dilated(
            h, conv["w"].astype(dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
        h = h + conv["b"].astype(dtype)
        if train:
            mean = h.mean(axis=(0, 1, 2))
            var = jnp.square(h - mean).mean(axis=(0, 1, 2))
        else:
            mean = stats[i]["mean"].astype(dtype)
            var = stats[i]["var"].astype(dtype)
        h = (h - mean) * jax.lax.rsqrt(var + BN_EPS)
        h = h * p["bn"][i]["scale"].astype(dtype) \
            + p["bn"][i]["bias"].astype(dtype)
        h = jnp.maximum(h, 0)
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    mu = jnp.dot(h, p["mu"]["w"].astype(dtype), precision=prec) \
        + p["mu"]["b"].astype(dtype)
    lv = jnp.dot(h, p["lv"]["w"].astype(dtype), precision=prec) \
        + p["lv"]["b"].astype(dtype)
    return mu, jnp.clip(lv, -LV_CLIP, LV_CLIP)


def _encoders(params, stats, views, *, train: bool, dtype, prec):
    """All J nodes; `stats` (running BatchNorm statistics) only at
    inference."""
    enc = {k: params[k] for k in ("conv", "bn", "mu", "lv")}
    if train:
        return jax.vmap(lambda p, x: _encoder(p, None, x, train=True,
                                              dtype=dtype, prec=prec))(
            enc, views)
    return jax.vmap(lambda p, s, x: _encoder(p, s, x, train=False,
                                             dtype=dtype, prec=prec))(
        enc, stats, views)


def _decoder(params, u_cat, *, dtype, prec, key=None):
    """Fusion MLP; `key` draws the training-time dropout masks."""
    h = u_cat.astype(dtype)
    for layer in params["dense"][:-1]:
        h = jnp.maximum(jnp.dot(h, layer["w"].astype(dtype), precision=prec)
                        + layer["b"].astype(dtype), 0)
        if key is not None:
            key, sub = jax.random.split(key)
            keep = jax.random.bernoulli(sub, 1.0 - DROP, h.shape)
            h = jnp.where(keep, h / (1.0 - DROP), 0)
    last = params["dense"][-1]
    return jnp.dot(h, last["w"].astype(dtype), precision=prec) \
        + last["b"].astype(dtype)


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def loss(params, views, labels, key, cfg: dict, *, dtype=jnp.float32):
    """The eq.-(6) training loss of one round: views (J, B, H, W, C),
    labels (B,), the round's key."""
    J, B = views.shape[0], views.shape[1]
    prec = _prec(cfg, dtype)
    r_cut, r_dec = jax.random.split(key)
    mu, lv = _encoders(params, None, views, train=True, dtype=dtype,
                       prec=prec)
    eps = jax.random.normal(r_cut, mu.shape, jnp.float32)
    muf, lvf = mu.astype(jnp.float32), lv.astype(jnp.float32)
    u = muf + jnp.exp(0.5 * lvf) * eps
    rate = 0.5 * jnp.sum(u * u - (u - muf) ** 2 * jnp.exp(-lvf) - lvf, -1)
    u = u.astype(dtype)
    u_cat = jnp.moveaxis(u, 0, 1).reshape(B, -1)
    joint = _decoder(params, u_cat, dtype=dtype, prec=prec, key=r_dec)
    branch = jnp.einsum("jbd,jdc->jbc", u, params["branch"]["w"].astype(dtype),
                        precision=prec) \
        + params["branch"]["b"].astype(dtype)[:, None]
    ce_branch = sum(_xent(branch[j], labels) for j in range(J))
    return _xent(joint, labels) + cfg["s"] * (ce_branch + jnp.sum(
        jnp.mean(rate, axis=1)))


def predict(params, stats, views, cfg: dict, *, dtype=jnp.float32):
    """Inference (deterministic latents u = mu, BatchNorm on its running
    statistics): views (J, B, H, W, C) -> class probabilities (B, C)."""
    J, B = views.shape[0], views.shape[1]
    prec = _prec(cfg, dtype)
    mu, _ = _encoders(params, stats, views, train=False, dtype=dtype,
                      prec=prec)
    u_cat = jnp.moveaxis(mu, 0, 1).reshape(B, -1)
    logits = _decoder(params, u_cat, dtype=dtype, prec=prec)
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))), tree)


def train(params, views, labels, keys, cfg: dict, *, lr: float,
          dtype=jnp.float32):
    """Adam over K rounds: views (K, J, B, ...), labels (K, B), keys (K,).
    Returns (per-round losses (K,), per-leaf norms of the first round's
    gradient as Adam receives it (after clipping), final params)."""
    b1, b2, eps = 0.9, 0.95, 1e-8
    grad_fn = jax.value_and_grad(
        lambda p, v, y, k: loss(p, v, y, k, cfg, dtype=dtype))
    zeros = jax.tree.map(jnp.zeros_like, params)

    def step(carry, xs):
        p, m, v, t = carry
        view, lab, k = xs
        val, g = grad_fn(p, view, lab, k)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, 1.0 / jnp.maximum(
            gnorm, 1e-9)), g)
        t = t + 1
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(lambda w, a, b: w - lr * (a / bc1) / (
            jnp.sqrt(b / bc2) + eps), p, m, v)
        return (p, m, v, t), (val, _leaf_norms(g))

    (p, _, _, _), (losses, gnorms) = jax.lax.scan(
        step, (params, zeros, zeros, jnp.zeros((), jnp.float32)),
        (views, labels, keys))
    return losses, jax.tree.map(lambda x: x[0], gnorms), p
