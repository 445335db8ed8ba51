"""Plain reference of the xLSTM INL split (core/inl_llm.py on
arXiv:2405.04517's blocks), in straightforward jax.numpy, independent of
the program under test: no kernels, no remat, no vmap, no scan over
layers, and the mLSTM in the paper's stabilised PARALLEL form where the
program runs the recurrence step by step.

The model, as the configuration states it:

  J nodes, each: embedding of the tokens, plus its view noise 0.1 * N(0, 1)
  (a per-node key); `encoder_periods` periods of (mLSTM, mLSTM, mLSTM,
  sLSTM) pre-norm residual blocks; an RMS norm; a dense (mu, logvar) head,
  logvar clipped to [-8, 8].
  The cut on a full-precision link: u = mu + exp(logvar / 2) * eps, and the
  eq.-(6) rate at the sampled u against N(0, I),
      rate = 1/2 sum_d (u^2 - (u - mu)^2 exp(-logvar) - logvar).
  The fusion node: the J latents concatenated per token (eq. 5), a dense
  projection to d_model, one period of blocks, an RMS norm, the LM head;
  one linear branch head per node on its own latent.  Per token,
      loss = CE(joint) + s * sum_j (CE(branch_j) + mean rate_j).
  AdamW as launch/train.make_optimizer builds it: global-norm clipping,
  b1 0.9, b2 0.95, eps 1e-8, decoupled weight decay, linear warm-up then
  cosine decay, float32 master weights behind bfloat16 parameters.

mLSTM, parallel form: with log f_t = log sigmoid(f~_t), F_t = sum_{r<=t}
log f_r, log D_ts = F_t - F_s + i~_s for s <= t, m_t = max_s log D_ts,
    h_t = sum_s (q_t . k_s) D'_ts v_s / max(|sum_s (q_t . k_s) D'_ts|,
                                            exp(-m_t)),
    D'_ts = exp(log D_ts - m_t).
sLSTM: the paper's stabilised scalar recurrence as a plain loop over time,
with head-wise recurrent matrices on h_{t-1}.

Departures of the model (the program's, which this file computes) from
arXiv:2405.04517:
  - q, k and v are dense d_in x d_in projections (the paper: block-diagonal
    ones of 4 x 4 blocks); the input and forget gates are one dense
    projection of the conv branch (the paper: of [q, k, v]), without biases;
  - no mLSTM output gate, learnable skip or group norm: the mLSTM block is
    RMS norm of h, times silu(z), down-projected;
  - the sLSTM block is the recurrence, an RMS norm, and a residual gated
    (SwiGLU, 4/3) FFN inside the block; no causal conv before its gates,
    no group norm;
  - pre-norm blocks use RMS norm; the sLSTM is the fourth block of every
    period; 12 blocks in 3 periods;
  - the forget-gate pre-activations start from zero-mean weights (the
    paper initialises a positive forget bias).

Precision, as the configuration states: bfloat16 parameters and
activations, float32 recurrent state and gates, float32 products at
`matmul_precision`.  The mLSTM's (S, S) products stand for the state's
(C q_t and n . q_t), so they are computed at the state's precision
(`state` "float32": HIGHEST); at "default" they would round their
operands to bfloat16, which the float32 state does not.  Every number is computed in float32; where the
program holds a tensor in bfloat16 (a layer's output, the residual stream,
a dense product), it is rounded to bfloat16 here too
(`lax.reduce_precision`, which XLA keeps), and parameter gradients are
rounded to bfloat16 as the program's are.  The weights are the program's
own initial weights, made from the seed; the randomness follows its keys:
a step key folds in 0 for the J view-noise keys and 1 for the cut's eps of
shape (J, B, S, d_b).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

LV_CLIP = 8.0


@dataclasses.dataclass(frozen=True)
class Spec:
    """The numbers of a configuration file the reference reads."""
    pattern: tuple
    J: int
    enc_periods: int
    s: float
    d: int
    H: int
    vocab: int
    eps: float
    d_in: int
    prec: jax.lax.Precision
    state_prec: jax.lax.Precision
    bf16: bool

    @classmethod
    def of(cls, conf: dict) -> "Spec":
        return cls(
            pattern=tuple(conf["block_pattern"]),
            J=conf["inl"]["num_nodes"],
            enc_periods=conf["inl"]["encoder_periods"],
            s=conf["inl"]["s"], d=conf["d_model"], H=conf["num_heads"],
            vocab=conf["vocab_size"], eps=conf["norm_eps"],
            d_in=conf["mlstm"]["proj_factor"] * conf["d_model"],
            prec={"default": jax.lax.Precision.DEFAULT,
                  "highest": jax.lax.Precision.HIGHEST}[
                      conf["precision"]["matmul_precision"]],
            state_prec={"float32": jax.lax.Precision.HIGHEST,
                        "bfloat16": jax.lax.Precision.DEFAULT}[
                            conf["precision"]["state"]],
            bf16=conf["precision"]["activations"] == "bfloat16")


def _act(spec: Spec, x):
    """A tensor the program holds in bfloat16, rounded to it (in float32)."""
    return jax.lax.reduce_precision(x, 8, 7) if spec.bf16 else x


def _f32(w):
    return w.astype(jnp.float32)


def _dense(spec, p, x):
    y = _act(spec, jnp.matmul(x, _f32(p["w"]), precision=spec.prec))
    if "b" in p:
        y = _act(spec, y + _f32(p["b"]))
    return y


def _rmsnorm(spec, p, x):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + spec.eps)
    return _act(spec, y * _f32(p["scale"]))


def _silu(spec, x):
    return _act(spec, x * jax.nn.sigmoid(x))


def _causal_conv(spec, p, x):
    """Depthwise causal conv over time: out_t = sum_w x_{t-W+1+w} w_w + b."""
    w = _f32(p["w"])                                    # (W, C)
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + S] * w[i] for i in range(W))
    return _act(spec, _act(spec, out) + _f32(p["b"]))


def mlstm_parallel(q, k, v, i_raw, f_raw, *, prec=None):
    """The stabilised mLSTM in parallel form.  q, k, v: (B, S, H, dh);
    i_raw, f_raw: (B, S, H).  Returns h (B, S, H, dh)."""
    S = q.shape[1]
    F = jnp.cumsum(jax.nn.log_sigmoid(f_raw), axis=1)             # (B,S,H)
    logD = F[:, :, None, :] - F[:, None, :, :] + i_raw[:, None, :, :]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    logD = jnp.where(causal, logD, -jnp.inf)                      # (B,t,s,H)
    m = jnp.max(logD, axis=2)                                     # (B,t,H)
    Dp = jnp.exp(logD - m[:, :, None, :])
    w = jnp.einsum("bthd,bshd->btsh", q, k, precision=prec) * Dp
    num = jnp.einsum("btsh,bshd->bthd", w, v, precision=prec)
    den = jnp.maximum(jnp.abs(jnp.sum(w, axis=2)), jnp.exp(-m))
    return num / den[..., None]


def _mlstm_inputs(spec, p, x):
    """The mLSTM's q, k, v (B, S, H, dh), gate pre-activations i~, f~
    (B, S, H), and its output branch z."""
    B, S, _ = x.shape
    H, d_in = spec.H, spec.d_in
    dh = d_in // H
    up = _dense(spec, p["up"], x)
    xm, z = up[..., :d_in], up[..., d_in:]
    xc = _silu(spec, _causal_conv(spec, p["conv"], xm))

    def heads(t):
        return t.reshape(B, S, H, dh)
    q = heads(_dense(spec, p["wq"], xc)) / np.sqrt(dh)
    k = heads(_dense(spec, p["wk"], xc)) / np.sqrt(dh)
    v = heads(_dense(spec, p["wv"], xm))
    gates = _dense(spec, p["w_if"], xc)                           # (B,S,2H)
    return (q, k, v, gates[..., :H], gates[..., H:]), z


def _mlstm_block(spec, p, x):
    B, S, _ = x.shape
    qkvif, z = _mlstm_inputs(spec, p, x)
    h = mlstm_parallel(*qkvif, prec=spec.state_prec)
    h = _act(spec, h.reshape(B, S, spec.d_in))
    h = _act(spec, _rmsnorm(spec, p["norm"], h) * _silu(spec, z))
    return _dense(spec, p["down"], h)


def slstm_loop(r, x_gates, *, prec=None):
    """The stabilised sLSTM over time.  r: (H, dh, 4 dh) recurrent
    matrices; x_gates: (B, S, 4 d) input pre-activations, laid out
    (head, gate i/f/z/o, dh).  Returns h (B, S, H, dh)."""
    B, S, _ = x_gates.shape
    H, dh = r.shape[0], r.shape[1]

    def step(carry, g_x):
        c, n, h, m = carry
        rec = jnp.einsum("bhd,hde->bhe", h, r, precision=prec)
        g = g_x.reshape(B, H, 4, dh) + rec.reshape(B, H, 4, dh)
        i_raw, f_raw, z_raw, o_raw = (g[:, :, 0], g[:, :, 1], g[:, :, 2],
                                      g[:, :, 3])
        log_f = jax.nn.log_sigmoid(f_raw)
        m_new = jnp.maximum(log_f + m, i_raw)
        i_g = jnp.exp(i_raw - m_new)
        f_g = jnp.exp(log_f + m - m_new)
        c = f_g * c + i_g * jnp.tanh(z_raw)
        n = f_g * n + i_g
        h = jax.nn.sigmoid(o_raw) * c / n      # n >= 1 once stabilised
        return (c, n, h, m_new), h

    z = jnp.zeros((B, H, dh), jnp.float32)
    init = (z, z, z, jnp.full((B, H, dh), -1e30, jnp.float32))
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(x_gates, 1, 0))
    return jnp.moveaxis(hs, 0, 1)


def _slstm_block(spec, p, x):
    B, S, d = x.shape
    hs = slstm_loop(_f32(p["r"]), _dense(spec, p["wx"], x), prec=spec.prec)
    h = _rmsnorm(spec, p["norm"], _act(spec, hs.reshape(B, S, d)))
    f = p["ffn"]
    g = _rmsnorm(spec, p["ffn_norm"], h)
    ff = _act(spec, _silu(spec, _dense(spec, f["wi"], g))
              * _dense(spec, f["wg"], g))
    return _act(spec, h + _dense(spec, f["wo"], ff))


def _stack(spec, pattern, x, periods: int, pick):
    """`periods` periods of pre-norm residual blocks; `pick(leaf, period)`
    takes one block's weights from the stacked ones."""
    for per in range(periods):
        for pos, kind in enumerate(spec.pattern):
            p = jax.tree.map(lambda a: pick(a, per), pattern[pos])
            block = _mlstm_block if kind == "mlstm" else _slstm_block
            x = _act(spec, x + block(spec, p[kind],
                                     _rmsnorm(spec, p["norm"], x)))
    return x


def node_keys(spec, key):
    """The J nodes' view-noise keys of a step key."""
    return jax.random.split(jax.random.fold_in(key, 0), spec.J)


def _view(spec, enc, tokens, noise_key):
    """A node's input: its embedding of the tokens plus its view noise."""
    B, S = tokens.shape
    x = enc["embed"]["w"][tokens]
    noise = jax.random.normal(noise_key, (B, S, spec.d), jnp.float32)
    return _act(spec, x + _act(spec, 0.1 * noise))


def node(spec, enc, tokens, noise_key):
    """One sensing node (its weights `enc`, float32): (mu, logvar), each
    (B, S, d_b)."""
    x = _view(spec, enc, tokens, noise_key)
    x = _stack(spec, enc["stack"]["pattern"], x, spec.enc_periods,
               lambda a, per: a[per])
    x = _rmsnorm(spec, enc["norm"], x)
    mu = _dense(spec, enc["head"]["mu"], x)
    lv = jnp.clip(_dense(spec, enc["head"]["logvar"], x), -LV_CLIP, LV_CLIP)
    return mu, lv


def fusion(spec, rest, mu, lv, eps, labels):
    """The cut, the fusion node and the eq.-(6) loss, from the J nodes'
    (mu, logvar) (J, B, S, d_b): (loss, its parts).  `rest`: the decoder's
    and the branch heads' weights (float32)."""
    J, B, S, db = mu.shape
    u = mu + jnp.exp(0.5 * lv) * eps
    rate = 0.5 * jnp.sum(u * u - (u - mu) ** 2 * jnp.exp(-lv) - lv, axis=-1)
    u = _act(spec, u)
    dec = rest["decoder"]
    x = _dense(spec, dec["in_proj"],
               jnp.moveaxis(u, 0, 2).reshape(B, S, J * db))
    periods = jax.tree.leaves(dec["stack"]["pattern"][0])[0].shape[0]
    x = _stack(spec, dec["stack"]["pattern"], x, periods,
               lambda a, per: a[per])
    x = _rmsnorm(spec, dec["final_norm"], x)
    n = B * S
    ce_joint = _ce(_dense(spec, dec["unembed"], x)[..., :spec.vocab],
                   labels) / n
    wb = rest["branch_heads"]["w"]
    ce_branch = sum(
        _ce(_act(spec, jnp.matmul(u[j], wb[j], precision=spec.prec)
                 )[..., :spec.vocab], labels)
        for j in range(J)) / n
    rate_total = jnp.sum(jnp.mean(rate.reshape(J, -1), axis=-1))
    total = ce_joint + spec.s * (ce_branch + rate_total)
    return total, {"ce_joint": ce_joint, "ce_branch": ce_branch,
                   "rate_total": rate_total}


def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None],
                                        axis=-1)[..., 0])


def _node_weights(params, j):
    return jax.tree.map(lambda a: a[j], params["encoders"])


def _rest(params):
    return {"decoder": params["decoder"],
            "branch_heads": params["branch_heads"]}


def _eps(spec, key, mu):
    return jax.random.normal(jax.random.fold_in(key, 1), mu.shape,
                             jnp.float32)


def loss(params, conf: dict, batch, key):
    """The loss of one step, in one function: (loss, parts, with the J
    nodes' cut means under "mu")."""
    spec = Spec.of(conf)
    params = jax.tree.map(_f32, params)
    keys = node_keys(spec, key)
    mu, lv = (jnp.stack(t) for t in zip(*[
        node(spec, _node_weights(params, j), batch["tokens"], keys[j])
        for j in range(spec.J)]))
    total, parts = fusion(spec, _rest(params), mu, lv, _eps(spec, key, mu),
                          batch["labels"])
    return total, {**parts, "mu": mu}


# The gradient in pieces, so that it fits beside the weights at published
# widths: each node forward, the fusion node's value and gradient (also
# with respect to the nodes' mu and logvar), then each node's backward from
# those.  Each piece takes the parameters in their own dtype and computes
# in float32, so its gradient comes back rounded to that dtype.

@functools.partial(jax.jit, static_argnums=0)
def _node_forward(spec, enc, tokens, noise_key):
    return node(spec, jax.tree.map(_f32, enc), tokens, noise_key)


@functools.partial(jax.jit, static_argnums=0)
def _fusion_grad(spec, rest, mu, lv, eps, labels):
    def f(r, m, v):
        return fusion(spec, jax.tree.map(_f32, r), m, v, eps, labels)[0]
    return jax.value_and_grad(f, argnums=(0, 1, 2))(rest, mu, lv)


@functools.partial(jax.jit, static_argnums=0)
def _node_grad(spec, enc, tokens, noise_key, g_mu, g_lv):
    _, back = jax.vjp(
        lambda e: node(spec, jax.tree.map(_f32, e), tokens, noise_key), enc)
    return back((g_mu, g_lv))[0]


def loss_and_grad(params, conf: dict, batch, key):
    """(loss, the nodes' cut means, gradients in the parameters' dtypes)."""
    spec = Spec.of(conf)
    tokens, keys = batch["tokens"], node_keys(spec, key)
    mu, lv = (jnp.stack(t) for t in zip(*[
        _node_forward(spec, _node_weights(params, j), tokens, keys[j])
        for j in range(spec.J)]))
    total, (g_rest, g_mu, g_lv) = _fusion_grad(
        spec, _rest(params), mu, lv, _eps(spec, key, mu), batch["labels"])
    g_enc = [_node_grad(spec, _node_weights(params, j), tokens, keys[j],
                        g_mu[j], g_lv[j]) for j in range(spec.J)]
    grads = {**g_rest, "priors": params.get("priors", {}),
             "encoders": jax.tree.map(lambda *g: jnp.stack(g), *g_enc)}
    return total, mu, grads


# ---------------------------------------------------------------------------
# AdamW as launch/train.make_optimizer builds it
# ---------------------------------------------------------------------------

def learning_rate(opt: dict, step):
    """Linear warm-up over `warmup_steps`, then cosine decay to
    `final_frac` of the peak at `total_steps`."""
    step = jnp.asarray(step, jnp.float32)
    warm, total = opt["warmup_steps"], opt["total_steps"]
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 * (
        1 + jnp.cos(np.pi * prog))
    return opt["lr"] * jnp.where(step < warm, step / max(warm, 1), cos)


def _round_to(x, dtype):
    """x (float32) rounded to `dtype`, kept in float32."""
    return jax.lax.reduce_precision(x, 8, 7) \
        if jnp.dtype(dtype) == jnp.bfloat16 else x


def adamw(opt: dict, grads, state, step: int):
    """One update.  state: {"m", "v", "master"} float32 trees; grads in the
    parameters' dtype.  Returns (new parameters in that dtype, new
    state)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(_f32(g)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    lr = learning_rate(opt, step)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def one(g, m, v, w):
        g32 = _round_to(_f32(g) * _f32(scale.astype(g.dtype)), g.dtype)
        m = b1 * m + (1 - b1) * g32
        v = b2 * v + (1 - b2) * g32 * g32
        w = w - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
                      + opt["weight_decay"] * w)
        return {"m": m, "v": v, "master": w}
    out = jax.tree.map(one, grads, state["m"], state["v"], state["master"])
    tdef = jax.tree.structure(grads)
    new = {k: tdef.unflatten([o[k] for o in tdef.flatten_up_to(out)])
           for k in ("m", "v", "master")}
    params = jax.tree.map(lambda w, g: w.astype(g.dtype), new["master"],
                          grads)
    return params, new


def first_state(params):
    """AdamW's state before the first update: zero moments, the float32
    master copy of the parameters."""
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": zeros, "v": zeros, "master": jax.tree.map(_f32, params)}
