"""In-network learning applied to the assigned LLM architectures.

The paper's vertical split, instantiated with transformer-family blocks:
J edge nodes each observe a VIEW of the token stream (its own embedding table
+ view-specific Gaussian feature noise — the LLM analogue of the paper's
noisy CIFAR views), run `inl.encoder_layers` periods of the architecture's
own block pattern, and emit per-token stochastic bottleneck latents u_j of
width `inl.d_bottleneck`.  Node (J+1) concatenates (eq. 5: J * d_bottleneck
== decoder input width == d_model), projects into the remaining stack and
decodes with the LM head.  Eq. (6) applies per token.

Sharding: encoder params/views carry a leading J axis -> sharded over the
first `J` slices of the 'data' mesh axis; only u_j / delta_j cross the
client boundary (the paper's bandwidth argument, now an ICI argument).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bottleneck, linkmodel, losses
from repro.models import layers, transformer, zoo


class INLLLMParams(NamedTuple):
    encoders: dict     # stacked (J, ...): embed + encoder stack + bottleneck head
    decoder: dict      # in_proj + decoder stack + final norm + lm head
    branch_heads: dict # (J, d_b, vocab_pad) per-node decoders (at node J+1)
    priors: dict = {}  # learned per-node Q_psi (J, d_b) mean/logvar; {} = N(0,I)


def encoder_cfg(cfg):
    pat = transformer.block_pattern(cfg)
    # NOTE: moe_impl="gspmd" — the shard_map EP dispatch cannot run under the
    # vmap over J stacked encoders (jax's vmap rule for psum inside shard_map
    # rejects it); the partitioner path is vmap-compatible.
    return dataclasses.replace(
        cfg, num_layers=cfg.inl.encoder_layers * len(pat),
        moe=dataclasses.replace(cfg.moe, first_dense_layers=0),
        moe_impl="gspmd")


def decoder_cfg(cfg):
    pat = transformer.block_pattern(cfg)
    dec_periods = transformer.num_periods(cfg) - cfg.inl.encoder_layers
    assert dec_periods >= 1, f"{cfg.name}: not enough periods for INL split"
    return dataclasses.replace(
        cfg, num_layers=(dec_periods * len(pat)
                         + cfg.moe.first_dense_layers),
        moe_impl="gspmd")


def init(cfg, key):
    J = cfg.inl.num_nodes
    dtype = jnp.dtype(cfg.dtype)
    e_cfg, d_cfg = encoder_cfg(cfg), decoder_cfg(cfg)
    ks = jax.random.split(key, 5)

    def one_encoder(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "embed": layers.embed_init(k1, cfg.vocab_size, cfg.d_model, dtype),
            "stack": transformer.stack_init(k2, e_cfg, dtype),
            "norm": layers.rmsnorm_init(cfg.d_model, dtype),
            "head": bottleneck.head_init(k3, cfg.d_model, cfg.inl.d_bottleneck,
                                         dtype),
        }

    encoders = jax.vmap(one_encoder)(jax.random.split(ks[0], J))
    decoder = {
        "in_proj": layers.dense_init(ks[1], J * cfg.inl.d_bottleneck,
                                     cfg.d_model, dtype=dtype),
        "stack": transformer.stack_init(ks[2], d_cfg, dtype),
        "final_norm": layers.rmsnorm_init(cfg.d_model, dtype),
        "unembed": layers.dense_init(ks[3], cfg.d_model,
                                     layers.pad_vocab(cfg.vocab_size),
                                     dtype=dtype),
    }
    vpad = layers.pad_vocab(cfg.vocab_size)
    bh = (jax.random.normal(ks[4], (J, cfg.inl.d_bottleneck, vpad),
                            jnp.float32) * 0.02).astype(dtype)
    priors = bottleneck.prior_init(cfg.inl.d_bottleneck,
                                   learned=cfg.inl.learned_prior,
                                   num_nodes=J)
    return INLLLMParams(encoders, decoder, {"w": bh}, priors)


def encode(params: INLLLMParams, cfg, tokens, rng, *, train: bool = True,
           rate_estimator: str = "sample", backend: str = "auto"):
    """tokens: (B,S).  Views differ by per-node embedding + feature noise.
    Returns (u, mu, logvar, rate): u/mu/logvar (J, B, S, d_b); rate
    (J, B, S) fp32 from the fused cut-layer kernel (None when train=False).
    See `_encode`."""
    return _encode(params, cfg, tokens, rng, train=train,
                   rate_estimator=rate_estimator, backend=backend)[:4]


def _encode(params: INLLLMParams, cfg, tokens, rng, *, train: bool = True,
            rate_estimator: str = "sample", backend: str = "auto"):
    """`encode`'s (u, mu, logvar, rate), then the encoders' block states:
    None, or with cfg.ssm.record_mlstm their mLSTM blocks' recurrence
    records, (J, periods, ...) per block of the period.

    The per-node encoders run under vmap, but the cut layer itself —
    sample + link quantizer + rate — is ONE fused kernel launch over all
    J * B * S rows (kernels/ops.cutlayer), with the hand-written eq.-(10)
    backward.  With link_bits <= 8 the int8 wire in `decode` carries the
    quantization instead, so the kernel runs with a full-precision link."""
    J = cfg.inl.num_nodes
    e_cfg = encoder_cfg(cfg)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    noise_keys = jax.random.split(jax.random.fold_in(rng, 0), J)

    def one(enc, nk):
        h = layers.embed(enc["embed"], tokens)
        # view-specific observation noise (sigma grows with node index via key
        # folding is NOT used here: homogeneous sigma keeps nodes exchangeable)
        with jax.named_scope("cut"):
            noise = jax.random.normal(nk, h.shape, jnp.float32)
        h = h + (0.1 * noise).astype(h.dtype)
        h, states, _ = transformer.stack_apply(enc["stack"], e_cfg, h,
                                               positions, mode="train")
        h = layers.rmsnorm(enc["norm"], h, cfg.norm_eps)
        return bottleneck.head_apply(enc["head"], h), states

    with jax.named_scope("encoder"):
        (mu, logvar), states = jax.vmap(one)(params.encoders, noise_keys)
    bits = cfg.inl.link_bits if cfg.inl.link_bits > 8 else 32
    if train:
        # the eps draw is the cut's; the kernel call stays outside every
        # scope, which would rename its instructions (`_cutlayer_call`)
        with jax.named_scope("cut"):
            eps = jax.random.normal(jax.random.fold_in(rng, 1), mu.shape,
                                    jnp.float32)
        u, rate = bottleneck.fused_sample_rate(
            None, mu, logvar, link_bits=bits, rate_estimator=rate_estimator,
            prior=params.priors, backend=backend, eps=eps)
    else:
        # deterministic inference cut: same kernel, no-noise mode
        u, _ = bottleneck.fused_sample_rate(
            None, mu, logvar, link_bits=bits, rate_estimator="none",
            backend=backend)
        rate = None
    return u, mu, logvar, rate, states


def decode(params: INLLLMParams, cfg, u, tokens_shape):
    """u: (J,B,S,d_b) -> (joint_logits, branch_logits).

    The eq.-(5) concatenation is the client->center boundary: with
    link_bits <= 8 it runs over a compressed wire so the client-axis
    all-gather moves small buffers — the paper's bandwidth idea applied to
    the ICI.  link_bits == 8 rides the int8 wire (linkmodel.wire_concat);
    link_bits < 8 bit-packs sub-byte codewords into uint32 lanes
    (linkmodel.packed_wire_concat), 32/link_bits fewer collective bytes.
    Both pin their gathers via launch/sharding.wire_specs."""
    J, B, S, db = u.shape
    d_cfg = decoder_cfg(cfg)
    if cfg.inl.link_bits <= 8:
        from repro.launch.mesh import current_abstract_mesh
        from repro.launch.sharding import wire_specs
        gathered, client = wire_specs(current_abstract_mesh())
        if cfg.inl.link_bits < 8:                    # sub-byte packed wire
            u_cat = linkmodel.packed_wire_concat(u, cfg.inl.link_bits,
                                                 gathered, client)
        else:
            u_cat = linkmodel.wire_concat(u, gathered, client)  # int8 wire
    else:
        u_cat = linkmodel.float_concat(u)                 # eq. (5)
    h = layers.dense(params.decoder["in_proj"], u_cat)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h, _, aux = transformer.stack_apply(params.decoder["stack"], d_cfg, h,
                                        positions, mode="train")
    h = layers.rmsnorm(params.decoder["final_norm"], h, cfg.norm_eps)
    return h, aux


def _chunked_inl_ce(params: INLLLMParams, cfg, h, u, labels,
                    chunk: int = 512):
    """Joint + per-branch CE, chunked over the sequence so the (B, S, vocab)
    joint logits and the (J, B, S, vocab) branch logits never materialise
    (at 128k vocab the branch logits alone are petabyte-scale).  Each chunk
    is jax.checkpoint'ed and recomputed in the backward pass."""
    J, B, S, db = u.shape
    chunk = min(chunk, S)
    nch = -(-S // chunk)
    pad = nch * chunk - S
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        u = jnp.pad(u, ((0, 0), (0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hb = jnp.moveaxis(h.reshape(B, nch, chunk, -1), 1, 0)
    ub = jnp.moveaxis(u.reshape(J, B, nch, chunk, db), 2, 0)
    lb = jnp.moveaxis(labels.reshape(B, nch, chunk), 1, 0)

    def ce_sum(logits, lab):
        mask = (lab != -1).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, jnp.maximum(lab, 0)[..., None],
                                 axis=-1)[..., 0]
        return -(ll * mask).sum(), mask.sum()

    @jax.checkpoint
    def body(carry, inp):
        j_nll, b_nll, cnt, hits = carry
        h_c, u_c, lab_c = inp
        joint = layers.dense(params.decoder["unembed"],
                             h_c)[..., :cfg.vocab_size]
        nll, n = ce_sum(joint, lab_c)
        branch = jnp.einsum("jbsd,jdv->jbsv", u_c,
                            params.branch_heads["w"])[..., :cfg.vocab_size]
        bn = ce_sum(branch, lab_c[None])[0]
        hits = hits + ((jnp.argmax(joint, -1) == lab_c)
                       & (lab_c != -1)).sum()
        return (j_nll + nll, b_nll + bn, cnt + n, hits), None

    z = jnp.zeros((), jnp.float32)
    (j_nll, b_nll, cnt, hits), _ = jax.lax.scan(
        body, (z, z, z, jnp.zeros((), jnp.int32)), (hb, ub, lb))
    cnt = jnp.maximum(cnt, 1.0)
    return j_nll / cnt, b_nll / cnt, hits / cnt


def loss_fn(params: INLLLMParams, cfg, batch, rng, *,
            rate_estimator: str = "sample", backend: str = "auto"):
    tokens, labels = batch["tokens"], batch["labels"]
    u, mu, logvar, rates, states = _encode(
        params, cfg, tokens, rng, train=True, rate_estimator=rate_estimator,
        backend=backend)
    with jax.named_scope("decoder"):
        h, moe_aux = decode(params, cfg, u, tokens.shape)
    with jax.named_scope("loss"):
        ce_joint, ce_branch_sum, acc = _chunked_inl_ce(params, cfg, h, u,
                                                       labels)
        # rates (J,B,S) come from the fused cut-layer kernel — not
        # recomputed
        rate_total = jnp.mean(rates.reshape(cfg.inl.num_nodes, -1),
                              axis=-1).sum()
        loss = ce_joint + cfg.inl.s * (ce_branch_sum + rate_total)
        metrics = {"ce_joint": ce_joint,
                   "ce_branch_mean": ce_branch_sum / cfg.inl.num_nodes,
                   "rate_mean": rate_total / cfg.inl.num_nodes,
                   "rate_total": rate_total, "accuracy": acc}
        if cfg.is_moe:
            loss = loss + cfg.moe.router_aux_weight * moe_aux["lb_loss"] \
                        + cfg.moe.router_z_weight * moe_aux["z_loss"]
    metrics["loss"] = loss
    J = cfg.inl.num_nodes
    metrics["bits_per_token"] = jnp.asarray(
        2 * J * cfg.inl.d_bottleneck * cfg.inl.link_bits, jnp.float32)
    if cfg.ssm.record_mlstm:
        # the J cut means, and node 0's first mLSTM block's recurrence in
        # its first period
        first = states["pattern"][
            list(transformer.block_pattern(cfg)).index("mlstm")]
        metrics.update({"record." + k: v[0, 0] for k, v in first.items()})
        metrics["record.mu"] = mu
    return loss, metrics


def make_train_step(cfg, optimizer):
    def step(params, opt_state, batch, rng):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, cfg, batch, rng)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, metrics
    return step


def input_specs(cfg, shape_cfg):
    B, S = shape_cfg.global_batch, shape_cfg.seq_len
    i32 = jnp.int32
    return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32)}
