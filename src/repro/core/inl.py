"""In-network learning (INL) — the paper's architecture (§III).

J edge nodes encode their local views into stochastic bottleneck latents u_j;
node (J+1) concatenates them (eq. 5) and decodes.  Training optimises eq. (6)
end-to-end: JAX AD through the concatenation reproduces exactly the paper's
error-vector split (eq. 8c / Remark 2) — node j receives only its chunk
delta[j] of the decoder-input cotangent, plus the local gradient of its own
rate term (eq. 10).  tests/test_inl_grads.py verifies the hand-derived split
against AD.

Encoder parameters are STACKED along a leading J axis so the whole system
shards over a 'client' mesh axis (each client's encoder params + data live on
its own devices; only u_j / delta_j cross the boundary — the paper's
bandwidth story).  A heterogeneous (list-of-different-encoders) path is also
provided, since the paper allows per-node architectures to differ.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import (bottleneck, linkfault, linkmodel, losses,
                        paper_model, wirefmt)
from repro.core import topology as topology_lib


class INLParams(NamedTuple):
    encoders: dict          # stacked: leading axis J
    decoder: dict
    priors: dict            # {} when standard-normal


def init(cfg, key):
    """cfg: PaperExperimentConfig.  Returns (INLParams, state).

    cfg.learned_prior=True adds per-node trainable Gaussian-prior params
    ((J, d) mean/logvar, init at the standard normal); the rate term then
    runs the fused kernel's learned-prior path — same one-pass-per-direction
    substrate, no unfused fallback."""
    J = cfg.num_clients
    ks = jax.random.split(key, 3)
    enc_keys = jax.random.split(ks[0], J)
    stacked = jax.vmap(lambda k: paper_model.encoder_init(k, cfg))(enc_keys)
    enc_params, enc_state = stacked
    dec = paper_model.decoder_init(ks[1], cfg)
    priors = bottleneck.prior_init(
        cfg.d_bottleneck, learned=getattr(cfg, "learned_prior", False),
        num_nodes=J)
    return (INLParams(enc_params, dec, priors), {"encoders": enc_state})


def _encode_mu_logvar(params: INLParams, state, views, *, train: bool):
    """All J per-node encoders under one vmap: views (J,B,H,W,C) ->
    ((mu, logvar) (J,B,d), new encoder state).  The single definition the
    stochastic, deterministic and wire-aware paths all share."""
    return jax.vmap(
        lambda p, s, v: paper_model.encoder_apply(p, s, v, train=train)
    )(params.encoders, state["encoders"], views)


def encode_and_rate(params: INLParams, state, views, *, train: bool, rng,
                    link_bits: int = 32, rate_estimator: str = "sample",
                    backend: str = "auto"):
    """The fused edge hot path: views (J,B,H,W,C) ->
    (u (J,B,d), mu, logvar, rate (J,B), new_state).

    After the per-node encoders produce (mu, logvar), ONE cut-layer kernel
    launch (client axis folded into the row grid, kernels/ops.cutlayer)
    yields both the quantized transmission u and the per-sample rate term
    of eq. (6); the backward pass is the paper's eq.-(10) error-vector +
    rate-gradient split.  Learned priors (params.priors non-empty) ride the
    same launch via the kernel's per-node prior grid."""
    (mu, logvar), new_state = _encode_mu_logvar(params, state, views,
                                                train=train)
    u, rate = bottleneck.fused_sample_rate(
        rng, mu, logvar, link_bits=link_bits, rate_estimator=rate_estimator,
        prior=params.priors, backend=backend)
    return u, mu, logvar, rate, {"encoders": new_state}


def encode(params: INLParams, state, views, *, train: bool, rng=None,
           link_bits: int = 32, sample_latent: bool = True,
           backend: str = "auto"):
    """views: (J,B,H,W,C) -> (u (J,B,d), mu, logvar, new_state).

    This is everything that runs AT THE EDGE.  u is what crosses the links
    (quantized to link_bits).  Both paths run the fused cut-layer kernel:
    sampling draws eps, the deterministic path (inference, u = quantize(mu))
    is the kernel's no-noise "none" mode — one measured substrate for every
    scheme."""
    if sample_latent and rng is not None:
        u, mu, logvar, _, new_state = encode_and_rate(
            params, state, views, train=train, rng=rng, link_bits=link_bits,
            backend=backend)
        return u, mu, logvar, new_state
    (mu, logvar), new_state = _encode_mu_logvar(params, state, views,
                                                train=train)
    u_sent, _ = bottleneck.fused_sample_rate(
        None, mu, logvar, link_bits=link_bits, rate_estimator="none",
        backend=backend)
    return u_sent, mu, logvar, {"encoders": new_state}


def decode(params: INLParams, u, *, train: bool, rng=None, u_joint=None):
    """Node (J+1): u (J,B,d) -> (joint_logits, branch_logits (J,B,C)).

    u_joint — the latents as RECEIVED over the wire (wirefmt.cut_and_ship's
    third output; defaults to u).  The fusion decoder reads the received
    buffer, the per-branch heads the same values — with a packed wire both
    are bit-identical to the dense path, but the joint-decoder cotangent
    flows back through the wire's straight-through VJP (where
    "packed_duplex" compresses the backward link too)."""
    if u_joint is None:
        u_joint = u
    J, B, d = u_joint.shape
    u_cat = jnp.moveaxis(u_joint, 0, 1).reshape(B, J * d)  # eq. (5) concat
    joint = paper_model.decoder_apply(params.decoder, u_cat, train=train,
                                      rng=rng)
    branch = paper_model.branch_heads_apply(params.decoder, u)
    return joint, branch


def loss_fn(params: INLParams, state, views, labels, rng, cfg, *,
            train: bool = True, rate_estimator: str = "sample",
            backend: str = "auto", wire: str = "dense", topology=None,
            delivery=None):
    """Full eq.-(6) loss.  Returns (loss, (metrics, new_state)).

    The encode side runs the fused cut-layer megakernel, which also emits
    the per-sample rate — losses.inl_loss consumes it instead of
    recomputing the rate from (u, mu, logvar).

    wire selects the u_j -> node-(J+1) format (core/wirefmt.py): "dense"
    is the pre-existing graph; "packed"/"packed_duplex" route the latents
    through bit-packed codewords (here as an on-device round trip — the
    sharded rounds run the same format over the real 'client' collective).
    cfg.compute_dtype="bf16" applies the mixed-precision policy: params
    and views drop to bf16 INSIDE this function, so gradients and the
    optimizer's master params stay fp32.

    topology — a core/topology.Topology (defaults to cfg.topology, then
    the implicit star): non-star graphs cut each node at its first hop's
    width and route the latents through the edges' re-encoding hops in
    topological order before the eq.-(5) concatenation at the fuse node
    (graph_cut_and_ship); the default star keeps this function's
    pre-topology graph bit for bit.

    Unreliable links (core/linkfault.py): when any edge carries a
    LinkModel, cfg.edge_dropout > 0, or cfg.fusion_deadline_ms is set,
    a deterministic per-(round, edge) delivery mask drops the views whose
    route failed this round and the fusion center fuses what arrived
    (mask + renormalise, `linkfault.partial_fuse`) — eq.-(10) error
    chunks then flow back only over the surviving reverse edges.  Branch
    heads and rate terms stay local and unmasked: a cut-off node keeps
    training its own head.

    delivery — an EXPLICIT (J,) or (J, B) delivery mask that overrides the
    in-graph fault draw entirely: the transport layer
    (repro/transport/NetworkTransport) measures which views actually
    arrived this round — after retries, circuit breakers and chaos — and
    feeds the outcome in as data.  None keeps the legacy in-graph draws
    (or the perfect network) bit for bit."""
    topo_full = topology_lib.resolve(topology, cfg)
    faulty = delivery is None and linkfault.active(topo_full, cfg,
                                                   train=train)
    topo = topology_lib.nontrivial(topology, cfg)
    dt = paper_model.compute_dtype(cfg)
    params_c = paper_model.cast_compute(params, dt)
    views = views.astype(dt)
    r_enc, r_dec = jax.random.split(rng)
    # named scopes, disjoint, so device time splits by the paper's layers
    # (backward ops inherit them as transpose(jvp(<scope>))).  The cut
    # layer's kernel call stays outside every scope: a scope would rename
    # its instruction (jvp_jit__cutlayer_call__ becomes _cutlayer_call),
    # and a trace finds the kernel by that name (jit(_cutlayer_call) in
    # its op_name marks it as the cut's).
    with jax.named_scope("encoder"):
        (mu, logvar), new_enc = _encode_mu_logvar(params_c, state, views,
                                                  train=train)
    with jax.named_scope("cut"):
        eps = jax.random.normal(r_enc, mu.shape, jnp.float32)
    if topo is None:
        u, rate, u_joint = wirefmt.cut_and_ship(
            None, mu, logvar, link_bits=cfg.link_bits,
            rate_estimator=rate_estimator, wire=wire, prior=params_c.priors,
            eps=eps, backend=backend)
    else:
        u, rate, u_joint = topology_lib.graph_cut_and_ship(
            topo, cfg, mu, logvar, eps, rate_estimator=rate_estimator,
            wire=wire, prior=params_c.priors, backend=backend)
    with jax.named_scope("cut"):
        if delivery is not None:
            u_joint = linkfault.partial_fuse(u_joint, delivery)
        elif faulty:
            mask = linkfault.round_delivery_mask(rng, topo_full, cfg,
                                                 labels.shape[0],
                                                 train=train)
            u_joint = linkfault.partial_fuse(u_joint, mask)
    new_state = {"encoders": new_enc}
    with jax.named_scope("decoder"):
        joint, branch = decode(params_c, u, train=train, rng=r_dec,
                               u_joint=u_joint)
    J = u.shape[0]
    with jax.named_scope("loss"):
        loss, metrics = losses.inl_loss(
            joint, list(branch), labels,
            list(mu), list(logvar), list(u),
            s=cfg.s, rate_estimator=rate_estimator, rates=list(rate))
        metrics["accuracy"] = losses.accuracy(joint, labels)
    # §III-C accounting: activations forward + error vectors backward
    # (per-edge payloads summed when a topology re-routes them)
    if topo is None:
        p_total = J * cfg.d_bottleneck
        bits_sent = linkmodel.training_step_bits(labels.shape[0], p_total,
                                                 cfg.link_bits)
    else:
        bits_sent = topology_lib.round_bits(topo, cfg, labels.shape[0])
    metrics["bits_sent"] = jnp.asarray(bits_sent, jnp.float32)
    return loss, (metrics, new_state)


def make_train_step(cfg, optimizer, *, rate_estimator: str = "sample",
                    wire: str = "dense", topology=None,
                    explicit_delivery: bool = False):
    """jit-able train step closed over the experiment config + optimizer.

    explicit_delivery=True returns the TRANSPORT-mode step: it takes a
    trailing (J,) / (J, B) delivery-mask argument (the measured transport
    outcome) instead of drawing faults in-graph."""
    if explicit_delivery:
        @jax.jit
        def step_d(params, state, opt_state, views, labels, rng, delivery):
            (loss, (metrics, new_state)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(
                    params, state, views, labels, rng, cfg, train=True,
                    rate_estimator=rate_estimator, wire=wire,
                    topology=topology, delivery=delivery)
            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)
            return new_params, new_state, new_opt, metrics
        return step_d

    @jax.jit
    def step(params, state, opt_state, views, labels, rng):
        (loss, (metrics, new_state)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, views, labels, rng, cfg,
                                   train=True, rate_estimator=rate_estimator,
                                   wire=wire, topology=topology)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_state, new_opt, metrics
    return step


def predict(params: INLParams, state, views, *, cfg=None, topology=None,
            delivery=None, wire: str = "dense"):
    """Inference phase (§III-B): deterministic latents (u = mu), soft output.

    delivery — an optional (J,) or (J, B) boolean delivery mask
    (core/linkfault.py): views whose route dropped or missed the fusion
    deadline are masked out of the concatenation and the survivors
    renormalised (fuse-what-arrived).  None is the perfect network —
    bit-identical to the pre-fault path.

    wire — the per-hop link encoding for graph topologies
    (core/wirefmt.py): "packed" moves each hop's payload as bit-packed
    codeword lanes.  Hop values are already on the edge's quantizer grid,
    so packing is a lossless re-encoding — graph predictions are
    bit-identical across wire formats; only the measured bytes ledger
    moves.  The star path ships unquantized latents (the golden-pinned
    seed convention, see NOTE below) and ignores `wire`.

    A non-star `topology` (needs `cfg` for the edge widths) routes the
    deterministic latents through the same multi-hop re-encoding the
    training graph runs — what the fuse node actually receives.  NOTE the
    deliberate convention split: the star path ships UNQUANTIZED latents
    at inference (the seed convention, pinned by the golden accuracies),
    while the graph path models the real quantized multi-hop delivery —
    so at full-precision links (every hop the identity) chain/tree
    inference is bit-identical to the star, and at narrow links the
    difference IS the deployment effect (a 2-bit uplink visibly costs
    accuracy).  Compare star-vs-graph accuracy curves at link_bits=32, or
    read narrow-width comparisons as including inference-time
    quantization."""
    topo = None if cfg is None else topology_lib.nontrivial(topology, cfg)
    if topo is None:
        u, _, _, _ = encode(params, state, views, train=False,
                            sample_latent=False)
        u_joint = None if delivery is None else linkfault.partial_fuse(
            u, delivery)
        joint, _ = decode(params, u, train=False, u_joint=u_joint)
        return jax.nn.softmax(joint, axis=-1)
    (mu, logvar), _ = _encode_mu_logvar(params, state, views, train=False)
    u, _, u_fused = topology_lib.graph_cut_and_ship(
        topo, cfg, mu, logvar, jnp.zeros(mu.shape, jnp.float32),
        rate_estimator="none", wire=wire)
    if delivery is not None:
        u_fused = linkfault.partial_fuse(u_fused, delivery)
    joint, _ = decode(params, u, train=False, u_joint=u_fused)
    return jax.nn.softmax(joint, axis=-1)


def evaluate(params: INLParams, state, views, labels):
    probs = predict(params, state, views)
    return losses.accuracy(jnp.log(probs + 1e-30), labels)


# ---------------------------------------------------------------------------
# Heterogeneous-encoder variant (paper: NNs "need not be identical")
# ---------------------------------------------------------------------------

def init_heterogeneous(cfgs, key):
    """One (possibly different) PaperExperimentConfig per client; returns
    list-based params usable with loss_fn_heterogeneous."""
    ks = jax.random.split(key, len(cfgs) + 1)
    encs = [paper_model.encoder_init(ks[j], c) for j, c in enumerate(cfgs)]
    dec = paper_model.decoder_init(ks[-1], cfgs[0])
    params = {"encoders": [e[0] for e in encs], "decoder": dec}
    state = {"encoders": [e[1] for e in encs]}
    return params, state


def loss_fn_heterogeneous(params, state, views, labels, rng, cfg, *,
                          train: bool = True, backend: str = "auto"):
    """Per-node encoder architectures may differ, but every node emits the
    same d_bottleneck — so after the (necessarily sequential) encoder
    applies, the cut layer is still ONE fused kernel launch over the
    stacked (J, B, d) latents."""
    mus, lvs, new_states = [], [], []
    for j, (ep, es) in enumerate(zip(params["encoders"], state["encoders"])):
        (mu, lv), ns = paper_model.encoder_apply(ep, es, views[j], train=train)
        mus.append(mu); lvs.append(lv); new_states.append(ns)
    rng, r_cut, r_dec = jax.random.split(rng, 3)
    u, rate = bottleneck.fused_sample_rate(
        r_cut, jnp.stack(mus), jnp.stack(lvs), link_bits=cfg.link_bits,
        rate_estimator="sample", backend=backend)
    fake = INLParams(None, params["decoder"], {})
    joint, branch = decode(fake, u, train=train, rng=r_dec)
    loss, metrics = losses.inl_loss(joint, list(branch), labels, mus, lvs,
                                    list(u), s=cfg.s, rates=list(rate))
    metrics["accuracy"] = losses.accuracy(joint, labels)
    return loss, (metrics, {"encoders": new_states})
