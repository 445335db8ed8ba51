"""SplitFed behind the unified Scheme API (Thapa et al.'s SplitFedV1
synchronisation, recast on the paper's multi-view setting).

One round == one parallel SL-style step against a shared server stub PLUS
one FedAvg of the client-side weights: every client encoder ships its
DETERMINISTIC cut-layer activations (the fused kernel's no-noise mode —
`wirefmt.cut_and_ship(key=None, ...)`, the same substrate SL's boundary
uses) to the server decoder, the eq.-(10) error chunks flow back per
client, each client applies its optimizer step, and the freshly-updated
client encoders are averaged and re-broadcast.  Bandwidth per round is
therefore the INL-style cut exchange (per-edge, wire-encoded) PLUS an
FL-style fp32 weight exchange of the (small) client-side network — both
decomposed per edge in `edge_ledger`, closed == measured by construction.

`cfg.cut_depth` picks how many conv blocks stay client-side (`client_cfg`
truncates the trunk); None keeps the full trunk — the classic boundary
right before the bottleneck head.

Under faults a dead route costs BOTH exchanges: the client's activations
drop out of the fusion (partial_fuse renormalises over survivors) and its
weights drop out of the round's average (masked FedAvg; the stranded
client keeps its local update and rejoins when the route heals).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import optim
from repro.core import bottleneck, linkfault, losses, paper_model, wirefmt
from repro.core import schemes as _schemes
from repro.core import topology as topology_lib
from repro.core.schemes import base


def client_cfg(cfg):
    """The config the CLIENT-side network is built from: conv trunk
    truncated to the first `cfg.cut_depth` blocks (None = full trunk)."""
    k = getattr(cfg, "cut_depth", None)
    if k is None:
        return cfg
    k = int(k)
    if not 1 <= k <= len(cfg.conv_channels):
        raise ValueError(
            f"cut_depth must be in [1, {len(cfg.conv_channels)}] (the conv "
            f"trunk has {len(cfg.conv_channels)} blocks), got {k}")
    return dataclasses.replace(cfg, cut_depth=None,
                               conv_channels=cfg.conv_channels[:k])


def tree_nbytes(tree) -> float:
    return float(sum(x.size * jnp.dtype(x.dtype).itemsize
                     for x in jax.tree.leaves(tree)))


def fedavg(new, old, mask):
    """Masked FedAvg over the stacked leading-J axis: surviving clients
    (mask) receive the average of the survivors' updates, dead routes keep
    their LOCAL update (they neither uploaded nor heard the broadcast).
    With an all-ones mask every client gets sum/J — bitwise the unfaulted
    plain average, so perfect links cannot move a trajectory."""
    J = mask.shape[0]
    w = mask.astype(jnp.float32)
    n = jnp.sum(w)

    def avg(x, o):
        wx = w.reshape((J,) + (1,) * (x.ndim - 1))
        a = jnp.sum(x.astype(jnp.float32) * wx, axis=0) / jnp.maximum(n, 1.0)
        a = jnp.where(n > 0, a, o[0].astype(jnp.float32))
        bcast = jnp.broadcast_to(a, x.shape).astype(x.dtype)
        return jnp.where(wx > 0, bcast, x)

    return jax.tree.map(avg, new, old)


def _encode(params, state, views, *, train):
    return jax.vmap(
        lambda p, s, v: paper_model.encoder_apply(p, s, v, train=train)
    )(params, state, views)


def _fuse_cat(u_joint):
    J, B, d = u_joint.shape
    return jnp.moveaxis(u_joint, 0, 1).reshape(B, J * d)


@_schemes.register
class SplitFedScheme(base.Scheme):
    name = "splitfed"

    def init(self, cfg, key, *, lr: float = 2e-3):
        ccfg = client_cfg(cfg)
        k_enc, k_dec = jax.random.split(key)
        enc_p, enc_s = jax.vmap(
            lambda k: paper_model.encoder_init(k, ccfg)
        )(jax.random.split(k_enc, cfg.num_clients))
        params = {"encoders": enc_p, "decoder": paper_model.decoder_init(
            k_dec, cfg)}
        opt = optim.adam(lr)
        return {"params": params, "state": {"encoders": enc_s},
                "opt": opt.init(params)}

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------

    def _loss(self, params, enc_state, views, labels, rng, cfg, *, wire,
              topo, delivery):
        dt = paper_model.compute_dtype(cfg)
        params_c = paper_model.cast_compute(params, dt)
        (mu, logvar), new_enc = _encode(params_c["encoders"],
                                        enc_state["encoders"],
                                        views.astype(dt), train=True)
        if topo is None:
            _, _, u_joint = wirefmt.cut_and_ship(
                None, mu, logvar, link_bits=cfg.link_bits,
                rate_estimator="none", wire=wire)
        else:
            _, _, u_joint = topology_lib.graph_cut_and_ship(
                topo, cfg, mu, logvar, jnp.zeros(mu.shape, jnp.float32),
                rate_estimator="none", wire=wire)
        u_joint = linkfault.partial_fuse(u_joint, delivery)
        logits = paper_model.decoder_apply(params_c["decoder"],
                                           _fuse_cat(u_joint), train=True,
                                           rng=rng)
        loss = losses.xent(logits, labels)
        metrics = {"loss": loss, "accuracy": losses.accuracy(logits, labels)}
        return loss, (metrics, {"encoders": new_enc})

    def make_round(self, cfg, *, lr: float = 2e-3, wire: str = "dense",
                   topology=None):
        # a (J,) delivery mask masks BOTH of the round's exchanges: a dead
        # route's activations leave the fusion AND its weights leave the
        # average — one fault, two degradations
        opt = optim.adam(lr)
        topo_full = topology_lib.resolve(topology, cfg)
        topo = topology_lib.nontrivial(topology, cfg)
        faulty = linkfault.active(topo_full, cfg, train=True)

        @jax.jit
        def step(state, views, labels, rng, delivery):
            _, r_dec = jax.random.split(rng)
            grad_fn = jax.value_and_grad(self._loss, has_aux=True)
            (_, (metrics, new_enc)), grads = grad_fn(
                state["params"], state["state"], views, labels, r_dec, cfg,
                wire=wire, topo=topo, delivery=delivery)
            params, opt_state = opt.update(grads, state["opt"],
                                           state["params"])
            params = dict(params, encoders=fedavg(
                params["encoders"], state["params"]["encoders"], delivery))
            return ({"params": params, "state": new_enc, "opt": opt_state},
                    metrics)

        def round_fn(state, views, labels, rng, delivery=None):
            # the fault stream folds off rng (linkfault.fault_key) without
            # disturbing the round's own key consumption.  The no-fault
            # path ships an all-ones mask as a RUNTIME argument rather
            # than a trace-time None: a constant mask lets XLA fold the
            # masked FedAvg into a different (reciprocal-multiply)
            # division than the traced graph uses, so the two spellings
            # would differ in the last ulp — one traced graph keeps
            # perfect links bitwise identical to the fault-free run
            if delivery is None:
                delivery = linkfault.round_delivery_mask(
                    rng, topo_full, cfg, labels.shape[-1], train=True) \
                    if faulty else jnp.ones((cfg.num_clients,), bool)
            return step(state, views[0], labels[0], rng, delivery)
        return round_fn

    # ------------------------------------------------------------------
    # inference
    # ------------------------------------------------------------------

    def _predict(self, state, views, topology, cfg, delivery=None,
                 wire: str = "dense"):
        topo = None if cfg is None else topology_lib.nontrivial(topology,
                                                                cfg)
        (mu, logvar), _ = _encode(state["params"]["encoders"],
                                  state["state"]["encoders"], views,
                                  train=False)
        if topo is None:
            # the star ships unquantized at inference (INL's convention:
            # bottleneck.fused_sample_rate at the default 32-bit grid)
            u, _ = bottleneck.fused_sample_rate(None, mu, logvar,
                                                rate_estimator="none")
        else:
            _, _, u = topology_lib.graph_cut_and_ship(
                topo, cfg, mu, logvar, jnp.zeros(mu.shape, jnp.float32),
                rate_estimator="none", wire=wire)
        if delivery is not None:
            u = linkfault.partial_fuse(u, delivery)
        logits = paper_model.decoder_apply(state["params"]["decoder"],
                                           _fuse_cat(u), train=False)
        return jax.nn.softmax(logits, axis=-1)

    def predict(self, state, views, topology=None, cfg=None):
        return self._predict(state, views, topology, cfg)

    def predict_batched(self, state, views, *, delivery=None, topology=None,
                        cfg=None, wire: str = "dense"):
        return self._predict(state, views, topology, cfg, delivery=delivery,
                             wire=wire)

    def predict_under_faults(self, state, views, key, topology=None,
                             cfg=None):
        # like INL: each sample draws a (J,) route-survival mask and the
        # server fuses (renormalised) whatever arrived — one lost vote,
        # not a lost prediction
        topo_full = topology_lib.resolve(topology, cfg)
        delivery = linkfault.sample_delivery_mask(key, topo_full, cfg,
                                                  views.shape[1])
        return self._predict(state, views, topology, cfg, delivery=delivery)

    # ------------------------------------------------------------------
    # bandwidth
    # ------------------------------------------------------------------

    def _weight_charges(self, cfg, state):
        """(closed bits, measured bytes) ONE client's weight exchange costs
        per direction: the client-side encoder at fp32."""
        n_enc = paper_model.encoder_param_count(client_cfg(cfg))
        enc_nbytes = tree_nbytes(state["params"]["encoders"]) \
            / cfg.num_clients
        return 32.0 * n_enc, enc_nbytes

    def edge_ledger(self, cfg, state, batch_size: int, *,
                    wire: str = "dense", topology=None):
        # per edge: the cut exchange the edge's payload occupies (closed /
        # wire-measured, exactly INL's charge) + the FedAvg exchange of the
        # payload clients' encoders, fp32 both directions (up to the
        # server-side aggregator, averaged copy back down the same route)
        topo = topology_lib.resolve(topology, cfg)
        w_bits, w_nbytes = self._weight_charges(cfg, state)
        bits = topology_lib.round_edge_bits(topo, cfg, batch_size)
        nbytes = topology_lib.round_edge_wire_bytes(topo, cfg, batch_size,
                                                    wire=wire)
        out = {}
        for e in topo.topo_edges():
            k = len(topo.payload(e))
            out[e.key] = (bits[e.key] + 2.0 * k * w_bits,
                          nbytes[e.key] + 2.0 * k * w_nbytes)
        return out

    def bits_per_round(self, cfg, state, batch_size: int, *,
                       topology=None) -> float:
        return float(sum(b for b, _ in self.edge_ledger(
            cfg, state, batch_size, topology=topology).values()))

    def wire_bytes_per_round(self, cfg, state, batch_size: int, *,
                             wire: str = "dense", topology=None) -> float:
        return float(sum(n for _, n in self.edge_ledger(
            cfg, state, batch_size, wire=wire, topology=topology).values()))
