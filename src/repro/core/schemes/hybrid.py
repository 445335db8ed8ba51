"""Hybrid FL/SL participation behind the unified Scheme API.

Each client picks HOW it participates (cfg.hybrid_fl_clients): CUT-mode
clients run the SL-style boundary — deterministic cut-layer activations to
the fusion center, eq.-(10) error chunks back — while WEIGHT-mode clients
train their full local model (client-side encoder + own branch head) and
sync fp32 weights with the server each round, FL-style.  The Guo-et-al.
hybrid trade: a weight-mode client's per-round cost is independent of the
batch, a cut-mode client's is independent of the model — the crossover is
what `repro/search` maps.

Training: every view is encoded, the CUT latents that arrived are
partial-fused into the eq.-(5) joint decoder, and all J branch heads train
on their local latent — a weight-mode client's whole gradient flows
through its branch head (its latent never ships), which is exactly its
local FL objective.  Inference ensembles the joint decoder (one vote per
fused cut latent) with the weight-mode clients' local branch predictions
(one vote each) in probability space.

Faults: a dead route drops a cut client's latent from the fusion
(renormalised partial fusion) and costs a weight client its whole round —
the server keeps the stale model copy (per-client revert), the classic
FL skip.  Bandwidth decomposes per edge: the cut payload's activation
exchange (closed == wirefmt-measured) plus 2 x 32 x N_client-side for
every weight-mode client the edge serves.

The graph simulation computes all J latents for vmap convenience; the
MODEL says weight-mode clients never transmit activations — they are
masked from every fusion and never charged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core import bottleneck, linkfault, losses, paper_model, wirefmt
from repro.core import schemes as _schemes
from repro.core import topology as topology_lib
from repro.core.schemes import base, splitfed


def fl_clients(cfg):
    """Validated, sorted weight-mode client indices from
    cfg.hybrid_fl_clients.  At least one client must stay cut-mode (the
    fusion center needs something to fuse)."""
    J = cfg.num_clients
    idx = tuple(sorted({int(j) for j in
                        (getattr(cfg, "hybrid_fl_clients", ()) or ())}))
    bad = [j for j in idx if not 0 <= j < J]
    if bad:
        raise ValueError(f"hybrid_fl_clients {bad} out of range for "
                         f"num_clients={J}")
    if len(idx) >= J:
        raise ValueError(
            f"hybrid needs at least one cut-mode client: hybrid_fl_clients="
            f"{idx} claims all {J} clients for weight-mode participation")
    return idx


def cut_mask(cfg) -> np.ndarray:
    """(J,) bool, True where the client ships cut-layer activations."""
    w = set(fl_clients(cfg))
    return np.array([j not in w for j in range(cfg.num_clients)], bool)


def _and_mask(static, delivery):
    """static (J,) & delivery (J,) or (J, B), broadcasting the static
    mode mask over the sample axis when needed."""
    if delivery is None:
        return static
    s = static if delivery.ndim == 1 else static[:, None]
    return jnp.logical_and(s, delivery)


@_schemes.register
class HybridScheme(base.Scheme):
    name = "hybrid"

    def init(self, cfg, key, *, lr: float = 2e-3):
        state = splitfed.SplitFedScheme().init(cfg, key, lr=lr)
        # the mode split rides in the state so inference (which may not
        # see cfg — the parity fixtures call bare predict) always fuses
        # exactly the latents training fused
        state["modes"] = jnp.asarray(cut_mask(cfg))
        return state

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------

    def _loss(self, params, enc_state, modes, views, labels, rng, cfg, *,
              wire, topo, delivery):
        dt = paper_model.compute_dtype(cfg)
        params_c = paper_model.cast_compute(params, dt)
        (mu, logvar), new_enc = splitfed._encode(
            params_c["encoders"], enc_state["encoders"], views.astype(dt),
            train=True)
        if topo is None:
            u, _, u_joint = wirefmt.cut_and_ship(
                None, mu, logvar, link_bits=cfg.link_bits,
                rate_estimator="none", wire=wire)
        else:
            u, _, u_joint = topology_lib.graph_cut_and_ship(
                topo, cfg, mu, logvar, jnp.zeros(mu.shape, jnp.float32),
                rate_estimator="none", wire=wire)
        u_joint = linkfault.partial_fuse(u_joint, _and_mask(modes, delivery))
        logits = paper_model.decoder_apply(params_c["decoder"],
                                           splitfed._fuse_cat(u_joint),
                                           train=True, rng=rng)
        joint_loss = losses.xent(logits, labels)
        branch = paper_model.branch_heads_apply(params_c["decoder"], u)
        branch_loss = jnp.mean(jax.vmap(losses.xent, in_axes=(0, None))(
            branch, labels))
        loss = joint_loss + branch_loss
        metrics = {"loss": loss, "accuracy": losses.accuracy(logits, labels),
                   "branch_loss": branch_loss}
        return loss, (metrics, {"encoders": new_enc})

    def make_round(self, cfg, *, lr: float = 2e-3, wire: str = "dense",
                   topology=None):
        fl_clients(cfg)                      # validate the mode split early
        opt = optim.adam(lr)
        topo_full = topology_lib.resolve(topology, cfg)
        topo = topology_lib.nontrivial(topology, cfg)
        faulty = linkfault.active(topo_full, cfg, train=True)

        @jax.jit
        def step(state, views, labels, rng, delivery):
            _, r_dec = jax.random.split(rng)
            modes = state["modes"]
            grad_fn = jax.value_and_grad(self._loss, has_aux=True)
            (_, (metrics, new_enc)), grads = grad_fn(
                state["params"], state["state"], modes, views, labels,
                r_dec, cfg, wire=wire, topo=topo, delivery=delivery)
            params, opt_state = opt.update(grads, state["opt"],
                                           state["params"])
            # FL skip semantics: a weight-mode client whose route died
            # never reached the server — revert its per-client rows
            # (encoder + branch head) to the stale server copy.  Cut
            # clients keep local updates (their branch stays on-node).
            revert = jnp.logical_and(~modes, ~delivery)

            def keep(new, old):
                m = revert.reshape((revert.shape[0],)
                                   + (1,) * (new.ndim - 1))
                return jnp.where(m, old, new)

            old = state["params"]
            params = dict(params, encoders=jax.tree.map(
                keep, params["encoders"], old["encoders"]))
            params["decoder"] = dict(
                params["decoder"], branch_heads=jax.tree.map(
                    keep, params["decoder"]["branch_heads"],
                    old["decoder"]["branch_heads"]))
            return ({"params": params, "state": new_enc, "opt": opt_state,
                     "modes": modes}, metrics)

        def round_fn(state, views, labels, rng, delivery=None):
            # all-ones as a RUNTIME argument, not a trace-time None, so
            # the no-fault and perfect-link cases share one jitted graph
            # (see splitfed.py: a constant mask constant-folds into
            # different last-ulp arithmetic)
            if delivery is None:
                delivery = linkfault.round_delivery_mask(
                    rng, topo_full, cfg, labels.shape[-1], train=True) \
                    if faulty else jnp.ones((cfg.num_clients,), bool)
            return step(state, views[0], labels[0], rng, delivery)
        return round_fn

    # ------------------------------------------------------------------
    # inference: joint decoder over fused cut latents, ensembled with the
    # weight-mode clients' local branch predictions
    # ------------------------------------------------------------------

    def _predict(self, state, views, topology, cfg, delivery=None,
                 wire: str = "dense"):
        modes = state["modes"]
        topo = None if cfg is None else topology_lib.nontrivial(topology,
                                                                cfg)
        (mu, logvar), _ = splitfed._encode(
            state["params"]["encoders"], state["state"]["encoders"], views,
            train=False)
        if topo is None:
            u, _ = bottleneck.fused_sample_rate(None, mu, logvar,
                                                rate_estimator="none")
            u_joint = u
        else:
            u, _, u_joint = topology_lib.graph_cut_and_ship(
                topo, cfg, mu, logvar, jnp.zeros(mu.shape, jnp.float32),
                rate_estimator="none", wire=wire)
        cut_m = _and_mask(modes, delivery)
        w_m = _and_mask(~modes, delivery)
        dec = state["params"]["decoder"]
        u_f = linkfault.partial_fuse(u_joint, cut_m)
        p_dec = jax.nn.softmax(paper_model.decoder_apply(
            dec, splitfed._fuse_cat(u_f), train=False), axis=-1)
        p_branch = jax.nn.softmax(paper_model.branch_heads_apply(dec, u),
                                  axis=-1)                      # (J, B, C)
        B = views.shape[1]
        cut2 = jnp.broadcast_to(
            (cut_m if cut_m.ndim == 2 else cut_m[:, None]).astype(
                jnp.float32), (modes.shape[0], B))
        w2 = jnp.broadcast_to(
            (w_m if w_m.ndim == 2 else w_m[:, None]).astype(jnp.float32),
            (modes.shape[0], B))
        cut_votes = jnp.sum(cut2, axis=0)                       # (B,)
        w_votes = jnp.sum(w2, axis=0)
        numer = p_dec * cut_votes[:, None] \
            + jnp.sum(p_branch * w2[:, :, None], axis=0)
        total = cut_votes + w_votes
        probs = numer / jnp.maximum(total, 1.0)[:, None]
        uniform = jnp.full_like(probs, 1.0 / probs.shape[-1])
        return jnp.where(total[:, None] > 0, probs, uniform)

    def predict(self, state, views, topology=None, cfg=None):
        return self._predict(state, views, topology, cfg)

    def predict_batched(self, state, views, *, delivery=None, topology=None,
                        cfg=None, wire: str = "dense"):
        return self._predict(state, views, topology, cfg, delivery=delivery,
                             wire=wire)

    def predict_under_faults(self, state, views, key, topology=None,
                             cfg=None):
        # per-sample route survival: a dead cut route loses one fusion
        # vote, a dead weight route loses that client's ensemble vote
        topo_full = topology_lib.resolve(topology, cfg)
        delivery = linkfault.sample_delivery_mask(key, topo_full, cfg,
                                                  views.shape[1])
        return self._predict(state, views, topology, cfg, delivery=delivery)

    # ------------------------------------------------------------------
    # bandwidth
    # ------------------------------------------------------------------

    def _weight_charges(self, cfg, state):
        """(closed bits, measured bytes) per weight-mode client and
        direction: client-side encoder + its branch head, fp32."""
        J = cfg.num_clients
        n_cs = paper_model.encoder_param_count(splitfed.client_cfg(cfg)) \
            + cfg.d_bottleneck * cfg.num_classes + cfg.num_classes
        nbytes = (splitfed.tree_nbytes(state["params"]["encoders"])
                  + splitfed.tree_nbytes(
                      state["params"]["decoder"]["branch_heads"])) / J
        return 32.0 * n_cs, nbytes

    def edge_ledger(self, cfg, state, batch_size: int, *,
                    wire: str = "dense", topology=None):
        topo = topology_lib.resolve(topology, cfg)
        wset = set(fl_clients(cfg))
        w_bits, w_nbytes = self._weight_charges(cfg, state)
        dt = paper_model.compute_dtype(cfg)
        out = {}
        for e in topo.topo_edges():
            pay = topo.payload(e)
            n_cut = sum(1 for j in pay if j not in wset)
            n_w = len(pay) - n_cut
            q = topology_lib.edge_bits(e, cfg)
            bits = 2.0 * batch_size * n_cut * cfg.d_bottleneck * q
            nbytes = 0.0 if n_cut == 0 else float(wirefmt.round_wire_bytes(
                batch_size * n_cut, cfg.d_bottleneck, link_bits=q,
                wire=topology_lib.edge_wire(e, wire),
                dtype=topology_lib.edge_dtype(e, cfg))["total"])
            out[e.key] = (bits + 2.0 * n_w * w_bits,
                          nbytes + 2.0 * n_w * w_nbytes)
        return out

    def bits_per_round(self, cfg, state, batch_size: int, *,
                       topology=None) -> float:
        return float(sum(b for b, _ in self.edge_ledger(
            cfg, state, batch_size, topology=topology).values()))

    def wire_bytes_per_round(self, cfg, state, batch_size: int, *,
                             wire: str = "dense", topology=None) -> float:
        return float(sum(n for _, n in self.edge_ledger(
            cfg, state, batch_size, wire=wire, topology=topology).values()))
