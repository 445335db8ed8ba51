"""Node-parallel scheme execution: shard_map rounds over a (client, data) mesh.

The paper's claim that INL is *naturally distributed* (J nodes compute
features in parallel, a fusion center combines them) becomes an execution
strategy here: each scheme's training round is re-expressed as a
`shard_map` body over `launch/mesh.make_inl_host_mesh` /`make_inl_mesh`
axes —

    'client'  holds the J INL/FL branches (encoder params, branch heads,
              per-node priors, per-client FL replicas are sharded on their
              leading J axis),
    'data'    shards the batch.

Cross-node traffic is exactly the paper's cut-layer exchange: the fused
`kernels/ops.cutlayer` kernel runs per-shard on the local (J/c, B/d, d_b)
latent block, and the ONLY collectives are the fusion-center fan-in
(`all_gather` of u over 'client' — eq. (5)'s concatenation as a wire
transfer), the decoder/aggregation reductions (`psum` over 'client'), and
batch-mean reductions (`pmean` over 'data').

The fan-in's WIRE FORMAT is selectable (`wire=`, core/wirefmt.py): "dense"
all-gathers the quantized latents at their storage dtype (the baseline the
goldens pin); "packed" runs the pack-emitting cut-layer kernel and gathers
`link_bits`-bit codewords in uint32 lanes — 32/link_bits fewer collective
bytes, values and trajectories bit-identical; "packed_duplex" additionally
quantizes the eq.-(10) error chunks on the way back, making measured bytes
equal the paper's symmetric 2 b p s accounting (lossy: each node receives
exactly the q-bit-coded error chunk the modeled link delivers — execution
sums the replicated decoder's partial cotangents with a dense psum_scatter
first and quantizes after, a shard_map artifact the meter does not charge;
see core/wirefmt.py).  FL's weight exchange stays fp32 — quantized
FedAvg is a different algorithm, not a wire format.  `cfg.compute_dtype`
applies the mixed-precision policy inside every round body (params/views
drop to bf16 before local AD; grads, optimizer state and collective
reductions stay fp32).

Single-device semantics are preserved exactly (golden-trajectory parity,
tests/test_sharded_parity.py):

- all randomness (bottleneck eps, decoder dropout masks) is drawn OUTSIDE
  the shard_map body at global batch shape, so shards consume the same
  random stream the single-device run does;
- BatchNorm statistics are made global with pmean (paper_model.bn_apply
  axis_name) in the two-pass form matching jnp.var's numerics;
- the redundantly-replicated fusion term is scaled by 1/n_client before
  local AD: the all_gather transpose (psum_scatter) sums the n_client
  identical joint-CE cotangents, restoring the exact coefficient, while
  replicated decoder grads are psum'ed back up.  Verified against
  single-device AD at 1e-7.

Gradients come OUT of the shard_map body; the (elementwise) optimizer
update runs outside under plain jit so GSPMD keeps m/v in the params'
layout for free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import linkfault, linkmodel, losses, paper_model, wirefmt
from repro.core import topology as topology_lib
from repro.core.inl import INLParams
from repro.kernels import ops


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def check_mesh(mesh, num_clients: int):
    """The sharded rounds need ('client', 'data') axes with J divisible by
    the client axis (make_inl_host_mesh guarantees this via its replicated
    fallback)."""
    for ax in ("client", "data"):
        if ax not in mesh.axis_names:
            raise ValueError(f"sharded schemes need a {ax!r} mesh axis; "
                             f"got {mesh.axis_names} (use "
                             f"launch.mesh.make_inl_host_mesh)")
    n_c = axis_size(mesh, "client")
    if num_clients % n_c:
        raise ValueError(f"client axis {n_c} does not divide J="
                         f"{num_clients}; make_inl_host_mesh falls back to "
                         f"a replicated client axis for such J")


def _check_batch(batch: int, n_d: int):
    if batch % n_d:
        raise ValueError(f"batch {batch} not divisible by data axis {n_d}; "
                         f"pick a batch size divisible by the device count")


def _pmean(tree, axis: str):
    return jax.tree.map(lambda x: jax.lax.pmean(x, axis), tree)


def _psum(tree, axis: str):
    return jax.tree.map(lambda x: jax.lax.psum(x, axis), tree)


# ---------------------------------------------------------------------------
# INL: encoders sharded over 'client', batch over 'data', all_gather fan-in
# ---------------------------------------------------------------------------

def make_inl_sharded_round(cfg, mesh, optimizer, *, wire: str = "dense",
                           topology=None):
    """(state, views (1,J,B,H,W,C), labels (1,B), rng) -> (state, metrics),
    numerically matching core/inl.make_train_step on one device.

    A non-star `topology` swaps the fan-in for the graph execution
    (core/topology.graph_cut_and_ship): each node's cut runs per-shard at
    its first-hop width (group masks stay SPMD-uniform via the sharded
    `group_ids` input), the 'client' all_gather remains the one physical
    collective, and the per-edge re-encoding hops run replicated on the
    gathered buffer — values exactly the modeled multi-hop network's, so
    single-device parity holds at the same rtol as the star."""
    check_mesh(mesh, cfg.num_clients)
    wirefmt.resolve_wire(wire, cfg.link_bits)        # fail at build time
    topo = topology_lib.nontrivial(topology, cfg)
    topo_full = topology_lib.resolve(topology, cfg)
    faulty = linkfault.active(topo_full, cfg, train=True)
    J, s = cfg.num_clients, cfg.s
    n_c, n_d = axis_size(mesh, "client"), axis_size(mesh, "data")
    d_ax = "data"
    dt = paper_model.compute_dtype(cfg)
    if topo is None:
        gid_of_view = (0,) * J
    else:
        _, gid_of_view = topology_lib.first_hop_groups(topo, cfg)

    def local_grads(params, enc_state, views, labels, eps, masks, gids,
                    fmask):
        def obj_fn(p):
            p = paper_model.cast_compute(p, dt)
            (mu, logvar), new_st = jax.vmap(
                lambda pp, ss, v: paper_model.encoder_apply(
                    pp, ss, v, train=True, axis_name=d_ax)
            )(p.encoders, enc_state, views.astype(dt))
            # fusion-center fan-in: eq. (5)'s concat as a wire transfer —
            # dense values or packed codewords over the 'client' collective
            if topo is None:
                u, rate, u_all = wirefmt.cut_and_ship(
                    None, mu, logvar, eps=eps, link_bits=cfg.link_bits,
                    rate_estimator="sample", wire=wire, axis_name="client",
                    prior=p.priors or {})
            else:
                u, rate, u_all = topology_lib.graph_cut_and_ship(
                    topo, cfg, mu, logvar, eps, rate_estimator="sample",
                    wire=wire, prior=p.priors or {}, axis_name="client",
                    group_ids=gids)
            b_l = u.shape[1]
            if faulty:
                # fuse-what-arrived: the (J,) mask is replicated (drawn at
                # global scope from the round rng), so every shard fuses
                # the same survivors the single-device round does
                u_all = linkfault.partial_fuse(u_all, fmask)
            u_cat = jnp.moveaxis(u_all, 0, 1).reshape(b_l, J * u.shape[-1])
            joint = paper_model.decoder_apply(p.decoder, u_cat, train=True,
                                              drop_masks=masks)
            branch = paper_model.branch_heads_apply(p.decoder, u)
            ce_joint = losses.xent(joint, labels)
            ce_branch = jnp.stack([losses.xent(bl, labels) for bl in branch])
            rate_m = jnp.mean(rate, axis=1)                  # (J_local,)
            # 1/n_c on the replicated joint term: the all_gather transpose
            # psums the n_c identical cotangents back to full strength
            obj = ce_joint / n_c + s * (jnp.sum(ce_branch) + jnp.sum(rate_m))
            return obj, (ce_joint, jnp.sum(ce_branch), jnp.sum(rate_m),
                         joint, new_st)
        grads, aux = jax.grad(obj_fn, has_aux=True)(params)
        ce_joint, ce_b_sum, rate_sum, joint, new_st = aux
        # decoder dense grads carried 1/n_c each: restore via psum('client')
        grads = INLParams(
            grads.encoders,
            {"dense": _psum(grads.decoder["dense"], "client"),
             "branch_heads": grads.decoder["branch_heads"]},
            grads.priors)
        grads = _pmean(grads, d_ax)                # global batch mean
        ce_joint_g = jax.lax.pmean(ce_joint, d_ax)
        ce_b_g = jax.lax.pmean(jax.lax.psum(ce_b_sum, "client"), d_ax)
        rate_g = jax.lax.pmean(jax.lax.psum(rate_sum, "client"), d_ax)
        metrics = {
            "loss": ce_joint_g + s * (ce_b_g + rate_g),
            "ce_joint": ce_joint_g,
            "ce_branch_mean": ce_b_g / J,
            "rate_mean": rate_g / J,
            "rate_total": rate_g,
            "accuracy": jax.lax.pmean(losses.accuracy(joint, labels), d_ax),
        }
        return grads, metrics, new_st

    def round_fn(state, views, labels, rng):
        params, mstate, opt_state = (state["params"], state["state"],
                                     state["opt"])
        views, labels = views[0], labels[0]
        B = labels.shape[0]
        _check_batch(B, n_d)
        # same split chain as core/inl.loss_fn: eps + dropout at global shape
        r_enc, r_dec = jax.random.split(rng)
        eps = jax.random.normal(r_enc, (J, B, cfg.d_bottleneck), jnp.float32)
        masks = paper_model.decoder_dropout_masks(r_dec, cfg.dense_units, B)
        # delivery mask from the round rng's FOLDED fault stream — the same
        # draw core/inl.loss_fn and the host-side meter replay
        fmask = (linkfault.round_delivery_mask(rng, topo_full, cfg, B,
                                               train=True)
                 if faulty else jnp.ones((J,), bool))

        c = P("client")
        p_specs = INLParams(c, {"dense": P(), "branch_heads": c}, c)
        grads, metrics, new_enc_st = shard_map(
            local_grads, mesh=mesh,
            in_specs=(p_specs, c, P("client", "data"), P("data"),
                      P("client", "data"), P("data"), c, P()),
            out_specs=(p_specs, P(), c),
            check_vma=False,
        )(params, mstate["encoders"], views, labels, eps, masks,
          jnp.asarray(gid_of_view, jnp.int32), fmask)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        if topo is None:
            p_total = J * cfg.d_bottleneck
            bits_sent = linkmodel.training_step_bits(B, p_total,
                                                     cfg.link_bits)
        else:
            bits_sent = topology_lib.round_bits(topo, cfg, B)
        metrics["bits_sent"] = jnp.asarray(bits_sent, jnp.float32)
        return ({"params": new_params, "state": {"encoders": new_enc_st},
                 "opt": new_opt}, metrics)
    return jax.jit(round_fn)


# ---------------------------------------------------------------------------
# FL: the J client replicas (params, opt state, local steps) over 'client'
# ---------------------------------------------------------------------------

def make_fl_sharded_round(cfg, mesh, optimizer, local_steps: int, *,
                          topology=None):
    """FedAvg round with the per-client local-step scans running in parallel
    across the 'client' axis; server aggregation is one psum.  The weight
    exchange stays fp32 whatever the wire format (quantizing FedAvg updates
    changes the algorithm); cfg.compute_dtype still applies inside each
    client's local steps.

    When the (star) topology carries LinkModels or cfg.edge_dropout > 0,
    each round draws the same (J,) client delivery mask the single-device
    round does (core/linkfault.client_delivery_mask on the round rng) and
    the psum average runs over the uploads that arrived — all lost keeps
    the previous global model.  An all-ones mask divides by exactly J, so
    a modelled-perfect network stays bitwise on the legacy trajectory."""
    from repro.core import fl
    check_mesh(mesh, cfg.num_clients)
    J = cfg.num_clients
    topo_full = topology_lib.resolve(topology, cfg)
    faulty = linkfault.active(topo_full, cfg, train=True)
    one_client = fl.make_one_client(
        optimizer, compute_dtype=getattr(cfg, "compute_dtype", "fp32"))

    def local_round(params, mstate, opt_state, views, labels, rngs, mask):
        p, st, opt, m = jax.vmap(one_client)(params, mstate, opt_state,
                                             views, labels, rngs)
        j_l = labels.shape[0]
        if not faulty:
            # server aggregation: mean over ALL J clients = psum of local sums
            avg = jax.tree.map(
                lambda x: jax.lax.psum(jnp.sum(x, axis=0), "client") / J, p)
        else:
            w = mask.astype(jnp.float32)
            n = jax.lax.psum(jnp.sum(w), "client")

            def masked_avg(x, old):
                wx = w.reshape((j_l,) + (1,) * (x.ndim - 1))
                s = jax.lax.psum(jnp.sum(x * wx, axis=0), "client")
                return jnp.where(n > 0, s / jnp.maximum(n, 1.0),
                                 old[0].astype(x.dtype))

            avg = jax.tree.map(masked_avg, p, params)
        p_new = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (j_l,) + x.shape), avg)
        metrics = jax.tree.map(
            lambda x: jax.lax.psum(jnp.sum(x, axis=0), "client") / J, m)
        return p_new, st, opt, metrics

    sharded = shard_map(
        local_round, mesh=mesh,
        in_specs=(P("client"), P("client"), P("client"), P("client"),
                  P("client"), P("client"), P("client")),
        out_specs=(P("client"), P("client"), P("client"), P()),
        check_vma=False)

    def round_fn(state, views, labels, rng):
        # identical packing to FLScheme.make_round's single-device path
        ls = local_steps
        R, Jv, B = views.shape[:3]
        v5 = views.reshape((J, ls) + views.shape[1:])
        own = v5[jnp.arange(J)[:, None], jnp.arange(ls)[None, :],
                 jnp.arange(J)[:, None]]
        packed = jnp.broadcast_to(own[:, :, None],
                                  (J, ls, J) + own.shape[2:])
        lab = labels.reshape(J, ls, B)
        rngs = jax.random.split(rng, J)
        mask = (linkfault.client_delivery_mask(rng, topo_full, cfg,
                                               train=True)
                if faulty else jnp.ones((J,), bool))
        p, st, opt, metrics = sharded(state["params"], state["state"],
                                      state["opt"], packed, lab, rngs, mask)
        return ({"params": p, "state": st, "opt": opt}, metrics)
    return jax.jit(round_fn)


# ---------------------------------------------------------------------------
# SL: client/server split is sequential by construction; the batch shards
# ---------------------------------------------------------------------------

def make_sl_sharded_round(cfg, mesh, opt_client, opt_server, *,
                          wire: str = "dense"):
    """One SL client->server->client exchange with the minibatch sharded
    over 'data' (the J conv branches all live client-side, so 'client' only
    replicates); grads are pmean'ed back to the exact global-batch values.
    The cut crossing honours `wire` (packed codewords are a per-row
    re-encoding, so any batch sharding sees identical values)."""
    check_mesh(mesh, cfg.num_clients)
    wirefmt.resolve_wire(wire, cfg.link_bits)
    n_d = axis_size(mesh, "data")
    d_ax = "data"
    dt = paper_model.compute_dtype(cfg)

    def local_grads(client, server, mstate, views, labels, masks):
        def obj_fn(cs):
            cl, srv = cs
            cl = paper_model.cast_compute(cl, dt)
            srv = paper_model.cast_compute(srv, dt)
            mus, lvs, new_states = [], [], []
            for j, (ep, es) in enumerate(zip(cl["encoders"],
                                             mstate["encoders"])):
                (mu, lv), ns = paper_model.encoder_apply(
                    ep, es, views[j].astype(dt), train=True, axis_name=d_ax)
                mus.append(mu)
                lvs.append(lv)
                new_states.append(ns)
            u, _ = ops.cutlayer(jnp.stack(mus), jnp.stack(lvs),
                                jnp.zeros((len(mus),) + mus[0].shape,
                                          jnp.float32),
                                link_bits=cfg.link_bits,
                                rate_estimator="none")
            u_w = wirefmt.ship(u, link_bits=cfg.link_bits, wire=wire)
            j, b_l, d = u_w.shape
            u_cat = jnp.moveaxis(u_w, 0, 1).reshape(b_l, j * d)
            logits = paper_model.decoder_apply(srv["decoder"], u_cat,
                                               train=True, drop_masks=masks)
            loss = losses.xent(logits, labels)
            return loss, (logits, {"encoders": new_states})
        (loss, (logits, new_state)), grads = jax.value_and_grad(
            obj_fn, has_aux=True)((client, server))
        g_client, g_server = _pmean(grads, d_ax)
        metrics = {"loss": jax.lax.pmean(loss, d_ax),
                   "accuracy": jax.lax.pmean(
                       losses.accuracy(logits, labels), d_ax)}
        return g_client, g_server, metrics, new_state

    def round_fn(state, views, labels, rng):
        views, labels = views[0], labels[0]
        B = labels.shape[0]
        _check_batch(B, n_d)
        masks = paper_model.decoder_dropout_masks(rng, cfg.dense_units, B)
        g_c, g_s, metrics, new_state = shard_map(
            local_grads, mesh=mesh,
            in_specs=(P(), P(), P(), P(None, "data"), P("data"), P("data")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )(state["client"], state["server"], state["state"], views, labels,
          masks)
        new_client, new_opt_c = opt_client.update(g_c, state["opt_c"],
                                                  state["client"])
        new_server, new_opt_s = opt_server.update(g_s, state["opt_s"],
                                                  state["server"])
        return ({"client": new_client, "server": new_server,
                 "state": new_state, "opt_c": new_opt_c,
                 "opt_s": new_opt_s}, metrics)
    return jax.jit(round_fn)
