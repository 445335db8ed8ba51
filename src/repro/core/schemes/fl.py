"""Federated learning (FedAvg) behind the unified Scheme API (wraps
core/fl.py).

One round == one FedAvg round: each of the J clients takes `local_steps`
optimizer steps on its own minibatches, then the server averages weights
and re-broadcasts — so one round consumes J * local_steps minibatches and
moves 2 N J s bits (full weights down + up, Table I).  Per the paper's
Exp-2 setting, client j only observes its own noise level: its view of the
batch images is broadcast to all J branch inputs of the full Fig.-4 model.
Inference is central: the aggregated model on the average-quality view.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import optim
from repro.core import bandwidth, fl, linkfault, paper_model
from repro.core import schemes as _schemes
from repro.core import topology as topology_lib
from repro.core.schemes import base
from repro.data import multiview


def _pack_exp2_views(views, labels, J: int, ls: int):
    """(R, J, B, ...) round views -> FedAvg packing: client j takes
    minibatches [j*ls, (j+1)*ls) and sees only ITS view of them, broadcast
    to the model's J branch inputs (paper Exp-2).  Returns
    ((J, ls, J, B, ...) views, (J, ls, B) labels)."""
    B = views.shape[2]
    v5 = views.reshape((J, ls) + views.shape[1:])
    own = v5[jnp.arange(J)[:, None], jnp.arange(ls)[None, :],
             jnp.arange(J)[:, None]]               # (J, ls, B, ...)
    packed = jnp.broadcast_to(own[:, :, None], (J, ls, J) + own.shape[2:])
    return packed, labels.reshape(J, ls, B)


@_schemes.register
class FLScheme(base.Scheme):
    name = "fl"
    local_steps = 2

    def batches_per_round(self, cfg) -> int:
        return cfg.num_clients * self.local_steps

    def init(self, cfg, key, *, lr: float = 2e-3):
        params, state = fl.init(cfg, key)
        opt = optim.adam(lr)
        return {"params": params, "state": state,
                "opt": jax.vmap(opt.init)(params)}

    def make_round(self, cfg, *, lr: float = 2e-3, wire: str = "dense",
                   topology=None):
        # FL has no cut-layer exchange: the wire carries full fp32 weights
        # (quantized FedAvg would be a different algorithm), so `wire` is
        # accepted for interface parity and ignored; the weight exchange is
        # a client<->server star by definition, so non-star topologies are
        # rejected up front.  A (J,) delivery mask is the set of client
        # uploads that ARRIVED: missing clients are dropped from the FedAvg
        # average and their whole round of local work is lost (all lost
        # keeps the previous global model).  Without one, a star whose
        # edges carry LinkModels (or cfg.edge_dropout > 0) draws the mask
        # in-graph (core/linkfault.client_delivery_mask).
        topology_lib.require_star(topology, cfg, scheme=self.name)
        topo_full = topology_lib.resolve(topology, cfg)
        faulty = linkfault.active(topo_full, cfg, train=True)
        opt = optim.adam(lr)
        J, ls = cfg.num_clients, self.local_steps
        round_impl = fl.make_round(cfg, opt, ls)
        masked_impl = fl.make_round(cfg, opt, ls, faulty=True)

        @jax.jit
        def round_fn(state, views, labels, rng, delivery=None):
            packed, lab = _pack_exp2_views(views, labels, J, ls)
            rngs = jax.random.split(rng, J)
            args = (state["params"], state["state"], state["opt"],
                    packed, lab, rngs)
            if delivery is None and faulty:
                delivery = linkfault.client_delivery_mask(rng, topo_full, cfg,
                                                          train=True)
            if delivery is None:
                params, st, opt_state, metrics = round_impl(*args)
            else:
                params, st, opt_state, metrics = masked_impl(*args, delivery)
            return ({"params": params, "state": st, "opt": opt_state},
                    metrics)
        return round_fn

    def make_sharded_round(self, cfg, mesh, *, lr: float = 2e-3,
                           wire: str = "dense", topology=None):
        from repro.core import sharded
        topology_lib.require_star(topology, cfg, scheme=self.name)
        return sharded.make_fl_sharded_round(cfg, mesh, optim.adam(lr),
                                             self.local_steps,
                                             topology=topology)

    def state_shardings(self, cfg, state, mesh):
        # every FL state leaf is a stacked per-client replica (leading J):
        # params, model state, and the vmapped optimizer state all shard
        # over 'client'
        from jax.sharding import NamedSharding, PartitionSpec as P
        cl = NamedSharding(mesh, P("client"))
        return jax.tree.map(lambda _: cl, state)

    def predict(self, state, views, topology=None, cfg=None):
        # FL inference is central: aggregated model, average-quality view
        return fl.predict(state["params"], state["state"],
                          multiview.average_view(views))

    def bits_per_round(self, cfg, state, batch_size: int, *,
                       topology=None) -> float:
        topology_lib.require_star(topology, cfg, scheme=self.name)
        N = paper_model.fl_param_count(cfg)
        return bandwidth.fl_round_bits(N, cfg.num_clients, cfg.link_bits)

    def wire_bytes_per_round(self, cfg, state, batch_size: int, *,
                             wire: str = "dense", topology=None) -> float:
        # weights down + weights up for every client, at the buffers'
        # actual (fp32 master) sizes — FL keeps a full-precision exchange
        # regardless of the wire format
        stacked_nbytes = sum(x.size * x.dtype.itemsize
                             for x in jax.tree.leaves(state["params"]))
        return float(2 * stacked_nbytes)      # leading J axis = per client
