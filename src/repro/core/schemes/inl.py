"""In-network learning behind the unified Scheme API (wraps core/inl.py).

One round == one eq.-(6) optimizer step on a (J, B) multi-view batch; the
cut layer (sample + link quantizer + rate, learned priors included) is the
fused kernel.  Bandwidth per round is the paper's 2 b p s — activations
forward, eq.-(10) error vectors backward — expressed through the Table-I
closed form so measured and published accounting share one source.

INL is the scheme the network GRAPH belongs to: `topology=` compiles
non-star graphs (chains, trees, heterogeneous per-edge widths) to
multi-hop execution (core/topology.graph_cut_and_ship) and decomposes both
bandwidth ledgers per edge (`edge_ledger`), each edge charged for the
payload it carries.  The default star keeps every path bit-identical to
the pre-topology code.
"""
from __future__ import annotations

from repro import optim
from repro.core import bandwidth, inl, linkfault, paper_model, wirefmt
from repro.core import schemes as _schemes
from repro.core import topology as topology_lib
from repro.core.schemes import base


@_schemes.register
class INLScheme(base.Scheme):
    name = "inl"

    def init(self, cfg, key, *, lr: float = 2e-3):
        params, state = inl.init(cfg, key)
        opt = optim.adam(lr)
        return {"params": params, "state": state, "opt": opt.init(params)}

    def make_round(self, cfg, *, lr: float = 2e-3, wire: str = "dense",
                   topology=None):
        # a given (J,) delivery mask partial-fuses the surviving views
        # (renormalised by J/n): a lost one costs exactly its own vote —
        # rate terms and branch heads stay local, so a cut-off node keeps
        # training its encoder
        opt = optim.adam(lr)
        step = inl.make_train_step(cfg, opt, wire=wire, topology=topology)

        def round_fn(state, views, labels, rng, delivery=None):
            params, st, opt_state, metrics = step(
                state["params"], state["state"], state["opt"],
                views[0], labels[0], rng, delivery)
            return ({"params": params, "state": st, "opt": opt_state},
                    metrics)
        return round_fn

    def make_sharded_round(self, cfg, mesh, *, lr: float = 2e-3,
                           wire: str = "dense", topology=None):
        from repro.core import sharded
        return sharded.make_inl_sharded_round(cfg, mesh, optim.adam(lr),
                                              wire=wire, topology=topology)

    def state_shardings(self, cfg, state, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        cl = NamedSharding(mesh, P("client"))
        rep = NamedSharding(mesh, P())

        def param_sh(params):
            return inl.INLParams(
                jax.tree.map(lambda _: cl, params.encoders),
                {"dense": jax.tree.map(lambda _: rep,
                                       params.decoder["dense"]),
                 "branch_heads": jax.tree.map(
                     lambda _: cl, params.decoder["branch_heads"])},
                jax.tree.map(lambda _: cl, params.priors))

        p_sh = param_sh(state["params"])
        return {"params": p_sh,
                "state": jax.tree.map(lambda _: cl, state["state"]),
                "opt": {k: (rep if k == "step" else p_sh)
                        for k in state["opt"]}}

    def predict(self, state, views, topology=None, cfg=None):
        return inl.predict(state["params"], state["state"], views,
                           cfg=cfg, topology=topology)

    def predict_batched(self, state, views, *, delivery=None, topology=None,
                        cfg=None, wire: str = "dense"):
        # the serving-plane entry: per-request partial fusion (delivery is
        # the (J, B) fuse-what-arrived mask) with the engine's wire format
        # threaded through the graph hops.  delivery=None reproduces
        # `predict` bit for bit — the bucket-padding parity contract.
        return inl.predict(state["params"], state["state"], views, cfg=cfg,
                           topology=topology, delivery=delivery, wire=wire)

    def predict_under_faults(self, state, views, key, topology=None,
                             cfg=None):
        # INL degrades per VIEW, not per request: each sample draws its own
        # (J,) route-survival mask and the fusion center renormalises over
        # the latents that arrived (linkfault.partial_fuse) — a lost link
        # costs one vote, not the prediction
        topo_full = topology_lib.resolve(topology, cfg)
        delivery = linkfault.sample_delivery_mask(key, topo_full, cfg,
                                                  views.shape[1])
        return inl.predict(state["params"], state["state"], views,
                           cfg=cfg, topology=topology, delivery=delivery)

    def bits_per_round(self, cfg, state, batch_size: int, *,
                       topology=None) -> float:
        topo = topology_lib.nontrivial(topology, cfg)
        if topo is not None:
            return topology_lib.round_bits(topo, cfg, batch_size)
        # §III-C: each of the J nodes holds q/J of the round's q = b*J
        # node-points and sends p/J = d_bottleneck values per point, both
        # directions -> 2 b p s with p = J * d_bottleneck.
        p = cfg.num_clients * cfg.d_bottleneck
        return bandwidth.inl_epoch_bits(p, batch_size * cfg.num_clients,
                                        cfg.num_clients, cfg.link_bits)

    def wire_bytes_per_round(self, cfg, state, batch_size: int, *,
                             wire: str = "dense", topology=None) -> float:
        topo = topology_lib.nontrivial(topology, cfg)
        if topo is not None:
            return topology_lib.round_wire_bytes(topo, cfg, batch_size,
                                                 wire=wire)
        # the round's exchange is J*B latent d_b-vectors forward and their
        # eq.-(10) error chunks back, at the sizes wirefmt actually ships
        return wirefmt.round_wire_bytes(
            cfg.num_clients * batch_size, cfg.d_bottleneck,
            link_bits=cfg.link_bits, wire=wire,
            dtype=paper_model.compute_dtype(cfg))["total"]

    def edge_ledger(self, cfg, state, batch_size: int, *,
                    wire: str = "dense", topology=None):
        # always decomposable for INL — the star is J single-latent edges
        # whose charges sum to the Table-I totals exactly
        topo = topology_lib.resolve(topology, cfg)
        bits = topology_lib.round_edge_bits(topo, cfg, batch_size)
        nbytes = topology_lib.round_edge_wire_bytes(topo, cfg, batch_size,
                                                    wire=wire)
        return {k: (bits[k], nbytes[k]) for k in bits}
