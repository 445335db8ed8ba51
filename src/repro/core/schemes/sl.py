"""Split learning behind the unified Scheme API (wraps core/sl.py).

One round == one client->server->client exchange on a minibatch: the
client-side conv branches emit deterministic cut-layer activations through
the fused kernel's no-noise mode, the server decoder computes the loss, and
the custom VJP returns the cut-layer error vector.  Per §III-C the epoch
cost is (2 p q + eta N J) s — the activation/error traffic accrues per
round, the J sequential client->client weight hand-offs once per epoch.
"""
from __future__ import annotations

import jax

from repro import optim
from repro.core import bandwidth, linkfault, paper_model, sl, wirefmt
from repro.core import schemes as _schemes
from repro.core import topology as topology_lib
from repro.core.schemes import base


@_schemes.register
class SLScheme(base.Scheme):
    name = "sl"
    # bounded retry on the single client->server uplink: a round runs iff
    # one of (1 + max_link_retries) attempts survives the link's erasure
    # draw; otherwise the round is SKIPPED (state carried unchanged) — SL
    # has no partial-fusion reading.  Every attempt is charged as offered
    # bandwidth (linkfault.round_fault_charges).
    max_link_retries = 2

    def init(self, cfg, key, *, lr: float = 2e-3):
        (client, server), state = sl.init(cfg, key)
        oc, osrv = optim.adam(lr), optim.adam(lr)
        return {"client": client, "server": server, "state": state,
                "opt_c": oc.init(client), "opt_s": osrv.init(server)}

    def _skip_failed_round(self, cfg, topology, round_fn):
        """Wrap a round with SL's whole-round skip: the exchange rides ONE
        client->server boundary, so it has no partial reading.  A given
        (J,) delivery mask lets the round run iff every link delivered
        (the transport already spent the retry budget); without one, a
        star that models unreliable links draws the bounded-retry survival
        from the round rng.  On failure the state carries through
        UNCHANGED and the whole round is lost."""
        import jax.numpy as jnp
        topo_full = topology_lib.resolve(topology, cfg)
        faulty = linkfault.active(topo_full, cfg, train=True)
        attempts = self.max_link_retries + 1

        def skipping_round(state, views, labels, rng, delivery=None):
            new_state, metrics = round_fn(state, views, labels, rng)
            if delivery is not None:
                ok = jnp.all(delivery)
            elif faulty:
                ok = linkfault.round_success(rng, topo_full, cfg, attempts)
            else:
                return new_state, metrics
            new_state = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                     new_state, state)
            return new_state, metrics
        return skipping_round

    def make_round(self, cfg, *, lr: float = 2e-3, wire: str = "dense",
                   topology=None):
        # SL's cut is ONE client->server boundary (all conv branches live on
        # the active client), so only the star topology has a reading here
        topology_lib.require_star(topology, cfg, scheme=self.name)
        oc, osrv = optim.adam(lr), optim.adam(lr)
        step = sl.make_train_step(
            oc, osrv, link_bits=cfg.link_bits, wire=wire,
            compute_dtype=getattr(cfg, "compute_dtype", "fp32"))

        def round_fn(state, views, labels, rng):
            client, server, st, opt_c, opt_s, metrics = step(
                state["client"], state["server"], state["state"],
                state["opt_c"], state["opt_s"], views[0], labels[0], rng)
            return ({"client": client, "server": server, "state": st,
                     "opt_c": opt_c, "opt_s": opt_s}, metrics)
        return self._skip_failed_round(cfg, topology, round_fn)

    def make_sharded_round(self, cfg, mesh, *, lr: float = 2e-3,
                           wire: str = "dense", topology=None):
        # SL is sequential client/server by construction; the batch shards
        # over 'data' (params replicated — the base state_shardings default)
        from repro.core import sharded
        topology_lib.require_star(topology, cfg, scheme=self.name)
        inner = sharded.make_sl_sharded_round(cfg, mesh, optim.adam(lr),
                                              optim.adam(lr), wire=wire)
        return self._skip_failed_round(cfg, topology, inner)

    def predict(self, state, views, topology=None, cfg=None):
        return sl.predict(state["client"], state["server"], state["state"],
                          views)

    def bits_per_round(self, cfg, state, batch_size: int, *,
                       topology=None) -> float:
        topology_lib.require_star(topology, cfg, scheme=self.name)
        # activation/error traffic only (eta = 0 cancels the hand-off term)
        p = cfg.num_clients * cfg.d_bottleneck
        N = paper_model.fl_param_count(cfg)
        return bandwidth.sl_epoch_bits(p, batch_size, N, cfg.num_clients,
                                       0.0, cfg.link_bits)

    def epoch_overhead_bits(self, cfg, state) -> float:
        # q = 0 isolates the eta*N*J hand-off term; eta*N == client params
        p = cfg.num_clients * cfg.d_bottleneck
        N = paper_model.fl_param_count(cfg)
        eta = self.param_count(state["client"]) / N
        return bandwidth.sl_epoch_bits(p, 0, N, cfg.num_clients, eta,
                                       cfg.link_bits)

    def wire_bytes_per_round(self, cfg, state, batch_size: int, *,
                             wire: str = "dense", topology=None) -> float:
        # J*B deterministic cut d_b-vectors to the server, error vectors
        # back — same per-vector wire encoding as INL's exchange
        return wirefmt.round_wire_bytes(
            cfg.num_clients * batch_size, cfg.d_bottleneck,
            link_bits=cfg.link_bits, wire=wire,
            dtype=paper_model.compute_dtype(cfg))["total"]

    def epoch_overhead_wire_bytes(self, cfg, state) -> float:
        # the J sequential client->client hand-offs each move the actual
        # client-side param buffers (fp32 master weights — the wire format
        # does not quantize weight transfers)
        import jax
        client_nbytes = sum(x.size * x.dtype.itemsize
                            for x in jax.tree.leaves(state["client"]))
        return float(client_nbytes * cfg.num_clients)
