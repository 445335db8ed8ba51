"""The `Scheme` interface all registered training schemes implement.

A scheme's `state` is an opaque pytree (dict) bundling its parameters,
model state (e.g. BatchNorm running stats) and optimizer state(s); only the
scheme itself looks inside.  The runner interacts purely through the
interface, so schemes with wildly different structure (INL's stacked
encoders, FL's per-client model copies, SL's client/server split) drive the
same benchmark loop.

Rounds vs batches: a "round" is the scheme's natural training transaction —
one optimizer step for INL/SL, one full FedAvg round (local steps on every
client + server aggregation) for FL.  `batches_per_round` tells the runner
how many (views, labels) minibatches to stack into one round call; the
round receives them as (R, J, B, ...) / (R, B) arrays.

Bandwidth: `bits_per_round` must route through the closed-form §III-C /
Table-I accounting in `core/bandwidth.py` (tests/test_scheme_parity.py
asserts exact agreement), so the measured curves and the published formulas
cannot drift apart.

Topology: every entry point accepts `topology=` (a core/topology.Topology;
None resolves to cfg.topology, then the implicit `star(cfg.num_clients)`).
The default star dispatches to the pre-topology code paths bit for bit;
INL compiles non-star graphs to multi-hop execution and per-edge
accounting (`edge_ledger`), while schemes whose exchange has no multi-hop
reading validate the topology is a star and raise otherwise.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax


class Scheme:
    """Base class: override `init`, `make_round`, `predict`,
    `bits_per_round` and `wire_bytes_per_round` (the rest have defaults);
    keep `state` a pure pytree."""

    name: str = ""

    def batches_per_round(self, cfg) -> int:
        """Minibatches one round consumes (the runner stacks this many)."""
        return 1

    def init(self, cfg, key, *, lr: float = 2e-3) -> Any:
        """Build params + optimizer state for `cfg` (PaperExperimentConfig).

        Must be deterministic in `key`; `lr` must match `make_round`'s."""
        raise NotImplementedError

    def make_round(self, cfg, *, lr: float = 2e-3, wire: str = "dense",
                   topology=None):
        """Return round_fn(state, views, labels, rng, delivery=None) ->
        (new_state, metrics) with views (R, J, B, H, W, C), labels (R, B),
        R == batches_per_round(cfg).  metrics must include "loss".

        delivery — the round's fault outcome as a (J,) boolean mask, given
        as data: the runner passes the transport's measured verdict
        (repro/transport.NetworkTransport.round_outcome — retries, circuit
        breakers and chaos already applied).  None draws the mask in-graph
        from `rng` when the topology's links are unreliable
        (core/linkfault.py), and is a clean round otherwise.  Each scheme
        applies its own degradation to the same mask: INL partial-fuses
        the surviving views (one vote lost per failed route), FL drops the
        missing clients from the FedAvg average (their whole round of
        local work lost), SL carries the state through unchanged unless
        every link delivered (the whole round lost).

        wire — the cut-layer link format (core/wirefmt.py): "dense" moves
        quantized values at their storage dtype (the golden baseline),
        "packed" moves bit-packed codewords (trajectory bit-identical),
        "packed_duplex" packs the backward error vectors too.  Schemes
        without a cut-layer exchange (FL's weight transfer) ignore it.

        topology — the inference graph (core/topology.py); the default
        star keeps the pre-topology round bit for bit."""
        raise NotImplementedError

    def make_sharded_round(self, cfg, mesh, *, lr: float = 2e-3,
                           wire: str = "dense", topology=None):
        """Round with the same signature/semantics as make_round's, executed
        across a ('client', 'data') mesh via shard_map (core/sharded.py):
        the J client branches on 'client', the batch on 'data'.  Must match
        the single-device round's trajectory at rtol 1e-4 (bit-exact for
        wire="packed" vs "dense" — packing is a re-encoding)."""
        raise NotImplementedError(f"scheme {self.name!r} has no sharded "
                                  "round")

    def make_epoch(self, cfg, *, lr: float = 2e-3, mesh=None, donate=None,
                   wire: str = "dense", topology=None):
        """K rounds in ONE jitted lax.scan — the whole-epoch dispatch unit.

        Returns epoch_fn(state, views, labels, rngs) -> (state, metrics)
        with views (K, R, J, B, ...), labels (K, R, B), rngs (K,) PRNG keys
        (one per round, the same chain the per-round path splits), and
        metrics stacked (K,) leaves.  mesh switches the body to the
        shard_map round; wire selects the cut-layer link format and
        topology the inference graph for every round in the scan.
        donate=None donates (params/opt buffers reused in-place) on
        accelerators only — CPU XLA cannot alias and would warn."""
        round_fn = (self.make_sharded_round(cfg, mesh, lr=lr, wire=wire,
                                            topology=topology)
                    if mesh is not None
                    else self.make_round(cfg, lr=lr, wire=wire,
                                         topology=topology))

        def epoch_fn(state, views, labels, rngs):
            def body(st, xs):
                v, lab, r = xs
                st, metrics = round_fn(st, v, lab, r)
                return st, metrics
            return jax.lax.scan(body, state, (views, labels, rngs))

        if donate is None:
            donate = jax.default_backend() != "cpu"
        return jax.jit(epoch_fn, donate_argnums=(0,) if donate else ())

    def state_shardings(self, cfg, state, mesh):
        """NamedSharding layout for this scheme's state on `mesh` (leading-J
        leaves on 'client' where the sharded round expects them).  Default:
        fully replicated."""
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())
        return jax.tree.map(lambda _: rep, state)

    def predict(self, state, views, topology=None, cfg=None) -> Any:
        """views (J, B, ...) -> class probabilities (B, C); rows sum to 1.

        Each scheme applies its own inference convention (INL: deterministic
        latents, routed through the topology's hops when one is given — that
        path needs `cfg` for the edge-width defaults; FL: central model on
        the average-quality view; SL: client forward + server decoder)."""
        raise NotImplementedError

    # serving bucket sizes (repro/serving): in-flight requests are padded
    # to the smallest bucket, so the engine jits at most ONE predict per
    # bucket size — no retracing under request churn
    serve_buckets: Tuple[int, ...] = (1, 4, 16, 64)

    def predict_batched(self, state, views, *, delivery=None, topology=None,
                        cfg=None, wire: str = "dense") -> Any:
        """The serving plane's batched inference entry (repro/serving):
        `predict` plus an optional (J,) or (J, B) per-request delivery mask
        and the serving wire format.

        delivery=None is the clean network and MUST equal `predict` bit for
        bit — the engine's bucket-padding parity test pins it.  Default
        masked semantics (single-uplink schemes: FL's central model, SL's
        one boundary): a request answers only if its whole uplink payload
        arrived — any dropped view degrades it to the uniform distribution.
        INL overrides with per-request partial fusion (a lost view costs
        one vote, not the request) and threads `wire` through its graph
        hops."""
        import jax.numpy as jnp
        from repro.core import linkfault
        probs = self.predict(state, views, topology=topology, cfg=cfg)
        if delivery is None:
            return probs
        ok = jnp.all(delivery, axis=0)
        return linkfault.degrade_probs(probs, ok)

    def predict_under_faults(self, state, views, key, topology=None,
                             cfg=None) -> Any:
        """`predict` when the topology's links are unreliable
        (core/linkfault.py): per-request fault draws from `key` decide what
        the decoding side actually receives.

        Default (FL's central model, SL's client->server boundary): the
        answer rides ONE uplink — requests whose erasure/deadline draw
        fails get the uninformative uniform distribution (the server
        answers, but not from this request's data).  INL overrides with
        per-sample partial fusion: only the views that failed are masked,
        the survivors still vote — the graceful-degradation gap the
        links benchmark measures.  A topology with no LinkModels (and no
        deadline) reduces to plain `predict` for every scheme."""
        from repro.core import linkfault
        from repro.core import topology as topology_lib
        probs = self.predict(state, views, topology=topology, cfg=cfg)
        topo = topology_lib.resolve(topology, cfg)
        ok = linkfault.request_survival(key, topo, cfg, views.shape[1])
        return linkfault.degrade_probs(probs, ok)

    def bits_per_round(self, cfg, state, batch_size: int, *,
                       topology=None) -> float:
        """Bits moved by ONE round, via core/bandwidth.py closed forms (a
        non-star topology sums its per-edge charges — identical for the
        star)."""
        raise NotImplementedError

    def epoch_overhead_bits(self, cfg, state) -> float:
        """Bits charged once per epoch on top of the per-round cost
        (split learning's sequential weight hand-offs).  Default 0."""
        return 0.0

    def wire_bytes_per_round(self, cfg, state, batch_size: int, *,
                             wire: str = "dense", topology=None) -> float:
        """MEASURED bytes one round actually puts on the wire under `wire`
        — the nbytes of the transmitted buffers (core/wirefmt.py derives
        them from the real wire ops), not the closed-form accounting.
        tests/test_scheme_parity.py asserts the two ledgers agree whenever
        the wire carries what the formulas charge (packed links, fp32
        weight exchanges)."""
        raise NotImplementedError

    def epoch_overhead_wire_bytes(self, cfg, state) -> float:
        """Measured bytes of the once-per-epoch transfers (SL's weight
        hand-offs: the actual nbytes of the client param buffers).
        Default 0."""
        return 0.0

    def edge_ledger(self, cfg, state, batch_size: int, *,
                    wire: str = "dense",
                    topology=None) -> Optional[Dict[str, Tuple[float,
                                                               float]]]:
        """Per-edge bandwidth of one round: {edge_key: (closed-form bits,
        measured wire bytes)}, summing to bits_per_round /
        wire_bytes_per_round exactly.  None (the default) for schemes whose
        exchange has no per-edge decomposition — the runner then meters
        totals only."""
        return None

    # -- conveniences shared by implementations ---------------------------

    @staticmethod
    def param_count(tree) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(tree))

    def __repr__(self):
        return f"<Scheme {self.name!r}>"


# One jitted predict per (scheme, topology, cfg): topology and cfg are
# closed over as statics (they change the traced graph), while state/views
# changes hit jax.jit's OWN cache — a new treedef or shape retraces, so
# switching cfgs mid-process can never reuse a stale closure (the former
# cache pinned the first-ever jitted predict on the registry singleton
# forever).  LRU-bounded so a config sweep (placement search over
# (topology, width) settings) cannot grow it monotonically.
_PREDICT_JIT: dict = {}
_PREDICT_JIT_CAP = 32


def evaluate_accuracy(scheme: Scheme, state, views, labels,
                      topology=None, cfg=None) -> float:
    """Shared top-1 accuracy via the scheme's own predict convention.

    The predict forward is jitted once per (scheme, topology, cfg) — the
    per-epoch eval in the runner would otherwise run the whole
    encoder/decoder stack op-by-op."""
    import jax.numpy as jnp
    key = (scheme.name, topology, cfg)
    jitted = _PREDICT_JIT.pop(key, None)
    if jitted is None:
        def _predict(st, v):
            return scheme.predict(st, v, topology=topology, cfg=cfg)
        jitted = jax.jit(_predict)
    _PREDICT_JIT[key] = jitted                   # most-recently-used last
    while len(_PREDICT_JIT) > _PREDICT_JIT_CAP:
        _PREDICT_JIT.pop(next(iter(_PREDICT_JIT)))
    probs = jitted(state, views)
    return float((jnp.argmax(probs, axis=-1) == labels).mean())


def evaluate_accuracy_under_faults(scheme: Scheme, state, views, labels,
                                   key, topology=None, cfg=None) -> float:
    """Top-1 accuracy through `predict_under_faults`: the per-request fault
    draws come from `key` (a PRNG key — vary it to average over network
    realisations).  Jitted per (scheme, topology, cfg) like
    evaluate_accuracy, with the key a traced argument."""
    import jax.numpy as jnp
    cache_key = ("faults", scheme.name, topology, cfg)
    jitted = _PREDICT_JIT.pop(cache_key, None)
    if jitted is None:
        def _predict(st, v, k):
            return scheme.predict_under_faults(st, v, k, topology=topology,
                                               cfg=cfg)
        jitted = jax.jit(_predict)
    _PREDICT_JIT[cache_key] = jitted
    while len(_PREDICT_JIT) > _PREDICT_JIT_CAP:
        _PREDICT_JIT.pop(next(iter(_PREDICT_JIT)))
    probs = jitted(state, views, key)
    return float((jnp.argmax(probs, axis=-1) == labels).mean())
