"""Unified Scheme API — the paper's three-way comparison as a subsystem.

The paper's headline claim (Figs. 5/7, Table I) is a COMPARISON: in-network
learning beats federated and split learning on accuracy per epoch AND per
bit exchanged.  That comparison is only meaningful when all three schemes
run on the same measured substrate, so this package makes the harness
first-class: every scheme sits behind one `Scheme` interface

    init(cfg, key, *, lr)        -> opaque state pytree (params + opt state)
    make_round(cfg, *, lr)       -> round_fn(state, views, labels, rng,
                                    delivery=None) -> (state, metrics);
                                    delivery is the round's (J,) fault
                                    outcome, None to draw it in-graph
    predict(state, views)        -> class probabilities (B, C), rows sum to 1
    bits_per_round(cfg, state, batch_size)
                                 -> bits moved by ONE round, via the
                                    closed-form §III-C / Table-I accounting
                                    in core/bandwidth.py
    epoch_overhead_bits(cfg, state)
                                 -> bits charged once per epoch (split
                                    learning's client->client weight
                                    hand-offs; 0 for the others)

and every cut-layer exchange — INL's stochastic bottleneck, SL's
deterministic activations, FL's in-model branch latents — runs through the
SAME fused kernel (`kernels/ops.cutlayer`).  Every entry point also takes
`topology=` (core/topology.py): the network graph the exchange routes
over — star by default (bit-identical to the pre-topology paths), chains/
trees/arbitrary single-sink DAGs for INL, with per-edge link widths, wire
formats and a per-edge bandwidth ledger.  See the "Topologies" section of
core/schemes/README.md.

Registering a new scheme
------------------------
Subclass `base.Scheme`, implement the five methods above, and register an
instance:

    from repro.core import schemes
    from repro.core.schemes import base

    @schemes.register
    class MyScheme(base.Scheme):
        name = "my-scheme"
        ...                         # the five methods; optionally override
                                    # batches_per_round(cfg) (default 1)

`schemes.get("my-scheme")` then returns it, and the registry-driven runner
(`schemes.runner.run_scheme`), `benchmarks/accuracy_curves.py`, and
`examples/compare_schemes.py` pick it up with zero further glue — a new
scheme variant is a ~100-line plugin, not a fork of the benchmark loop.
See core/schemes/README.md for a walk-through.
"""
from __future__ import annotations

from repro.core.schemes.base import Scheme  # noqa: F401  (public API)

_REGISTRY: dict = {}


def register(cls):
    """Class decorator: instantiate and register a Scheme under cls.name."""
    inst = cls()
    if not inst.name:
        raise ValueError(f"{cls.__name__} must set a non-empty .name")
    _REGISTRY[inst.name] = inst
    return cls


def get(name: str) -> Scheme:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown scheme {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def available():
    """Registered scheme names, INL first (the paper's ordering)."""
    order = {"inl": 0, "sl": 1, "fl": 2}
    return tuple(sorted(_REGISTRY, key=lambda n: (order.get(n, 99), n)))


# importing the built-in schemes self-registers them
from repro.core.schemes import fl, hybrid, inl, runner, sl, \
    splitfed  # noqa: E402,F401
