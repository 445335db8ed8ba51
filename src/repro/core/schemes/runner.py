"""Registry-driven training/benchmark runner — ONE loop for every scheme.

The scheme supplies init / round / predict / bandwidth through the Scheme
interface; this module supplies the epoch pipeline, minibatch grouping, the
BandwidthMeter, and the accuracy-vs-epoch / accuracy-vs-Gbit curve — so a
newly registered scheme benchmarks itself with zero extra glue.

Dispatch strategies (the perf ladder tests/benchmarks compare):

    "per_round"  the seed-style loop: one host->device transfer + one jitted
                 dispatch per round (kept as the benchmark baseline);
    "scan"       the default: the view set is placed on the device once,
                 each epoch's (K, R, b) index matrix, labels and round keys
                 move through the double-buffered prefetcher
                 (data/prefetch.py), ONE jitted gather on the device builds
                 the epoch's (K, R, J, b, ...) superbatch from the resident
                 set, and ONE jitted lax.scan (Scheme.make_epoch) runs it —
                 K rounds per dispatch instead of K dispatches.

`mesh` (a ('client', 'data') mesh from launch.mesh.make_inl_host_mesh /
make_inl_mesh) switches the scan body to the scheme's shard_map round
(core/sharded.py): J node branches in parallel over 'client', batch over
'data', state placed once via Scheme.state_shardings, the resident view set
replicated and each epoch's superbatch gathered straight into the batch
sharding.  Trajectories match the single-device run at
rtol 1e-4 (tests/test_sharded_parity.py).
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import checkpoint as checkpoint_lib
from repro import tracing
from repro.core import bandwidth, linkfault
from repro.core import topology as topology_lib
from repro.core.schemes import base
from repro.data import multiview, prefetch


class CurvePoint(NamedTuple):
    epoch: int
    accuracy: float
    gbits: float                 # cumulative ACCOUNTED bits (§III-C), Gbit
    measured_gbits: float = 0.0  # cumulative MEASURED wire-buffer bits, Gbit
    delivered_gbits: float = 0.0  # what actually reached its consumer, Gbit


@partial(jax.jit, static_argnums=1)
def _split_chain(key, n: int):
    """n sequential (key, sub) splits in one dispatch — the exact chain the
    per-round loop produces with repeated jax.random.split(rng)."""
    def body(k, _):
        k, sub = jax.random.split(k)
        return k, sub
    return jax.lax.scan(body, key, None, length=n)


@partial(jax.jit, static_argnums=1)
def _resident(views, n_eval: int):
    """(J, n, ...) views -> the resident (J, n, prod(...)) rows, one row per
    view, and the (J, n_eval, ...) evaluation slice.  Relaid one node's
    views at a time: a TPU keeps a (J, n, 32, 32, 3) set with the sample
    axis fastest, and relaying it whole needs temporaries of 2.6x the set."""
    rows = jax.lax.map(lambda v: v.reshape(v.shape[0], -1), views)
    return rows, views[:, :n_eval]


def _superbatch(rows, idx, *, image_shape):
    """The epoch's scan input from the resident (J, n, D) rows at the
    (K, R, b) index matrix: (K, R, J, b) + image_shape, an exact copy of
    `np.moveaxis(views[:, idx], 0, 2)`.  Whole rows are gathered, one
    round group at a time (lax.map), so each group's rows are relaid into
    the output in on-chip memory rather than through a copy of the epoch."""
    J, n, _ = rows.shape
    flat = rows.reshape(J * n, -1)
    offsets = (jnp.arange(J, dtype=idx.dtype) * n)[:, None]

    def group(ix):                     # (R, b) -> (R, J, b) + image_shape
        return flat[ix[:, None] + offsets].reshape(
            ix.shape[:1] + (J,) + ix.shape[1:] + image_shape)
    return jax.lax.map(group, idx)


def _round_charges(scheme, cfg, state, batch_size, *, wire, topology):
    """ONE round's bandwidth charges, computed once per run (they depend
    only on static shapes, and the measured side runs 2 eval_shape traces
    per edge — per-round recomputation would tax the per_round dispatch
    baseline): the per-edge ledger where the scheme decomposes its
    exchange over the topology's links (INL; per-edge charges sum to the
    totals exactly), else the scalar totals."""
    ledger = scheme.edge_ledger(cfg, state, batch_size, wire=wire,
                                topology=topology)
    if ledger is not None:
        return ledger
    return {None: (scheme.bits_per_round(cfg, state, batch_size,
                                         topology=topology),
                   scheme.wire_bytes_per_round(cfg, state, batch_size,
                                               wire=wire,
                                               topology=topology))}


def _meter_rounds(meter, charges, rounds=1, delivered=None):
    """Charge `rounds` rounds of `charges` as offered traffic, and
    `delivered` (defaults to the same charges — the fault-free case where
    everything offered arrives) on the delivered ledger."""
    for edge, (bits, nbytes) in charges.items():
        if edge is None:
            meter.add(rounds * bits)
            meter.add_measured(rounds * nbytes)
        else:
            meter.add_edge(edge, bits=rounds * bits, nbytes=rounds * nbytes)
    for edge, (bits, nbytes) in (charges if delivered is None
                                 else delivered).items():
        meter.add_delivered(bits=rounds * bits, nbytes=rounds * nbytes,
                            edge=edge)


def _meter_fault_rounds(meter, scheme, topo_full, cfg, batch_size, charges,
                        round_keys):
    """Per-round fault metering: replay each round key's fault draws
    (linkfault.round_fault_charges folds the SAME keys the in-graph masks
    consume) and split the round between the offered and delivered
    ledgers."""
    for sub in round_keys:
        off, dlv = linkfault.round_fault_charges(
            jnp.asarray(sub), scheme.name, topo_full, cfg, batch_size,
            charges)
        _meter_rounds(meter, off, delivered=dlv)


def _meter_dump(meter) -> dict:
    """The meter's full ledger state, JSON-serialisable (resume context)."""
    return {"total_bits": meter.total_bits,
            "measured_bytes": meter.measured_bytes,
            "delivered_bits": meter.delivered_bits,
            "delivered_measured_bytes": meter.delivered_measured_bytes,
            "edge_bits": dict(meter.edge_bits),
            "edge_measured_bytes": dict(meter.edge_measured_bytes),
            "edge_delivered_bits": dict(meter.edge_delivered_bits)}


def _meter_load(meter, d: dict) -> None:
    meter.total_bits = float(d["total_bits"])
    meter.measured_bytes = float(d["measured_bytes"])
    meter.delivered_bits = float(d["delivered_bits"])
    meter.delivered_measured_bytes = float(d["delivered_measured_bytes"])
    meter.edge_bits = {k: float(v) for k, v in d["edge_bits"].items()}
    meter.edge_measured_bytes = {k: float(v) for k, v
                                 in d["edge_measured_bytes"].items()}
    meter.edge_delivered_bits = {k: float(v) for k, v
                                 in d["edge_delivered_bits"].items()}


def _save_epoch(ckpt_dir, name, ep, state, curve, meter,
                transport=None) -> None:
    """One epoch-granular checkpoint: the FULL training state (params,
    model state, optimizer) plus the curve and both meter ledgers in the
    sidecar — everything a bit-identical resume needs (fp32/int leaves are
    npz-lossless; bf16 stores as fp32 and round-trips bitwise).  A
    transport run also records `transport.snapshot()` — breaker counters
    for the record, adaptive-policy state for restore — so resumed runs
    replay the same retry/threshold knob trajectory."""
    extra = {"scheme": name, "epoch": ep,
             "curve": [list(map(float, p)) for p in curve],
             "meter": _meter_dump(meter)}
    if transport is not None:
        extra["transport"] = transport.snapshot()
    checkpoint_lib.save(ckpt_dir, ep, jax.device_get(state), extra=extra)


def _try_resume(ckpt_dir, state, meter):
    """Restore the latest epoch checkpoint when one exists: returns
    (state, curve-so-far, epochs-already-done, transport-snapshot-or-None).
    A fresh directory resumes from nothing — epoch 0 with the given init
    state."""
    step = checkpoint_lib.latest_step(ckpt_dir) if ckpt_dir else None
    if step is None:
        return state, [], 0, None
    restored, _ = checkpoint_lib.restore(ckpt_dir, jax.device_get(state),
                                         step=step)
    meta = checkpoint_lib.load_meta(ckpt_dir, step)
    curve = [CurvePoint(int(p[0]), *map(float, p[1:]))
             for p in meta["curve"]]
    _meter_load(meter, meta["meter"])
    return restored, curve, int(meta["epoch"]), meta.get("transport")


def _meter_overheads(meter, scheme, cfg, state):
    """Once-per-epoch charges (SL's weight hand-offs ride a reliable
    control channel here — charged and delivered in full)."""
    bits = scheme.epoch_overhead_bits(cfg, state)
    nbytes = scheme.epoch_overhead_wire_bytes(cfg, state)
    meter.add(bits)
    meter.add_measured(nbytes)
    meter.add_delivered(bits=bits, nbytes=nbytes)


def rounds_per_epoch(scheme, cfg, n: int, batch_size: int) -> int:
    """Rounds one epoch of an n-sample set runs: full minibatches grouped
    by the scheme's batches_per_round.  Public because the search
    subsystem's closed-form pricing (repro/search/pricing.py) must charge
    EXACTLY the rounds the runner will execute — one rule, two callers."""
    return (n // batch_size) // scheme.batches_per_round(cfg)


def run_scheme(name: str, views, labels, cfg, *, epochs: int,
               batch_size: int = 64, lr: float = 2e-3, seed: int = 0,
               eval_n: int = 512, dispatch: str = "scan", mesh=None,
               prefetch_size: int = 2, wire: str = "dense",
               topology=None, meter=None, transport=None,
               ckpt_dir=None, ckpt_every: int = 1,
               resume: bool = False) -> List[CurvePoint]:
    """Train scheme `name` for `epochs` over the (J, n, ...) multi-view set
    and return its accuracy/bandwidth curve (paper Figs. 5/7 rows).

    Minibatches are grouped `batches_per_round(cfg)` at a time into round
    calls; a trailing partial group is dropped (same rounding the paper's
    per-epoch accounting uses).  Bandwidth accrues on TWO ledgers: the
    §III-C closed forms (`gbits`, as published) and the MEASURED nbytes of
    the buffers the chosen wire format actually transmits per round
    (`measured_gbits`; Scheme.wire_bytes_per_round via core/wirefmt.py) —
    per EDGE where the scheme decomposes its exchange over the topology's
    links (pass `meter=` a BandwidthMeter to read the per-edge ledgers
    afterwards).

    dispatch="scan" (default) runs each epoch as one jitted lax.scan.  It
    keeps the view set resident on the device (replicated over `mesh`) and
    gathers each epoch's superbatch there from the prefetched index matrix,
    so its device memory is the views' bytes plus one epoch superbatch;
    dispatch="per_round" (the seed-style loop, one dispatch per round)
    streams sets larger than that.  `mesh` enables shard_map execution (scan
    dispatch only).  wire="packed" moves the cut-layer collectives as
    bit-packed codewords (trajectories identical to dense);
    "packed_duplex" packs the backward error vectors too.  topology — a
    core/topology.Topology routing the INL exchange over a multi-hop graph
    (the default star reproduces the pre-topology behaviour bit for bit;
    FL/SL validate and reject non-star graphs).

    Elastic recovery: `ckpt_dir` saves an epoch-granular checkpoint every
    `ckpt_every` epochs (full state + curve + meter ledgers);
    `resume=True` restores the latest one and fast-forwards the data/rng
    streams, so the resumed trajectory is BIT-IDENTICAL to the
    uninterrupted run (tests/test_recovery.py pins it).

    transport — a repro/transport.NetworkTransport over the resolved
    topology: fault outcomes then come from the transport's retrying
    channels / breakers / chaos schedule per round instead of in-graph
    draws (Scheme.make_transport_round), metered on the transport's
    offered/delivered ledgers.  Transport execution is per-round
    (host-side masks), so it excludes mesh/scan dispatch.
    """
    from repro.core import schemes
    scheme = schemes.get(name)
    if transport is not None:
        if mesh is not None:
            raise ValueError("transport execution is per-round; no mesh")
        if meter is not None and meter is not transport.meter:
            raise ValueError("pass either meter= or transport= (the "
                             "transport owns the run's meter)")
        return _run_transport(scheme, views, labels, cfg, epochs=epochs,
                              batch_size=batch_size, lr=lr, seed=seed,
                              eval_n=eval_n, wire=wire, topology=topology,
                              transport=transport, ckpt_dir=ckpt_dir,
                              ckpt_every=ckpt_every, resume=resume)
    if dispatch == "per_round":
        if mesh is not None:
            raise ValueError("mesh execution needs dispatch='scan'")
        return _run_per_round(scheme, views, labels, cfg, epochs=epochs,
                              batch_size=batch_size, lr=lr, seed=seed,
                              eval_n=eval_n, wire=wire, topology=topology,
                              meter=meter, ckpt_dir=ckpt_dir,
                              ckpt_every=ckpt_every, resume=resume)
    if dispatch != "scan":
        raise ValueError(f"unknown dispatch {dispatch!r}")

    state = scheme.init(cfg, jax.random.PRNGKey(seed), lr=lr)
    epoch_fn = scheme.make_epoch(cfg, lr=lr, mesh=mesh, wire=wire,
                                 topology=topology)
    bpr = scheme.batches_per_round(cfg)
    labels_np = np.asarray(labels)
    n = labels_np.shape[0]
    rounds = rounds_per_epoch(scheme, cfg, n, batch_size)
    n_eval = min(eval_n, n)

    with tracing.span("runner.resident") as sp:
        resident, ev = _resident(views, n_eval)
        if mesh is not None:
            resident = jax.device_put(resident, NamedSharding(mesh, P()))
        sp.set_metadata(bytes=resident.nbytes)
    # one epoch's (K, R, J, b, ...) superbatch, in bytes
    superbatch_bytes = resident.nbytes // resident.shape[1] * (
        rounds * bpr * batch_size)

    xs_shardings = item_shardings = None
    if mesh is not None:
        from repro.launch import sharding as sharding_lib
        state = jax.device_put(state,
                               scheme.state_shardings(cfg, state, mesh))
        xs_shardings = sharding_lib.scheme_batch_shardings(
            mesh, cfg.num_clients, batch_size)
        # the index matrix is laid out as the labels are
        item_shardings = (xs_shardings[1],) + xs_shardings[1:]
    gather = jax.jit(_superbatch, static_argnames="image_shape",
                     out_shardings=None if mesh is None else xs_shardings[0])
    image_shape = tuple(views.shape[2:])

    meter = bandwidth.BandwidthMeter() if meter is None else meter
    start_ep = 0
    if resume and ckpt_dir:
        state, curve0, start_ep, _ = _try_resume(ckpt_dir, state, meter)
        if mesh is not None and start_ep:
            state = jax.device_put(state,
                                   scheme.state_shardings(cfg, state, mesh))
    else:
        curve0 = []

    def epoch_items():
        """(index matrix (K,R,b), labels (K,R,b), rngs (K,2)) per epoch —
        what the device needs to gather the epoch's superbatch from the
        resident views; the prefetcher moves it while the previous epoch
        computes.  A resumed run fast-forwards the rng chain through the
        completed epochs WITHOUT assembling their items — the downstream
        subkeys (and so the trajectory) are exactly the uninterrupted
        run's."""
        rng = jax.random.PRNGKey(seed + 1)
        for ep in range(epochs):
            rng, subs = _split_chain(rng, rounds)
            if ep < start_ep:
                continue
            with tracing.span("runner.assemble", rounds=rounds) as sp:
                idx = np.stack(list(multiview.batch_indices(
                    n, batch_size, seed=ep)))
                idx = idx[:rounds * bpr].reshape(
                    rounds, bpr, batch_size).astype(np.int32)
                item = (idx, labels_np[idx], subs)
                sp.set_metadata(bytes=prefetch.nbytes(item))
            yield item

    charges = _round_charges(scheme, cfg, state, batch_size, wire=wire,
                             topology=topology)
    topo_full = topology_lib.resolve(topology, cfg)
    faulty = linkfault.active(topo_full, cfg, train=True)
    el = jnp.asarray(labels_np[:n_eval])

    curve: List[CurvePoint] = list(curve0)
    items = prefetch.prefetch_to_device(
        epoch_items() if rounds else iter(()), size=prefetch_size,
        shardings=item_shardings)
    for ep in range(start_ep, epochs):
        if rounds:
            ep_idx, ep_labels, ep_rngs = next(items)
            with tracing.span("runner.gather", rounds=rounds,
                              bytes=superbatch_bytes):
                ep_views = gather(resident, ep_idx, image_shape=image_shape)
            state, _ = epoch_fn(state, ep_views, ep_labels, ep_rngs)
            # freed once the epoch program is done with it, so the next
            # gather finds at most this one superbatch beside the views
            del ep_views
            if faulty:
                # the scan's per-round subkeys ARE the round rngs — replay
                # their folded fault draws host-side for the two ledgers
                _meter_fault_rounds(meter, scheme, topo_full, cfg,
                                    batch_size, charges,
                                    jax.device_get(ep_rngs))
            else:
                _meter_rounds(meter, charges, rounds)
        _meter_overheads(meter, scheme, cfg, state)
        eval_state = jax.device_get(state) if mesh is not None else state
        acc = base.evaluate_accuracy(scheme, eval_state, ev, el,
                                     topology=topology, cfg=cfg)
        curve.append(CurvePoint(ep + 1, acc, meter.gbits,
                                meter.measured_gbits, meter.delivered_gbits))
        if ckpt_dir and ((ep + 1) % max(ckpt_every, 1) == 0
                         or ep + 1 == epochs):
            _save_epoch(ckpt_dir, scheme.name, ep + 1, state, curve, meter)
    return curve


def _run_per_round(scheme, views, labels, cfg, *, epochs, batch_size, lr,
                   seed, eval_n, wire="dense", topology=None, meter=None,
                   ckpt_dir=None, ckpt_every: int = 1, resume: bool = False):
    """The seed-style path: one transfer + one jitted dispatch per round.
    Kept verbatim as the semantics reference the scan path is tested
    against."""
    state = scheme.init(cfg, jax.random.PRNGKey(seed), lr=lr)
    round_fn = scheme.make_round(cfg, lr=lr, wire=wire, topology=topology)
    bpr = scheme.batches_per_round(cfg)

    meter = bandwidth.BandwidthMeter() if meter is None else meter
    start_ep = 0
    if resume and ckpt_dir:
        state, curve0, start_ep, _ = _try_resume(ckpt_dir, state, meter)
    else:
        curve0 = []
    charges = _round_charges(scheme, cfg, state, batch_size, wire=wire,
                             topology=topology)
    topo_full = topology_lib.resolve(topology, cfg)
    faulty = linkfault.active(topo_full, cfg, train=True)
    rounds = rounds_per_epoch(scheme, cfg, labels.shape[0], batch_size)
    rng = jax.random.PRNGKey(seed + 1)
    if start_ep and rounds:
        # replay the completed epochs' split chain so the next subkey (and
        # the trajectory downstream of it) matches the uninterrupted run
        rng, _ = _split_chain(rng, start_ep * rounds)
    n_eval = min(eval_n, labels.shape[0])
    ev = jnp.asarray(views[:, :n_eval])
    el = jnp.asarray(labels[:n_eval])

    curve: List[CurvePoint] = list(curve0)
    for ep in range(start_ep, epochs):
        group_v, group_l = [], []
        for v, l in multiview.multiview_batches(views, labels, batch_size,
                                                seed=ep):
            group_v.append(v)
            group_l.append(l)
            if len(group_v) < bpr:
                continue
            rng, sub = jax.random.split(rng)
            state, metrics = round_fn(
                state, jnp.asarray(np.stack(group_v)),
                jnp.asarray(np.stack(group_l)), sub)
            if faulty:
                _meter_fault_rounds(meter, scheme, topo_full, cfg,
                                    batch_size, charges, [sub])
            else:
                _meter_rounds(meter, charges)
            group_v, group_l = [], []
        _meter_overheads(meter, scheme, cfg, state)
        acc = base.evaluate_accuracy(scheme, state, ev, el,
                                     topology=topology, cfg=cfg)
        curve.append(CurvePoint(ep + 1, acc, meter.gbits,
                                meter.measured_gbits, meter.delivered_gbits))
        if ckpt_dir and ((ep + 1) % max(ckpt_every, 1) == 0
                         or ep + 1 == epochs):
            _save_epoch(ckpt_dir, scheme.name, ep + 1, state, curve, meter)
    return curve


def _run_transport(scheme, views, labels, cfg, *, epochs, batch_size, lr,
                   seed, eval_n, wire="dense", topology=None, transport=None,
                   ckpt_dir=None, ckpt_every: int = 1, resume: bool = False):
    """Per-round execution where fault outcomes come from the TRANSPORT:
    each round calls `transport.round_outcome(tick, ...)` — the retrying
    channels, circuit breakers, and chaos schedule decide the (J,) delivery
    mask — and hands the host-side verdict to the scheme's
    `make_transport_round` round (explicit delivery, no in-graph draws).
    The transport owns the run's meter: offered accrues per attempt,
    delivered per surviving payload fraction.

    Degradation semantics (the chaos bench's comparison): INL partial-fuses
    the surviving views (one vote lost per failed route), FL drops missing
    clients from the FedAvg average (their whole round of local work lost),
    SL skips the whole round unless every link delivered.

    A resume replays the completed ticks with ``charge=False`` — the breaker
    trajectories are reproduced without re-charging the restored ledgers —
    so the resumed run is bit-identical to the uninterrupted one."""
    state = scheme.init(cfg, jax.random.PRNGKey(seed), lr=lr)
    round_fn = scheme.make_transport_round(cfg, lr=lr, wire=wire,
                                           topology=topology)
    bpr = scheme.batches_per_round(cfg)
    meter = transport.meter
    charges = _round_charges(scheme, cfg, state, batch_size, wire=wire,
                             topology=topology)
    edges = transport.topo.edges
    if set(charges) == {None}:
        # scalar totals (FL/SL): split the round's charge equally across
        # the (star) edges so per-edge attempts re-offer their own share
        b, nb = charges[None]
        charges = {e.key: (b / len(edges), nb / len(edges)) for e in edges}
    rounds = rounds_per_epoch(scheme, cfg, labels.shape[0], batch_size)

    start_ep = 0
    tsnap = None
    if resume and ckpt_dir:
        state, curve0, start_ep, tsnap = _try_resume(ckpt_dir, state, meter)
    else:
        curve0 = []
    rng = jax.random.PRNGKey(seed + 1)
    tick = start_ep * rounds
    if tick:
        rng, _ = _split_chain(rng, tick)
        for t in range(tick):                 # breaker replay, ledger-free
            transport.round_outcome(t, batch_size, charges=charges,
                                    charge=False)
    if tsnap is not None:
        # the replay above already reproduced the adaptive knob trajectory
        # (observe runs on uncharged rounds too); loading the sidecar's
        # copy on top makes the checkpoint authoritative over the replay
        transport.load_snapshot(tsnap)

    n_eval = min(eval_n, labels.shape[0])
    ev = jnp.asarray(views[:, :n_eval])
    el = jnp.asarray(labels[:n_eval])

    curve: List[CurvePoint] = list(curve0)
    for ep in range(start_ep, epochs):
        group_v, group_l = [], []
        for v, l in multiview.multiview_batches(views, labels, batch_size,
                                                seed=ep):
            group_v.append(v)
            group_l.append(l)
            if len(group_v) < bpr:
                continue
            rng, sub = jax.random.split(rng)
            rep = transport.round_outcome(tick, batch_size, charges=charges)
            tick += 1
            state, metrics = round_fn(
                state, jnp.asarray(np.stack(group_v)),
                jnp.asarray(np.stack(group_l)), sub, jnp.asarray(rep.mask))
            group_v, group_l = [], []
        _meter_overheads(meter, scheme, cfg, state)
        acc = base.evaluate_accuracy(scheme, state, ev, el,
                                     topology=topology, cfg=cfg)
        curve.append(CurvePoint(ep + 1, acc, meter.gbits,
                                meter.measured_gbits, meter.delivered_gbits))
        if ckpt_dir and ((ep + 1) % max(ckpt_every, 1) == 0
                         or ep + 1 == epochs):
            _save_epoch(ckpt_dir, scheme.name, ep + 1, state, curve, meter,
                        transport=transport)
    return curve


def run_all(names: Sequence[str], views, labels, cfg, *, epochs: int,
            **kw) -> dict:
    """Curves for several registered schemes on the same data.

    A caller-supplied `meter=` is per RUN: sharing one across schemes
    would accumulate every earlier scheme's traffic into the later curves'
    gbits, so it is only accepted for a single-scheme list."""
    if kw.get("meter") is not None and len(names) > 1:
        raise ValueError("meter= accumulates across runs; pass it to "
                         "run_scheme per scheme (or run one scheme)")
    return {n: run_scheme(n, views, labels, cfg, epochs=epochs, **kw)
            for n in names}


def efficiency(curve: Sequence[CurvePoint]) -> float:
    """Final accuracy per Gbit exchanged (the paper's headline metric).

    An empty curve (epochs=0, or a rounds == 0 run that never evaluated)
    has no final point — 0.0, not an IndexError."""
    if not curve:
        return 0.0
    last = curve[-1]
    return last.accuracy / max(last.gbits, 1e-9)
