"""Benchmark — deterministic chaos: fault tolerance as numbers per scheme.

Every fault here is SCRIPTED (repro/chaos.ChaosSchedule) and every
transport draw is counter-seeded (repro/transport/), so the whole bench
replays bit-identically — the asserts below are stable CI contracts, not
flaky statistics.

Sections, written to BENCH_chaos.json (--json):

  serving_goodput   requests served through the continuous-batching engine
                    over a transport whose edges take turns going down
                    (staggered flap: J-1 of J uplinks dark at any tick).
                    INL partial-fuses whatever arrived — a request keeps a
                    real answer as long as ONE view lands.  The FL/SL
                    serving reading (links_bench: the single client<->server
                    uplink answers or the request degrades to uniform) rides
                    the SAME chaos schedule.  ASSERTS INL goodput (correct
                    answers / offered requests) >= 2x FL and SL.

  breaker           a 40-round edge outage under retrying transport, with
                    and without circuit breakers.  Without, every round
                    re-offers max_attempts full charges into a dead link;
                    with, the breaker opens after 3 consecutive failures
                    and short-circuits the window (probes only).  ASSERTS
                    the breaker's delivered/offered ratio is STRICTLY above
                    the no-breaker baseline, that it actually opened and
                    short-circuited, and that it recloses within
                    2*cooldown+2 ticks of the outage ending (recovery
                    time).

  training_churn    a client node SIGKILLed mid-training (kill window in
                    round ticks) under transport execution.  ASSERTS the
                    degradation semantics behaviourally: across a round
                    with the node dead, SL's state is UNCHANGED (whole
                    round lost) while INL's state moved (one vote lost,
                    survivors renormalised); per partial round INL loses
                    exactly one vote.  Records accuracy of the churned INL
                    run vs its clean twin and asserts the churned run still
                    recovers (final accuracy within 0.2 of clean).

  crash_resume      elastic recovery at the runner level: a transport-mode
                    run checkpointed every epoch, restarted from the
                    midpoint, asserted BIT-IDENTICAL to the uninterrupted
                    run (curve, meter ledgers, breaker trajectory).  The
                    subprocess SIGKILL variant (torn-file crash atomicity
                    included) is `python -m repro.chaos` — the CI
                    crash-resume leg.

--smoke shrinks shapes/epochs for the CI bench-smoke step so the asserts
cannot bit-rot between nightly runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.chaos import ChaosSchedule
from repro.configs.paper_inl import PaperExperimentConfig
from repro.core import schemes
from repro.core import topology as topology_lib
from repro.core.schemes import base as schemes_base
from repro.core.schemes import runner
from repro.data import multiview
from repro.serving import ServingEngine
from repro.transport import (DEFAULT_RETRY, NO_RETRY, CircuitBreaker,
                             NetworkTransport)


def _cfg(*, smoke: bool):
    if smoke:
        return PaperExperimentConfig(
            conv_channels=(4,), d_bottleneck=8, dense_units=(32,),
            image_shape=(16, 16, 3), dataset_size=640)
    return PaperExperimentConfig(
        conv_channels=(8, 16), d_bottleneck=16, dense_units=(64,),
        image_shape=(32, 32, 3), dataset_size=2048)


def _data(cfg, seed):
    imgs, labels = multiview.make_base_dataset(
        cfg.dataset_size, image_shape=cfg.image_shape, seed=seed)
    views = multiview.make_views(imgs, cfg.noise_stds)
    return jnp.asarray(views), jnp.asarray(labels)


def _edge_keys(cfg):
    topo = topology_lib.resolve(None, cfg)
    return [e.key for e in topo.edges], topo


# ---------------------------------------------------------------------------
# serving goodput under churn
# ---------------------------------------------------------------------------

def serving_goodput_section(*, smoke: bool, epochs: int, seed: int):
    cfg = _cfg(smoke=smoke)
    views, labels = _data(cfg, seed)
    keys, topo = _edge_keys(cfg)
    J = len(keys)
    n = min(64, labels.shape[0])

    # the churn script: staggered flaps — at (almost) every tick exactly
    # ONE of the J uplinks is up, the other J-1 dark
    chaos = ChaosSchedule()
    for i, key in enumerate(keys):
        chaos = chaos.flap_edge(key, start=i, stop=10_000, period=J,
                                duty=J - 1)

    # train each scheme CLEAN (INL with the edge-dropout curriculum so the
    # fusion center has learned to renormalise over survivors).  One-view
    # robustness needs the curriculum to have converged — 2 smoke epochs
    # leave the noisier views near chance, 4 put their single-vote
    # accuracy at ~0.57 — so the section floors the training at 4 epochs
    # (seconds at these shapes).
    epochs = max(epochs, 4)
    preds, states = {}, {}
    for name in ("inl", "fl", "sl"):
        # a HARD dropout curriculum: under the staggered flap most fusions
        # see a single surviving view, so the fusion center must have
        # trained to answer from any one vote alone
        tcfg = dataclasses.replace(cfg, edge_dropout=0.5) \
            if name == "inl" else cfg
        scheme = schemes.get(name)
        # train via the round path directly (run_scheme returns the curve,
        # not the state, and these shapes retrain in seconds)
        state = scheme.init(tcfg, jax.random.PRNGKey(seed))
        round_fn = scheme.make_round(tcfg)
        bpr = scheme.batches_per_round(tcfg)
        rng = jax.random.PRNGKey(seed + 1)
        for ep in range(epochs):
            group_v, group_l = [], []
            for v, l in multiview.multiview_batches(views, labels, 32,
                                                    seed=ep):
                group_v.append(v)
                group_l.append(l)
                if len(group_v) < bpr:
                    continue
                rng, sub = jax.random.split(rng)
                state, _ = round_fn(state, jnp.asarray(np.stack(group_v)),
                                    jnp.asarray(np.stack(group_l)), sub)
                group_v, group_l = [], []
        states[name] = state
        preds[name] = np.argmax(np.asarray(
            scheme.predict(state, views[:, :n], cfg=tcfg)), -1)
    el = np.asarray(labels[:n])

    # INL: the real engine over the chaotic transport, one request per tick
    tr = NetworkTransport(topo, cfg, seed=seed + 7, policy=NO_RETRY,
                          breaker=None, chaos=chaos)
    engine = ServingEngine(schemes.get("inl"), states["inl"], cfg,
                           seed=seed + 2, transport=tr)
    engine.warmup()
    with engine:
        probs, results = engine.serve(np.asarray(views[:, :n]))
    fused = np.asarray([r.views_fused for r in results])
    inl_correct = (np.argmax(probs, -1) == el) & (fused > 0)
    goodput = {"inl": float(inl_correct.mean())}
    tr.close()

    # FL/SL: same chaos, single-uplink reading — request rid rides its
    # owner client's edge (owner strided so it is NOT phase-locked to the
    # flap script: with period J and one edge up per tick, a 2-stride owner
    # sees its uplink up for exactly 1/J of requests — the fair baseline,
    # not an accidental 0); a dark uplink degrades the answer to uniform
    for name in ("fl", "sl"):
        t2 = NetworkTransport(topo, cfg, seed=seed + 7, policy=NO_RETRY,
                              breaker=None, chaos=chaos)
        ok = np.zeros(n, bool)
        for rid in range(n):
            rep = t2.send_request(rid)
            up = bool(rep.eventual[(2 * rid + 1) % J])
            ok[rid] = up and preds[name][rid] == el[rid]
        goodput[name] = float(ok.mean())
        t2.close()

    print("serving goodput under churn (correct answers / requests): "
          + " ".join(f"{k}={v:.3f}" for k, v in goodput.items()))
    for rival in ("fl", "sl"):
        assert goodput["inl"] >= 2.0 * goodput[rival], (
            f"INL goodput {goodput['inl']:.3f} must be >= 2x {rival} "
            f"{goodput[rival]:.3f} under churn: partial fusion keeps a "
            "vote per surviving uplink, the single-uplink schemes lose "
            "the whole request")
    return {"goodput": goodput, "requests": int(n),
            "mean_views_fused": float(fused.mean()),
            "uplinks_up_per_tick": 1}


# ---------------------------------------------------------------------------
# circuit breaker vs none over a dead window
# ---------------------------------------------------------------------------

def breaker_section(*, smoke: bool, seed: int):
    cfg = _cfg(smoke=smoke)
    keys, topo = _edge_keys(cfg)
    outage_start, outage_len, ticks = 4, 40, 64
    chaos = ChaosSchedule().down_edge(keys[0], outage_start, outage_len)
    cooldown = 4

    record = {}
    recovery_tick = None
    for label, breaker in (("no_breaker", None),
                           ("breaker",
                            lambda: CircuitBreaker(cooldown=cooldown))):
        tr = NetworkTransport(topo, cfg, seed=seed + 11, policy=DEFAULT_RETRY,
                              breaker=breaker, chaos=chaos)
        for t in range(ticks):
            tr.round_outcome(t, 32)
            if label == "breaker" and recovery_tick is None \
                    and t >= outage_start + outage_len \
                    and tr.breaker_states()[keys[0]] == "closed":
                recovery_tick = t
        snap = tr.snapshot()
        record[label] = {"offered_bits": snap["offered_bits"],
                         "delivered_bits": snap["delivered_bits"],
                         "delivery_ratio": snap["delivery_ratio"],
                         "breaker": snap["breaker"][keys[0]]}
        tr.close()

    nb, wb = record["no_breaker"], record["breaker"]
    print(f"breaker: delivered/offered {wb['delivery_ratio']:.3f} with vs "
          f"{nb['delivery_ratio']:.3f} without "
          f"(opens={wb['breaker']['opens']}, "
          f"short_circuits={wb['breaker']['short_circuits']}, "
          f"reclosed_at_tick={recovery_tick})")
    assert wb["delivery_ratio"] > nb["delivery_ratio"], (
        "the breaker must deliver a STRICTLY higher fraction of what it "
        "offers: short-circuited attempts stop re-offering full charges "
        "into a dead link")
    assert wb["breaker"]["opens"] >= 1 and \
        wb["breaker"]["short_circuits"] > 0, wb["breaker"]
    assert recovery_tick is not None and \
        recovery_tick - (outage_start + outage_len) <= 2 * cooldown + 2, (
        f"breaker must reclose within 2*cooldown+2 ticks of the outage "
        f"ending; reclosed at {recovery_tick}")
    record["recovery_ticks"] = recovery_tick - (outage_start + outage_len)
    return record


# ---------------------------------------------------------------------------
# training under a node kill: one vote vs whole round
# ---------------------------------------------------------------------------

def training_churn_section(*, smoke: bool, epochs: int, seed: int):
    cfg = _cfg(smoke=smoke)
    views, labels = _data(cfg, seed)
    keys, topo = _edge_keys(cfg)
    J = len(keys)
    dead = topo.view_nodes()[1]
    kill_at, kill_len = 2, 4
    chaos = ChaosSchedule().kill_node(dead, at=kill_at, duration=kill_len)

    def make_tr(with_chaos):
        return NetworkTransport(topo, cfg, seed=seed + 5,
                                policy=DEFAULT_RETRY,
                                chaos=chaos if with_chaos else None)

    # the behavioural semantics, one round each (deterministic): the same
    # partial delivery moves INL's state but leaves SL's untouched
    delivery = jnp.asarray(np.arange(J) != 1)          # the dead node's vote
    v1 = views[:, :32][None]
    l1 = labels[:32][None]
    rng1 = jax.random.PRNGKey(seed + 9)
    inl_scheme, sl_scheme = schemes.get("inl"), schemes.get("sl")
    st_inl = inl_scheme.init(cfg, jax.random.PRNGKey(seed))
    new_inl, _ = inl_scheme.make_round(cfg)(
        st_inl, v1, l1, rng1, delivery)
    inl_moved = any(not np.array_equal(a, b) for a, b in
                    zip(jax.tree.leaves(jax.device_get(new_inl)),
                        jax.tree.leaves(jax.device_get(st_inl))))
    st_sl = sl_scheme.init(cfg, jax.random.PRNGKey(seed))
    new_sl, _ = sl_scheme.make_round(cfg)(
        st_sl, v1, l1, rng1, delivery)
    sl_held = all(np.array_equal(a, b) for a, b in
                  zip(jax.tree.leaves(jax.device_get(new_sl)),
                      jax.tree.leaves(jax.device_get(st_sl))))
    assert inl_moved, "INL must partial-fuse the surviving J-1 votes"
    assert sl_held, ("SL must carry its state UNCHANGED through a round "
                     "with a failed link — the whole round is lost")

    # vote accounting over the kill window, straight off the round reports
    replay = make_tr(True)
    masks = [replay.round_outcome(t, 32, charge=False).mask
             for t in range(kill_at + kill_len + 2)]
    replay.close()
    partial = [m for m in masks if not m.all()]
    votes_lost_inl = int(sum(J - m.sum() for m in partial))
    rounds_lost_sl = len(partial)
    assert votes_lost_inl == rounds_lost_sl == kill_len, (
        "one dead node for k rounds must cost INL exactly k votes and SL "
        f"exactly k whole rounds; got votes={votes_lost_inl} "
        f"rounds={rounds_lost_sl} k={kill_len}")
    assert all(m.all() for m in masks[kill_at + kill_len:]), \
        "the node must rejoin the fusion the tick its kill window closes"

    # the churned training run still converges (elastic leave/rejoin)
    tr = make_tr(True)
    churn = runner.run_scheme("inl", views, labels, cfg, epochs=epochs,
                              batch_size=32, seed=seed, transport=tr)
    tr.close()
    clean = runner.run_scheme("inl", views, labels, cfg, epochs=epochs,
                              batch_size=32, seed=seed,
                              dispatch="per_round")
    print(f"training churn: kill {dead} for {kill_len} rounds -> "
          f"acc {churn[-1].accuracy:.3f} vs clean {clean[-1].accuracy:.3f} "
          f"(votes lost: inl={votes_lost_inl}, "
          f"whole rounds lost: sl={rounds_lost_sl})")
    assert churn[-1].accuracy >= clean[-1].accuracy - 0.2, (
        f"a {kill_len}-round client leave must not sink the run: "
        f"{churn[-1].accuracy:.3f} vs clean {clean[-1].accuracy:.3f}")
    return {"dead_node": dead, "kill_rounds": kill_len,
            "votes_lost_inl": votes_lost_inl,
            "whole_rounds_lost_sl": rounds_lost_sl,
            "accuracy_churn": churn[-1].accuracy,
            "accuracy_clean": clean[-1].accuracy}


# ---------------------------------------------------------------------------
# elastic crash-resume identity (runner level)
# ---------------------------------------------------------------------------

def crash_resume_section(*, smoke: bool, epochs: int, seed: int):
    cfg = _cfg(smoke=smoke)
    views, labels = _data(cfg, seed)
    keys, topo = _edge_keys(cfg)
    chaos = ChaosSchedule().down_edge(keys[0], 3, 2)

    def make_tr():
        return NetworkTransport(topo, cfg, seed=seed + 13,
                                policy=DEFAULT_RETRY, chaos=chaos)

    epochs = max(epochs, 2)
    half = epochs // 2
    tg = make_tr()
    golden = runner.run_scheme("inl", views, labels, cfg, epochs=epochs,
                               batch_size=32, seed=seed, transport=tg)
    gsnap = tg.snapshot()
    tg.close()

    workdir = tempfile.mkdtemp(prefix="chaos_bench_ckpt_")
    try:
        t1 = make_tr()
        runner.run_scheme("inl", views, labels, cfg, epochs=half,
                          batch_size=32, seed=seed, transport=t1,
                          ckpt_dir=workdir)
        t1.close()
        t2 = make_tr()
        resumed = runner.run_scheme("inl", views, labels, cfg, epochs=epochs,
                                    batch_size=32, seed=seed, transport=t2,
                                    ckpt_dir=workdir, resume=True)
        rsnap = t2.snapshot()
        t2.close()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    assert golden == resumed, (
        "the resumed curve must equal the uninterrupted run's exactly "
        "(state, rng fast-forward, AND meter ledgers)")
    assert gsnap == rsnap, (
        "the resumed transport snapshot (ledgers + breaker trajectories) "
        "must equal the uninterrupted run's")
    print(f"crash-resume: {half}+{epochs - half} epochs == {epochs} epochs "
          f"bit for bit (final acc {golden[-1].accuracy:.3f})")
    return {"epochs": epochs, "resume_from_epoch": half,
            "bitwise_identical": True,
            "final_accuracy": golden[-1].accuracy}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes/epochs (CI bench-smoke step)")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="BENCH_chaos.json",
                    help="output path ('' disables)")
    args = ap.parse_args(argv)
    epochs = 2 if args.smoke else args.epochs

    record = {"smoke": args.smoke,
              "serving_goodput": serving_goodput_section(
                  smoke=args.smoke, epochs=epochs, seed=args.seed),
              "breaker": breaker_section(smoke=args.smoke, seed=args.seed),
              "training_churn": training_churn_section(
                  smoke=args.smoke, epochs=epochs, seed=args.seed),
              "crash_resume": crash_resume_section(
                  smoke=args.smoke, epochs=epochs, seed=args.seed)}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}")
    return record


if __name__ == "__main__":
    main()
