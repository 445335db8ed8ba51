"""Elastic training recovery: resume is BIT-IDENTICAL, clients leave and
rejoin, transport rounds degrade per the scheme's semantics.

The contracts:

  * run_scheme with ckpt_dir/resume reproduces the uninterrupted curve
    EXACTLY on every dispatch path (scan, per_round, transport) — state,
    rng fast-forward, and both meter ledgers included;
  * transport execution: the (J,) delivery verdict reaches the round as an
    explicit argument — INL partial-fuses survivors (state moves on a
    partial round), SL carries its state unchanged (whole round lost), FL
    drops the missing client from the FedAvg average;
  * a transport-mode resume replays breaker trajectories without
    re-charging the ledgers;
  * a node kill mid-training = a client leave; the mask returns to full
    the tick its window closes (rejoin).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.chaos import ChaosSchedule
from repro.core import schemes
from repro.core import topology as topology_lib
from repro.core.schemes import runner
from repro.transport import DEFAULT_RETRY, NetworkTransport
from tests._schemes_common import CFG, fixture_data

EPOCHS = 4
HALF = 2


def _run(name="inl", *, cfg=CFG, epochs=EPOCHS, **kw):
    views, labels = fixture_data()
    return runner.run_scheme(name, views, labels, cfg, epochs=epochs,
                             batch_size=32, seed=3, **kw)


@pytest.mark.parametrize("dispatch", ["scan", "per_round"])
def test_resume_bit_identical(dispatch, tmp_path):
    golden = _run(dispatch=dispatch)
    d = str(tmp_path)
    _run(dispatch=dispatch, epochs=HALF, ckpt_dir=d)
    resumed = _run(dispatch=dispatch, ckpt_dir=d, resume=True)
    assert resumed == golden        # CurvePoints compare exactly — accuracy,
    #                                 offered/measured/delivered Gbit included


def test_resume_bit_identical_under_linkfaults(tmp_path):
    # the scan path's fault metering replays per-round subkeys — the resume
    # fast-forward must reproduce them exactly
    cfg = dataclasses.replace(CFG, edge_dropout=0.3)
    golden = _run(cfg=cfg)
    d = str(tmp_path)
    _run(cfg=cfg, epochs=HALF, ckpt_dir=d)
    assert _run(cfg=cfg, ckpt_dir=d, resume=True) == golden


def _make_transport(chaos=None, seed=7):
    topo = topology_lib.resolve(None, CFG)
    return NetworkTransport(topo, CFG, seed=seed, policy=DEFAULT_RETRY,
                            chaos=chaos)


def test_transport_resume_replays_breakers_without_recharging(tmp_path):
    chaos = ChaosSchedule().down_edge("m0->fuse", 1, 2)
    tg = _make_transport(chaos)
    golden = _run(transport=tg)
    gsnap = tg.snapshot()
    tg.close()

    d = str(tmp_path)
    t1 = _make_transport(chaos)
    _run(transport=t1, epochs=HALF, ckpt_dir=d)
    t1.close()
    t2 = _make_transport(chaos)
    resumed = _run(transport=t2, ckpt_dir=d, resume=True)
    rsnap = t2.snapshot()
    t2.close()
    assert resumed == golden
    assert gsnap == rsnap           # ledgers AND breaker counters


def test_transport_round_semantics_partial_delivery():
    # one partial round, same delivery verdict for all three schemes:
    # INL's state moves, SL's does not, FL drops the client (moves too,
    # but averages only the survivors)
    views, labels = fixture_data()
    J = CFG.num_clients
    delivery = jnp.asarray(np.arange(J) != 2)
    v1, l1 = views[:, :32][None], labels[:32][None]
    rng = jax.random.PRNGKey(11)

    def moved(scheme_name, bpr_views):
        scheme = schemes.get(scheme_name)
        state = scheme.init(CFG, jax.random.PRNGKey(0))
        new, _ = scheme.make_round(CFG)(
            state, bpr_views[0], bpr_views[1], rng, delivery)
        return any(not np.array_equal(a, b) for a, b in
                   zip(jax.tree.leaves(jax.device_get(new)),
                       jax.tree.leaves(jax.device_get(state))))

    assert moved("inl", (v1, l1))
    assert not moved("sl", (v1, l1))
    fl = schemes.get("fl")
    R = fl.batches_per_round(CFG)
    vR = jnp.broadcast_to(v1, (R,) + v1.shape[1:])
    lR = jnp.broadcast_to(l1, (R,) + l1.shape[1:])
    assert moved("fl", (vR, lR))


def test_transport_round_all_lost_keeps_state():
    # every vote lost: INL has nothing to fuse but still takes a step on
    # the renormalised zeros?  No — the semantics pin: SL holds; FL keeps
    # the previous global model (all clients dropped from the average)
    views, labels = fixture_data()
    J = CFG.num_clients
    none = jnp.zeros(J, bool)
    rng = jax.random.PRNGKey(11)
    fl = schemes.get("fl")
    R = fl.batches_per_round(CFG)
    v = jnp.broadcast_to(views[:, :32][None], (R, J, 32) + views.shape[2:])
    l = jnp.broadcast_to(labels[:32][None], (R, 32))
    state = fl.init(CFG, jax.random.PRNGKey(0))
    new, _ = fl.make_round(CFG)(state, v, l, rng, none)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(jax.device_get(new["params"])),
                   jax.tree.leaves(jax.device_get(state["params"]))))


def test_node_kill_is_leave_then_rejoin():
    chaos = ChaosSchedule().kill_node("m1", at=2, duration=3)
    tr = _make_transport(chaos)
    masks = np.stack([tr.round_outcome(t, 32, charge=False).mask
                      for t in range(8)])
    tr.close()
    assert masks[:2].all() and masks[5:].all()      # before + after: full
    assert not masks[2:5, 1].any()                  # the leave window
    assert masks[2:5, [0, 2, 3, 4]].all()           # survivors keep voting


def test_transport_excludes_mesh_and_foreign_meter():
    from repro.core import bandwidth
    views, labels = fixture_data()
    tr = _make_transport()
    with pytest.raises(ValueError, match="meter"):
        runner.run_scheme("inl", views, labels, CFG, epochs=1,
                          batch_size=32, transport=tr,
                          meter=bandwidth.BandwidthMeter())
    tr.close()


def test_transport_curve_meters_on_transport_ledger():
    tr = _make_transport(ChaosSchedule().down_edge("m0->fuse", 0, 2))
    curve = _run(transport=tr, epochs=2)
    snap = tr.snapshot()
    tr.close()
    assert curve[-1].gbits > 0
    assert curve[-1].delivered_gbits < curve[-1].gbits   # the outage cost
    assert snap["delivery_ratio"] < 1.0
