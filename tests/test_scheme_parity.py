"""Scheme-parity harness: every REGISTERED scheme satisfies the unified
Scheme contract on the same fixture —

  * a jitted round on a fixed seed improves the training loss,
  * `predict` returns a probability distribution (rows sum to 1),
  * a round given an all-delivered (J,) mask equals the round that draws
    its own on a clean network,
  * `bits_per_round` agrees EXACTLY with the closed-form §III-C / Table-I
    accounting in core/bandwidth.py (and, for INL, with the bits the train
    step itself meters),

so a newly registered scheme is covered by tier-1 the moment it registers,
and a refactor of any one scheme cannot silently leave the comparison
running on different substrates.  The deterministic trajectories are shared
with tests/test_scheme_golden.py via tests/_schemes_common.py (compiling
each scheme once per process).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _schemes_common import (BATCH, CFG, fixture_data, round_inputs,
                             trajectory)

from repro.core import bandwidth, inl, paper_model, schemes

PAPER_SCHEMES = ("inl", "fl", "sl")


def test_registry_exposes_the_papers_three_schemes():
    names = schemes.available()
    assert set(PAPER_SCHEMES) <= set(names)
    assert names[0] == "inl"                    # the paper's ordering
    with pytest.raises(KeyError):
        schemes.get("no-such-scheme")


@pytest.mark.parametrize("name", PAPER_SCHEMES)
def test_round_improves_loss_on_fixed_seed(name):
    losses = trajectory(name)["losses"]
    assert np.mean(losses[-2:]) < losses[0], (name, losses)


@pytest.mark.parametrize("name", PAPER_SCHEMES)
def test_predict_is_a_distribution(name):
    views, labels = fixture_data()
    scheme = schemes.get(name)
    probs = scheme.predict(trajectory(name)["state"], views[:, :BATCH])
    assert probs.shape == (BATCH, CFG.num_classes)
    np.testing.assert_allclose(np.asarray(probs.sum(axis=-1)), 1.0,
                               atol=1e-5)
    assert float(probs.min()) >= 0.0


@pytest.mark.parametrize("name", schemes.available())
def test_round_with_all_delivered_mask_equals_clean_round(name):
    """make_round's one round_fn: a (J,) all-ones delivery mask given as
    data (the transport's verdict) lands where the clean round does."""
    views, labels = fixture_data()
    scheme = schemes.get(name)
    state = scheme.init(CFG, jax.random.PRNGKey(0))
    v, lab = round_inputs(scheme, CFG, views, labels)
    round_fn = scheme.make_round(CFG)
    rng = jax.random.PRNGKey(5)
    want_state, want = round_fn(state, v, lab, rng)
    got_state, got = round_fn(state, v, lab, rng,
                              jnp.ones(CFG.num_clients, bool))
    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]),
                               rtol=1e-6)
    want_leaves = jax.tree.leaves(jax.device_get(want_state))
    got_leaves = jax.tree.leaves(jax.device_get(got_state))
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)


def test_bits_per_round_match_table1_closed_forms():
    key = jax.random.PRNGKey(0)
    p = CFG.num_clients * CFG.d_bottleneck
    N = paper_model.fl_param_count(CFG)
    J = CFG.num_clients

    s_inl = schemes.get("inl")
    st = trajectory("inl")["state"]
    assert s_inl.bits_per_round(CFG, st, BATCH) == \
        bandwidth.inl_epoch_bits(p, BATCH * J, J, CFG.link_bits)
    assert s_inl.epoch_overhead_bits(CFG, st) == 0.0

    s_fl = schemes.get("fl")
    st = trajectory("fl")["state"]
    assert s_fl.bits_per_round(CFG, st, BATCH) == \
        bandwidth.fl_round_bits(N, J, CFG.link_bits)
    assert s_fl.epoch_overhead_bits(CFG, st) == 0.0

    s_sl = schemes.get("sl")
    st = trajectory("sl")["state"]
    eta = s_sl.param_count(st["client"]) / N
    # per-round traffic + once-per-epoch hand-offs == the published formula
    assert (s_sl.bits_per_round(CFG, st, BATCH)
            + s_sl.epoch_overhead_bits(CFG, st)) == \
        bandwidth.sl_epoch_bits(p, BATCH, N, J, eta, CFG.link_bits)


def test_measured_wire_bytes_match_closed_forms():
    """The MEASURED ledger (actual wire-buffer nbytes, Scheme.
    wire_bytes_per_round via core/wirefmt.py) == the §III-C closed forms
    whenever the wire carries exactly what the formulas charge:

      * dense fp32 links at link_bits=32 (every scheme);
      * packed_duplex links at link_bits=q (INL/SL cut traffic: both
        directions as q-bit codewords — the paper's symmetric 2 b p s);
      * weight transfers always fp32 (FL rounds, SL hand-offs at s=32).
    """
    import dataclasses
    J = CFG.num_clients
    p = CFG.num_clients * CFG.d_bottleneck
    N = paper_model.fl_param_count(CFG)

    # dense @ 32-bit links: measured == accounted for all three schemes
    s_inl = schemes.get("inl")
    st = trajectory("inl")["state"]
    assert s_inl.wire_bytes_per_round(CFG, st, BATCH) * 8 == \
        s_inl.bits_per_round(CFG, st, BATCH)
    s_fl = schemes.get("fl")
    st_fl = trajectory("fl")["state"]
    assert s_fl.wire_bytes_per_round(CFG, st_fl, BATCH) * 8 == \
        bandwidth.fl_round_bits(N, J, 32)
    s_sl = schemes.get("sl")
    st_sl = trajectory("sl")["state"]
    assert s_sl.wire_bytes_per_round(CFG, st_sl, BATCH) * 8 == \
        bandwidth.sl_epoch_bits(p, BATCH, N, J, 0.0, 32)
    eta = s_sl.param_count(st_sl["client"]) / N
    assert s_sl.epoch_overhead_wire_bytes(CFG, st_sl) * 8 == \
        bandwidth.sl_epoch_bits(p, 0, N, J, eta, 32)

    # packed_duplex @ q-bit links: measured == the symmetric Table-I charge
    # whenever the codewords fill the uint32 lanes exactly; a d_bottleneck
    # too narrow for the lane (d*q < 32, e.g. 8 values at 2 bits) pays real
    # lane padding, and the measured ledger must report THAT, not the ideal
    from repro.kernels import ref as kref
    for bits in (2, 4, 8):
        cfg_q = dataclasses.replace(CFG, link_bits=bits)
        measured = s_inl.wire_bytes_per_round(cfg_q, st, BATCH,
                                              wire="packed_duplex") * 8
        lanes = kref.packed_width(CFG.d_bottleneck, bits)
        assert measured == 2 * BATCH * J * lanes * 32          # real lanes
        if (CFG.d_bottleneck * bits) % 32 == 0:                # lanes full
            assert measured == bandwidth.inl_epoch_bits(p, BATCH * J, J,
                                                        bits)
            assert s_sl.wire_bytes_per_round(
                cfg_q, st_sl, BATCH, wire="packed_duplex") * 8 == \
                bandwidth.sl_epoch_bits(p, BATCH, N, J, 0.0, bits)
    # forward-only packing: the client->server half shrinks by 32/q, the
    # dense backward half stays — the measured ledger reports the truth
    cfg8 = dataclasses.replace(CFG, link_bits=8)
    packed = s_inl.wire_bytes_per_round(cfg8, st, BATCH, wire="packed")
    dense = s_inl.wire_bytes_per_round(cfg8, st, BATCH, wire="dense")
    assert packed == dense / 2 * (1 + 8 / 32)


def test_runner_meters_measured_bytes():
    """schemes/runner.run_scheme accrues the measured ledger per round:
    with dense 32-bit links the curve's measured_gbits == its accounted
    gbits exactly (the satellite's 'today accounting is purely analytical'
    gap, closed)."""
    from repro.core.schemes import runner
    views, labels = fixture_data()
    views, labels = np.asarray(views[:, :64]), np.asarray(labels[:64])
    curve = runner.run_scheme("inl", views, labels, CFG, epochs=2,
                              batch_size=16, eval_n=32)
    assert curve[-1].measured_gbits > 0
    assert curve[-1].measured_gbits == curve[-1].gbits
    # a packed_duplex run at 8-bit links matches its (much smaller)
    # accounted charge exactly too
    import dataclasses
    cfg8 = dataclasses.replace(CFG, link_bits=8)
    curve8 = runner.run_scheme("inl", views, labels, cfg8, epochs=2,
                               batch_size=16, eval_n=32,
                               wire="packed_duplex")
    assert curve8[-1].measured_gbits == curve8[-1].gbits
    assert curve8[-1].gbits == curve[-1].gbits / 4     # 8 vs 32-bit links


def test_inl_metered_bits_equal_scheme_accounting():
    """The bits the INL train step itself reports == the registry's
    closed-form accounting (measured and published cannot drift)."""
    views, labels = fixture_data()
    params, state = inl.init(CFG, jax.random.PRNGKey(0))
    _, (m, _) = inl.loss_fn(params, state, views[:, :BATCH], labels[:BATCH],
                            jax.random.PRNGKey(3), CFG)
    scheme = schemes.get("inl")
    st = trajectory("inl")["state"]
    assert float(m["bits_sent"]) == scheme.bits_per_round(CFG, st, BATCH)


def test_learned_prior_scheme_state_trains():
    """cfg.learned_prior routes the INL scheme through the fused kernel's
    prior path end to end (no unfused fallback): prior params exist, get
    gradients, and the rounds still improve the loss."""
    rec = trajectory("inl", learned_prior=True)
    losses = rec["losses"]
    assert np.mean(losses[-2:]) < losses[0], losses
    priors = rec["state"]["params"].priors
    assert priors["mu"].shape == (CFG.num_clients, CFG.d_bottleneck)
    assert np.abs(np.asarray(priors["logvar"])).max() > 0.0
