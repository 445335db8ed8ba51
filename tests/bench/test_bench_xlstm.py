"""The xLSTM INL split (core/inl_llm.py, models/ssm.py) against its plain
reference (bench/references/xlstm_inl.py) at a tiny size on the CPU.

The mLSTM's recurrent scan, plain and remat'd in chunks, equals the
paper's parallel form where the stabiliser m_t is far from 0 (where the
normaliser's lower bound exp(-m_t) differs from 1), and decoding step by
step equals the scan.  In float32 the system's cut means, loss and
gradients equal the reference's.  The operation count against hand
counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xlstm_testlib as xt
from bench import flops_llm
from bench.drivers import llm_train as D
from bench.references import xlstm_inl as ref
from repro.core import inl_llm
from repro.models import ssm


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _mlstm_inputs(key, B=1, S=32, H=2, dh=16):
    """q, k, v and gate pre-activations whose stabiliser m_t ranges far
    from 0, and q small enough that |n_t . q_t| often falls under
    exp(-m_t)."""
    ks = jax.random.split(key, 5)
    q = 0.05 * jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, H, dh)) / np.sqrt(dh)
    v = jax.random.normal(ks[2], (B, S, H, dh))
    i_raw = 3.0 * jax.random.normal(ks[3], (B, S, H)) - 2.0
    f_raw = 2.0 * jax.random.normal(ks[4], (B, S, H))
    return q, k, v, i_raw, f_raw


@pytest.mark.parametrize("chunk", [64, 8], ids=["plain", "chunked"])
def test_recurrent_scan_equals_parallel_form(chunk):
    q, k, v, i_raw, f_raw = _mlstm_inputs(jax.random.PRNGKey(0))
    h_scan, (C, n, m) = jax.jit(ssm.mlstm_scan, static_argnums=5)(
        q, k, v, i_raw, f_raw, chunk)
    h_par = ref.mlstm_parallel(q, k, v, i_raw, f_raw,
                               prec=jax.lax.Precision.HIGHEST)
    assert _gap(h_scan, h_par) < 1e-5
    # the case the normaliser's bound decides: the stabiliser is far from
    # 0, and on many steps exp(-m_t) is the larger term
    F = np.cumsum(np.asarray(jax.nn.log_sigmoid(f_raw)), axis=1)
    logD = F[:, :, None] - F[:, None, :] + np.asarray(i_raw)[:, None]
    logD = np.where(np.tril(np.ones((32, 32), bool))[None, :, :, None],
                    logD, -np.inf)
    m_t = logD.max(axis=2)
    assert np.abs(m_t).mean() > 1.0
    w = np.exp(logD - m_t[:, :, None]) * np.einsum(
        "bthd,bshd->btsh", np.asarray(q), np.asarray(k))
    nq = np.abs(w.sum(axis=2))
    paper, bound_one = np.maximum(nq, np.exp(-m_t)), np.maximum(nq, 1.0)
    assert (np.abs(paper - bound_one) > 1e-3 * bound_one).mean() > 0.2
    np.testing.assert_allclose(np.asarray(m), m_t[:, -1], rtol=1e-5)


def test_decode_steps_equal_the_scan():
    conf = xt.tiny_xlstm("float32")
    cfg = D.program_config(conf)
    p = ssm.mlstm_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, conf["d_model"]))
    y_train, _ = ssm.mlstm_apply(p, cfg, x, mode="train")
    state = ssm.mlstm_make_state(cfg, 1, jnp.float32)
    step = jax.jit(lambda s, xt: ssm.mlstm_apply(p, cfg, xt, mode="decode",
                                                 state=s))
    ys = []
    for t in range(x.shape[1]):
        y, state = step(state, x[:, t:t + 1])
        ys.append(y)
    assert _gap(jnp.concatenate(ys, axis=1), y_train) < 1e-5


@pytest.fixture(scope="module")
def float32_pair():
    """The system's and the reference's cut means, loss and gradients for
    one step, in float32, on seeded weights and tokens."""
    conf = xt.tiny_xlstm("float32")
    cfg = D.program_config(conf)
    params = inl_llm.init(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    batch = {"tokens": rng.integers(0, 256, (1, 32)).astype(np.int32),
             "labels": rng.integers(0, 256, (1, 32)).astype(np.int32)}
    key = jax.random.PRNGKey(4)
    mu = jax.jit(lambda p: inl_llm.encode(p, cfg, batch["tokens"],
                                          key)[1])(params)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: inl_llm.loss_fn(p, cfg, batch, key), has_aux=True))(params)
    r_loss, r_mu, r_grads = ref.loss_and_grad(D.as_dict(params), conf,
                                              batch, key)
    return {"mu": (mu, r_mu), "loss": (loss, r_loss),
            "grads": (D.as_dict(grads), r_grads)}


@pytest.mark.parametrize("what,tol", [("mu", 1e-5), ("loss", 1e-6),
                                      ("grads", 1e-4)])
def test_system_matches_reference_in_float32(float32_pair, what, tol):
    prog, want = float32_pair[what]
    gaps = [_gap(a, b) for a, b in zip(jax.tree.leaves(prog),
                                       jax.tree.leaves(want))]
    assert len(gaps) == len(jax.tree.leaves(want)) and max(gaps) < tol


def test_the_record_is_the_steps_own_recurrence():
    """With `ssm.record_mlstm` (the cell's program) a train step returns
    the J cut means and node 0's first mLSTM recurrence, and computes what
    it computes without them: the same loss and gradients.  The recorded h
    is the scan of the recorded inputs, and the cut means are encode's."""
    import dataclasses
    conf = xt.tiny_xlstm("float32")
    cfg = D.program_config(conf)
    plain = dataclasses.replace(
        cfg, ssm=dataclasses.replace(cfg.ssm, record_mlstm=False))
    params = inl_llm.init(cfg, jax.random.PRNGKey(6))
    rng = np.random.default_rng(6)
    batch = {"tokens": rng.integers(0, 256, (1, 32)).astype(np.int32),
             "labels": rng.integers(0, 256, (1, 32)).astype(np.int32)}
    key = jax.random.PRNGKey(7)

    def step(c):
        return jax.jit(jax.value_and_grad(
            lambda p: inl_llm.loss_fn(p, c, batch, key), has_aux=True))(
                params)
    (loss, ms), grads = step(cfg)
    (loss_plain, ms_plain), grads_plain = step(plain)
    assert float(loss) == float(loss_plain)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not [k for k in ms_plain if k.startswith("record.")]
    rec = {k.split(".", 1)[1]: v for k, v in ms.items()
           if k.startswith("record.")}
    assert set(rec) == {"q", "k", "v", "i", "f", "h", "mu"}
    assert rec["q"].shape == (1, 32, 2, 64) and rec["i"].shape == (1, 32, 2)
    h = ssm.mlstm_scan(rec["q"], rec["k"], rec["v"], rec["i"], rec["f"],
                       conf["mlstm"]["chunk_size"])[0]
    np.testing.assert_array_equal(np.asarray(h), np.asarray(rec["h"]))
    mu = jax.jit(lambda p: inl_llm.encode(p, cfg, batch["tokens"],
                                          key)[1])(params)
    np.testing.assert_allclose(np.asarray(rec["mu"]), np.asarray(mu),
                               rtol=1e-6, atol=1e-6)


def test_flops_by_hand():
    conf = xt.tiny_xlstm()
    # mLSTM, d=64, d_in=128, H=2, dh=64, conv 4: up 64*256, q/k/v 3*128^2,
    # gates 128*4, down 128*64, conv 4*128, readout and update 2*2*64^2,
    # normaliser 2*64
    ml = 64 * 256 + 3 * 128 ** 2 + 128 * 4 + 128 * 64 + 4 * 128 \
        + 2 * 2 * 64 ** 2 + 2 * 64
    assert flops_llm.mlstm_macs(conf) == ml
    # sLSTM, dh=32: gates 64*256, recurrence 2*32*128, FFN 3*64*128
    sl = 64 * 256 + 2 * 32 * 128 + 3 * 64 * 128
    assert flops_llm.slstm_macs(conf) == sl
    f = flops_llm.forward_macs(conf)
    assert f == {"encoders": 2 * (ml + sl + 2 * 64 * 32),
                 "decoder": 2 * 32 * 64 + ml + sl,
                 "lm_head": 64 * 256, "branch_heads": 2 * 32 * 256}
    assert flops_llm.train_flops_per_token(conf) == 6 * sum(f.values())


def test_flops_at_the_cells_widths():
    """About 2.7 GFLOP per trained token at the published widths."""
    from bench import registry
    conf = registry.Registry().config("xlstm_125m_inl")
    assert round(flops_llm.train_flops_per_token(conf) / 1e9, 1) == 2.7
