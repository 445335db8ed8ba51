"""The chunkwise-parallel mLSTM (`models/ssm.mlstm_scan`) against the
recurrence it replaces: a step-by-step `lax.scan` of `_mlstm_cell`, kept
here as the oracle.  h, the final stabilised state (C, n, m) that prefill
hands to decode, and the gradients agree in every shape the chunking has
to handle: whole chunks, one short chunk, a single token, a ragged tail,
gates that move the stabiliser far within a chunk, and batch dims under
`vmap`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ssm


def _oracle(q, k, v, i_raw, f_raw):
    """The recurrence token by token from the zero state, in q's dtype."""
    B, _, H, dh = q.shape
    init = (jnp.zeros((B, H, dh, dh), q.dtype), jnp.zeros((B, H, dh), q.dtype),
            jnp.full((B, H), -1e30, q.dtype))
    carry, hs = jax.lax.scan(ssm._mlstm_cell, init, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, i_raw, f_raw)))
    return jnp.moveaxis(hs, 0, 1), carry


def _inputs(key, S, *, extreme=False, lead=(1,), H=2, dh=16):
    """q small enough that |n . q| often falls under exp(-m); with
    `extreme`, i_raw up to +30 and f_raw down to -30 (the forget gate
    near 0 or near 1), so the stabiliser jumps within a chunk."""
    ks = jax.random.split(key, 6)
    q = 0.05 * jax.random.normal(ks[0], lead + (S, H, dh))
    k = jax.random.normal(ks[1], lead + (S, H, dh)) / np.sqrt(dh)
    v = jax.random.normal(ks[2], lead + (S, H, dh))
    if extreme:
        i_raw = jax.random.uniform(ks[3], lead + (S, H), minval=-30.0,
                                   maxval=30.0)
        f_raw = jnp.where(jax.random.bernoulli(ks[4], 0.3, lead + (S, H)),
                          jax.random.uniform(ks[5], lead + (S, H),
                                             minval=-30.0, maxval=-5.0),
                          jax.random.uniform(ks[5], lead + (S, H),
                                             minval=0.0, maxval=8.0))
    else:
        i_raw = 3.0 * jax.random.normal(ks[3], lead + (S, H)) - 2.0
        f_raw = 2.0 * jax.random.normal(ks[4], lead + (S, H))
    return q, k, v, i_raw, f_raw


def _gap(a, b):
    """Relative L2 gap; absolute where b is zero (from the zero state the
    forget gate's gradient at the first token is)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) or 1.0))


def _vmapped(fn, n_lead):
    for _ in range(n_lead - 1):
        fn = jax.vmap(fn)
    return fn


CASES = {
    "whole_chunks": dict(S=64, chunk=16),
    "shorter_than_a_chunk": dict(S=12, chunk=16),
    "one_token": dict(S=1, chunk=16),
    "ragged_tail": dict(S=45, chunk=16),
    "extreme_gates": dict(S=64, chunk=16, extreme=True),
    "extreme_gates_ragged": dict(S=37, chunk=8, extreme=True),
    "J_and_B_under_vmap": dict(S=40, chunk=16, lead=(3, 2)),
}


def _case(name):
    c = dict(CASES[name])
    S, chunk = c.pop("S"), c.pop("chunk")
    lead = c.get("lead", (1,))
    x = _inputs(jax.random.PRNGKey(len(name) + S), S, **c)
    chunked = _vmapped(lambda *a: ssm.mlstm_scan(*a, chunk), len(lead))
    return x, chunked, _vmapped(_oracle, len(lead))


@pytest.mark.parametrize("name", list(CASES))
def test_chunkwise_equals_the_recurrence(name):
    x, chunked, oracle = _case(name)
    h, (C, n, m) = jax.jit(chunked)(*x)
    h0, (C0, n0, m0) = jax.jit(oracle)(*x)
    assert h.shape == h0.shape
    assert _gap(h, h0) < 1e-5
    assert _gap(C, C0) < 1e-5 and _gap(n, n0) < 1e-5
    np.testing.assert_allclose(np.asarray(m), np.asarray(m0), rtol=1e-5)


@pytest.mark.parametrize("name", list(CASES))
def test_chunkwise_gradients_equal_the_recurrences(name):
    """Against the oracle's gradients in float64: in float32 its own
    backward through 1,024 stabiliser steps is off by up to 3e-4 with the
    extreme gates, the chunkwise form's by 2e-5."""
    x, chunked, oracle = _case(name)

    def grads(fn, x):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(3.0 * fn(*a)[0])),
                                argnums=(0, 1, 2, 3, 4)))(*x)
    got = grads(chunked, x)
    with jax.enable_x64(True):
        want = grads(oracle, [jnp.asarray(np.asarray(t), jnp.float64)
                              for t in x])
        want = [np.asarray(g) for g in want]
    for g, g0 in zip(got, want):
        assert g0.dtype == np.float64
        assert np.all(np.isfinite(np.asarray(g)))
        assert _gap(g, g0) < 1e-4


def _scan_lengths(jaxpr):
    """The `length` of every scan in a jaxpr and the jaxprs inside it."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)       # a ClosedJaxpr's
                if hasattr(sub, "eqns"):
                    out += _scan_lengths(sub)
    return out


def test_only_the_chunk_states_are_scanned():
    """At the cell's 1,024 tokens and 64-token chunks, forward and
    backward scan the 16 chunk states and never the 1,024 tokens."""
    x = _inputs(jax.random.PRNGKey(0), 1024, H=1, dh=8)
    fwd = jax.make_jaxpr(lambda *a: ssm.mlstm_scan(*a, 64))(*x)
    bwd = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssm.mlstm_scan(*a, 64)[0]),
        argnums=(0, 1, 2, 3, 4)))(*x)
    for closed in (fwd, bwd):
        lengths = _scan_lengths(closed.jaxpr)
        assert lengths and set(lengths) == {16}, lengths
