"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(deliverable c).  Each kernel also gets a hypothesis property pass."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or deterministic fallback

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.inl_bottleneck import bottleneck_fused
from repro.kernels.ssm_scan import ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,Dh,bq,bk", [
    (1, 128, 4, 4, 32, 64, 64),        # MHA
    (2, 256, 8, 2, 64, 128, 128),      # GQA 4:1
    (1, 256, 8, 1, 64, 128, 64),       # MQA
    (1, 512, 2, 2, 128, 128, 256),     # MXU-width heads
])
def test_flash_attention_sweep(B, S, H, KV, Dh, bq, bk, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, S, H, Dh), dtype)
    k = jax.random.normal(k2, (B, S, KV, Dh), dtype)
    v = jax.random.normal(k3, (B, S, KV, Dh), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_attention_window(window):
    B, S, H, KV, Dh = 1, 256, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh))
    k = jax.random.normal(ks[1], (B, S, KV, Dh))
    v = jax.random.normal(ks[2], (B, S, KV, Dh))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kernel", ["flash_attention", "ssd_scan"])
def test_interpret_none_interprets_off_tpu(kernel):
    """interpret=None resolves through kernels/ops: interpret mode on this
    CPU host (compiling Mosaic here would raise), compiled on a TPU."""
    from repro.kernels import ops
    assert ops.resolve_interpret(None) is (jax.default_backend() != "tpu")
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    if kernel == "flash_attention":
        q, k, v = (jax.random.normal(kk, (1, 64, 2, 32)) for kk in ks[:3])
        out = flash_attention(q, k, v, causal=True, interpret=None)
        want = ref.attention_ref(q, k, v, causal=True)
    else:
        B, S, H, P, N = 1, 32, 2, 16, 8
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        a = -jnp.exp(jax.random.normal(ks[2], (H,)))
        bm = jax.random.normal(ks[3], (B, S, N))
        cm = jax.random.normal(ks[4], (B, S, N))
        d = jax.random.normal(ks[5], (H,))
        out = ssd_scan(x, dt, a, bm, cm, d, chunk=16, interpret=None)
        want = ref.ssd_scan_ref(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(out, want, atol=5e-4, rtol=1e-4)


def test_flash_attention_q_offset():
    """Chunked prefill: q at positions [64:128) attending to k[0:128)."""
    B, S, H, KV, Dh = 1, 128, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh))
    k = jax.random.normal(ks[1], (B, S, KV, Dh))
    v = jax.random.normal(ks[2], (B, S, KV, Dh))
    out = flash_attention(q[:, 64:], k, v, causal=True, q_offset=64,
                          block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=True)[:, 64:]
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# INL bottleneck fusion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,d,bt", [(256, 64, 64), (512, 128, 256),
                                    (1024, 32, 1024)])
def test_bottleneck_sweep(T, d, bt, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    mu = jax.random.normal(ks[0], (T, d), dtype)
    lv = (jax.random.normal(ks[1], (T, d)) * 0.3).astype(dtype)
    eps = jax.random.normal(ks[2], (T, d), dtype)
    u, kl = bottleneck_fused(mu, lv, eps, block_t=bt)
    u_ref, kl_ref = ref.bottleneck_ref(mu, lv, eps)
    np.testing.assert_allclose(u.astype(jnp.float32),
                               u_ref.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(kl, kl_ref, atol=5e-2, rtol=1e-2)


@settings(max_examples=20, deadline=None)
@given(t_blocks=st.integers(1, 4), d=st.sampled_from([16, 64, 96]),
       seed=st.integers(0, 2 ** 16))
def test_bottleneck_property(t_blocks, d, seed):
    """KL >= 0 and u == mu when eps == 0, for arbitrary mu/logvar."""
    T = 64 * t_blocks
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    mu = jax.random.normal(ks[0], (T, d))
    lv = jnp.clip(jax.random.normal(ks[1], (T, d)), -4, 2)
    u, kl = bottleneck_fused(mu, lv, jnp.zeros((T, d)), block_t=64)
    assert bool((kl >= -1e-4).all())
    np.testing.assert_allclose(u, mu, atol=1e-6)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 128, 2, 32, 16, 32),
    (2, 256, 4, 64, 64, 128),
    (1, 192, 2, 16, 8, 64),            # S a non-power-of-two multiple
])
@pytest.mark.slow
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    bm = jax.random.normal(ks[3], (B, S, N), dtype)
    cm = jax.random.normal(ks[4], (B, S, N), dtype)
    d = jnp.ones((H,))
    y = ssd_scan(x, dt, a, bm, cm, d, chunk=chunk)
    want = ref.ssd_scan_ref(x, dt, a, bm, cm, d)
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) + 1e-6
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                - want.astype(jnp.float32)))) / scale
    assert err < (2e-2 if dtype == jnp.bfloat16 else 2e-5), err


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), chunk=st.sampled_from([16, 32, 64]))
def test_ssd_chunk_invariance(seed, chunk):
    """The chunked kernel must be invariant to the chunk size."""
    B, S, H, P, N = 1, 128, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    bm = jax.random.normal(ks[3], (B, S, N))
    cm = jax.random.normal(ks[4], (B, S, N))
    d = jnp.zeros((H,))
    y1 = ssd_scan(x, dt, a, bm, cm, d, chunk=chunk)
    y2 = ssd_scan(x, dt, a, bm, cm, d, chunk=S)
    np.testing.assert_allclose(y1, y2, atol=5e-4, rtol=1e-4)


def test_model_ssd_matches_kernel():
    """models/ssm.py's chunked jnp SSD == the Pallas kernel contract."""
    from repro.models.ssm import _ssd_chunked
    B, S, H, P, N = 2, 128, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    bm = jax.random.normal(ks[3], (B, S, N))
    cm = jax.random.normal(ks[4], (B, S, N))
    d = jnp.ones((H,))
    y1, _ = _ssd_chunked(x, dt, a, bm, cm, d, 64)
    y2 = ssd_scan(x, dt, a, bm, cm, d, chunk=64)
    np.testing.assert_allclose(y1, y2, atol=2e-4, rtol=1e-4)
