"""The cut-layer Pallas kernels compile for a TPU v5e — without the chip.

Interpret mode never checks Mosaic's tiling rules, so every kernel of the
main path is compiled here for a described v5e (jax.experimental.topologies)
at the shapes the chip runs: the paper model's 1,280-row cut (J=5 x batch
256, d_b=64), the xLSTM INL split's 4,096-row cut at d_b=192, and the
serving plane's 5-row bucket.  Each compiled program must hold the Pallas
kernel (`tpu_custom_call`).  Nothing runs, so results are checked by the
interpret-mode tests (test_cutlayer_vjp.py, test_wireformat.py).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the test workers must all
collect the same tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import inl_bottleneck as bn
from repro.kernels import ref

PAPER = (1280, 64)          # J=5 x batch 256 rows, d_b=64
XLSTM = (4096, 192)         # J=4 x batch 1 x seq 1024 rows, d_b=192
MODES = ("sample", "analytic", "none")
DTYPES = (jnp.float32, jnp.bfloat16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


def _fused(mode):
    def fwd(mu, lv, eps, *prior):
        pm, pv = prior or (None, None)
        return bn.cutlayer_fused(mu, lv, eps, link_bits=4,
                                 rate_estimator=mode, impl="pallas",
                                 prior_mu=pm, prior_logvar=pv,
                                 interpret=False)
    return fwd


def _grad(fwd, n_args=3):
    """The fused backward: gradients of every input of `fwd`."""
    def loss(*args):
        u, rate = fwd(*args)
        return jnp.sum(u.astype(jnp.float32)) + jnp.sum(rate)
    return jax.grad(loss, argnums=tuple(range(n_args)))


@pytest.mark.parametrize("rows,d", [PAPER, XLSTM], ids=["paper", "xlstm"])
@pytest.mark.parametrize("dtype", DTYPES, ids=["fp32", "bf16"])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_cutlayer_compiles(one_chip, direction, mode, dtype, rows, d):
    fwd = _fused(mode)
    fn = fwd if direction == "fwd" else _grad(fwd)
    _compile(fn, *(_spec(one_chip, (rows, d), dtype) for _ in range(3)))


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("kernel", ["pack_forward", "pack", "unpack"])
def test_wire_kernels_compile(one_chip, kernel, bits):
    rows, d = PAPER
    x = _spec(one_chip, (rows, d))
    if kernel == "pack_forward":
        _compile(lambda m, l, e: bn.cutlayer_pack_forward(
            m, l, e, link_bits=bits, impl="pallas", interpret=False), x, x, x)
    elif kernel == "pack":
        _compile(lambda u: bn.pack_values(u, link_bits=bits, impl="pallas",
                                          interpret=False), x)
    else:
        w = ref.packed_width(d, bits)
        _compile(lambda p: bn.unpack_dequant(p, d, link_bits=bits,
                                             impl="pallas", interpret=False),
                 _spec(one_chip, (rows, w), jnp.uint32))


@pytest.mark.parametrize("mode", ["sample", "analytic"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_learned_prior_compiles(one_chip, direction, mode):
    J, d = 5, PAPER[1]
    fwd = _fused(mode)
    args = ([_spec(one_chip, (J, PAPER[0] // J, d)) for _ in range(3)]
            + [_spec(one_chip, (J, d)) for _ in range(2)])
    _compile(fwd if direction == "fwd" else _grad(fwd, len(args)), *args)


def test_serving_bucket_1_compiles(one_chip):
    """The smallest serving bucket: J=5 views of one request, 5 rows."""
    x = _spec(one_chip, (5, 1, PAPER[1]))
    _compile(_fused("none"), x, x, x)
