"""The cut-layer Pallas kernels compile for a TPU v5e — without the chip.

Interpret mode never checks Mosaic's tiling rules, so every kernel of the
main path is compiled here for a described v5e (jax.experimental.topologies)
at the shapes the chip runs: the paper model's 1,280-row cut (J=5 x batch
256, d_b=64), the xLSTM INL split's 4,096-row cut at d_b=192, and the
serving plane's 5-row bucket.  Each compiled program must hold the Pallas
kernel (`tpu_custom_call`).  Nothing runs, so results are checked by the
interpret-mode tests (test_cutlayer_vjp.py, test_wireformat.py).  The
scan runner's device gather is compiled here too, for its memory.

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


def test_inl_loss_keeps_the_cut_kernels_instruction_names(one_chip,
                                                         monkeypatch):
    """The INL loss's named scopes (core/inl.py) leave the cut layer's
    kernels named as a profiler trace shows them and the cut-layer reader
    (bench/metrics/cutlayer_us_per_round.paper_train.py) finds them: a
    scope around their call would rename them `_cutlayer_call`."""
    import re

    from repro.configs.paper_inl import PaperExperimentConfig
    from repro.core import inl
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = PaperExperimentConfig(conv_channels=(8,), d_bottleneck=PAPER[1],
                                dense_units=(32,), image_shape=(8, 8, 3))
    B = PAPER[0] // cfg.num_clients

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                            tree)
    params, state = on_chip(jax.eval_shape(
        lambda k: inl.init(cfg, k), jax.random.PRNGKey(0)))
    views = _spec(one_chip, (cfg.num_clients, B) + cfg.image_shape)
    labels = _spec(one_chip, (B,), jnp.int32)
    rng = _spec(one_chip, (2,), jnp.uint32)

    def loss(*args):
        return inl.loss_fn(*args, cfg)[0]
    text = jax.jit(jax.grad(loss)).lower(
        params, state, views, labels, rng).compile().as_text()
    kernels = sorted(
        re.sub(r"\.\d+$", "", line.split(" = ")[0].strip())
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    assert kernels == ["%jvp_jit__cutlayer_call__",
                       "%transpose_jvp_jit__cutlayer_call___"]


def test_runner_device_gather_fits_the_chip(one_chip):
    """The scan runner's resident view set and per-epoch gather
    (core/schemes/runner.py) at the paper_inl_train cell's shapes: 40,960
    views per node, 160 rounds of 256.  The superbatch must come out at its
    unpadded 2,516,582,400 bytes, and neither the gather nor the set-up
    relayout may need a padded copy of the set: a TPU keeps a
    (..., 32, 32, 3) array with its leading axis fastest, and a gather that
    took it as it is would need temporaries of 7.5x the superbatch."""
    from repro.core.schemes import runner
    J, n, image = 5, 40960, (32, 32, 3)
    set_bytes = J * n * 32 * 32 * 3 * 4
    rows = runner._resident.lower(
        _spec(one_chip, (J, n) + image), 512).compile().memory_analysis()
    assert rows.temp_size_in_bytes < 0.5 * set_bytes
    gather = jax.jit(runner._superbatch, static_argnames="image_shape").lower(
        _spec(one_chip, (J, n, 32 * 32 * 3)),
        _spec(one_chip, (160, 1, 256), jnp.int32),
        image_shape=image).compile().memory_analysis()
    assert abs(gather.output_size_in_bytes - 2_516_582_400) \
        < 0.05 * 2_516_582_400
    assert gather.temp_size_in_bytes < 1e9


@pytest.fixture(scope="module")
def xlstm_inl_step_hlo(one_chip):
    """The compiled text of the xLSTM INL split's train step
    (core/inl_llm.py, at a tiny size: 128 tokens, two 64-token mLSTM
    chunks) for the described v5e, with the Pallas cut layer."""
    import dataclasses

    from repro import optim
    from repro.configs import get_smoke_config
    from repro.core import inl_llm
    from repro.kernels import ops
    from repro.launch import steps
    cfg = get_smoke_config("xlstm-125m")
    cfg = dataclasses.replace(cfg, num_layers=4)
    opt = optim.adamw(1e-3)

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                            tree)
    params = on_chip(jax.eval_shape(lambda k: inl_llm.init(cfg, k),
                                    jax.random.PRNGKey(0)))
    opt_state = on_chip(jax.eval_shape(opt.init, params))
    batch = {"tokens": _spec(one_chip, (1, 128), jnp.int32),
             "labels": _spec(one_chip, (1, 128), jnp.int32)}
    rng = _spec(one_chip, (2,), jnp.uint32)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_tpu", lambda: True)
        return jax.jit(steps.make_inl_train_step(cfg, opt)).lower(
            params, opt_state, batch, rng).compile().as_text()


def test_xlstm_inl_step_keeps_the_cut_kernels_instruction_names(
        xlstm_inl_step_hlo):
    """The xLSTM INL split's train step with its named scopes: the cut
    layer's two kernels keep the names the cut-layer reader
    (bench/metrics/cutlayer_us_per_step.xlstm_train.py) finds, and the
    scopes reach the compiled program's op_names."""
    import re
    text = xlstm_inl_step_hlo
    kernels = sorted(
        re.sub(r"\.\d+$", "", line.split(" = ")[0].strip())
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    assert kernels == ["%jvp_jit__cutlayer_call__",
                       "%transpose_jvp_jit__cutlayer_call___"]
    for scope in ("encoder", "cut", "decoder", "loss", "optimizer", "mlstm",
                  "slstm"):
        assert re.search(rf'op_name="[^"]*/(?:\w+\()*{scope}\)*/', text), \
            scope


def test_the_recurrence_reader_sees_the_chunkwise_mlstms_matmuls(
        xlstm_inl_step_hlo):
    """The instructions `recurrence_us_per_step.xlstm_train` times
    (bench/drivers/llm_train.recurrence_program) hold the chunkwise
    mLSTM's matrix products, forward and backward, and not only the
    sLSTM's."""
    import re

    from bench import program_trace
    from bench.drivers import llm_train
    text = xlstm_inl_step_hlo
    recurrent = llm_train.recurrence_program(text)["recurrent"]
    _, names = program_trace.op_names(text)
    opcode = {}
    for line in text.splitlines():
        hit = re.match(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*\S+\s+"
                       r"([a-z][\w-]*)\(", line)
        if hit:
            opcode[hit.group(1)] = hit.group(2)
    products = [names[i] for i in recurrent
                if opcode.get(i) in ("dot", "convolution")]
    mlstm = [n for n in products if re.search(r"/(?:\w+\()*mlstm\)*/", n)]
    assert recurrent and mlstm, sorted(opcode.get(i) for i in recurrent)
    assert any("transpose(" in n for n in mlstm)
    assert any("transpose(" not in n for n in mlstm)
