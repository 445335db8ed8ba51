"""The scan runner's device gather: `run_scheme(dispatch="scan")` keeps the
view set resident on the device and builds each epoch's superbatch there.

Every epoch program call must receive exactly what the host-side assembly
gave it: `np.moveaxis(views[:, idx], 0, 2)`, `labels[idx]` and the epoch's
`_split_chain` round keys, bit for bit, for numpy and device inputs, with
and without a 2-device mesh; and a scan run resumed from a checkpoint must
still equal the uninterrupted run."""
import jax
import numpy as np
import pytest

from repro.configs.paper_inl import PaperExperimentConfig
from repro.core.schemes import base, runner
from repro.data import multiview
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as sharding_lib

CFG = PaperExperimentConfig(num_clients=2, noise_stds=(0.4, 2.0),
                            conv_channels=(4,), d_bottleneck=8,
                            dense_units=(32,), image_shape=(8, 8, 3),
                            dataset_size=72)
N, B, EPOCHS, SEED = 72, 16, 3, 5          # 72 % 16: a dropped remainder


def _data():
    rng = np.random.default_rng(0)
    views = rng.standard_normal((CFG.num_clients, N) + CFG.image_shape,
                                dtype=np.float32)
    labels = rng.integers(0, CFG.num_classes, N).astype(np.int32)
    return views, labels


def _expected(views, labels):
    """Per epoch, the (views, labels, keys) the host-side assembly built."""
    rounds = N // B
    rng = jax.random.PRNGKey(SEED + 1)
    for ep in range(EPOCHS):
        rng, keys = runner._split_chain(rng, rounds)
        idx = np.stack(list(multiview.batch_indices(N, B, seed=ep)))
        idx = idx.reshape(rounds, 1, B)
        yield (np.moveaxis(views[:, idx], 0, 2), labels[idx],
               np.asarray(keys))


@pytest.mark.parametrize("placement", ["one_device", "mesh"])
@pytest.mark.parametrize("kind", ["numpy", "jax"])
def test_epoch_calls_receive_the_host_assembly(kind, placement,
                                               monkeypatch):
    if placement == "mesh" and jax.device_count() < 2:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=2")
    mesh = mesh_lib.make_inl_host_mesh(2) if placement == "mesh" else None
    views, labels = _data()
    calls = []
    make_epoch = base.Scheme.make_epoch

    def hooked(self, *a, **kw):
        epoch_fn = make_epoch(self, *a, **kw)

        def epoch(state, ep_views, ep_labels, ep_rngs):
            calls.append(jax.device_get((ep_views, ep_labels, ep_rngs)))
            if mesh is not None:       # the layout the prefetcher gave
                assert ep_views.sharding.is_equivalent_to(
                    sharding_lib.scheme_batch_shardings(
                        mesh, CFG.num_clients, B)[0], ep_views.ndim)
            return epoch_fn(state, ep_views, ep_labels, ep_rngs)
        return epoch
    monkeypatch.setattr(base.Scheme, "make_epoch", hooked)
    given = (views, labels) if kind == "numpy" else \
        (jax.numpy.asarray(views), jax.numpy.asarray(labels))
    runner.run_scheme("inl", *given, CFG, epochs=EPOCHS, batch_size=B,
                      seed=SEED, eval_n=16, dispatch="scan", mesh=mesh)
    want = list(_expected(views, labels))
    assert len(calls) == len(want) == EPOCHS
    for got, exp in zip(calls, want):
        for g, e in zip(got, exp):
            assert g.dtype == e.dtype and g.shape == e.shape
            np.testing.assert_array_equal(g, e)


def test_scan_resume_equals_the_uninterrupted_run(tmp_path):
    views, labels = _data()
    kw = dict(batch_size=B, seed=SEED, eval_n=16, dispatch="scan")
    golden = runner.run_scheme("inl", views, labels, CFG, epochs=EPOCHS,
                               **kw)
    d = str(tmp_path)
    runner.run_scheme("inl", views, labels, CFG, epochs=1, ckpt_dir=d, **kw)
    resumed = runner.run_scheme("inl", views, labels, CFG, epochs=EPOCHS,
                                ckpt_dir=d, resume=True, **kw)
    assert resumed == golden


def test_superbatch_is_an_exact_copy_of_the_host_gather():
    """The gather alone, with a round group of two batches (FL's shape)."""
    views, _ = _data()
    rows, ev = runner._resident(views, 16)
    assert rows.shape == (CFG.num_clients, N, 8 * 8 * 3)
    np.testing.assert_array_equal(np.asarray(ev), views[:, :16])
    idx = np.random.default_rng(1).integers(0, N, (4, 2, B), np.int32)
    got = jax.jit(lambda r, i: runner._superbatch(
        r, i, image_shape=CFG.image_shape))(rows, idx)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.moveaxis(views[:, idx], 0, 2))
