"""Smoke test: the INL system's main paths, once, on one TPU chip.

Drives the entry points a user calls, at the paper model's published widths
and at xLSTM-125M's, with random weights from fixed seeds.  The phases run
in one process, in order, and any failure exits non-zero:

  (a) device       stop unless JAX's first device is a TPU
  (b) pallas       the cut layer resolves to the compiled Pallas kernels, and
                   the lowered INL epoch program holds a tpu_custom_call
  (c) training     core/schemes/runner.run_scheme(dispatch="scan"), one
                   epoch at batch 256 of PaperExperimentConfig() (J=5, conv
                   (32, 64, 128), d_b=64, dense (512, 256)): inl on the packed
                   4-bit wire, inl with learned priors, sl and fl
  (d) serving      serving/engine.ServingEngine on (c)'s learned-prior INL
                   state (star, packed wire, buckets {1, 4, 16, 64}); served
                   argmax == the jitted scheme.predict, at most one trace
                   per bucket
  (e) transformer  the launch/train.py path for --arch xlstm-125m --scheme
                   inl at published widths: 4 steps, 2 per scan, batch 1 x
                   sequence 1024 (J=4 nodes: 4,096 cut rows at d_b=192)

The last line of standard output is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.

`--four-chips` runs only the node-parallel path instead: run_scheme("inl")
with J=4 on a 4-device `client` mesh axis, compared with the same run on one
device.

Usage:  python3 chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

BATCH = 256
# data scale, not width: one short epoch (10 INL/SL rounds, 1 FedAvg round
# of J=5 clients x 2 local steps) instead of the paper's 50,000 images
DATASET_SIZE = 2560
SERVE_REQUESTS = 64          # served in blocks that fill each bucket
# the transformer cut: published widths, 4 steps, 2 per scan, 1 x 1024
# tokens (the params, fp32 masters and AdamW moments take 7.4 GiB and the
# mLSTM scan's matrix states most of the rest of one chip)
LLM_ARCH, LLM_STEPS, LLM_SCAN, LLM_BATCH, LLM_SEQ = "xlstm-125m", 4, 2, 1, 1024
# --four-chips: per-round losses and the final accuracy of the mesh run
# against the one-device run, on the full-precision link: at any quantized
# width the sampled eq.-(6) rate is evaluated at a latent clipped to
# +-QUANT_RANGE and has no lower bound in logvar, so those runs diverge and
# amplify any rounding difference.  Both runs are the same fp32 program,
# but TPU f32 matmuls take bf16 passes by default and the sharded program
# reduces BN statistics, decoder grads and the fan-in in another order, so
# ten Adam rounds drift apart by more than the CPU's rtol 1e-4.
FOUR_CHIP_LOSS_RTOL = 2e-2
FOUR_CHIP_ACC_ATOL = 0.05


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


@contextlib.contextmanager
def observe_epochs(scheme):
    """What run_scheme's epoch programs return — the per-round metrics and
    the trained state, which the runner keeps to itself — recorded by
    wrapping the scheme's make_epoch for the duration."""
    seen = {"metrics": [], "state": None}
    make_epoch = scheme.make_epoch

    def observed_make_epoch(*args, **kw):
        epoch_fn = make_epoch(*args, **kw)

        def epoch(state, *xs):
            state, metrics = epoch_fn(state, *xs)
            seen["metrics"].append(metrics)
            seen["state"] = state
            return state, metrics
        return epoch

    scheme.make_epoch = observed_make_epoch
    try:
        yield seen
    finally:
        del scheme.make_epoch


def paper_data(cfg):
    from repro.data import multiview
    imgs, labels = multiview.make_base_dataset(
        cfg.dataset_size, image_shape=cfg.image_shape, seed=cfg.seed)
    return multiview.make_views(imgs, cfg.noise_stds), labels


def train_scheme(name, cfg, views, labels, *, mesh=None, wire="dense"):
    """One epoch through run_scheme; returns (per-round losses, accuracy,
    trained state)."""
    import numpy as np
    from repro.core import schemes
    from repro.core.schemes import runner
    with observe_epochs(schemes.get(name)) as seen:
        curve = runner.run_scheme(name, views, labels, cfg, epochs=1,
                                  batch_size=BATCH, dispatch="scan",
                                  mesh=mesh, wire=wire)
    check(len(curve) == 1, f"{name}: no accuracy recorded")
    check(seen["metrics"], f"{name}: run_scheme ran no epoch program")
    losses = np.concatenate([np.asarray(m["loss"]).reshape(-1)
                             for m in seen["metrics"]])
    check(np.all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    acc = curve[-1].accuracy
    check(math.isfinite(acc), f"{name}: accuracy {acc}")
    return losses, acc, seen["state"]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device():
    import jax
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no TPU: JAX's first device is {d0.platform} ({d0.device_kind})")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("a", f"device platform={d0.platform} kind={d0.device_kind} "
             f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    return d0


def phase_pallas(cfg):
    import jax
    import jax.numpy as jnp
    from repro.core import schemes
    from repro.kernels import ops
    backend = ops.resolve_backend("auto")
    check(backend == "pallas", f"cut layer resolves to {backend!r}")
    scheme = schemes.get("inl")
    state = jax.eval_shape(lambda k: scheme.init(cfg, k),
                           jax.random.PRNGKey(0))
    rounds = cfg.dataset_size // BATCH
    J, (H, W, C) = cfg.num_clients, cfg.image_shape
    views = jax.ShapeDtypeStruct((rounds, 1, J, BATCH, H, W, C), jnp.float32)
    labels = jax.ShapeDtypeStruct((rounds, 1, BATCH), jnp.int32)
    rngs = jax.ShapeDtypeStruct((rounds, 2), jnp.uint32)
    text = scheme.make_epoch(cfg, wire="packed").lower(
        state, views, labels, rngs).as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, "the lowered INL epoch program holds no tpu_custom_call")
    say("b", f"cut layer backend={backend}; lowered INL epoch program "
             f"holds {n} tpu_custom_call(s)")
    say("b", kernels_match_reference(J * BATCH, cfg.d_bottleneck,
                                     cfg.link_bits))


def kernels_match_reference(rows, d, bits):
    """The compiled cut-layer kernels against the jnp reference
    (kernels/ref.py) on one (rows, d) cut: forward and eq.-(10) backward
    in every rate mode, and the packed wire.  Mosaic's and XLA's exp may
    differ in the last ulp, so values compare at rtol 1e-5; the packed
    lanes must re-encode the kernel's own latent exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import inl_bottleneck as bn
    from repro.kernels import ref
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    mu = jax.random.normal(ks[0], (rows, d))
    lv = 0.5 * jax.random.normal(ks[1], (rows, d))
    eps = jax.random.normal(ks[2], (rows, d))
    gu = jax.random.normal(ks[3], (rows, d))
    gr = jax.random.normal(ks[4], (rows,))
    worst = 0.0
    for mode in ("sample", "analytic", "none"):
        outs = {}
        for impl in ("pallas", "reference"):
            def loss(m, l, e, impl=impl):
                u, rate = bn.cutlayer_fused(m, l, e, link_bits=32,
                                            rate_estimator=mode, impl=impl)
                return jnp.sum(u * gu) + jnp.sum(rate * gr), (u, rate)
            (_, fwd), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(mu, lv, eps)
            outs[impl] = [np.asarray(x) for x in (*fwd, *grads)]
        for a, b in zip(outs["pallas"], outs["reference"]):
            check(np.allclose(a, b, rtol=1e-5, atol=1e-5),
                  f"{mode}: Pallas differs from the reference by "
                  f"{np.max(np.abs(a - b)):.3g}")
            worst = max(worst, float(np.max(np.abs(a - b))))
    u, packed, _ = bn.cutlayer_pack_forward(mu, lv, eps, link_bits=bits,
                                            impl="pallas")
    check(np.array_equal(np.asarray(packed),
                         np.asarray(ref.pack_values_ref(u, bits))),
          "packed lanes differ from the reference packing of u")
    check(np.array_equal(np.asarray(bn.unpack_dequant(packed, d,
                                                      link_bits=bits)),
                         np.asarray(u)),
          "unpack(pack(u)) != u")
    return (f"compiled kernels == jnp reference on a {rows}x{d} cut: fwd+bwd "
            f"x 3 modes within {worst:.3g}; q{bits} pack/unpack exact")


def phase_training(cfg):
    views, labels = paper_data(cfg)
    say("c", f"paper model J={cfg.num_clients} conv={cfg.conv_channels} "
             f"d_b={cfg.d_bottleneck} dense={cfg.dense_units} batch={BATCH}; "
             f"reduced: dataset_size={cfg.dataset_size} (paper: 50000), "
             f"1 epoch")
    full = dataclasses.replace(cfg, link_bits=32)
    runs = [("inl", "inl packed q4", cfg, "packed"),
            ("inl", "inl learned prior",
             dataclasses.replace(full, learned_prior=True), "dense"),
            ("sl", "sl", full, "dense"),
            ("fl", "fl", full, "dense")]
    states = {}
    for name, label, run_cfg, wire in runs:
        t0 = time.perf_counter()
        losses, acc, states[label] = train_scheme(name, run_cfg, views,
                                                  labels, wire=wire)
        say("c", f"{label}: rounds={len(losses)} loss first={losses[0]:.6f}"
                 f" last={losses[-1]:.6f} accuracy={acc:.4f} "
                 f"wall={time.perf_counter() - t0:.1f}s (host clock, "
                 f"compile included)")
    # serve the INL model that learns: on a quantized link the sampled
    # rate has no lower bound (ROADMAP Queue 3) and the q4 run's outputs
    # can sit near uniform, where argmax comparisons mean little
    serve_cfg = dataclasses.replace(runs[1][2], link_bits=cfg.link_bits)
    return serve_cfg, states["inl learned prior"], views, labels


def phase_serving(cfg, state, views, labels):
    import jax
    import numpy as np
    from repro.core import schemes
    from repro.core import topology as topology_lib
    from repro.serving import ServingEngine
    scheme = schemes.get("inl")
    topo = topology_lib.star(cfg.num_clients)
    engine = ServingEngine(scheme, state, cfg, topology=topo, wire="packed")
    t0 = time.perf_counter()
    engine.warmup()
    warm = time.perf_counter() - t0
    ev = np.asarray(views[:, :SERVE_REQUESTS])
    # blocks of 1, 3, 12 and 48 requests, served inline, land in buckets
    # 1, 4, 16 and 64: every bucket answers real requests
    t0 = time.perf_counter()
    parts = [engine.serve(ev[:, lo:hi])
             for lo, hi in ((0, 1), (1, 4), (4, 16), (16, SERVE_REQUESTS))]
    served = time.perf_counter() - t0
    probs = np.concatenate([p for p, _ in parts])
    results = [r for _, rs in parts for r in rs]
    ref = np.asarray(jax.jit(
        lambda st, v: scheme.predict(st, v, cfg=cfg))(state, ev))
    check(len(results) == SERVE_REQUESTS, f"served {len(results)} requests")
    check(np.all(np.isfinite(probs)), "non-finite served probabilities")
    agree = int(np.sum(np.argmax(probs, -1) == np.argmax(ref, -1)))
    check(agree == SERVE_REQUESTS,
          f"served argmax differs from jitted scheme.predict on "
          f"{SERVE_REQUESTS - agree} of {SERVE_REQUESTS} requests")
    traces = dict(engine.trace_counts)
    check(all(c <= 1 for c in traces.values()),
          f"a bucket predict retraced: {traces}")
    acc = float(np.mean(np.argmax(probs, -1) == labels[:SERVE_REQUESTS]))
    say("d", f"served {len(results)} requests over {engine.topo.describe()} "
             f"wire=packed launches={engine.stats.launches} accuracy={acc} "
             f"traces={traces} argmax==jit(predict) on {agree}/"
             f"{SERVE_REQUESTS} max|dprob|="
             f"{float(np.max(np.abs(probs - ref))):.3g} "
             f"warmup={warm:.1f}s serve={served:.2f}s (host clock)")


def phase_transformer(device):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core import inl_llm
    from repro.launch import steps as steps_lib
    from repro.launch import train
    argv = ["--arch", LLM_ARCH, "--scheme", "inl", "--steps", str(LLM_STEPS),
            "--scan-steps", str(LLM_SCAN), "--batch", str(LLM_BATCH),
            "--seq", str(LLM_SEQ)]
    cfg = get_config(LLM_ARCH)
    k, b, s = LLM_SCAN, LLM_BATCH, LLM_SEQ
    rows = cfg.inl.num_nodes * b * s
    # what one scan group needs on the device, before anything runs
    optimizer = train.make_optimizer(3e-4, LLM_STEPS)
    params = jax.eval_shape(lambda key: inl_llm.init(cfg, key),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    batches = {n: jax.ShapeDtypeStruct((k, b, s), jnp.int32)
               for n in ("tokens", "labels")}
    rngs = jax.ShapeDtypeStruct((k, 2), jnp.uint32)
    t0 = time.perf_counter()
    compiled = steps_lib.make_scan_train_step(
        cfg, optimizer, scheme="inl").lower(
        params, opt_state, batches, rngs).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    limit = (device.memory_stats() or {}).get("bytes_limit", 0)
    n_params = sum(math.prod(x.shape) for x in jax.tree.leaves(params))
    say("e", f"{cfg.name} inl J={cfg.inl.num_nodes} d_b="
             f"{cfg.inl.d_bottleneck} params={n_params:,} batch={b} seq={s} "
             f"cut rows={rows:,}; memory_analysis: args="
             f"{ma.argument_size_in_bytes / 2**30:.2f} GiB temp="
             f"{ma.temp_size_in_bytes / 2**30:.2f} GiB total~"
             f"{need / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB; "
             f"compile={time.perf_counter() - t0:.1f}s")
    check("tpu_custom_call" in compiled.as_text(),
          "the xLSTM INL step holds no Pallas cut layer")
    check(not limit or need < limit, "the scan step does not fit the chip")
    t0 = time.perf_counter()
    history = train.main(argv)
    losses = np.asarray([h["loss"] for h in history])
    check(len(history) == LLM_STEPS // k,
          f"train.main ran {len(history)} scan groups")
    check(np.all(np.isfinite(losses)), f"non-finite LLM loss {losses}")
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    say("e", f"train.main {' '.join(argv)}: losses={losses.tolist()} "
             f"peak_bytes_in_use={peak / 2**30:.2f} GiB "
             f"wall={time.perf_counter() - t0:.1f}s (host clock)")


def phase_four_chips(cfg):
    import jax
    import numpy as np
    from repro.launch import mesh as mesh_lib
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = dataclasses.replace(cfg, num_clients=4,
                              noise_stds=cfg.noise_stds[:4], link_bits=32)
    views, labels = paper_data(cfg)
    mesh = mesh_lib.make_inl_host_mesh(cfg.num_clients)
    check(mesh.shape["client"] == 4,
          f"client axis is {mesh.shape['client']}, not 4")
    say("g", f"mesh {dict(mesh.shape)} over "
             f"{[d.id for d in mesh.devices.flat]}; inl J=4 q32 dense wire "
             f"batch={BATCH} dataset_size={cfg.dataset_size}")
    out = {}
    for label, m in (("mesh", mesh), ("one device", None)):
        t0 = time.perf_counter()
        losses, acc, _ = train_scheme("inl", cfg, views, labels, mesh=m)
        out[label] = (losses, acc)
        say("g", f"{label}: rounds={len(losses)} losses={losses.tolist()} "
                 f"accuracy={acc:.4f} wall={time.perf_counter() - t0:.1f}s "
                 f"(host clock)")
    (l4, a4), (l1, a1) = out["mesh"], out["one device"]
    rel = float(np.max(np.abs(l4 - l1) / np.abs(l1)))
    say("g", f"max relative loss difference {rel:.3g} (limit "
             f"{FOUR_CHIP_LOSS_RTOL}); accuracy {a4:.4f} vs {a1:.4f} "
             f"(limit {FOUR_CHIP_ACC_ATOL})")
    check(rel <= FOUR_CHIP_LOSS_RTOL, "mesh losses drifted from one device")
    check(abs(a4 - a1) <= FOUR_CHIP_ACC_ATOL,
          "mesh accuracy drifted from one device")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the node-parallel INL path on a 4-chip "
                         "client axis and its one-device comparison")
    args = ap.parse_args(argv)
    try:
        from repro import compile_cache
        from repro.configs.paper_inl import PaperExperimentConfig
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    compile_cache.enable()
    import jax
    # the packed wire needs a narrow link: 4-bit codewords, 8 per lane
    cfg = PaperExperimentConfig(dataset_size=DATASET_SIZE, link_bits=4)
    t_start = time.perf_counter()
    try:
        device = phase_device()
        if args.four_chips:
            phase_four_chips(cfg)
        else:
            phase_pallas(cfg)
            phase_serving(*phase_training(cfg))
            phase_transformer(device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"# all phases passed in {time.perf_counter() - t_start:.1f}s "
          f"(host clock)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
