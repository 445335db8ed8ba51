"""Serving driver: the CLI front end over the INL serving plane
(`repro/serving/`) plus the LLM batched prefill+decode demo.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 8 --prompt-len 64 --gen-len 32

`--arch paper-inl` serves the paper's in-network model: requests fan their
J views into per-node queues, the continuous-batching engine coalesces
whatever is in flight into bucketed fused-cutlayer launches (one compile
per bucket size), and with `--deadline-ms` / `--erasure` the fusion center
fuses WHAT ARRIVED per request — a straggling view misses only its own
fusion, never its batchmates' (per-request-id fault draws).  `--load-gen`
switches from the one-shot block to a seeded Poisson offered-load sweep
with p50/p99 latency and goodput per load point.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import signal
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_config, get_smoke_config
from repro.data import tokens as token_data
from repro.launch import steps as steps_lib
from repro.models import zoo


def greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def clamp_requests(n: int, available: int, *, strict: bool = False) -> int:
    """`--requests` larger than the dataset used to truncate SILENTLY to
    the available rows — the reported accuracy/latency then covered fewer
    requests than asked for.  Clamp loudly (RuntimeWarning), or raise under
    `--strict`."""
    if n <= available:
        return n
    msg = (f"--requests {n} exceeds the {available} requests available in "
           f"the dataset; serving {available}")
    if strict:
        raise ValueError(msg + " is disallowed in strict mode")
    warnings.warn(msg, RuntimeWarning, stacklevel=2)
    return available


def serve_batch(cfg, params, prompts, gen_len: int, *, trace_log=None):
    """prompts: (B, P) int32.  Returns (B, gen_len) generated ids.
    Prefill once, then greedy decode against the growing cache.

    The argmax lives INSIDE the jitted decode step
    (`make_decode_step(greedy=True)`) and the token rides the device
    between steps — the loop never issues a per-token eager argmax against
    in-flight logits, so gen_len steps dispatch back-to-back with no
    blocking host transfer (tests/test_serving.py pins one compile and a
    transfer-guard-clean loop).  `trace_log` is forwarded to the decode
    step for the one-compile assertion."""
    B, P = prompts.shape
    prefill = jax.jit(steps_lib.make_prefill_step(cfg))
    decode = jax.jit(
        steps_lib.make_decode_step(cfg, greedy=True, trace_log=trace_log),
        donate_argnums=(2,))

    if cfg.modality == "audio_tokens":
        batch = {"tokens_mc": jnp.broadcast_to(
            prompts[..., None], (B, P, cfg.num_codebooks))}
    else:
        batch = {"tokens": prompts}
    last_logits, cache = prefill(params, batch)
    cache = zoo.pad_cache(cache, gen_len)

    tok = greedy(last_logits)            # once per batch, not per token
    if tok.ndim > 1:                     # audio: (B, K) -> first codebook
        tok = tok[:, 0]
    out = [tok]
    for t in range(gen_len - 1):
        step_batch = {"cache_len": jnp.asarray(P + t, jnp.int32)}
        if cfg.modality == "audio_tokens":
            step_batch["tokens_mc"] = jnp.broadcast_to(
                tok[:, None, None],
                (B, 1, cfg.num_codebooks)).astype(jnp.int32)
        else:
            step_batch["tokens"] = tok[:, None]
        tok, cache = decode(params, step_batch, cache)
        out.append(tok)
    return jnp.stack(out, axis=1)


@contextlib.contextmanager
def drain_on_signal(engine):
    """SIGTERM/Ctrl-C become a GRACEFUL engine shutdown: admission stops,
    queued work drains, and still-pending futures fail with
    `EngineShutdown` instead of hanging their waiters.  The serve flows
    catch that and flush ServeStats + the bit ledgers before exiting, so
    an interrupted run still reports what it actually served.

    Yields a dict that gains a "sig" key if a signal fired (the caller
    uses it to pick a clean exit code over a crash)."""
    fired = {}

    def _handler(signum, frame):
        fired["sig"] = signum
        engine.shutdown()

    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev[sig] = signal.signal(sig, _handler)
        except ValueError:      # not the main thread (embedded use)
            pass
    try:
        yield fired
    finally:
        for sig, h in prev.items():
            signal.signal(sig, h)


def flush_stats(engine, *, label: str = "shutdown") -> None:
    """The ledger flush every exit path owes the operator: whatever the
    engine completed is reported even when the run was cut short."""
    st = engine.stats
    print(f"[{label}] served={st.completed} launches={st.launches} "
          f"shed={st.shed} patched={st.patched} "
          f"pad_fraction={st.pad_fraction:.2f}")
    print(f"[{label}] ledger: offered={engine.meter.gbits * 1e3:.3f} Mbits "
          f"delivery_ratio={engine.meter.delivery_ratio:.3f}")


def _inl_setup(args):
    """Train a smoke INL model and build the requested serving topology.
    Returns (scheme, state, cfg, topology-or-None, (J, n) views, labels)."""
    from repro.configs.paper_inl import PaperExperimentConfig
    from repro.core import linkfault, schemes
    from repro.core import topology as topology_lib
    from repro.data import multiview

    cfg = PaperExperimentConfig(
        conv_channels=(4,), d_bottleneck=8, dense_units=(32,),
        image_shape=(16, 16, 3), dataset_size=640) if args.smoke \
        else PaperExperimentConfig()
    if args.topology == "tree":
        topo = topology_lib.tree(2, 2)
        cfg = dataclasses.replace(
            cfg, num_clients=topo.num_views(),
            noise_stds=cfg.noise_stds
            + (1.5,) * (topo.num_views() - len(cfg.noise_stds)))
    else:
        topo = topology_lib.star(cfg.num_clients)
    if args.wire == "packed" and cfg.link_bits > 16:
        cfg = dataclasses.replace(cfg, link_bits=8)

    scheme = schemes.get("inl")
    state = scheme.init(cfg, jax.random.PRNGKey(args.seed))
    imgs, labels = multiview.make_base_dataset(
        cfg.dataset_size, image_shape=cfg.image_shape, seed=args.seed)
    views = multiview.make_views(imgs, cfg.noise_stds)
    ckpt_dir = getattr(args, "ckpt_dir", "")
    restored = False
    if ckpt_dir:
        from repro import checkpoint
        if checkpoint.latest_step(ckpt_dir) is not None:
            state, step = checkpoint.restore(ckpt_dir, jax.device_get(state))
            print(f"serving from checkpoint step {step} ({ckpt_dir})")
            restored = True
    if not restored:
        round_fn = scheme.make_round(cfg)
        rng = jax.random.PRNGKey(args.seed + 1)
        epochs = 2 if args.smoke else 5
        for ep in range(epochs):
            for v, l in multiview.multiview_batches(views, labels, 32,
                                                    seed=ep):
                rng, sub = jax.random.split(rng)
                state, _ = round_fn(state, jnp.asarray(v)[None],
                                    jnp.asarray(l)[None], sub)
        if ckpt_dir:
            from repro import checkpoint
            checkpoint.save(ckpt_dir, epochs, jax.device_get(state),
                            extra={"arch": "paper-inl", "epochs": epochs})

    # a network whose uplinks straggle: exponential latency tails around
    # the deadline, plus a little outright loss
    link = None
    if args.deadline_ms is not None:
        link = linkfault.LinkModel(erasure=max(args.erasure, 0.05),
                                   latency_ms=5.0, jitter_ms=10.0)
    elif args.erasure > 0:
        link = linkfault.LinkModel(erasure=args.erasure)
    if link is not None:
        topo = linkfault.with_links(topo, link)
    return scheme, state, cfg, topo, np.asarray(views), np.asarray(labels)


def serve_inl(args):
    """One-shot fuse-what-arrived serving through the continuous-batching
    engine: submit a block of requests, report fused-view stats, accuracy
    under the deadline vs clean, and the per-request bit ledger."""
    from repro.serving import EngineShutdown, ServingEngine

    scheme, state, cfg, topo, views, labels = _inl_setup(args)
    n = clamp_requests(args.requests, views.shape[1], strict=args.strict)
    ev, el = views[:, :n], labels[:n]

    transport = None
    if args.transport:
        from repro.transport import DEFAULT_RETRY, NetworkTransport
        transport = NetworkTransport(topo, cfg, seed=args.seed + 3,
                                     policy=DEFAULT_RETRY,
                                     channels=args.transport)
    engine = ServingEngine(scheme, state, cfg, topology=topo,
                           wire=args.wire, deadline_ms=args.deadline_ms,
                           seed=args.seed + 2, transport=transport,
                           speculative=args.speculative)
    engine.warmup()
    t0 = time.time()
    try:
        with engine, drain_on_signal(engine) as fired:
            probs, results = engine.serve(ev)
    except EngineShutdown:
        flush_stats(engine, label="drained")
        if transport is not None:
            transport.close()
        raise SystemExit(0 if fired.get("sig") else 1)
    dt = time.time() - t0
    arrived = np.asarray([r.views_fused for r in results])
    acc = float(np.mean(np.argmax(probs, -1) == el))
    # the jitted reference: same compiled-prediction semantics as the
    # engine's bucketed launches.  Executables compiled at different batch
    # shapes may round the last ulp differently, so the clean-parity bar
    # is tight-allclose + identical decisions (the eager path is further
    # off still, ~1e-7 of XLA fusion rounding)
    ref_topo = None if args.topology == "star" else topo
    clean = np.asarray(jax.jit(
        lambda st, vv: scheme.predict(st, vv, cfg=cfg, topology=ref_topo)
    )(state, jnp.asarray(ev)))
    clean_acc = float(np.mean(np.argmax(clean, -1) == el))
    dl = "none" if args.deadline_ms is None else f"{args.deadline_ms:g}ms"
    J = engine.topo.num_views()
    print(f"arch=paper-inl served {n} requests over {engine.topo.describe()}"
          f" wire={args.wire}, deadline={dl} ({dt:.1f}s post-warmup)")
    print(f"views fused per request: min={int(arrived.min())} "
          f"mean={arrived.mean():.2f} max={int(arrived.max())} of {J}")
    print(f"launches={engine.stats.launches} "
          f"pad_fraction={engine.stats.pad_fraction:.2f} "
          f"traces={dict(engine.trace_counts)}")
    print(f"accuracy: {acc:.4f} under the deadline vs {clean_acc:.4f} on a "
          f"clean network; offered={engine.meter.gbits * 1e3:.3f} Mbits "
          f"delivery_ratio={engine.meter.delivery_ratio:.3f}")
    if transport is not None:
        snap = transport.snapshot()
        print(f"transport: channels={args.transport} "
              f"patched={engine.stats.patched} "
              f"views_recovered={engine.stats.views_recovered} "
              f"breakers={ {k: b['state'] for k, b in snap['breaker'].items()} }")
        transport.close()
    assert all(c <= 1 for c in engine.trace_counts.values()), \
        f"bucket predict retraced: {engine.trace_counts}"
    if args.deadline_ms is not None:
        # speculative fusion RECOVERS stragglers (their patched fusion
        # fuses everything that eventually arrived), so the evidence the
        # deadline bit is either a short fusion or a patched request
        assert int(arrived.min()) < J or engine.stats.patched > 0, \
            "deadline never bit — straggler path not exercised"
    if not engine.faulty:
        assert np.allclose(probs, clean, atol=2e-6, rtol=0), \
            "clean-network serving drifted from jitted scheme.predict"
        assert np.array_equal(np.argmax(probs, -1), np.argmax(clean, -1)), \
            "clean-network serving changed a decision vs scheme.predict"
    assert arrived.min() >= 0 and acc >= 0.0


def serve_inl_loadgen(args):
    """Poisson offered-load sweep: calibrate serial capacity, then offer
    multiples of it and print p50/p99 latency + goodput per point."""
    from repro.serving import (EngineShutdown, ServingEngine,
                               measure_serial_capacity, run_poisson)

    scheme, state, cfg, topo, views, labels = _inl_setup(args)
    n = clamp_requests(args.requests, views.shape[1], strict=args.strict)
    pool = views[:, :n]

    serial = ServingEngine(scheme, state, cfg, topology=topo,
                           wire=args.wire, deadline_ms=args.deadline_ms,
                           buckets=(1,), seed=args.seed + 2)
    serial.warmup()
    with serial:
        cap = measure_serial_capacity(serial, pool,
                                      num_requests=min(n, 32))
    print(f"serial capacity: {cap:.1f} req/s over {serial.topo.describe()}")

    engine = ServingEngine(scheme, state, cfg, topology=topo,
                           wire=args.wire, deadline_ms=args.deadline_ms,
                           seed=args.seed + 2, max_queue=args.max_queue)
    engine.warmup()
    print(f"{'offered_rps':>12} {'goodput_rps':>12} {'p50_ms':>9} "
          f"{'p99_ms':>9} {'fused':>6} {'shed':>5}")
    try:
        with engine, drain_on_signal(engine) as fired:
            for mult in (0.5, 2.0, 8.0):
                s = run_poisson(engine, pool, rate_rps=cap * mult,
                                num_requests=n,
                                seed=args.seed + int(mult * 10))
                print(f"{s['offered_rps']:12.1f} {s['goodput_rps']:12.1f} "
                      f"{s['p50_ms']:9.2f} {s['p99_ms']:9.2f} "
                      f"{s['mean_views_fused']:6.2f} {s['shed']:5d}")
    except EngineShutdown:
        flush_stats(engine, label="drained")
        raise SystemExit(0 if fired.get("sig") else 1)
    flush_stats(engine, label="done")
    assert all(c <= 1 for c in engine.trace_counts.values()), \
        f"bucket predict retraced: {engine.trace_counts}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="paper-inl: per-request fusion deadline — latents "
                         "missing it are dropped and the survivors fused")
    ap.add_argument("--topology", choices=("star", "tree"), default="star",
                    help="paper-inl: serving graph (tree = tree(2, 2))")
    ap.add_argument("--erasure", type=float, default=0.0,
                    help="paper-inl: per-link erasure probability")
    ap.add_argument("--wire", choices=("dense", "packed"), default="dense",
                    help="paper-inl: relay-hop wire format (graph paths)")
    ap.add_argument("--strict", action="store_true",
                    help="error (rather than clamp) when --requests "
                         "exceeds the dataset")
    ap.add_argument("--ckpt-dir", default="",
                    help="paper-inl: serve the latest checkpoint under this "
                         "directory (skipping the smoke training), or save "
                         "the smoke-trained model there when none exists — "
                         "serving restarts recover instead of retraining")
    ap.add_argument("--transport", choices=("loopback", "socket"),
                    default=None,
                    help="paper-inl: ride each view fragment over a real "
                         "retrying edge channel (repro/transport/) instead "
                         "of in-graph fault draws")
    ap.add_argument("--speculative", action="store_true",
                    help="paper-inl (needs --transport): fuse what arrived "
                         "at the deadline, patch late stragglers into the "
                         "next bucket")
    ap.add_argument("--load-gen", action="store_true",
                    help="paper-inl: Poisson offered-load sweep instead of "
                         "the one-shot block")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="paper-inl --load-gen: bound per-node queue depth; "
                         "arrivals over the bound are shed with a typed "
                         "Rejected result instead of growing latency "
                         "without limit")
    args = ap.parse_args()
    compile_cache.enable()

    if args.arch == "paper-inl":
        (serve_inl_loadgen if args.load_gen else serve_inl)(args)
        return

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg, dtype="float32")
    if cfg.modality == "vlm":
        raise SystemExit("serve demo supports text/audio archs; VLM decode "
                         "is exercised via the dry-run")

    params = zoo.init_params(cfg, jax.random.PRNGKey(args.seed))
    prompts = np.asarray(
        token_data.markov_stream(cfg.vocab_size,
                                 args.requests * args.prompt_len,
                                 seed=args.seed)
    ).reshape(args.requests, args.prompt_len).astype(np.int32)

    t0 = time.time()
    gen = serve_batch(cfg, params, jnp.asarray(prompts), args.gen_len)
    dt = time.time() - t0
    toks = args.requests * args.gen_len
    print(f"arch={cfg.name} served {args.requests} requests, "
          f"prompt={args.prompt_len}, generated {args.gen_len} each "
          f"({toks} tokens, {dt:.1f}s, {toks/dt:.1f} tok/s incl. compile)")
    print("sample:", np.asarray(gen[0, :16]).tolist())


if __name__ == "__main__":
    main()
