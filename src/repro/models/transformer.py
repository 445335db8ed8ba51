"""Block composition and the generic decoder stack.

A model is a repeating `block_pattern` (period) of typed blocks scanned over
`num_layers // period` periods, with optional pre-layers outside the scan
(e.g. DeepSeek-V2's dense layer 0) and optional parameter-SHARED blocks
(Zamba2's global attention).  Scanning keeps the HLO small enough that the
80 production dry-run compiles stay tractable; `cfg.scan_layers=False`
unrolls for cost-analysis cross-checks.

Block kinds:
  attn              pre-norm attention + (MLP | MoE [+ dense residual]) block
  mamba             Mamba2 (SSD) block
  mamba+shared_attn Mamba2 block followed by the shared global attention
  mlstm / slstm     xLSTM blocks
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from repro.models import attention, layers, moe, ssm


def block_pattern(cfg):
    if cfg.block_pattern:
        return tuple(cfg.block_pattern)
    return ("attn",)


def num_periods(cfg):
    pat = block_pattern(cfg)
    n_scanned = cfg.num_layers - cfg.moe.first_dense_layers
    assert n_scanned % len(pat) == 0, (
        f"{cfg.name}: {n_scanned} layers not divisible by period {len(pat)}")
    return n_scanned // len(pat)


# ---------------------------------------------------------------------------
# Single blocks
# ---------------------------------------------------------------------------

def _attn_block_init(key, cfg, dtype, *, use_moe: bool):
    ks = jax.random.split(key, 4)
    p = {
        "attn_norm": layers.rmsnorm_init(cfg.d_model, dtype),
        "attn": attention.attn_init(ks[0], cfg, dtype),
        "ffn_norm": layers.rmsnorm_init(cfg.d_model, dtype),
    }
    if use_moe:
        p["moe"] = moe.moe_init(ks[1], cfg, dtype)
        if cfg.moe.dense_residual:
            p["mlp"] = layers.mlp_init(ks[2], cfg.d_model, cfg.d_ff,
                                       act=cfg.act, dtype=dtype)
    else:
        p["mlp"] = layers.mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                   act=cfg.act, dtype=dtype)
    return p


def _attn_block_apply(p, cfg, x, positions, *, mode, cache, cache_len,
                      use_moe: bool):
    h, new_cache = attention.attn_apply(
        p["attn"], cfg, layers.rmsnorm(p["attn_norm"], x, cfg.norm_eps),
        positions, mode=mode, cache=cache, cache_len=cache_len)
    x = x + h
    x = _checkpoint_name(x, "block_out")  # post-AR (see
    hn = layers.rmsnorm(p["ffn_norm"], x, cfg.norm_eps)    # stack_apply)
    aux = _zero_aux(cfg)
    if use_moe:
        if mode == "decode":
            moe_fn = moe.moe_decode_apply
        elif cfg.moe_impl == "ep":
            moe_fn = moe.moe_apply_ep
        else:
            moe_fn = moe.moe_apply
        mo, aux = moe_fn(p["moe"], cfg, hn)
        if cfg.moe.dense_residual:
            mo = mo + layers.mlp(p["mlp"], hn, act=cfg.act)
        x = x + mo
    else:
        x = x + layers.mlp(p["mlp"], hn, act=cfg.act)
    return x, new_cache, aux


def _zero_aux(cfg):
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32),
            "expert_load": jnp.zeros((max(cfg.moe.num_experts, 1),),
                                     jnp.float32)}


# --- shared global attention (Zamba2) --------------------------------------

def _shared_attn_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "in_proj": layers.dense_init(ks[0], 2 * cfg.d_model, cfg.d_model,
                                     dtype=dtype),
        "norm": layers.rmsnorm_init(cfg.d_model, dtype),
        "attn": attention.attn_init(ks[1], cfg, dtype),
        "ffn_norm": layers.rmsnorm_init(cfg.d_model, dtype),
        "ffn": layers.mlp_init(ks[2], cfg.d_model, cfg.d_ff, act=cfg.act,
                               dtype=dtype),
    }


def _shared_attn_apply(shared, adapter, cfg, x, emb0, positions, *, mode,
                       cache, cache_len):
    g = layers.dense(shared["in_proj"], jnp.concatenate([x, emb0], axis=-1))
    h, new_cache = attention.attn_apply(
        shared["attn"], cfg, layers.rmsnorm(shared["norm"], g, cfg.norm_eps),
        positions, mode=mode, cache=cache, cache_len=cache_len)
    g = g + h
    g = g + layers.mlp(shared["ffn"],
                       layers.rmsnorm(shared["ffn_norm"], g, cfg.norm_eps),
                       act=cfg.act)
    # per-invocation (unshared) output adapter — Zamba2's LoRA analogue
    return x + layers.dense(adapter, g)


# ---------------------------------------------------------------------------
# Block dispatch
# ---------------------------------------------------------------------------

def block_init(key, cfg, kind: str, dtype, *, use_moe: bool = False):
    if kind == "attn":
        return _attn_block_init(key, cfg, dtype, use_moe=use_moe)
    if kind == "mamba":
        return {"norm": layers.rmsnorm_init(cfg.d_model, dtype),
                "mamba": ssm.mamba2_init(key, cfg, dtype)}
    if kind == "mamba+shared_attn":
        ks = jax.random.split(key, 2)
        return {"norm": layers.rmsnorm_init(cfg.d_model, dtype),
                "mamba": ssm.mamba2_init(ks[0], cfg, dtype),
                "adapter": layers.dense_init(ks[1], cfg.d_model, cfg.d_model,
                                             dtype=dtype, scale=1e-4)}
    if kind == "mlstm":
        return {"norm": layers.rmsnorm_init(cfg.d_model, dtype),
                "mlstm": ssm.mlstm_init(key, cfg, dtype)}
    if kind == "slstm":
        return {"norm": layers.rmsnorm_init(cfg.d_model, dtype),
                "slstm": ssm.slstm_init(key, cfg, dtype)}
    raise ValueError(kind)


def block_make_cache(cfg, kind: str, batch: int, max_len: int, dtype):
    if kind == "attn":
        return attention.attn_make_cache(cfg, batch, max_len, dtype)
    if kind == "mamba":
        return ssm.mamba2_make_state(cfg, batch, dtype)
    if kind == "mamba+shared_attn":
        return {"mamba": ssm.mamba2_make_state(cfg, batch, dtype),
                "attn": attention.attn_make_cache(cfg, batch, max_len, dtype)}
    if kind == "mlstm":
        return ssm.mlstm_make_state(cfg, batch, dtype)
    if kind == "slstm":
        return ssm.slstm_make_state(cfg, batch, dtype)
    raise ValueError(kind)


def block_apply(p, cfg, kind: str, x, positions, *, mode, cache=None,
                cache_len=None, shared=None, emb0=None, use_moe=False):
    """Returns (x, new_cache, aux)."""
    if kind == "attn":
        return _attn_block_apply(p, cfg, x, positions, mode=mode, cache=cache,
                                 cache_len=cache_len, use_moe=use_moe)
    aux = _zero_aux(cfg)
    if kind == "mamba":
        h, st = ssm.mamba2_apply(p["mamba"], cfg,
                                 layers.rmsnorm(p["norm"], x, cfg.norm_eps),
                                 mode=mode, state=cache)
        return x + h, st, aux
    if kind == "mamba+shared_attn":
        mcache = cache["mamba"] if cache is not None else None
        acache = cache["attn"] if cache is not None else None
        h, mst = ssm.mamba2_apply(p["mamba"], cfg,
                                  layers.rmsnorm(p["norm"], x, cfg.norm_eps),
                                  mode=mode, state=mcache)
        x = x + h
        # shared attention needs a dedicated sub-call to capture its cache
        g = layers.dense(shared["in_proj"], jnp.concatenate([x, emb0], -1))
        hh, ast = attention.attn_apply(
            shared["attn"], cfg,
            layers.rmsnorm(shared["norm"], g, cfg.norm_eps),
            positions, mode=mode, cache=acache, cache_len=cache_len)
        g = g + hh
        g = g + layers.mlp(shared["ffn"],
                           layers.rmsnorm(shared["ffn_norm"], g, cfg.norm_eps),
                           act=cfg.act)
        x = x + layers.dense(p["adapter"], g)
        new_cache = None if mode == "train" else {"mamba": mst, "attn": ast}
        return x, new_cache, aux
    if kind == "mlstm":
        h, st = ssm.mlstm_apply(p["mlstm"], cfg,
                                layers.rmsnorm(p["norm"], x, cfg.norm_eps),
                                mode=mode, state=cache)
        return x + h, st, aux
    if kind == "slstm":
        h, st = ssm.slstm_apply(p["slstm"], cfg,
                                layers.rmsnorm(p["norm"], x, cfg.norm_eps),
                                mode=mode, state=cache)
        return x + h, st, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

def stack_init(key, cfg, dtype):
    pat = block_pattern(cfg)
    nper = num_periods(cfg)
    ks = jax.random.split(key, 4)
    p = {}
    # pre-layers outside the scan (deepseek-v2 dense layer 0)
    if cfg.moe.first_dense_layers:
        pre_keys = jax.random.split(ks[0], cfg.moe.first_dense_layers)
        p["pre"] = [
            _attn_block_init(k, cfg, dtype, use_moe=False) for k in pre_keys]
    # scanned periods: one stacked param tree per position in the period
    pos_params = []
    for i, kind in enumerate(pat):
        keys = jax.random.split(jax.random.fold_in(ks[1], i), nper)
        use_moe = cfg.is_moe and kind == "attn"
        stacked = jax.vmap(
            lambda k: block_init(k, cfg, kind, dtype, use_moe=use_moe))(keys)
        pos_params.append(stacked)
    p["pattern"] = pos_params
    if any("shared_attn" in k for k in pat):
        p["shared"] = _shared_attn_init(ks[2], cfg, dtype)
    return p


def stack_param_count(cfg) -> int:
    pat = block_pattern(cfg)
    nper = num_periods(cfg)
    n = 0
    per_kind = {
        "attn": lambda: (attention.attn_param_count(cfg) + 2 * cfg.d_model
                         + (moe.moe_param_count(cfg)
                            + (layers.mlp_param_count(cfg.d_model, cfg.d_ff,
                                                      cfg.act)
                               if cfg.moe.dense_residual else 0)
                            if cfg.is_moe
                            else layers.mlp_param_count(cfg.d_model, cfg.d_ff,
                                                        cfg.act))),
        "mamba": lambda: ssm.mamba2_param_count(cfg) + cfg.d_model,
        "mamba+shared_attn": lambda: (ssm.mamba2_param_count(cfg) + cfg.d_model
                                      + cfg.d_model * cfg.d_model),
        "mlstm": lambda: ssm.mlstm_param_count(cfg) + cfg.d_model,
        "slstm": lambda: ssm.slstm_param_count(cfg) + cfg.d_model,
    }
    for kind in pat:
        n += nper * per_kind[kind]()
    if cfg.moe.first_dense_layers:
        n += cfg.moe.first_dense_layers * (
            attention.attn_param_count(cfg) + 2 * cfg.d_model
            + layers.mlp_param_count(cfg.d_model, cfg.d_ff, cfg.act))
    if any("shared_attn" in k for k in pat):
        n += (2 * cfg.d_model * cfg.d_model + 2 * cfg.d_model
              + attention.attn_param_count(cfg)
              + layers.mlp_param_count(cfg.d_model, cfg.d_ff, cfg.act))
    return n


def stack_make_cache(cfg, batch: int, max_len: int, dtype):
    pat = block_pattern(cfg)
    nper = num_periods(cfg)
    cache = {}
    if cfg.moe.first_dense_layers:
        cache["pre"] = [block_make_cache(cfg, "attn", batch, max_len, dtype)
                        for _ in range(cfg.moe.first_dense_layers)]
    cache["pattern"] = [
        jax.tree.map(lambda x: jnp.broadcast_to(x, (nper,) + x.shape).copy(),
                     block_make_cache(cfg, kind, batch, max_len, dtype))
        for kind in pat]
    return cache


def stack_apply(p, cfg, x, positions, *, mode, cache=None, cache_len=None):
    """x: (B,S,d) -> (x, new_cache, aux_sum)."""
    pat = block_pattern(cfg)
    nper = num_periods(cfg)
    shared = p.get("shared")
    emb0 = x if shared is not None else None
    aux_sum = _zero_aux(cfg)
    # train mode keeps the blocks' new states only as cfg.ssm.record_mlstm
    # asks (each mLSTM block's recurrence record)
    keep = mode != "train" or cfg.ssm.record_mlstm
    new_cache = {"pattern": []} if keep else None

    if "pre" in p:
        if mode != "train":
            new_cache["pre"] = []
        for i, bp in enumerate(p["pre"]):
            c = cache["pre"][i] if cache is not None else None
            x, nc, aux = block_apply(bp, cfg, "attn", x, positions, mode=mode,
                                     cache=c, cache_len=cache_len,
                                     use_moe=False)
            aux_sum = jax.tree.map(jnp.add, aux_sum, aux)
            if mode != "train":
                new_cache["pre"].append(nc)

    def period_body(carry, scanned):
        xx, aux_acc = carry
        caches_in = scanned["cache"] if mode == "decode" else [None] * len(pat)
        caches_out = []
        for i, kind in enumerate(pat):
            use_moe = cfg.is_moe and kind == "attn"
            xx, nc, aux = block_apply(
                scanned["params"][i], cfg, kind, xx, positions, mode=mode,
                cache=caches_in[i], cache_len=cache_len, shared=shared,
                emb0=emb0, use_moe=use_moe)
            # named so the remat policy can keep the post-all-reduce block
            # output: avoids re-running the TP output all-reduces during
            # backward recompute (EXPERIMENTS.md §Perf iter. 3)
            xx = _checkpoint_name(xx, "block_out")
            aux_acc = jax.tree.map(jnp.add, aux_acc, aux)
            caches_out.append(nc)
        out = {"cache": caches_out if keep else None}
        return (xx, aux_acc), out

    scanned_in = {"params": p["pattern"]}
    if mode == "decode":
        scanned_in["cache"] = cache["pattern"]

    if cfg.scan_layers:
        body = period_body
        if cfg.remat and mode == "train":
            # NOTE: save_only_these_names("block_out") was measured to cut
            # all-reduce by only 0.9% while adding 9 GB/device (the backward
            # recompute still needs the attention-internal all-reduces) —
            # full remat wins; see EXPERIMENTS.md §Perf iter. 3.
            body = jax.checkpoint(
                period_body,
                policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux_sum), outs = jax.lax.scan(body, (x, aux_sum), scanned_in)
        if keep:
            new_cache["pattern"] = outs["cache"]
    else:
        carry = (x, aux_sum)
        outs = []
        for per in range(nper):
            sl = jax.tree.map(lambda t: t[per], scanned_in)
            carry, out = period_body(carry, sl)
            outs.append(out)
        x, aux_sum = carry
        if keep:
            new_cache["pattern"] = jax.tree.map(
                lambda *ts: jnp.stack(ts), *[o["cache"] for o in outs])
    return x, new_cache, aux_sum
