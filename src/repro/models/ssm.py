"""State-space and recurrent blocks: Mamba2 (SSD) and xLSTM (mLSTM / sLSTM).

Mamba2 uses the chunked SSD formulation: intra-chunk terms are dense einsums
(MXU-friendly, fully vectorised over chunks -> visible to cost_analysis), with
a tiny lax.scan only for the inter-chunk state recurrence.  The Pallas kernel
(repro.kernels.ssm_scan) implements the same chunked contract for TPU.

xLSTM blocks keep exponential gating + max-stabiliser state, faithful to
arXiv:2405.04517.  Over a sequence the mLSTM runs chunkwise-parallel
(dense matmuls within a chunk, a lax.scan only over the chunk states); at
decode it steps its recurrent cell.  The sLSTM's recurrent gates leave it
a sequential lax.scan over time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers


# ---------------------------------------------------------------------------
# Causal depthwise conv1d (mamba's local conv)
# ---------------------------------------------------------------------------

def conv1d_init(key, channels: int, width: int, dtype):
    w = (jax.random.normal(key, (width, channels), jnp.float32)
         / np.sqrt(width)).astype(dtype)
    return {"w": w, "b": jnp.zeros((channels,), dtype)}


def conv1d_causal(p, x):
    """x: (B, S, C) -> (B, S, C), causal depthwise."""
    width = p["w"].shape[0]
    x = x.astype(p["w"].dtype)      # lax.conv requires matching dtypes
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp, p["w"][:, None, :],
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return out + p["b"]


def conv1d_step(p, x_t, conv_state):
    """Single decode step.  x_t: (B, C); conv_state: (B, width-1, C)."""
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B,w,C)
    out = jnp.einsum("bwc,wc->bc", window, p["w"]) + p["b"]
    return out, window[:, 1:]


# ---------------------------------------------------------------------------
# Mamba2 (SSD)
# ---------------------------------------------------------------------------

def mamba2_init(key, cfg, dtype):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.d_inner(d)
    H = s.num_heads(d)
    N = s.state_dim
    ks = jax.random.split(key, 6)
    # in_proj -> [z, x, B, C, dt]
    proj_out = 2 * d_in + 2 * N + H
    return {
        "in_proj": layers.dense_init(ks[0], d, proj_out, dtype=dtype),
        "conv": conv1d_init(ks[1], d_in + 2 * N, s.conv_width, dtype),
        "A_log": jnp.zeros((H,), jnp.float32),          # A = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),   # softplus(-2) ~ 0.127
        "norm": layers.rmsnorm_init(d_in, dtype),
        "out_proj": layers.dense_init(ks[2], d_in, d, dtype=dtype),
    }


def mamba2_param_count(cfg) -> int:
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.d_inner(d)
    H = s.num_heads(d)
    N = s.state_dim
    n = d * (2 * d_in + 2 * N + H)                      # in_proj
    n += s.conv_width * (d_in + 2 * N) + (d_in + 2 * N)  # conv
    n += 3 * H + d_in                                   # A_log, D, dt_bias, norm
    n += d_in * d                                       # out_proj
    return n


def _ssd_chunked(xh, dt, A, Bm, Cm, D, chunk: int, initial_state=None):
    """Chunked selective-state-space duality scan.

    xh: (B,S,H,P) inputs per head; dt: (B,S,H) post-softplus step sizes;
    A: (H,) negative decay rates; Bm, Cm: (B,S,N) input/output mixers
    (ngroups=1, shared over heads); D: (H,) skip.
    Returns (y: (B,S,H,P), final_state: (B,H,N,P)).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    nc = S // chunk
    assert nc * chunk == S, f"seq {S} not divisible by chunk {chunk}"

    xc = xh.reshape(Bsz, nc, chunk, H, P).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, chunk, N).astype(jnp.float32)

    dA = dtc * A[None, None, None, :]                    # (B,nc,cs,H), <= 0
    cum = jnp.cumsum(dA, axis=2)                         # within-chunk cumsum

    # --- intra-chunk (diagonal) term
    # decay(i<-j) = exp(cum_i - cum_j), applied causally
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,i,j,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)           # (B,nc,i,j)
    att = cb[..., None] * decay * dtc[:, :, None, :, :]  # weight dt_j
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", att, xc)

    # --- chunk-final states
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)      # (B,nc,cs,H)
    chunk_states = jnp.einsum("bcjn,bcjh,bcjhp->bchnp",
                              Bc, decay_to_end * dtc, xc)  # (B,nc,H,N,P)

    # --- inter-chunk recurrence (tiny scan over nc)
    gamma = jnp.exp(cum[:, :, -1, :])                    # (B,nc,H) total decay

    def step(state, inp):
        g, cs_ = inp                                     # (B,H), (B,H,N,P)
        new = state * g[..., None, None] + cs_
        return new, state                                # emit state *entering* chunk

    init = (jnp.zeros((Bsz, H, N, P), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))
    final_state, entering = jax.lax.scan(
        step, init, (jnp.moveaxis(gamma, 1, 0), jnp.moveaxis(chunk_states, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)              # (B,nc,H,N,P)

    # --- inter-chunk output term
    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp",
                         Cc, jnp.exp(cum), entering)

    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    y = y + D[None, None, :, None] * xh.astype(jnp.float32)
    return y, final_state


def mamba2_make_state(cfg, batch: int, dtype):
    s = cfg.ssm
    d_in = s.d_inner(cfg.d_model)
    H = s.num_heads(cfg.d_model)
    return {
        "ssm": jnp.zeros((batch, H, s.state_dim, s.head_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, d_in + 2 * s.state_dim),
                          dtype),
    }


def mamba2_apply(p, cfg, x, *, mode: str, state=None):
    """x: (B,S,d).  Returns (y, new_state)."""
    s = cfg.ssm
    Bsz, S, d = x.shape
    d_in = s.d_inner(d)
    H = s.num_heads(d)
    N = s.state_dim
    P = s.head_dim

    zxbcdt = layers.dense(p["in_proj"], x)
    z, xbc, dt_raw = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * N], axis=-1)
    A = -jnp.exp(p["A_log"])

    if mode == "decode":
        assert S == 1 and state is not None
        xbc_t, conv_state = conv1d_step(p["conv"], xbc[:, 0], state["conv"])
        xbc_t = jax.nn.silu(xbc_t)
        xh = xbc_t[:, :d_in].reshape(Bsz, H, P)
        Bm = xbc_t[:, d_in:d_in + N]
        Cm = xbc_t[:, d_in + N:]
        dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
        dA = jnp.exp(dt * A)                             # (B,H)
        # state update: S <- S * exp(dt A) + dt * B (x) outer
        upd = jnp.einsum("bh,bn,bhp->bhnp", dt, Bm.astype(jnp.float32),
                         xh.astype(jnp.float32))
        ssm_state = state["ssm"] * dA[..., None, None] + upd
        y = jnp.einsum("bn,bhnp->bhp", Cm.astype(jnp.float32), ssm_state)
        y = y + p["D"][None, :, None] * xh.astype(jnp.float32)
        y = y.reshape(Bsz, 1, d_in)
        new_state = {"ssm": ssm_state, "conv": conv_state}
    else:
        xbc = jax.nn.silu(conv1d_causal(p["conv"], xbc))
        xh = xbc[..., :d_in].reshape(Bsz, S, H, P)
        Bm = xbc[..., d_in:d_in + N]
        Cm = xbc[..., d_in + N:]
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
        y, fin = _ssd_chunked(xh, dt, A, Bm, Cm, p["D"], s.chunk_size)
        y = y.reshape(Bsz, S, d_in)
        new_state = None
        if mode == "prefill":
            conv_tail = jnp.pad(
                xbc, ((0, 0), (max(0, s.conv_width - 1 - S), 0), (0, 0))
            )[:, -(s.conv_width - 1):]
            # NOTE: conv state must hold PRE-activation xbc; recompute cheaply.
            raw = layers.dense(p["in_proj"], x)[..., d_in:2 * d_in + 2 * N]
            raw = jnp.pad(raw, ((0, 0), (max(0, s.conv_width - 1 - S), 0),
                                (0, 0)))[:, -(s.conv_width - 1):]
            new_state = {"ssm": fin, "conv": raw}
    y = layers.rmsnorm(p["norm"], y.astype(x.dtype) * jax.nn.silu(z),
                       cfg.norm_eps)
    return layers.dense(p["out_proj"], y), new_state


def _scan_chunked_remat(cell, init, seq, S: int, chunk: int):
    """Time scan with chunk-level rematerialisation.

    A plain lax.scan over S steps saves every per-step carry for the
    backward pass (the sLSTM's c, n, h, m per step).  Scanning
    checkpointed CHUNKS saves carries only at chunk boundaries and
    recomputes inside: S/chunk boundary saves + one in-chunk recompute,
    ~chunk x less carry residency.

    cell: (carry, step_inputs) -> (carry, y); seq: tuple of time-major
    (S, ...) arrays.  Falls back to the plain scan when chunk doesn't
    divide S (smoke shapes)."""
    chunk = min(chunk, S)
    if S % chunk or S == chunk:
        return jax.lax.scan(cell, init, seq)
    nch = S // chunk
    seq_c = jax.tree.map(
        lambda t: t.reshape((nch, chunk) + t.shape[1:]), seq)

    @jax.checkpoint
    def chunk_body(carry, chunk_seq):
        return jax.lax.scan(cell, carry, chunk_seq)

    carry, ys = jax.lax.scan(chunk_body, init, seq_c)
    ys = jax.tree.map(lambda t: t.reshape((S,) + t.shape[2:]), ys)
    return carry, ys


# ---------------------------------------------------------------------------
# xLSTM: mLSTM block (matrix memory)
# ---------------------------------------------------------------------------

def mlstm_init(key, cfg, dtype):
    d = cfg.d_model
    d_in = cfg.ssm.expand * d                            # pf = 2 up-projection
    H = cfg.num_heads
    dh = d_in // H
    ks = jax.random.split(key, 8)
    return {
        "up": layers.dense_init(ks[0], d, 2 * d_in, dtype=dtype),  # [x_m, z]
        "conv": conv1d_init(ks[1], d_in, cfg.ssm.conv_width, dtype),
        "wq": layers.dense_init(ks[2], d_in, d_in, dtype=dtype),
        "wk": layers.dense_init(ks[3], d_in, d_in, dtype=dtype),
        "wv": layers.dense_init(ks[4], d_in, d_in, dtype=dtype),
        "w_if": layers.dense_init(ks[5], d_in, 2 * H, dtype=dtype),  # i,f gates
        "norm": layers.rmsnorm_init(d_in, dtype),
        "down": layers.dense_init(ks[6], d_in, d, dtype=dtype),
    }


def mlstm_param_count(cfg) -> int:
    d = cfg.d_model
    d_in = cfg.ssm.expand * d
    H = cfg.num_heads
    n = d * 2 * d_in                                     # up
    n += cfg.ssm.conv_width * d_in + d_in                # conv
    n += 3 * d_in * d_in                                 # q,k,v
    n += d_in * 2 * H                                    # gates
    n += d_in + d_in * d                                 # norm + down
    return n


def mlstm_make_state(cfg, batch: int, dtype):
    d_in = cfg.ssm.expand * cfg.d_model
    H = cfg.num_heads
    dh = d_in // H
    return {
        "C": jnp.zeros((batch, H, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, H, dh), jnp.float32),
        "m": jnp.full((batch, H), -1e30, jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm.conv_width - 1, d_in), dtype),
    }


def _mlstm_cell(carry, qkvif):
    """One step of the stabilised mLSTM recurrence.  All fp32.

    C and n are held scaled by exp(-m), so the paper's normaliser
    max(|n_t . q_t|, 1) on the unscaled state reads max(|n . q|, exp(-m))
    on the scaled one (arXiv:2405.04517, the stabilised mLSTM)."""
    C, n, m = carry
    q, k, v, i_raw, f_raw = qkvif                        # (B,H,dh) x3, (B,H) x2
    log_f = -jax.nn.softplus(-f_raw)                     # log sigmoid(f)
    m_new = jnp.maximum(log_f + m, i_raw)
    i_g = jnp.exp(i_raw - m_new)
    f_g = jnp.exp(log_f + m - m_new)
    C_new = f_g[..., None, None] * C + i_g[..., None, None] * (
        k[..., :, None] * v[..., None, :])               # (B,H,dh_k,dh_v)
    n_new = f_g[..., None] * n + i_g[..., None] * k
    num = jnp.einsum("bhkv,bhk->bhv", C_new, q)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n_new, q)),
                      jnp.exp(-m_new))
    h = num / den[..., None]
    return (C_new, n_new, m_new), h


def _mlstm_chunk(carry, chunk):
    """One chunk of the mLSTM recurrence from the state entering it,
    chunkwise-parallel: the chunk's h and the state leaving it.

    carry: (C (B,H,dh,dh), n (B,H,dh), m (B,H)) in `_mlstm_cell`'s
    stabilised form; chunk: q, k, v (B,H,L,dh), i_raw, f_raw (B,H,L).
    With b_t the cumulative log forget gate from the chunk's start, m_t is
    the recurrent stabiliser max(b_t + m, max_{s<=t} b_t - b_s + i_s), and
    h_t = (w_t q_t C + sum_s D_ts (q_t . k_s) v_s)
          / max(|w_t q_t . n + sum_s D_ts (q_t . k_s)|, exp(-m_t))
    with w_t = exp(b_t + m - m_t), D_ts = exp(b_t - b_s + i_s - m_t).  Every
    product of the float32 state runs at HIGHEST.  h is invariant to m_t,
    so m_t takes no gradient.  The chunk's first L-1 tokens enter the state
    as one product; the last enters through `_mlstm_cell`, so the state
    carried from chunk to chunk is the recurrent cell's own."""
    C, n, m = carry
    q, k, v, i_raw, f_raw = chunk
    L = q.shape[-2]
    hi = jax.lax.Precision.HIGHEST
    b = jnp.cumsum(-jax.nn.softplus(-f_raw), axis=-1)     # (B,H,L)
    log_d = b[..., :, None] - b[..., None, :] + i_raw[..., None, :]
    log_d = jnp.where(jnp.tril(jnp.ones((L, L), bool)), log_d, -jnp.inf)
    m_t = jax.lax.stop_gradient(
        jnp.maximum(b + m[..., None], jnp.max(log_d, axis=-1)))
    w = jnp.exp(b + m[..., None] - m_t)                    # (B,H,L)
    d = jnp.exp(log_d - m_t[..., None])                    # (B,H,L,L)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=hi) * d
    num = w[..., None] * jnp.einsum("bhtd,bhde->bhte", q, C, precision=hi) \
        + jnp.einsum("bhts,bhse->bhte", s, v, precision=hi)
    den = jnp.maximum(
        jnp.abs(w * jnp.einsum("bhtd,bhd->bht", q, n, precision=hi)
                + jnp.sum(s, axis=-1)), jnp.exp(-m_t))
    h = num / den[..., None]
    if L > 1:
        # the state after the first L-1 tokens: row L-2 of w and D
        dk = d[..., L - 2, :, None] * k                    # (B,H,L,dh)
        carry = (w[..., L - 2, None, None] * C
                 + jnp.einsum("bhsd,bhse->bhde", dk, v, precision=hi),
                 w[..., L - 2, None] * n + jnp.sum(dk, axis=-2),
                 m_t[..., L - 2])
    last = (q[..., -1, :], k[..., -1, :], v[..., -1, :], i_raw[..., -1],
            f_raw[..., -1])
    carry, _ = _mlstm_cell(carry, last)
    return carry, h


def mlstm_scan(q, k, v, i_raw, f_raw, chunk: int):
    """The mLSTM recurrence over a sequence from the zero state, in chunks
    of `chunk` tokens (`_mlstm_chunk`), with a `lax.scan` over the chunk
    states only.  q, k, v: (B, S, H, dh); i_raw, f_raw: (B, S, H); all
    fp32.  A ragged tail is padded with i = -inf and log f = 0, which leave
    the state as it was, and its h dropped.  Returns (h (B, S, H, dh), the
    final (C, n, m))."""
    Bsz, S, H, dh = q.shape
    L = min(chunk, S)
    nc = -(-S // L)
    pad = nc * L - S
    with jax.named_scope("mlstm"):
        init = (jnp.zeros((Bsz, H, dh, dh), jnp.float32),
                jnp.zeros((Bsz, H, dh), jnp.float32),
                jnp.full((Bsz, H), -1e30, jnp.float32))

        def chunks(t, fill=0.0):
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2),
                        constant_values=fill)
            t = t.reshape((Bsz, nc, L) + t.shape[2:])
            return jnp.moveaxis(t, (1, 2), (0, 3))        # (nc, B, H, L, ...)
        seq = (chunks(q), chunks(k), chunks(v), chunks(i_raw, -jnp.inf),
               chunks(f_raw, jnp.inf))
        # checkpointed, the backward pass keeps one entering state per chunk
        # and recomputes the chunk; a closure of this call, so that the scan
        # traces the chunk anew with the `_mlstm_cell` the module holds now
        body = jax.checkpoint(lambda c, x: _mlstm_chunk(c, x))
        carry, hs = jax.lax.scan(body, init, seq)
        hs = jnp.moveaxis(hs, (0, 3), (1, 2)).reshape(Bsz, nc * L, H, dh)
    return hs[:, :S], carry


def mlstm_apply(p, cfg, x, *, mode: str, state=None):
    Bsz, S, d = x.shape
    d_in = cfg.ssm.expand * d
    H = cfg.num_heads
    dh = d_in // H
    up = layers.dense(p["up"], x)
    xm, z = jnp.split(up, 2, axis=-1)

    if mode == "decode":
        assert S == 1 and state is not None
        xc, conv_state = conv1d_step(p["conv"], xm[:, 0], state["conv"])
        xc = jax.nn.silu(xc)[:, None]
    else:
        xc = jax.nn.silu(conv1d_causal(p["conv"], xm))
        conv_state = None

    def heads(t):
        return t.reshape(Bsz, -1, H, dh).astype(jnp.float32)

    q = heads(layers.dense(p["wq"], xc)) / np.sqrt(dh)
    k = heads(layers.dense(p["wk"], xc)) / np.sqrt(dh)
    v = heads(layers.dense(p["wv"], xm))                  # v from pre-conv branch
    gates = layers.dense(p["w_if"], xc).astype(jnp.float32)
    i_raw, f_raw = jnp.split(gates.reshape(Bsz, -1, 2, H), 2, axis=2)
    i_raw, f_raw = i_raw[:, :, 0], f_raw[:, :, 0]         # (B,S,H)

    if mode == "decode":
        carry = (state["C"], state["n"], state["m"])
        with jax.named_scope("mlstm"):
            carry, h = _mlstm_cell(carry, (q[:, 0], k[:, 0], v[:, 0],
                                           i_raw[:, 0], f_raw[:, 0]))
        h = h[:, None]                                    # (B,1,H,dh)
        new_state = {"C": carry[0], "n": carry[1], "m": carry[2],
                     "conv": conv_state}
    else:
        h, carry = mlstm_scan(q, k, v, i_raw, f_raw, cfg.ssm.chunk_size)
        new_state = None
        if mode == "train" and cfg.ssm.record_mlstm:
            new_state = {"q": q, "k": k, "v": v, "i": i_raw, "f": f_raw,
                         "h": h}
        if mode == "prefill":
            raw_tail = jnp.pad(xm, ((0, 0), (max(0, cfg.ssm.conv_width - 1 - S),
                                             0), (0, 0)))
            new_state = {"C": carry[0], "n": carry[1], "m": carry[2],
                         "conv": raw_tail[:, -(cfg.ssm.conv_width - 1):]}

    h = h.reshape(Bsz, -1, d_in).astype(x.dtype)
    h = layers.rmsnorm(p["norm"], h, cfg.norm_eps) * jax.nn.silu(z)
    return layers.dense(p["down"], h), new_state


# ---------------------------------------------------------------------------
# xLSTM: sLSTM block (scalar memory, recurrent gates)
# ---------------------------------------------------------------------------

def slstm_init(key, cfg, dtype):
    d = cfg.d_model
    H = cfg.num_heads
    dh = d // H
    ks = jax.random.split(key, 4)
    ff = int(np.ceil(4 / 3 * d / 64) * 64)               # pf=4/3 gated FFN
    return {
        "wx": layers.dense_init(ks[0], d, 4 * d, dtype=dtype),   # i,f,z,o from x
        "r": (jax.random.normal(ks[1], (H, dh, 4 * dh), jnp.float32)
              / np.sqrt(dh)).astype(dtype),              # block-diag recurrence
        "norm": layers.rmsnorm_init(d, dtype),
        "ffn": layers.mlp_init(ks[2], d, ff, act="silu", dtype=dtype),
        "ffn_norm": layers.rmsnorm_init(d, dtype),
    }


def slstm_param_count(cfg) -> int:
    d = cfg.d_model
    H = cfg.num_heads
    dh = d // H
    ff = int(np.ceil(4 / 3 * d / 64) * 64)
    return d * 4 * d + H * dh * 4 * dh + 2 * d + 3 * d * ff


def slstm_make_state(cfg, batch: int, dtype):
    d = cfg.d_model
    H = cfg.num_heads
    dh = d // H
    return {
        "c": jnp.zeros((batch, H, dh), jnp.float32),
        "n": jnp.zeros((batch, H, dh), jnp.float32),
        "h": jnp.zeros((batch, H, dh), jnp.float32),
        "m": jnp.full((batch, H, dh), -1e30, jnp.float32),
    }


def _slstm_cell(p_r, carry, x_gates, H, dh):
    """x_gates: (B, 4d) pre-activations from the input path."""
    c, n, h, m = carry
    rec = jnp.einsum("bhd,hde->bhe", h, p_r.astype(jnp.float32))  # (B,H,4dh)
    g = x_gates.reshape(-1, H, 4, dh).astype(jnp.float32) \
        + rec.reshape(-1, H, 4, dh)
    i_raw, f_raw, z_raw, o_raw = (g[:, :, 0], g[:, :, 1], g[:, :, 2], g[:, :, 3])
    log_f = -jax.nn.softplus(-f_raw)
    m_new = jnp.maximum(log_f + m, i_raw)
    i_g = jnp.exp(i_raw - m_new)
    f_g = jnp.exp(log_f + m - m_new)
    c_new = f_g * c + i_g * jnp.tanh(z_raw)
    n_new = f_g * n + i_g
    h_new = jax.nn.sigmoid(o_raw) * c_new / jnp.maximum(n_new, 1.0)
    return (c_new, n_new, h_new, m_new)


def slstm_apply(p, cfg, x, *, mode: str, state=None):
    Bsz, S, d = x.shape
    H = cfg.num_heads
    dh = d // H
    xg = layers.dense(p["wx"], x)                        # (B,S,4d)

    if mode == "decode":
        assert S == 1 and state is not None
        carry = (state["c"], state["n"], state["h"], state["m"])
        with jax.named_scope("slstm"):
            carry = _slstm_cell(p["r"], carry, xg[:, 0], H, dh)
        hs = carry[2][:, None]                           # (B,1,H,dh)
        new_state = {"c": carry[0], "n": carry[1], "h": carry[2],
                     "m": carry[3]}
    else:
        def step(carry, g_t):
            new = _slstm_cell(p["r"], carry, g_t, H, dh)
            return new, new[2]
        init = (jnp.zeros((Bsz, H, dh), jnp.float32),
                jnp.zeros((Bsz, H, dh), jnp.float32),
                jnp.zeros((Bsz, H, dh), jnp.float32),
                jnp.full((Bsz, H, dh), -1e30, jnp.float32))
        with jax.named_scope("slstm"):
            carry, hs = _scan_chunked_remat(step, init,
                                            jnp.moveaxis(xg, 1, 0), S,
                                            cfg.ssm.chunk_size)
        hs = jnp.moveaxis(hs, 0, 1)                      # (B,S,H,dh)
        new_state = None
        if mode == "prefill":
            new_state = {"c": carry[0], "n": carry[1], "h": carry[2],
                         "m": carry[3]}

    h = hs.reshape(Bsz, -1, d).astype(x.dtype)
    h = layers.rmsnorm(p["norm"], h, cfg.norm_eps)
    out = h + layers.mlp(
        p["ffn"], layers.rmsnorm(p["ffn_norm"], h, cfg.norm_eps), act="silu")
    return out, new_state
