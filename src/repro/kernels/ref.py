"""Pure-jnp reference implementations (oracles) for every kernel.

These serve two roles:
  1. Oracles for kernel tests (``assert_allclose(pallas(interpret=True), ref)``).
  2. CPU dispatch targets for the dry-run: the blockwise variants have the
     same math/blocking as the Pallas kernels so the lowered HLO stays
     memory-bounded on any backend.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Grouped matmul
# ---------------------------------------------------------------------------

def grouped_matmul_ref(x, w, group_sizes):
    """Each row of x (m, k) times the weight of its group, w (G, k, n);
    groups are consecutive runs of rows, group_sizes (G,) summing to m.
    Oracle only (gathers a (k, n) matrix per row), f32 at HIGHEST."""
    ends = jnp.cumsum(group_sizes)
    gid = jnp.searchsorted(ends, jnp.arange(x.shape[0]), side="right")
    return jnp.einsum("mk,mkn->mn", x.astype(jnp.float32),
                      w.astype(jnp.float32)[gid],
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention_ref(q, k, v, *, causal=True, window=0, q_offset=0, kv_len=None,
                  softmax_scale=None):
    """Naive dense softmax attention with GQA. Oracle only (O(S^2) memory).

    q: (B, Sq, H, Dh); k, v: (B, Skv, KH, Dh); H % KH == 0.
    """
    B, Sq, H, Dh = q.shape
    _, Skv, KH, _ = k.shape
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Sq, KH, G, Dh) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqkgd,bjkd->bqkgj", qf, kf)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    mask = jnp.broadcast_to(mask[None], (B, Sq, Skv))
    if kv_len is not None:
        mask &= kpos[None, None, :] < kv_len[:, None, None]
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgj,bjkd->bqkgd", p, vf)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def flash_attention_jnp(q, k, v, *, causal=True, window=0, q_offset=0,
                        kv_len=None, softmax_scale=None, block_kv=512):
    """Blockwise (flash) attention: lax.scan over KV blocks, f32 accumulators.

    Same math as the Pallas kernel; bounded temp memory; GQA supported.
    """
    B, Sq, H, Dh = q.shape
    _, Skv, KH, _ = k.shape
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5

    block_kv = min(block_kv, Skv)
    pad = (-Skv) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = jnp.full((B,), Skv, jnp.int32)
    nb = (Skv + pad) // block_kv

    qf = q.astype(jnp.float32).reshape(B, Sq, KH, G, Dh) * scale
    qpos = q_offset + jnp.arange(Sq)

    def body(carry, ib):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, ib * block_kv, block_kv, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, ib * block_kv, block_kv, 1)
        s = jnp.einsum("bqkgd,bjkd->bqkgj", qf, kb.astype(jnp.float32))
        kpos = ib * block_kv + jnp.arange(block_kv)
        mask = jnp.ones((Sq, block_kv), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        mask = jnp.broadcast_to(mask[None], (B, Sq, block_kv))
        if kv_len is not None:
            mask &= kpos[None, None, :] < kv_len[:, None, None]
        maskx = mask[:, :, None, None, :]
        s = jnp.where(maskx, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(maskx, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgj,bjkd->bqkgd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KH, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KH, G, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nb))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def decode_attention_jnp(q, k, v, *, kv_len=None, window=0,
                         softmax_scale=None, return_stats=False):
    """Single-token decode attention, direct (non-blockwise) form.

    Written so that a sequence-sharded KV cache lowers to the flash-decode
    pattern under GSPMD (reductions over the sharded Skv axis become small
    logsumexp-combine collectives).  q: (B, 1, H, Dh); k, v: (B, Skv, KH, Dh);
    kv_len: (B,) current lengths (entries >= kv_len masked out).
    """
    B, Sq, H, Dh = q.shape
    _, Skv, KH, _ = k.shape
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5
    # NOTE: k/v stay in their storage dtype — einsum accumulates in f32 via
    # preferred_element_type.  Casting the (B, Skv, KH, Dh) cache to f32
    # would materialize a 2x copy of the whole KV cache per layer.
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qf = qf.reshape(B, Sq, KH, G, Dh)
    s = jnp.einsum("bqkgd,bjkd->bqkgj", qf, k,
                   preferred_element_type=jnp.float32)
    kpos = jnp.arange(Skv)
    if kv_len is not None:
        mask = kpos[None, :] < kv_len[:, None]  # (B, Skv)
        if window:
            mask &= kpos[None, :] >= kv_len[:, None] - window
        s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if kv_len is not None:
        p = jnp.where(mask[:, None, None, None, :], p, 0.0)
    out = jnp.einsum("bqkgj,bjkd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    l = jnp.maximum(p.sum(-1), 1e-20)
    out = out / l[..., None]
    if return_stats:  # (out, running max, sumexp) for streaming combines
        return out.reshape(B, Sq, H, Dh).astype(q.dtype), m[..., 0], l
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


def decode_attention_combine(q, out_old, m_old, l_old, k_new, v_new, *,
                             softmax_scale=None):
    """Fold ONE new (k, v) into a decode-attention partial result.

    Lets decode attend over the *pre-update* cache so the cache
    dynamic-update-slice is write-only (in-place under XLA).  q: (B,1,H,Dh);
    k_new/v_new: (B,1,KH,Dh); (out_old, m_old, l_old) from
    decode_attention_jnp(..., return_stats=True)."""
    B, Sq, H, Dh = q.shape
    KH = k_new.shape[2]
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KH, G, Dh)
    s_new = jnp.einsum("bqkgd,bqkd->bqkg", qf,
                       k_new.astype(jnp.float32))  # (B,1,KH,G)
    m_c = jnp.maximum(m_old, s_new)
    corr = jnp.exp(m_old - m_c) * l_old
    w_new = jnp.exp(s_new - m_c)
    l_c = corr + w_new
    oo = out_old.astype(jnp.float32).reshape(B, Sq, KH, G, Dh)
    vn = v_new.astype(jnp.float32)[:, :, :, None, :]  # (B,1,KH,1,Dh)
    out = (oo * corr[..., None] + vn * w_new[..., None]) / l_c[..., None]
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality)
# ---------------------------------------------------------------------------

def ssd_ref(x, dt, A, B, C, *, initial_state=None):
    """Sequential SSD recurrence (oracle).

    x: (b, s, h, p); dt: (b, s, h); A: (h,) (negative); B, C: (b, s, g, n).
    Returns y: (b, s, h, p), final_state: (b, h, p, n).
    """
    b, s, h, p = x.shape
    g = B.shape[2]
    n = B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)  # (b, s, h, n)
    Ch = jnp.repeat(C, rep, axis=2)
    decay = jnp.exp(dt * A[None, None, :])  # (b, s, h)
    xdt = x * dt[..., None]  # (b, s, h, p)

    def step(state, inp):
        dec_t, B_t, C_t, xdt_t = inp
        state = state * dec_t[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xdt_t, B_t)
        y_t = jnp.einsum("bhpn,bhn->bhp", state, C_t)
        return state, y_t

    state0 = (jnp.zeros((b, h, p, n), jnp.float32)
              if initial_state is None else initial_state)
    inps = (decay.transpose(1, 0, 2).astype(jnp.float32),
            Bh.transpose(1, 0, 2, 3).astype(jnp.float32),
            Ch.transpose(1, 0, 2, 3).astype(jnp.float32),
            xdt.transpose(1, 0, 2, 3).astype(jnp.float32))
    state, ys = jax.lax.scan(step, state0, inps)
    y = ys.transpose(1, 0, 2, 3)
    return y.astype(x.dtype), state


def _segsum(x):
    """x: (..., L) -> (..., L, L) with out[..., i, j] = sum_{k=j+1..i} x_k
    (lower-triangular; -inf above the diagonal)."""
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked_jnp(x, dt, A, B, C, *, chunk=64, initial_state=None):
    """Chunked SSD (matmul/dual form). Same result as ssd_ref.

    Sequence split into chunks; within-chunk quadratic attention-like matmuls
    (MXU friendly), across-chunk associative scan over the (h, p, n) states
    (log-depth, sequence-sharding friendly).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    pad = (-s) % chunk
    if pad:
        # dt=0 on padded steps => decay 1, no state/output contribution
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y, st = ssd_chunked_jnp(x, dt, A, B, C, chunk=chunk,
                                initial_state=initial_state)
        return y[:, :s], st
    nc = s // chunk

    f32 = jnp.float32
    Bh = jnp.repeat(B, rep, axis=2).astype(f32).reshape(b, nc, chunk, h, n)
    Ch = jnp.repeat(C, rep, axis=2).astype(f32).reshape(b, nc, chunk, h, n)
    xdt = (x * dt[..., None]).astype(f32).reshape(b, nc, chunk, h, p)
    dA = (dt * A[None, None, :]).astype(f32).reshape(b, nc, chunk, h)
    dA = dA.transpose(0, 1, 3, 2)  # (b, nc, h, L)

    # 1. within-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dA))  # (b, nc, h, L, L)
    y_diag = jnp.einsum("bclhn,bcshn,bchls,bcshp->bclhp", Ch, Bh, L, xdt)

    # 2. chunk-final states
    dA_cum = jnp.cumsum(dA, axis=-1)  # (b, nc, h, L)
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)  # (b, nc, h, L)
    states = jnp.einsum("bclhn,bchl,bclhp->bchpn", Bh, decay_states, xdt)

    # 3. inter-chunk recurrence via associative scan
    chunk_decay = jnp.exp(dA_cum[..., -1])  # (b, nc, h)
    if initial_state is not None:
        states = states.at[:, 0].add(
            chunk_decay[:, 0][..., None, None] * initial_state.astype(f32))

    def combine(a, c):
        a_l, s_l = a
        a_r, s_r = c
        return a_l * a_r, s_l * a_r[..., None, None] + s_r

    acc_decay, acc_states = jax.lax.associative_scan(
        combine, (chunk_decay.swapaxes(0, 1), states.swapaxes(0, 1)))
    acc_states = acc_states.swapaxes(0, 1)  # inclusive: state at end of chunk c
    prev_states = jnp.concatenate(
        [jnp.zeros_like(acc_states[:, :1]) if initial_state is None
         else initial_state.astype(f32)[:, None], acc_states[:, :-1]], axis=1)

    # 4. off-diagonal contribution
    decay_in = jnp.exp(dA_cum)  # (b, nc, h, L)
    y_off = jnp.einsum("bclhn,bchl,bchpn->bclhp", Ch, decay_in, prev_states)

    y = (y_diag + y_off).reshape(b, s, h, p).astype(x.dtype)
    return y, acc_states[:, -1]


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """One decode step of the SSD recurrence. state: (b,h,p,n)."""
    h = x_t.shape[-2]
    g = B_t.shape[-2]
    rep = h // g
    Bh = jnp.repeat(B_t, rep, axis=-2).astype(jnp.float32)
    Ch = jnp.repeat(C_t, rep, axis=-2).astype(jnp.float32)
    dec = jnp.exp(dt_t * A[None, :]).astype(jnp.float32)
    state = state * dec[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", (x_t * dt_t[..., None]).astype(jnp.float32), Bh)
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return state, y.astype(x_t.dtype)


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma)
# ---------------------------------------------------------------------------

RGLRU_C = 8.0


def rglru_gates(x, wa, wx, log_lambda):
    """Compute (log_a, gated_x) for the RG-LRU from inputs.

    x: (b, s, w); wa, wx: (w, w) recurrence/input gate weights;
    log_lambda: (w,) parametrizes a = sigmoid(log_lambda).
    """
    r = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", x, wa))
    i = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", x, wx))
    log_a = -RGLRU_C * r * jax.nn.softplus(-log_lambda)[None, None, :]
    a2 = jnp.exp(2.0 * log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12)) * (i * x)
    return log_a, gated


def rglru_ref(log_a, gated_x, *, h0=None):
    """Sequential linear recurrence h_t = a_t h_{t-1} + gx_t (oracle)."""
    b, s, w = gated_x.shape
    a = jnp.exp(log_a.astype(jnp.float32))

    def step(h, inp):
        a_t, gx_t = inp
        h = a_t * h + gx_t
        return h, h

    h_init = jnp.zeros((b, w), jnp.float32) if h0 is None else h0
    h, ys = jax.lax.scan(step, h_init,
                         (a.swapaxes(0, 1), gated_x.astype(jnp.float32).swapaxes(0, 1)))
    return ys.swapaxes(0, 1).astype(gated_x.dtype), h


def rglru_scan_jnp(log_a, gated_x, *, h0=None):
    """Associative-scan RG-LRU (log-depth; sequence-sharding friendly)."""
    a = jnp.exp(log_a.astype(jnp.float32)).swapaxes(0, 1)  # (s, b, w)
    gx = gated_x.astype(jnp.float32).swapaxes(0, 1)
    if h0 is not None:
        gx = gx.at[0].add(a[0] * h0)

    def combine(l, r):
        a_l, x_l = l
        a_r, x_r = r
        return a_l * a_r, x_l * a_r + x_r

    _, hs = jax.lax.associative_scan(combine, (a, gx))
    return hs.swapaxes(0, 1).astype(gated_x.dtype), hs[-1]


# ---------------------------------------------------------------------------
# Block-local top-k compression (the paper's Q operator)
# ---------------------------------------------------------------------------

def topk_mask_exact(x, theta, *, block=1024):
    """Exact per-block top-k masking via sort. x: (..., L) flat last dim
    padded to a multiple of `block`; theta: scalar in (0, 1] (may be traced).

    Returns (masked_x, kept_mask). Keeps ceil(theta*block) largest-|.| items
    in each block (ties resolved by magnitude order, deterministic)."""
    L = x.shape[-1]
    assert L % block == 0, (L, block)
    nb = L // block
    xb = x.reshape(*x.shape[:-1], nb, block)
    mag = jnp.abs(xb)
    k = jnp.clip(jnp.ceil(theta * block).astype(jnp.int32), 1, block)
    srt = jnp.sort(mag, axis=-1)  # ascending
    # threshold = k-th largest = srt[..., block - k]
    thr = jnp.take_along_axis(
        srt, jnp.broadcast_to(block - k, srt.shape[:-1])[..., None], axis=-1)
    keep = mag >= thr
    # resolve ties: keep exactly k by rank (stable): rank by (mag, index)
    masked = jnp.where(keep, xb, 0.0)
    return masked.reshape(x.shape), keep.reshape(x.shape)


def topk_mask_bisect_jnp(x, theta, *, block=1024, iters=16):
    """Bisection-threshold block top-k (same semantics as the Pallas kernel).

    Per block, binary-search a magnitude threshold t so that
    |{i : |x_i| > t}| ~= ceil(theta*block); keep entries above t.  Iteration
    count fixed (16) => deterministic, sort-free, VPU-friendly.
    """
    L = x.shape[-1]
    assert L % block == 0, (L, block)
    nb = L // block
    xb = x.reshape(*x.shape[:-1], nb, block)
    mag = jnp.abs(xb.astype(jnp.float32))
    k = jnp.clip(jnp.ceil(theta * block), 1.0, float(block))
    lo = jnp.zeros(mag.shape[:-1], jnp.float32)
    hi0 = mag.max(axis=-1)
    hi = hi0
    # Unrolled (not fori_loop): each compare+count fuses into one pass
    # over mag instead of paying loop-carried materialization — ~1.4x on
    # the 8x1M bench row.
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        cnt = (mag > mid[..., None]).sum(axis=-1).astype(jnp.float32)
        # too many kept -> raise threshold
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
    # lower-bound threshold: ties are kept (see kernels/topk_compress.py)
    keep = mag > lo[..., None]
    # Keep at least one element per block (the max) so theta>0 always
    # ships information even for near-constant blocks.  "nothing kept" is
    # equivalent to hi0 == 0 (all-zero block): the bisection invariant
    # keeps count(mag > lo) > k >= 1 whenever lo > 0, and at lo == 0 the
    # strict mag > 0 test only misses all-zero blocks — so the per-block
    # keep.sum recount is a redundant full pass over mag.
    is_max = mag >= hi0[..., None]
    keep = keep | (is_max & (hi0 == 0.0)[..., None])
    masked = jnp.where(keep, xb, 0.0)
    return masked.reshape(x.shape), keep.reshape(x.shape)
