"""Pallas TPU flash attention (blockwise, causal/windowed, GQA).

TARGET: TPU v5e (MXU 128x128, VMEM-resident q/kv tiles).  Validated on CPU
with interpret=True against ``ref.attention_ref``.

Layout: q (B, H, Sq, Dh); k, v (B, KH, Skv, Dh); out (B, H, Sq, Dh).
Grid (B, KH, nQ, nKV) with the KV dimension innermost; running (m, l, acc)
accumulators live in VMEM scratch and the output tile is written on the last
KV step.  Causal/window blocks that are fully masked are skipped with
``pl.when`` (no MXU work issued).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Paged KV gather (DESIGN.md §Serving contract)
# ---------------------------------------------------------------------------

def gather_kv_pages(pages, page_table, *, contiguous=False):
    """Assemble per-request KV views from the paged pool.

    pages: (NP, ps, ...) physical page pool (page 0 = null);
    page_table: (B, P) int32 physical page ids per request.
    Returns (B, P * ps, ...) — request b's logical positions in order.

    ``contiguous=True`` is the dense fallback: the caller asserts (host-
    side, static) that slot b owns exactly pages [1 + b*P, 1 + (b+1)*P),
    so the gather degenerates to a reshape of the pool — zero data
    movement, bit-for-bit identical to the gather (pinned in
    tests/test_serving.py).
    """
    B, P = page_table.shape
    ps = pages.shape[1]
    tail = pages.shape[2:]
    if contiguous:
        return jax.lax.dynamic_slice_in_dim(pages, 1, B * P, 0).reshape(
            (B, P * ps) + tail)
    return jnp.take(pages, page_table, axis=0).reshape((B, P * ps) + tail)


def _paged_kernel(pt_ref, kl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  m_scr, l_scr, acc_scr, *, ps, np_, scale):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kvl = kl_ref[b]

    @pl.when(j * ps < kvl)  # pages fully past kv_len issue no MXU work
    def _compute():
        # q rows are block-diagonal over the KH heads' lanes, so one
        # (H, KH*Dh) x (KH*Dh, ps) matmul gives every head its own scores
        q = q_ref[0].astype(jnp.float32) * scale      # (H, KH*Dh)
        k = k_ref[0].astype(jnp.float32)              # (ps, KH*Dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kvl, s, NEG_INF)
        m_prev = m_scr[...]                           # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(kpos < kvl, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(j == np_ - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        m_ref[0] = m_scr[...]
        l_ref[0] = l


def paged_decode_attention_pallas(q, k_pages, v_pages, page_table, kv_len, *,
                                  softmax_scale=None, interpret=False):
    """Single-token decode attention reading KV through a page table.

    q: (B, 1, H, Dh); k_pages/v_pages: (NP, ps, KH, Dh); page_table:
    (B, P) int32; kv_len: (B,) int32.  Returns (out (B,1,H,Dh),
    m (B,1,KH,G), l (B,1,KH,G)) — the same normalized-out + stats
    contract as ``ref.decode_attention_jnp(return_stats=True)`` so the
    caller folds the current token's (k, v) in with
    ``decode_attention_combine``.

    The page table and kv_len ride in as scalar-prefetch operands
    (``PrefetchScalarGridSpec``): the grid's page step j DMAs physical
    page ``page_table[b, j]`` directly from HBM — the gather never
    materializes a contiguous KV copy.

    One grid step reads a whole page, all KH heads, as a (ps, KH*Dh)
    tile (a free view of the pool), so every block's last two dims are
    full array dims whatever KH is.  q is laid out block-diagonally: row
    i holds head i's query in kv head i // G's Dh lanes and zeros
    elsewhere.  The scores then need no per-head slicing, and head i's
    output is the kv-head-(i // G) lane block of its row.
    """
    B, Sq, H, Dh = q.shape
    NP, ps, KH, _ = k_pages.shape
    _, P = page_table.shape
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5

    own = (jnp.arange(H)[:, None] // G) == jnp.arange(KH)[None, :]  # (H,KH)
    qbd = jnp.where(own[None, :, :, None], q[:, 0, :, None, :],
                    jnp.zeros((), q.dtype)).reshape(B, H, KH * Dh)
    kern = functools.partial(_paged_kernel, ps=ps, np_=P, scale=scale)
    page = lambda b, j, pt, kl: (pt[b, j], 0, 0)
    row = lambda b, j, pt, kl: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, KH * Dh), row),
            pl.BlockSpec((1, ps, KH * Dh), page),
            pl.BlockSpec((1, ps, KH * Dh), page),
        ],
        out_specs=[
            pl.BlockSpec((1, H, KH * Dh), row),
            pl.BlockSpec((1, H, 1), row),
            pl.BlockSpec((1, H, 1), row),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, KH * Dh), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, KH * Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32), qbd,
      k_pages.reshape(NP, ps, KH * Dh), v_pages.reshape(NP, ps, KH * Dh))
    heads = jnp.arange(KH)
    out = out.reshape(B, KH, G, KH, Dh)[:, heads, :, heads]  # (KH,B,G,Dh)
    return (out.transpose(1, 0, 2, 3).reshape(B, 1, H, Dh),
            m.reshape(B, 1, KH, G), l.reshape(B, 1, KH, G))


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal, window, q_offset, scale, bq, bkv, nkv, sq, skv):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + iq * bq  # global position of first q row
    k_start = ikv * bkv

    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window:
        live &= k_start + bkv - 1 > q_start - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale      # (G, bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)           # (bkv, Dh)
        v = v_ref[0, 0].astype(jnp.float32)           # (bkv, Dh)
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # s: (G, bq, bkv)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if skv % bkv:  # zero-padded tail keys
            mask &= kpos < skv
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(mask[None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[..., None] + pv
        m_scr[...] = m_new

    @pl.when(ikv == nkv - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-20)[..., None]
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0, q_offset=0,
                           softmax_scale=None, block_q=128, block_kv=128,
                           interpret=False):
    """q: (B, Sq, H, Dh); k, v: (B, Skv, KH, Dh) — same API as ref.

    Lengths past one block that are not a multiple of it are zero-padded
    to one (padded keys are masked, padded query rows dropped)."""
    B, Sq, H, Dh = q.shape
    _, Skv, KH, _ = k.shape
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    pad_q, pad_kv = (-Sq) % bq, (-Skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    nq, nkv = (Sq + pad_q) // bq, (Skv + pad_kv) // bkv

    qt = q.transpose(0, 2, 1, 3)  # (B, H, Sq, Dh)
    kt = k.transpose(0, 2, 1, 3)  # (B, KH, Skv, Dh)
    vt = v.transpose(0, 2, 1, 3)

    kern = functools.partial(
        _kernel, causal=causal, window=window, q_offset=q_offset, scale=scale,
        bq=bq, bkv=bkv, nkv=nkv, sq=Sq, skv=Skv)

    out = pl.pallas_call(
        kern,
        grid=(B, KH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, G, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, Dh), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, Dh), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :Sq]
