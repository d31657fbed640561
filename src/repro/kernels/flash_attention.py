"""Pallas TPU flash attention (blockwise, causal/windowed, GQA).

TARGET: TPU v5e (MXU 128x128, VMEM-resident q/kv tiles).  Validated on CPU
with interpret=True against ``ref.attention_ref``.

Layout: q (B, H, Sq, Dh); k, v (B, KH, Skv, Dh); out (B, H, Sq, Dh).
Grid (B, KH, nQ, nKV) with the KV dimension innermost; running (m, l, acc)
accumulators live in VMEM scratch and the output tile is written on the last
KV step.  Causal/window blocks that are fully masked are skipped with
``pl.when`` (no MXU work issued).

The backward is Pallas too (``flash_attention_pallas_bwd``, FlashAttention-2):
its residuals are q, k, v, the output and the per-row logsumexp that the
forward kernel writes when asked (``return_lse``).  A dK/dV kernel (q blocks
innermost) and a dQ kernel (KV blocks innermost) recompute each live tile's
probabilities from the logsumexp; all three kernels share the tile mask
(``_tile``) and the score computation (``_scores``).
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


def _tile(iq, ikv, *, causal, window, q_offset, bq, bkv, skv):
    """The score tile of q block ``iq`` and KV block ``ikv``.

    Returns ``(live, mask)``: ``live`` is false when causal/window mask
    every pair of the tile, which then issues no MXU work; ``mask()``
    builds the tile's (bq, bkv) mask, zero-padded tail keys included."""
    q_start = q_offset + iq * bq  # global position of first q row
    k_start = ikv * bkv

    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window:
        live &= k_start + bkv - 1 > q_start - window

    def mask():
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        m = jnp.ones((bq, bkv), jnp.bool_)
        if skv % bkv:  # zero-padded tail keys
            m &= kpos < skv
        if causal:
            m &= kpos <= qpos
        if window:
            m &= kpos > qpos - window
        return m

    return live, mask


def _fetch(i, other, n, *, sweep_q, causal, window, q_offset, bq, bkv,
           skv):
    """Block index a backward grid step fetches along the axis it sweeps
    (q blocks when ``sweep_q``, else KV blocks; ``other`` is the other
    axis' block): ``i`` where the tile is live, else the nearest live
    block of the sweep, so a skipped step starts no new DMA."""
    iq, ikv = (i, other) if sweep_q else (other, i)
    live, _ = _tile(iq, ikv, causal=causal, window=window,
                    q_offset=q_offset, bq=bq, bkv=bkv, skv=skv)
    lo, hi = 0, n - 1
    if sweep_q:
        k_start = ikv * bkv
        if causal:
            lo = jnp.maximum(lo, -((q_offset + bq - 1 - k_start) // bq))
        if window:
            hi = jnp.minimum(
                hi, -((q_offset - k_start - bkv + 1 - window) // bq) - 1)
    else:
        q_start = q_offset + iq * bq
        if causal:
            hi = jnp.minimum(hi, (q_start + bq - 1) // bkv)
        if window:
            lo = jnp.maximum(lo, (q_start - window - bkv + 1) // bkv + 1)
    near = jnp.maximum(jnp.minimum(jnp.maximum(i, lo), hi), 0)
    return jnp.where(live, i, near)


def _scores(q_ref, k_ref, scale):
    """The forward's scores of a tile, computed the same way in all three
    kernels so that the backward's exp(s - lse) is the forward's p."""
    q = q_ref[0].astype(jnp.float32) * scale      # (G, bq, Dh)
    k = k_ref[0, 0].astype(jnp.float32)           # (bkv, Dh)
    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return q, k, s                                # s: (G, bq, bkv)


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal, window, q_offset,
            scale, bq, bkv, nkv, skv):
    *lse_ref, m_scr, l_scr, acc_scr = rest  # lse_ref only with return_lse
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live, mask = _tile(iq, ikv, causal=causal, window=window,
                       q_offset=q_offset, bq=bq, bkv=bkv, skv=skv)

    @pl.when(live)
    def _compute():
        _, _, s = _scores(q_ref, k_ref, scale)
        v = v_ref[0, 0].astype(jnp.float32)           # (bkv, Dh)
        m = mask()[None]
        s = jnp.where(m, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(m, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[..., None] + pv
        m_scr[...] = m_new

    @pl.when(ikv == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l[..., None]).astype(o_ref.dtype)
        if lse_ref:
            lse_ref[0][0, 0] = m_scr[...] + jnp.log(l)


def _blocks(Sq, Skv, block_q, block_kv):
    """Block sizes and padded lengths: a length past one block that is not
    a multiple of it is zero-padded to one."""
    bq, bkv = min(block_q, Sq), min(block_kv, Skv)
    return bq, bkv, Sq + (-Sq) % bq, Skv + (-Skv) % bkv


def _heads_major(x, s_pad):
    """(B, S, N, Dh) -> (B, N, s_pad, Dh), zero-padded along S."""
    if s_pad > x.shape[1]:
        x = jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3)


def flash_attention_pallas(q, k, v, *, causal=True, window=0, q_offset=0,
                           softmax_scale=None, block_q=128, block_kv=128,
                           return_lse=False, interpret=False):
    """q: (B, Sq, H, Dh); k, v: (B, Skv, KH, Dh) — same API as ref.

    Lengths past one block that are not a multiple of it are zero-padded
    to one (padded keys are masked, padded query rows dropped).  With
    ``return_lse`` the kernel also writes each row's f32 logsumexp and
    returns ``(out, lse)``, lse (B, H, Sq_padded): the residual of
    ``flash_attention_pallas_bwd``."""
    B, Sq, H, Dh = q.shape
    _, Skv, KH, _ = k.shape
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5
    bq, bkv, sq_p, skv_p = _blocks(Sq, Skv, block_q, block_kv)
    nq, nkv = sq_p // bq, skv_p // bkv

    kern = functools.partial(
        _kernel, causal=causal, window=window, q_offset=q_offset, scale=scale,
        bq=bq, bkv=bkv, nkv=nkv, skv=Skv)
    o_spec = pl.BlockSpec((1, G, bq, Dh), lambda b, h, i, j: (b, h, i, 0))
    o_shape = jax.ShapeDtypeStruct((B, H, sq_p, Dh), q.dtype)
    if return_lse:
        lse_spec = pl.BlockSpec((1, 1, G, bq),
                                lambda b, h, i, j: (b, h, 0, i))
        out_specs = [o_spec, lse_spec]
        out_shape = [o_shape,
                     jax.ShapeDtypeStruct((B, KH, G, sq_p), jnp.float32)]
    else:
        out_specs, out_shape = o_spec, o_shape

    res = pl.pallas_call(
        kern,
        grid=(B, KH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, G, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, Dh), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, Dh), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(_heads_major(q, sq_p), _heads_major(k, skv_p), _heads_major(v, skv_p))
    if not return_lse:
        return res.transpose(0, 2, 1, 3)[:, :Sq]
    out, lse = res
    return out.transpose(0, 2, 1, 3)[:, :Sq], lse.reshape(B, H, sq_p)


# ---------------------------------------------------------------------------
# Backward (FlashAttention-2): dK/dV and dQ kernels
# ---------------------------------------------------------------------------

def _grad_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, mask, scale):
    """(scaled q, k, dO, p, ds) of a live tile, all f32: the forward's p
    recomputed as exp(s - lse), and ds = p (dO v^T - D)."""
    q, k, s = _scores(q_ref, k_ref, scale)
    m = mask()[None]
    p = jnp.where(m, jnp.exp(s - lse_ref[0, 0][..., None]), 0.0)
    do = do_ref[0].astype(jnp.float32)            # (G, bq, Dh)
    v = v_ref[0, 0].astype(jnp.float32)           # (bkv, Dh)
    dp = jax.lax.dot_general(do, v, (((2,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - di_ref[0, 0][..., None])
    return q, k, do, p, ds


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, causal, window, q_offset, scale, bq, bkv,
                nq, skv):
    ikv = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live, mask = _tile(iq, ikv, causal=causal, window=window,
                       q_offset=q_offset, bq=bq, bkv=bkv, skv=skv)

    @pl.when(live)
    def _compute():
        q, _, do, p, ds = _grad_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                     di_ref, mask, scale)
        # (G, bq, .) -> (G*bq, .): one matmul sums the G query heads
        rows = p.shape[0] * bq
        tn = (((0,), (0,)), ((), ()))
        dv_scr[...] += jax.lax.dot_general(
            p.reshape(rows, bkv), do.reshape(rows, -1), tn,
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds.reshape(rows, bkv), q.reshape(rows, -1), tn,
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr,
               *, causal, window, q_offset, scale, bq, bkv, nkv, skv):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live, mask = _tile(iq, ikv, causal=causal, window=window,
                       q_offset=q_offset, bq=bq, bkv=bkv, skv=skv)

    @pl.when(live)
    def _compute():
        _, k, _, _, ds = _grad_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    di_ref, mask, scale)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ikv == nkv - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def flash_attention_pallas_bwd(q, k, v, o, lse, do, *, causal=True,
                               window=0, q_offset=0, softmax_scale=None,
                               block_q=128, block_kv=128, interpret=False):
    """(dq, dk, dv) of ``flash_attention_pallas`` from its residuals: the
    inputs q, k, v, the output o and ``return_lse``'s lse, given the
    output's cotangent ``do`` (o's layout and dtype).

    D = rowsum(dO o) is computed here; two kernels then recompute each
    live tile's p = exp(s - lse).  The dK/dV kernel (grid (B, KH, nKV, nQ),
    q blocks innermost) sums p^T dO and ds^T q over the G query heads of
    a KV head; the dQ kernel (grid (B, KH, nQ, nKV)) sums ds k.  Both skip
    the tiles the forward skips, and keep its f32 operands and
    accumulators.  dQ is written as (B, KH, G, Sq, Dh), the grouping it
    is computed in."""
    B, Sq, H, Dh = q.shape
    _, Skv, KH, _ = k.shape
    G = H // KH
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5
    bq, bkv, sq_p, skv_p = _blocks(Sq, Skv, block_q, block_kv)
    nq, nkv = sq_p // bq, skv_p // bkv

    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = jnp.pad(di, ((0, 0), (0, sq_p - Sq), (0, 0)))
    di = di.transpose(0, 2, 1).reshape(B, KH, G, sq_p)
    args = (_heads_major(q, sq_p), _heads_major(k, skv_p),
            _heads_major(v, skv_p), _heads_major(do, sq_p),
            lse.reshape(B, KH, G, sq_p), di)
    mask_args = dict(causal=causal, window=window, q_offset=q_offset, bq=bq,
                     bkv=bkv, skv=Skv)

    def specs(q_block, kv_block):
        """in_specs of both kernels, given each grid step's blocks."""
        qi = lambda b, h, x, y: (b, h, q_block(x, y), 0)
        kvi = lambda b, h, x, y: (b, h, kv_block(x, y), 0)
        rowi = lambda b, h, x, y: (b, h, 0, q_block(x, y))
        return [pl.BlockSpec((1, G, bq, Dh), qi),
                pl.BlockSpec((1, 1, bkv, Dh), kvi),
                pl.BlockSpec((1, 1, bkv, Dh), kvi),
                pl.BlockSpec((1, G, bq, Dh), qi),
                pl.BlockSpec((1, 1, G, bq), rowi),
                pl.BlockSpec((1, 1, G, bq), rowi)]

    kv_out = pl.BlockSpec((1, 1, bkv, Dh), lambda b, h, j, i: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, nq=nq, **mask_args),
        grid=(B, KH, nkv, nq),
        in_specs=specs(lambda j, i: _fetch(i, j, nq, sweep_q=True,
                                            **mask_args),
                       lambda j, i: j),
        out_specs=[kv_out, kv_out],
        out_shape=[jax.ShapeDtypeStruct((B, KH, skv_p, Dh), k.dtype),
                   jax.ShapeDtypeStruct((B, KH, skv_p, Dh), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, Dh), jnp.float32),
                        pltpu.VMEM((bkv, Dh), jnp.float32)],
        interpret=interpret,
    )(*args)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, nkv=nkv, **mask_args),
        grid=(B, KH, nq, nkv),
        in_specs=specs(lambda i, j: i,
                       lambda i, j: _fetch(j, i, nkv, sweep_q=False,
                                           **mask_args)),
        out_specs=pl.BlockSpec((1, 1, G, bq, Dh),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, sq_p, Dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, bq, Dh), jnp.float32)],
        interpret=interpret,
    )(*args)
    dq = dq.reshape(B, H, sq_p, Dh).transpose(0, 2, 1, 3)[:, :Sq]
    return (dq, dk.transpose(0, 2, 1, 3)[:, :Skv],
            dv.transpose(0, 2, 1, 3)[:, :Skv])
