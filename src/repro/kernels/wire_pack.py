"""Pallas TPU kernels for the v2 wire formats (DESIGN.md §Wire format v2).

Two families:

``pack_offsets`` / ``unpack_offsets``
  Sorted ascending block-local offsets <-> the packed byte encodings of
  ``core.wire_format.offset_mode``:
    u8  raw uint8 offsets (wb <= 256) — a cast, no kernel needed;
    p4  lo nibbles (off & 15, two per byte) followed by the delta-unary
        bitmap of the non-decreasing hi stream (off >> 4): bit (i + hi_i)
        set for kept entry i.  Distinct sorted offsets give strictly
        increasing bit positions, so decode recovers offset i as the
        position of the i-th set bit (by rank) — lossless.

``encode_blocks``
  Fused single-pass wire encode: per wire block, bisection top-k_b
  threshold (the ``topk_compress`` bisect — same invariant), EXACT-k_b
  keep set (index-order fill of threshold ties), index-order compaction
  (kept offsets come out sorted ascending natively), per-block scale and
  value quantization (int8 / int4 nibble-packed / fp8 e4m3 bitcast to
  uint8 / f32 / bf16) — one read of the dense rows from HBM instead of a
  top_k + gather + quantize + pack chain.

Kernel shapes avoid gathers and cumsums: nibble packing and byte
expansion are one-hot matmuls over static patterns (f32 matmuls are
exact for the <= 255 integer values involved), ranks are triangular-ones
matmuls, and the compaction is a rank-one-hot contraction — all
VPU/MXU-friendly per pallas_guide §Common pitfalls (broadcasted_iota,
no 1D iota, static shapes only).

The ``*_jnp`` references implement identical math with plain jnp (used
on CPU and as the parity oracles in tests/test_wire_v2.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import wire_format as wf

BISECT_ITERS = 16


def _p4_sizes(wb: int, k_b: int):
    """(lo_bytes, bitmap_bytes) of the p4 encoding."""
    lo_bytes = -(-k_b // 2)
    nbits = k_b + -(-wb // 16)
    return lo_bytes, -(-nbits // 8)


# ---------------------------------------------------------------------------
# jnp references
# ---------------------------------------------------------------------------

def pack_nibbles_jnp(q):
    """q: (..., k) int in [0, 15] -> (..., ceil(k/2)) uint8, low nibble
    first."""
    k = q.shape[-1]
    if k % 2:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, 1)])
    return (q[..., 0::2] | (q[..., 1::2] << 4)).astype(jnp.uint8)


def unpack_nibbles_jnp(b, k: int):
    """(..., ceil(k/2)) uint8 -> (..., k) int32 in [0, 15]."""
    b = b.astype(jnp.int32)
    q = jnp.stack([b & 15, (b >> 4) & 15], axis=-1)
    return q.reshape(b.shape[:-1] + (2 * b.shape[-1],))[..., :k]


def pack_offsets_jnp(off, *, wb: int, mode: str):
    """off: (..., k_b) int32 sorted ascending -> (..., nbytes) uint8."""
    off = off.astype(jnp.int32)
    if mode == "u8":
        return off.astype(jnp.uint8)
    assert mode == "p4", mode
    k_b = off.shape[-1]
    lo_b = pack_nibbles_jnp(off & 15)
    _, bm_bytes = _p4_sizes(wb, k_b)
    P = bm_bytes * 8
    pos = (off >> 4) + jnp.arange(k_b, dtype=jnp.int32)
    bits = (pos[..., None] == jnp.arange(P, dtype=jnp.int32)).any(axis=-2)
    bm = (bits.astype(jnp.int32).reshape(bits.shape[:-1] + (bm_bytes, 8))
          << jnp.arange(8, dtype=jnp.int32)).sum(axis=-1)
    return jnp.concatenate([lo_b, bm.astype(jnp.uint8)], axis=-1)


def unpack_offsets_jnp(packed, *, wb: int, k_b: int, mode: str):
    """(..., nbytes) uint8 -> (..., k_b) int32 sorted ascending."""
    if mode == "u8":
        return packed.astype(jnp.int32)
    assert mode == "p4", mode
    lo_bytes, bm_bytes = _p4_sizes(wb, k_b)
    lo = unpack_nibbles_jnp(packed[..., :lo_bytes], k_b)
    bm = packed[..., lo_bytes:].astype(jnp.int32)
    bits = (bm[..., None] >> jnp.arange(8, dtype=jnp.int32)) & 1
    bits = bits.reshape(bm.shape[:-1] + (bm_bytes * 8,))
    # positions of the k_b set bits in ascending order: stable argsort
    # puts the (exactly k_b) one-bits first, preserving index order.
    pos = jnp.argsort(1 - bits, axis=-1)[..., :k_b]
    hi = pos.astype(jnp.int32) - jnp.arange(k_b, dtype=jnp.int32)
    return hi * 16 + lo


def encode_blocks_jnp(xb, k_b: int, *, wire_dtype: str):
    """xb: (m, nb, wb) f32 -> (vals, off, scale) with ASCENDING offsets.

    vals: f32/bf16 for the float wires, int8, or uint8 (int4 packed
    nibbles / fp8 e4m3 bitcast); off: (m, nb, k_b) int32 sorted
    ascending; scale: (m, nb) f32 per-block max |x| (the dequant scale of
    the quantized formats; returned for every dtype).
    """
    _, off = jax.lax.top_k(jnp.abs(xb), k_b)
    off = jnp.sort(off, axis=-1).astype(jnp.int32)
    vals = jnp.take_along_axis(xb, off, axis=-1)
    scale = jnp.max(jnp.abs(xb), axis=-1)
    return _quantize_vals(vals, scale, wire_dtype), off, scale


def _quantize_vals(vals, scale, wire_dtype: str):
    """(m, nb, k_b) f32 values + (m, nb) scales -> wire value array."""
    if wire_dtype == "f32":
        return vals.astype(jnp.float32)
    if wire_dtype == "bf16":
        return vals.astype(jnp.bfloat16)
    r = vals / jnp.maximum(scale, 1e-30)[..., None]
    if wire_dtype == "int8":
        return jnp.round(r * 127.0).astype(jnp.int8)
    if wire_dtype == "fp8":
        # normalized ratio in [-1, 1] stored e4m3, shipped as uint8 bits
        # (bitcast: collectives stay dtype-agnostic on the wire)
        return jax.lax.bitcast_convert_type(
            r.astype(jnp.float8_e4m3fn), jnp.uint8)
    assert wire_dtype == "int4", wire_dtype
    q = jnp.round(r * 7.0).astype(jnp.int32)
    return pack_nibbles_jnp(q & 15)  # two's-complement nibbles


def dequantize_vals_jnp(vals, scale, k_b: int, *, wire_dtype: str):
    """Wire value array -> (m, nb, k_b) f32 (inverse of _quantize_vals)."""
    if wire_dtype in ("f32", "bf16"):
        return vals.astype(jnp.float32)
    s = scale.astype(jnp.float32)[..., None]
    if wire_dtype == "int8":
        return vals.astype(jnp.float32) * (s / 127.0)
    if wire_dtype == "fp8":
        r = jax.lax.bitcast_convert_type(vals, jnp.float8_e4m3fn)
        return r.astype(jnp.float32) * s
    assert wire_dtype == "int4", wire_dtype
    q = unpack_nibbles_jnp(vals, k_b)
    q = q - 16 * (q > 7)  # two's-complement nibble -> [-8, 7]
    return q.astype(jnp.float32) * (s / 7.0)


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------

def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _fdot(a, b):
    # exact for the small-integer operands used here (values <= 2^16)
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _prefix_count(bits):
    """Inclusive prefix sum along lanes of a (rows, n) 0/1 f32 array, as a
    triangular-ones matmul (Mosaic has no cumsum; exact for n <= 2^24)."""
    n = bits.shape[-1]
    return _fdot(bits, (_iota((n, n), 0) <= _iota((n, n), 1))
                 .astype(jnp.float32))


def _pack_p4_tile(off, *, wb, k_b, rows):
    """(rows, k_b) int32 ascending -> (rows, nbytes) f32 of byte values."""
    k_pairs = (k_b + 1) // 2
    t = _iota((k_b, k_pairs), 0)
    j = _iota((k_b, k_pairs), 1)
    nib = ((t == 2 * j) + 16 * (t == 2 * j + 1)).astype(jnp.float32)
    lo_b = _fdot((off & 15).astype(jnp.float32), nib)  # (rows, k_pairs)
    _, bm_bytes = _p4_sizes(wb, k_b)
    pos = (off // 16 + _iota((rows, k_b), 1)).astype(jnp.float32)
    byte0 = 8.0 * _iota((rows, k_b, bm_bytes), 2).astype(jnp.float32)
    pf = pos[:, :, None]
    bm = jnp.where((pf >= byte0) & (pf < byte0 + 8.0),
                   jnp.exp2(pf - byte0), 0.0).sum(axis=1)  # (rows, bm_bytes)
    return jnp.concatenate([lo_b, bm], axis=-1)


def _to_u8(x):
    """f32 byte values -> uint8.  Mosaic casts to and from 8-bit integers
    only through int32."""
    return x.astype(jnp.int32).astype(jnp.uint8)


def _pack_p4_kernel(off_ref, out_ref, *, wb, k_b, rows):
    out = _pack_p4_tile(off_ref[0, 0].astype(jnp.int32), wb=wb, k_b=k_b,
                        rows=rows)
    out_ref[0, 0] = _to_u8(out)


def _unpack_p4_tile(pf, *, wb, k_b, rows):
    """(rows, nbytes) f32 byte values -> (rows, k_b) f32 offsets."""
    lo_bytes, bm_bytes = _p4_sizes(wb, k_b)
    P = bm_bytes * 8
    # lo nibble t lives in byte t // 2, shifted by 4 * (t % 2)
    jb = _iota((lo_bytes, k_b), 0)
    tb = _iota((lo_bytes, k_b), 1)
    lo_at = _fdot(pf[:, :lo_bytes], (jb == tb // 2).astype(jnp.float32))
    shift = jnp.where(_iota((rows, k_b), 1) % 2 == 1, 16.0, 1.0)
    lo_sh = jnp.floor(lo_at / shift)
    lo = lo_sh - 16.0 * jnp.floor(lo_sh / 16.0)
    # bitmap bytes -> P bit lanes (one-hot matmul + power-of-two divide)
    jq = _iota((bm_bytes, P), 0)
    q = _iota((bm_bytes, P), 1)
    byte_at = _fdot(pf[:, lo_bytes:], (jq == q // 8).astype(jnp.float32))
    bsh = jnp.floor(byte_at / jnp.exp2((_iota((rows, P), 1) % 8)
                                       .astype(jnp.float32)))
    bits = bsh - 2.0 * jnp.floor(bsh / 2.0)  # (rows, P) in {0, 1}
    # rank = inclusive prefix count of set bits (triangular-ones matmul)
    rank = _prefix_count(bits)
    # position of the i-th set bit: rank-one-hot contraction
    hit = (bits[:, None, :]
           * (rank[:, None, :]
              == (_iota((rows, k_b, P), 1) + 1).astype(jnp.float32)))
    pos = (hit * _iota((rows, k_b, P), 2).astype(jnp.float32)).sum(axis=-1)
    # clamp: an all-zero bitmap (a partial-perm zero-filled payload) has
    # no set bits — decode to offset 0 like the jnp reference, not to
    # negative (dropped-scatter) coordinates.
    hi = jnp.maximum(pos - _iota((rows, k_b), 1).astype(jnp.float32), 0.0)
    return hi * 16.0 + lo


def _unpack_p4_kernel(p_ref, out_ref, *, wb, k_b, rows):
    off = _unpack_p4_tile(p_ref[0, 0].astype(jnp.int32).astype(jnp.float32),
                          wb=wb, k_b=k_b, rows=rows)
    out_ref[0, 0] = off.astype(jnp.int32)


def _pick_rows(nb: int, per_row_elems: int) -> int:
    """Largest divisor of nb keeping the fattest intermediate under ~2 MiB
    of f32 (the rank-one-hot contraction is the kernel's VMEM high-water
    mark)."""
    target = max(1, (1 << 19) // max(per_row_elems, 1))
    rows = min(target, nb)
    while nb % rows:
        rows -= 1
    return rows


def _tiled_call(kernel, x, rows, outs, interpret):
    """Run ``kernel`` over (m, nb, n) ``x`` in (rows, n) tiles.

    Arrays are viewed as (m, nb // rows, rows, n), so each block's last
    two dims are full array dims for any ``rows`` (the TPU block-shape
    rule).  ``outs``: [(trailing width, dtype)] per output, each returned
    as (m, nb, width)."""
    m, nb, n = x.shape
    g = nb // rows
    spec = lambda w: pl.BlockSpec((1, 1, rows, w), lambda r, i: (r, i, 0, 0),
                                  memory_space=pltpu.VMEM)
    res = pl.pallas_call(
        kernel,
        grid=(m, g),
        in_specs=[spec(n)],
        out_specs=[spec(w) for w, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((m, g, rows, w), dt)
                   for w, dt in outs],
        interpret=interpret,
    )(x.reshape(m, g, rows, n))
    return [r.reshape(m, nb, w) for r, (w, _) in zip(res, outs)]


def pack_offsets_pallas(off, *, wb: int, mode: str, interpret=False):
    """off: (m, nb, k_b) int32 sorted ascending -> (m, nb, nbytes) uint8."""
    if mode == "u8":
        return off.astype(jnp.uint8)
    m, nb, k_b = off.shape
    lo_bytes, bm_bytes = _p4_sizes(wb, k_b)
    rows = _pick_rows(nb, k_b * bm_bytes)
    return _tiled_call(
        functools.partial(_pack_p4_kernel, wb=wb, k_b=k_b, rows=rows),
        off, rows, [(lo_bytes + bm_bytes, jnp.uint8)], interpret)[0]


def unpack_offsets_pallas(packed, *, wb: int, k_b: int, mode: str,
                          interpret=False):
    """(m, nb, nbytes) uint8 -> (m, nb, k_b) int32 sorted ascending."""
    if mode == "u8":
        return packed.astype(jnp.int32)
    m, nb, nbytes = packed.shape
    _, bm_bytes = _p4_sizes(wb, k_b)
    rows = _pick_rows(nb, k_b * bm_bytes * 8)
    return _tiled_call(
        functools.partial(_unpack_p4_kernel, wb=wb, k_b=k_b, rows=rows),
        packed, rows, [(k_b, jnp.int32)], interpret)[0]


def _encode_kernel(x_ref, vals_ref, off_ref, scale_ref, *, wb, k_b, rows,
                   wire_dtype):
    x = x_ref[0, 0].astype(jnp.float32)  # (rows, wb)
    mag = jnp.abs(x)
    # fixed-iteration bisection on the magnitude — same loop + invariant
    # as topk_compress._mask_tile (count(mag > lo) > k or lo == 0;
    # count(mag > hi) <= k), with a STATIC k = k_b.
    lo = jnp.zeros((rows, 1), jnp.float32)
    hi0 = mag.max(axis=-1, keepdims=True)
    hi = hi0

    def body(i, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = (mag > mid).sum(axis=-1, keepdims=True).astype(jnp.float32)
        lo = jnp.where(cnt > k_b, mid, lo)
        hi = jnp.where(cnt > k_b, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))
    primary = mag > hi  # <= k_b kept for sure
    nprim = primary.sum(axis=-1, keepdims=True).astype(jnp.float32)
    # fill the remaining budget from the (lo, hi] threshold band in INDEX
    # order (lo == 0 opens the whole block: an all-/mostly-zero block
    # fills with zeros — exactly k_b survivors always).
    band = jnp.logical_not(primary) & ((mag > lo) | (lo == 0.0))
    brank = _prefix_count(band.astype(jnp.float32))
    keep = primary | (band & (brank <= k_b - nprim))
    # index-order compaction: the i-th kept element (ascending offset) via
    # the rank one-hot — offsets come out SORTED natively.
    krank = _prefix_count(keep.astype(jnp.float32))
    hit = (keep[:, None, :]
           & (krank[:, None, :]
              == (_iota((rows, k_b, wb), 1) + 1).astype(jnp.float32)))
    hitf = hit.astype(jnp.float32)
    off = (hitf * _iota((rows, k_b, wb), 2).astype(jnp.float32)).sum(axis=-1)
    vals = (hitf * x[:, None, :]).sum(axis=-1)  # (rows, k_b)
    scale = hi0  # block max |x|: the max element is always kept
    off_ref[0, 0] = off.astype(jnp.int32)
    scale_ref[0, 0] = scale
    if wire_dtype in ("f32", "bf16"):
        vals_ref[0, 0] = vals.astype(vals_ref.dtype)
        return
    r = vals / jnp.maximum(scale, 1e-30)
    if wire_dtype == "int8":
        vals_ref[0, 0] = jnp.round(r * 127.0).astype(jnp.int32).astype(
            jnp.int8)
    elif wire_dtype == "fp8":
        vals_ref[0, 0] = jax.lax.bitcast_convert_type(
            r.astype(jnp.float8_e4m3fn), jnp.uint8)
    else:  # int4: two's-complement nibbles packed two per byte
        q = jnp.round(r * 7.0)
        q = q + 16.0 * (q < 0)  # & 15 in f32
        k_pairs = (k_b + 1) // 2
        t = _iota((k_b, k_pairs), 0)
        j = _iota((k_b, k_pairs), 1)
        nib = ((t == 2 * j) + 16 * (t == 2 * j + 1)).astype(jnp.float32)
        vals_ref[0, 0] = _to_u8(_fdot(q, nib))


def encode_blocks_pallas(xb, k_b: int, *, wire_dtype: str, interpret=False):
    """Fused encode: xb (m, nb, wb) f32 -> (vals, off, scale), identical
    to ``encode_blocks_jnp`` whenever block magnitudes are separated by
    more than the bisection resolution (max|x| * 2^-16; threshold ties
    inside one resolution band may legally swap set members)."""
    m, nb, wb = xb.shape
    val_dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                 "int8": jnp.int8}.get(wire_dtype, jnp.uint8)
    k_out = -(-k_b // 2) if wire_dtype == "int4" else k_b
    rows = _pick_rows(nb, k_b * wb)
    vals, off, scale = _tiled_call(
        functools.partial(_encode_kernel, wb=wb, k_b=k_b, rows=rows,
                          wire_dtype=wire_dtype),
        xb, rows, [(k_out, val_dtype), (k_b, jnp.int32), (1, jnp.float32)],
        interpret)
    return vals, off, scale[..., 0]
