"""Pallas TPU kernel for the paper's compression operator Q (Sec. 3.2).

Block-local top-k with FUSED error feedback:
  inputs delta (and optionally ef)  (flat, reshaped to (R, nb, block))
  output masked  = Q(delta + ef)    (kept coordinates, zeros elsewhere)
  output residual = (delta + ef) - masked   (new error-feedback buffer)

The EF add happens INSIDE the kernel in f32, per VMEM tile: callers pass
delta/ef in their storage dtype (bf16-native path) and never materialize
the f32 upcast of a whole model shard in HBM.

The per-block threshold is found by fixed-iteration bisection on the
magnitude (sort-free: TPU VPU-friendly, no O(block log block) sort).  Each
grid cell processes a (rows, block) tile resident in VMEM; theta is per
replica (leading R dim).  Keeps >=1 element per block so every block ships
information.  Identical math to ``ref.topk_mask_bisect_jnp`` (the oracle).

The contraction property (paper Eq. 7) holds per block and therefore
globally: ||Q(x) - x||^2 <= (1 - theta) ||x||^2  (tested by property tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BISECT_ITERS = 16


def _mask_tile(x, theta, masked_ref, resid_ref, *, block, rows):
    mag = jnp.abs(x)
    k = jnp.clip(jnp.ceil(theta * block), 1.0, float(block))
    lo = jnp.zeros((rows, 1), jnp.float32)
    hi0 = mag.max(axis=-1, keepdims=True)
    hi = hi0

    def body(i, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = (mag > mid).sum(axis=-1, keepdims=True).astype(jnp.float32)
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))
    # Threshold at the LOWER bisection bound: by loop invariant either
    # count(mag > lo) > k, or lo == 0 (then everything not kept is exactly
    # zero).  Using (lo+hi)/2 would drop threshold-TIES and can keep far
    # fewer than k elements, violating the contraction property (Eq. 7) —
    # found by hypothesis (tests/test_properties.py).
    keep = mag > lo
    # guarantee at least the max element of each block is kept.  "nothing
    # kept" only happens at lo == 0 on an all-zero block (the invariant
    # keeps > k elements whenever lo > 0), so testing hi0 == 0 replaces a
    # full keep.sum recount pass.
    keep = keep | ((mag >= hi0) & (hi0 == 0.0))
    masked = jnp.where(keep, x, 0.0)
    masked_ref[0, 0] = masked.astype(masked_ref.dtype)
    # residual via the SAME mask (bit-identical to x - masked: kept lanes
    # give x - x == +0.0, dropped lanes x - 0 == x) — reads the i1 mask
    # instead of a second f32 pass over masked.
    resid_ref[0, 0] = jnp.where(keep, 0.0, x).astype(resid_ref.dtype)


def _kernel(theta_ref, x_ref, masked_ref, resid_ref, *, block, rows):
    _mask_tile(x_ref[0, 0].astype(jnp.float32),
               theta_ref[pl.program_id(0)], masked_ref, resid_ref,
               block=block, rows=rows)


def _kernel_ef(theta_ref, x_ref, ef_ref, masked_ref, resid_ref, *, block,
               rows):
    # fused error-feedback add: f32 only inside the VMEM tile
    x = x_ref[0, 0].astype(jnp.float32) + ef_ref[0, 0].astype(jnp.float32)
    _mask_tile(x, theta_ref[pl.program_id(0)], masked_ref, resid_ref,
               block=block, rows=rows)


def _pick_rows(nb: int, rows: int, itemsize: int, block: int = 1024) -> int:
    """Largest divisor of nb <= the VMEM tile target.

    The sublane FLOOR follows dtype-native tiling (pallas_guide §Tiling):
    f32 8, bf16 16, int8 32.  On top of the floor the tile grows toward
    ~256 KiB so the grid has fewer, fatter cells (each cell re-runs the
    16-iteration bisection preamble; fat tiles amortize it and keep the
    DMA pipeline busy).  Worst case VMEM: 4 tiles (x, ef, masked, resid)
    x 2 double-buffered = 2 MiB, far under the ~16 MiB budget.  Falling
    back to smaller divisors keeps any nb legal: the caller views the
    blocks as (R, nb // rows, rows, block), so a tile's last two dims are
    always the array's full dims (the TPU block-shape rule).
    """
    floor = max(rows, (4 * rows) // max(itemsize, 1))
    target = max(floor, (1 << 18) // max(block * itemsize, 1))
    rows = min(target, nb)
    while nb % rows:
        rows -= 1
    return rows


def topk_compress_pallas(x, theta, *, ef=None, block=1024, rows=8,
                         interpret=False):
    """x (and optional ef): (R, L) with L % block == 0; theta: (R,) in
    (0, 1].

    Returns (masked, residual) with masked + residual == x + ef computed
    in f32 inside the kernel; masked is cast to x.dtype, residual to
    ef.dtype (or x.dtype without ef).
    """
    R, L = x.shape
    assert L % block == 0, (L, block)
    nb = L // block
    rows = _pick_rows(nb, rows, jnp.dtype(x.dtype).itemsize, block)
    shape4 = (R, nb // rows, rows, block)
    xb = x.reshape(shape4)

    # theta rides whole in SMEM (R scalars), indexed by the replica axis.
    tile = lambda: pl.BlockSpec((1, 1, rows, block),
                                lambda r, i: (r, i, 0, 0))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), tile()]
    args = [theta.astype(jnp.float32), xb]
    resid_dtype = x.dtype
    if ef is None:
        kern = functools.partial(_kernel, block=block, rows=rows)
    else:
        kern = functools.partial(_kernel_ef, block=block, rows=rows)
        in_specs.append(tile())
        args.append(ef.reshape(shape4))
        resid_dtype = ef.dtype
    masked, resid = pl.pallas_call(
        kern,
        grid=(R, nb // rows),
        in_specs=in_specs,
        out_specs=[tile(), tile()],
        out_shape=[
            jax.ShapeDtypeStruct(shape4, x.dtype),
            jax.ShapeDtypeStruct(shape4, resid_dtype),
        ],
        interpret=interpret,
    )(*args)
    return masked.reshape(R, L), resid.reshape(R, L)
