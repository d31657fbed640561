"""jit'd dispatch wrappers: Pallas on TPU, blockwise-jnp elsewhere.

Every op takes ``impl`` in {None, "pallas", "jnp", "ref"}; None = auto
(pallas iff running on TPU, unless ``forced_route`` says otherwise while
a program is traced).  ``interpret=True`` is used automatically when
"pallas" is forced on a non-TPU backend (kernel correctness tests).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import wire_pack
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           flash_attention_pallas_bwd,
                                           gather_kv_pages,
                                           paged_decode_attention_pallas)
from repro.kernels.ssd_scan import ssd_pallas
from repro.kernels.topk_compress import topk_compress_pallas


_forced = None


@contextlib.contextmanager
def forced_route(impl):
    """Route every op with ``impl=None`` to ``impl`` for programs TRACED
    inside the block (routing is decided at trace time, so jit a fresh
    function here).  Used to build the jnp twin of a Pallas program on the
    same device, e.g. for an agreement check."""
    global _forced
    assert impl in ("pallas", "jnp", "ref"), impl
    prev, _forced = _forced, impl
    try:
        yield
    finally:
        _forced = prev


def _route(impl):
    if impl in ("pallas", "jnp", "ref"):
        return impl
    if _forced is not None:
        return _forced
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _interp():
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_pallas(q, k, v, causal, window, q_offset, softmax_scale,
                  interpret):
    """Pallas flash attention with a Pallas backward: the fwd rule keeps
    (q, k, v, o, lse), the forward kernel writing each row's logsumexp;
    the bwd rule runs the dK/dV and dQ kernels of
    ``flash_attention_pallas_bwd`` on them."""
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        softmax_scale=softmax_scale, interpret=interpret)


def _flash_pallas_fwd(q, k, v, causal, window, q_offset, softmax_scale,
                      interpret):
    out, lse = flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        softmax_scale=softmax_scale, return_lse=True, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_pallas_bwd(causal, window, q_offset, softmax_scale, interpret,
                      res, g):
    return flash_attention_pallas_bwd(
        *res, g, causal=causal, window=window, q_offset=q_offset,
        softmax_scale=softmax_scale, interpret=interpret)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    kv_len=None, softmax_scale=None, impl=None, policy=None):
    """``policy``: the caller's sharding policy.  Under a mesh the Pallas
    kernel runs inside a shard_map over the policy's attention specs:
    Mosaic kernels cannot be partitioned automatically."""
    r = _route(impl)
    if r == "pallas" and kv_len is None:
        fn = functools.partial(
            _flash_pallas, causal=causal, window=window, q_offset=q_offset,
            softmax_scale=softmax_scale, interpret=_interp())
        if policy is not None and policy.mesh is not None:
            from repro.dist.compat import shard_map
            qs, kvs = policy.attention_specs(q.shape, k.shape)
            fn = shard_map(fn, mesh=policy.mesh, in_specs=(qs, kvs, kvs),
                           out_specs=qs, check_vma=False)
        return fn(q, k, v)
    if r == "ref":
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, kv_len=kv_len,
                                 softmax_scale=softmax_scale)
    return ref.flash_attention_jnp(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, kv_len=kv_len,
                                   softmax_scale=softmax_scale)


def decode_attention(q, k, v, *, kv_len=None, window=0, softmax_scale=None,
                     impl=None, return_stats=False):
    # Direct form on purpose: lowers to the flash-decode logsumexp-combine
    # pattern when the KV cache is sequence-sharded (see DESIGN.md §3).
    return ref.decode_attention_jnp(q, k, v, kv_len=kv_len, window=window,
                                    softmax_scale=softmax_scale,
                                    return_stats=return_stats)


def decode_attention_combine(q, out_old, m_old, l_old, k_new, v_new, *,
                             softmax_scale=None):
    return ref.decode_attention_combine(q, out_old, m_old, l_old, k_new,
                                        v_new, softmax_scale=softmax_scale)


def paged_decode_attention(q, k_pages, v_pages, page_table, kv_len, *,
                           k_scale=None, v_scale=None, contiguous=False,
                           softmax_scale=None, impl=None):
    """Decode attention over the paged KV pool (DESIGN.md §Serving
    contract).  Always returns (out, m, l) stats so the caller folds the
    current token's (k, v) in with ``decode_attention_combine`` — the
    page write stays write-only (in place under XLA), same as the dense
    decode path.

    q: (B, 1, H, Dh); k_pages/v_pages: (NP, ps, KH, Dh); page_table:
    (B, P); kv_len: (B,).  ``k_scale``/``v_scale`` (NP, ps, KH) f32
    activate the int8 block-scaled KV mode (pages hold int8 values,
    dequantized after the gather — the same value/scale split as the
    int8 wire format).  ``contiguous=True`` takes the dense fallback in
    ``gather_kv_pages`` (reshape, no gather) — bit-for-bit identical.

    The Pallas path (TPU, or forced via impl="pallas") DMAs pages
    straight from HBM via scalar-prefetched page-table indices; the jnp
    path gathers then runs the flash-decode reference — bitwise equal to
    the dense-cache decode on equal-sized caches.
    """
    r = _route(impl)
    if r == "pallas" and k_scale is None and not contiguous:
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, page_table, kv_len,
            softmax_scale=softmax_scale, interpret=_interp())
    k = gather_kv_pages(k_pages, page_table, contiguous=contiguous)
    v = gather_kv_pages(v_pages, page_table, contiguous=contiguous)
    if k_scale is not None:
        ks = gather_kv_pages(k_scale, page_table, contiguous=contiguous)
        vs = gather_kv_pages(v_scale, page_table, contiguous=contiguous)
        k = (k.astype(jnp.float32) * (ks / 127.0)[..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * (vs / 127.0)[..., None]).astype(q.dtype)
    return ref.decode_attention_jnp(q, k, v, kv_len=kv_len,
                                    softmax_scale=softmax_scale,
                                    return_stats=True)


def ssd(x, dt, A, B, C, *, chunk=64, impl=None):
    r = _route(impl)
    if r == "pallas":
        return ssd_pallas(x, dt, A, B, C, chunk=chunk, interpret=_interp())
    if r == "ref":
        y, _ = ref.ssd_ref(x, dt, A, B, C)
        return y
    y, _ = ref.ssd_chunked_jnp(x, dt, A, B, C, chunk=chunk)
    return y


def topk_compress(x, theta, *, block=1024, impl=None, ef=None):
    """x: (R, L); theta: (R,); ef: optional (R, L) error-feedback buffer.

    Returns (masked, residual) of Q(x + ef): the EF add is fused into the
    Pallas kernel (f32 per VMEM tile, no HBM upcast); the jnp/ref oracles
    add in f32 before masking so all impls agree bit-for-bit.
    """
    r = _route(impl)
    if r == "pallas":
        return topk_compress_pallas(x, theta, ef=ef, block=block,
                                    interpret=_interp())
    xf = x.astype(jnp.float32)
    if ef is not None:
        xf = xf + ef.astype(jnp.float32)
    mask_fn = ref.topk_mask_exact if r == "ref" else ref.topk_mask_bisect_jnp
    masked, keep = mask_fn(xf, theta[:, None], block=block)
    resid_dtype = x.dtype if ef is None else ef.dtype
    # bit-identical to xf - masked (kept: x - x == +0, dropped: x - 0 ==
    # x) without re-reading the f32 masked array — the keep mask is 1/4
    # the bytes.
    resid = jnp.where(keep, jnp.float32(0), xf)
    return masked.astype(x.dtype), resid.astype(resid_dtype)


def pack_offsets(off, *, wb, mode, impl=None):
    """Sorted ascending block-local offsets (m, nb, k_b) int32 -> packed
    uint8 (m, nb, nbytes) in the static ``mode`` ("u8" | "p4") chosen by
    ``core.wire_format.offset_mode``."""
    if _route(impl) == "pallas":
        return wire_pack.pack_offsets_pallas(off, wb=wb, mode=mode,
                                             interpret=_interp())
    return wire_pack.pack_offsets_jnp(off, wb=wb, mode=mode)


def unpack_offsets(packed, *, wb, k_b, mode, impl=None):
    """Inverse of ``pack_offsets`` (exact: the encodings are lossless for
    distinct sorted offsets)."""
    if _route(impl) == "pallas":
        return wire_pack.unpack_offsets_pallas(packed, wb=wb, k_b=k_b,
                                               mode=mode,
                                               interpret=_interp())
    return wire_pack.unpack_offsets_jnp(packed, wb=wb, k_b=k_b, mode=mode)


def encode_blocks(xb, k_b, *, wire_dtype, impl=None):
    """Fused wire encode: (m, nb, wb) f32 -> (vals, off, scale) with
    ASCENDING offsets; values already quantized/packed for the wire
    dtype.  Pallas path is one kernel (bisect + compaction + quantize +
    nibble pack — the dense rows are read from HBM once); jnp path is
    the top_k + sort reference with identical results on magnitude-
    separated data (see ``wire_pack.encode_blocks_pallas``)."""
    if _route(impl) == "pallas":
        return wire_pack.encode_blocks_pallas(xb, k_b, wire_dtype=wire_dtype,
                                              interpret=_interp())
    return wire_pack.encode_blocks_jnp(xb, k_b, wire_dtype=wire_dtype)


def rglru(log_a, gated_x, *, h0=None, impl=None):
    r = _route(impl)
    if r == "ref":
        return ref.rglru_ref(log_a, gated_x, h0=h0)
    return ref.rglru_scan_jnp(log_a, gated_x, h0=h0)
