"""Grouped matmul on TPU: JAX's Pallas megablox ``gmm``/``tgmm`` kernels,
with a backward of the same kernels and a batching rule for ``vmap``.

``x`` (m, k) holds its rows in consecutive groups; group g is multiplied
by ``w[g]`` (G, k, n); ``group_sizes`` (G,) int32 sum to m.

Under ``vmap`` (the round step's vmap over clients) a call with N batched
operands runs as ONE launch over N * G groups: the clients' rows are
stacked (each client's groups are contiguous and its sizes sum to its m,
so the stacked groups line up) and their weights stacked likewise,
(N * G, k, n).  Rows are padded with zeros to the kernels' row tile; the
padding rides in the last group and is sliced off (zero rows add nothing
to a weight gradient).
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# ``megablox/__init__`` re-exports the differentiable ``gmm`` under the
# module's own name; the kernels themselves live in the submodule.
_mb = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

TM = 128  # row tile: groups of a few hundred rows waste < 1 tile each


def _tiling(k: int, n: int):
    return (TM, min(k, 512), min(n, 512))


def _pad_rows(a, group_sizes):
    """Zero rows up to a multiple of TM, counted in the last group."""
    pad = -a.shape[0] % TM
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    return a, group_sizes


def _stack(axis_size, in_batched, args):
    return [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for b, a in zip(in_batched, args)]


@functools.lru_cache(maxsize=None)
def _gmm(out_dtype, transpose_rhs: bool, interpret: bool):
    """fn(x (m, k), w, group_sizes) -> (m, n): x_g @ w[g] (w[g].T with
    ``transpose_rhs``), one kernel launch, batched by stacking groups."""

    @jax.custom_batching.custom_vmap
    def fn(x, w, group_sizes):
        m = x.shape[0]
        xp, gs = _pad_rows(x, group_sizes)
        k, n = w.shape[1:] if not transpose_rhs else w.shape[:0:-1]
        out = _mb.gmm(xp, w, gs, out_dtype, _tiling(k, n),
                      transpose_rhs=transpose_rhs, interpret=interpret)
        return out[:m]

    @fn.def_vmap
    def _batched(axis_size, in_batched, x, w, group_sizes):
        x, w, group_sizes = _stack(axis_size, in_batched,
                                   (x, w, group_sizes))
        N, m = x.shape[:2]
        out = fn(x.reshape(N * m, -1), w.reshape((-1,) + w.shape[2:]),
                 group_sizes.reshape(-1))
        return out.reshape(N, m, -1), True

    return fn


@functools.lru_cache(maxsize=None)
def _tgmm(out_dtype, interpret: bool):
    """fn(x (m, k), dy (m, n), group_sizes) -> (G, k, n): x_g.T @ dy_g,
    one kernel launch, batched by stacking groups."""

    @jax.custom_batching.custom_vmap
    def fn(x, dy, group_sizes):
        xp, gs = _pad_rows(x, group_sizes)
        dyp, _ = _pad_rows(dy, group_sizes)
        return _mb.tgmm(xp.T, dyp, gs, out_dtype,
                        _tiling(x.shape[1], dy.shape[1]),
                        interpret=interpret)

    @fn.def_vmap
    def _batched(axis_size, in_batched, x, dy, group_sizes):
        x, dy, group_sizes = _stack(axis_size, in_batched,
                                    (x, dy, group_sizes))
        N, m = x.shape[:2]
        G = group_sizes.shape[1]
        out = fn(x.reshape(N * m, -1), dy.reshape(N * m, -1),
                 group_sizes.reshape(-1))
        return out.reshape((N, G) + out.shape[1:]), True

    return fn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul_pallas(x, w, group_sizes, out_dtype, interpret=False):
    """(m, n) = x_g @ w[g] for each group g of rows; the backward is the
    same kernels: dx_g = dy_g @ w[g].T, dw[g] = x_g.T @ dy_g, with the
    cotangent in the operands' dtype."""
    return _gmm(jnp.dtype(out_dtype), False, interpret)(x, w, group_sizes)


def _fwd(x, w, group_sizes, out_dtype, interpret):
    return (grouped_matmul_pallas(x, w, group_sizes, out_dtype, interpret),
            (x, w, group_sizes))


def _bwd(out_dtype, interpret, res, dy):
    x, w, group_sizes = res
    dy = dy.astype(x.dtype)
    dx = _gmm(x.dtype, True, interpret)(dy, w, group_sizes)
    dw = _tgmm(w.dtype, interpret)(x, dy, group_sizes)
    return dx, dw, None


grouped_matmul_pallas.defvjp(_fwd, _bwd)
