"""Weights and inputs made by the benchmark from ``--seed``, so that the
program and the plain reference start from the same numbers and neither
takes anything the other made."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int, *salt: int):
    """A JAX key from any whole-number seed (also past 32 bits)."""
    state = np.random.SeedSequence([int(seed)] + [int(s) for s in salt]) \
        .generate_state(2, np.uint32)
    return jnp.asarray(state, jnp.uint32)


def _is_norm(path) -> bool:
    name = str(getattr(path[-1], "key", path[-1]))
    return name.startswith("ln") or name.endswith("norm")


def param_maker(abstract, init_range: float):
    """fn(key) -> params shaped like ``abstract`` (a tree of
    ShapeDtypeStructs in the program's layout): norms are ones, every
    other leaf normal(0, init_range), drawn in f32 and cast to the leaf's
    dtype.  Jit it once; the draw happens on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, a) in zip(keys, flat):
            if _is_norm(path):
                out.append(jnp.ones(a.shape, a.dtype))
            else:
                out.append((init_range * jax.random.normal(
                    k, a.shape, jnp.float32)).astype(a.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def band_table(vocab: int, bands: int):
    """Start and width of ``bands`` contiguous vocabulary bands."""
    edges = np.linspace(0, vocab, bands + 1).astype(np.int64)
    return edges[:-1].astype(np.int32), np.diff(edges).astype(np.int32)


def noniid_mixtures(seed: int, n_clients: int, bands: int, beta: float):
    """(n_clients, bands) log mixture weights, Dirichlet(beta) per client:
    the non-IID split of the vocabulary across clients."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 17]))
    mix = rng.dirichlet(np.full(bands, beta), size=n_clients)
    return np.log(np.maximum(mix, 1e-30)).astype(np.float32)


def token_batcher(vocab: int, bands: int, n_clients: int, rows: int,
                  length: int):
    """fn(key, logmix) -> (n_clients * rows, length) int32 tokens; client d
    fills rows [d*rows, (d+1)*rows) from its own band mixture."""
    start, width = band_table(vocab, bands)
    start, width = jnp.asarray(start), jnp.asarray(width)

    def batch(key, logmix):
        kb, ko = jax.random.split(key)
        band = jax.random.categorical(
            kb, logmix[:, None, None, :], shape=(n_clients, rows, length))
        off = jax.random.randint(ko, (n_clients, rows, length), 0,
                                 jnp.iinfo(jnp.int32).max)
        tok = start[band] + off % width[band]
        return tok.reshape(n_clients * rows, length).astype(jnp.int32)

    return batch
