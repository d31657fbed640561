"""Plain reference of the system under test, in float32 at the highest
matmul precision, written from the published architecture and the HCEF
algorithm alone.  It imports nothing of the program.

The architecture is the configuration file's ``model_type``: its block
module (``bench/blocks/<model_type>.py``, found by ``spec.load_block``)
writes the forward from the primitives kept here: ``mm`` (a matmul at
``HIGHEST``, with the fp8 control), ``rms_norm``, ``rope`` and
``attention`` (causal GQA softmax attention at a given scale).
Parameters come in the layout the benchmark's weight maker fills
(``emb``, ``final_norm`` and per-layer leaves stacked on a leading layer
axis).  The embedding may hold more rows than the vocabulary; only the
first ``vocab_size`` are used.

``lowp="fp8"`` switches every matmul to the precision below the
configuration's bfloat16 (the control): each operand is scaled per tensor
into float8 e4m3 and rounded there, its cotangent likewise into e5m2, as
fp8 training does; the products are taken in f32.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8_round(x, dtype):
    """x rounded to ``dtype`` under a per-tensor scale (amax to the
    format's largest finite value), back in f32."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _fp8_round(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_fp8_round(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _cast(x, lowp):
    if lowp is None:
        return x
    if lowp != "fp8":
        raise ValueError(f"lowp {lowp!r} not in (None, 'fp8')")
    return _fp8(x)


def mm(eq, a, b, lowp):
    return jnp.einsum(eq, _cast(a, lowp), _cast(b, lowp), precision=HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, pos, theta):
    """Rotary embedding on (B, S, H, Dh), halves rotated (GPT-NeoX form)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, :, None] * inv  # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, pos, scale, lowp):
    """Causal softmax attention of q (B, S, H, Dh) over k and v
    (B, S, KH, Dh), each KV head shared by H / KH query heads, scores
    scaled by ``scale``; returns (B, S, H * Dh)."""
    B, S, H, Dh = q.shape
    KH = k.shape[2]
    k = jnp.repeat(k, H // KH, axis=2)
    v = jnp.repeat(v, H // KH, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, lowp) * scale
    causal = pos[:, None, :, None] >= pos[:, None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm("bhqk,bkhd->bqhd", p, v, lowp).reshape(B, S, H * Dh)


def logits(c, params, tokens, lowp=None, positions=None):
    """(B, S, vocab) logits of a causal forward over ``tokens``, by the
    block of the configuration's ``model_type``."""
    return spec.load_block(c["model_type"]).logits(c, params, tokens, lowp,
                                                   positions)


def loss(c, params, tokens, lowp=None):
    """Mean next-token cross-entropy of a sequence (B, S+1): the forward
    runs over the first S tokens, each predicting the next."""
    lg = logits(c, params, tokens[:, :-1], lowp)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


# ---------------------------------------------------------------------------
# HCEF round: tau local heavy-ball SGD steps per client, block top-k of
# (delta + error feedback), intra-cluster mean, and on gossip rounds the
# ring mix of the cluster means.  Parameters and error feedback are kept
# in the storage dtype the configuration states (rounded after each
# update), momentum in float32; all arithmetic is float32.
# ---------------------------------------------------------------------------

def ring_mixing(C: int) -> np.ndarray:
    """Symmetric ring with Metropolis weights: 1/3 to self and each
    neighbour; two clusters average; one keeps its model."""
    if C == 1:
        return np.ones((1, 1))
    if C == 2:
        return np.full((2, 2), 0.5)
    H = np.zeros((C, C))
    for i in range(C):
        for j in (i - 1, i, i + 1):
            H[i, j % C] = 1.0 / 3.0
    return H


def block_topk(x, theta: float, block: int):
    """Keep the ceil(theta * block) largest magnitudes of every block of
    ``block`` consecutive coordinates of the flattened leaf."""
    k = int(min(max(math.ceil(np.float32(theta) * np.float32(block)), 1),
                block))
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    xb = jnp.pad(flat, (0, pad)).reshape(-1, block)
    if k < block:
        thr = jax.lax.top_k(jnp.abs(xb), k)[0][:, -1:]
        xb = jnp.where(jnp.abs(xb) >= thr, xb, 0.0)
    return xb.reshape(-1)[:n].reshape(x.shape)


_JITS = {}


def _cached(key, build):
    """One jitted function per key, so a replay compiles each once."""
    key = json.dumps(key, sort_keys=True, default=str)
    if key not in _JITS:
        _JITS[key] = build()
    return _JITS[key]


def make_local_steps(c, tau: int, eta: float, momentum: float, lowp=None,
                     rows=None):
    """jit fn(params, mom, batch (tau, b, S+1)) -> (params, mom, losses).
    ``rows`` keeps only the first ``rows`` sequences of each step."""
    return _cached(["local", c, tau, eta, momentum, lowp, rows],
                   lambda: _local_steps(c, tau, eta, momentum, lowp, rows))


def _local_steps(c, tau, eta, momentum, lowp, rows):
    store = jax.tree.map

    def step(carry, tok):
        p, m = carry
        if rows is not None:
            tok = tok[:rows]
        pf = store(lambda a: a.astype(jnp.float32), p)
        l, g = jax.value_and_grad(lambda q: loss(c, q, tok, lowp))(pf)
        m = store(lambda mo, gg: momentum * mo + gg, m, g)
        p = store(lambda a, mo: (a.astype(jnp.float32) - eta * mo)
                  .astype(a.dtype), p, m)
        return (p, m), l

    @jax.jit
    def run(p, m, batch):
        (p, m), losses = jax.lax.scan(step, (p, m), batch)
        return p, m, losses

    return run


def make_compress(theta: float, block: int):
    """jit fn(p_tau, p0, ef) -> (compressed delta, new ef) in storage
    dtypes: delta = p_tau - p0 rounded to the parameters' dtype, Q applied
    to delta + ef."""
    return _cached(["compress", float(theta), block],
                   lambda: _compress(theta, block))


def _compress(theta, block):
    def one(pt, p0, e):
        d = (pt.astype(jnp.float32) - p0.astype(jnp.float32)).astype(
            pt.dtype).astype(jnp.float32)
        x = d + e.astype(jnp.float32)
        kept = block_topk(x, theta, block)
        return kept.astype(pt.dtype), (x - kept).astype(e.dtype)

    @jax.jit
    def run(pt, p0, ef):
        out = jax.tree.map(one, pt, p0, ef)
        comp = jax.tree.map(lambda o: o[0], out,
                            is_leaf=lambda o: isinstance(o, tuple))
        new_ef = jax.tree.map(lambda o: o[1], out,
                              is_leaf=lambda o: isinstance(o, tuple))
        return comp, new_ef

    return run


def aggregate(p0s, comps, clusters: int, dev: int, gossip: bool):
    """New per-client parameters: x0 + Q(.) averaged within each cluster,
    then (gossip) mixed over clusters; rounded to the storage dtype."""
    mix = _cached(["mix", clusters, dev, gossip],
                  lambda: _mixer(clusters, dev, gossip))
    return mix(p0s, comps)


def _mixer(clusters, dev, gossip):
    H = np.asarray(ring_mixing(clusters), np.float32)

    @jax.jit
    def mix(p0s, comps):
        def leaf(*xs):
            n = len(xs) // 2
            upd = [a.astype(jnp.float32) + b.astype(jnp.float32)
                   for a, b in zip(xs[:n], xs[n:])]
            means = [sum(upd[c * dev:(c + 1) * dev]) / dev
                     for c in range(clusters)]
            if gossip:
                means = [sum(H[c, j] * means[j] for j in range(clusters))
                         for c in range(clusters)]
            return [means[r // dev].astype(xs[0].dtype) for r in range(n)]
        flat = [jax.tree.leaves(t) for t in p0s + comps]
        out = [leaf(*xs) for xs in zip(*flat)]
        treedef = jax.tree.structure(p0s[0])
        return [jax.tree.unflatten(treedef, [o[r] for o in out])
                for r in range(len(p0s))]

    return mix
