"""Transformer LM families: dense, moe (EP), encdec — with modality stubs.

Pure-pytree models; layers stacked on a leading L dim and scanned (compact
HLO, one lowering per block).  Sharding is controlled by a Policy object via
``constrain`` hooks (see repro/dist/policies.py); everything works unsharded
when policy is None.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.common import (constrain, cross_entropy, dense_init,
                                 dtype_of, kv_quantize_int8, rms_norm, rope,
                                 softcap, split_keys)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: ModelConfig, cross: bool = False) -> Dict[str, tuple]:
    D, H, KH, Dh, F = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim, cfg.d_ff)
    s: Dict[str, tuple] = {
        "ln1": (D,), "ln2": (D,),
        "wq": (D, H * Dh), "wk": (D, KH * Dh), "wv": (D, KH * Dh),
        "wo": (H * Dh, D),
    }
    if cfg.qkv_bias:
        s.update(bq=(H * Dh,), bk=(KH * Dh,), bv=(KH * Dh,))
    if cross:
        s.update(lnx=(D,), wxq=(D, H * Dh), wxk=(D, KH * Dh),
                 wxv=(D, KH * Dh), wxo=(H * Dh, D))
    if cfg.num_experts:
        E = cfg.num_experts
        s.update(router=(D, E), we_gate=(E, D, F), we_up=(E, D, F),
                 we_down=(E, F, D))
        if cfg.moe_dense_ff:
            Fd = cfg.moe_dense_ff
            s.update(w_gate=(D, Fd), w_up=(D, Fd), w_down=(Fd, D))
    else:
        s.update(w_gate=(D, F), w_up=(D, F), w_down=(F, D))
    return s


def _stack_init(rng, shapes, L, dtype):
    out = {}
    keys = split_keys(rng, len(shapes))
    for key, (name, shp) in zip(keys, sorted(shapes.items())):
        if name.startswith("ln"):
            out[name] = jnp.ones((L,) + shp, dtype)
        else:
            out[name] = dense_init(key, (L,) + shp, dtype)
    return out


def init(cfg: ModelConfig, rng) -> Dict[str, Any]:
    dt = dtype_of(cfg.param_dtype)
    k_emb, k_layers, k_enc, k_head = split_keys(rng, 4)
    params: Dict[str, Any] = {
        "emb": dense_init(k_emb, (cfg.vocab_padded, cfg.d_model), dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "layers": _stack_init(k_layers, _layer_shapes(
            cfg, cross=cfg.cross_attention), cfg.num_layers, dt),
    }
    if not cfg.tie_embeddings:
        params["out_head"] = dense_init(k_head,
                                        (cfg.d_model, cfg.vocab_padded), dt)
    if cfg.enc_layers:
        enc_cfg = cfg.replace(num_experts=0, qkv_bias=cfg.qkv_bias)
        params["enc_layers"] = _stack_init(
            k_enc, _layer_shapes(enc_cfg, cross=False), cfg.enc_layers, dt)
        params["enc_norm"] = jnp.ones((cfg.d_model,), dt)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _attention(cfg, x, w, pol, positions, *, causal, window=0, prefix=""):
    B, S, D = x.shape
    H, KH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd = dtype_of(cfg.compute_dtype)
    q = (x @ w[prefix + "wq"]).astype(cd)
    k = (x @ w[prefix + "wk"]).astype(cd)
    v = (x @ w[prefix + "wv"]).astype(cd)
    if cfg.qkv_bias and not prefix:
        q = q + w["bq"].astype(cd)
        k = k + w["bk"].astype(cd)
        v = v + w["bv"].astype(cd)
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, KH, Dh)
    v = v.reshape(B, S, KH, Dh)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = constrain(pol, q, "heads")
    k = constrain(pol, k, "kv_full")  # gather over the sequence-shard axis
    v = constrain(pol, v, "kv_full")
    o = ops.flash_attention(q, k, v, causal=causal, window=window,
                            softmax_scale=_softmax_scale(cfg), policy=pol)
    o = constrain(pol, o, "heads")
    o = o.reshape(B, S, H * Dh) @ w[prefix + "wo"]
    return constrain(pol, o, "residual"), (k, v)


def _cross_attention(cfg, x, w, pol, mem_kv):
    B, S, D = x.shape
    H, KH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd = dtype_of(cfg.compute_dtype)
    q = (x @ w["wxq"]).astype(cd).reshape(B, S, H, Dh)
    k, v = mem_kv  # (B, S_enc, KH, Dh) each, precomputed from encoder output
    q = constrain(pol, q, "heads")
    o = ops.flash_attention(q, k, v, causal=False,
                            softmax_scale=_softmax_scale(cfg), policy=pol)
    o = o.reshape(B, S, H * Dh) @ w["wxo"]
    return constrain(pol, o, "residual")


def _dense_ffn(cfg, x, w, pol, prefix="w"):
    cd = dtype_of(cfg.compute_dtype)
    g = jax.nn.silu((x @ w[prefix + "_gate"]).astype(jnp.float32)).astype(cd)
    u = (x @ w[prefix + "_up"]).astype(cd)
    h = constrain(pol, g * u, "ffn_hidden")
    return constrain(pol, h @ w[prefix + "_down"], "residual")


# --- dropless mixture of experts ---------------------------------------------
# Every (token, expert) assignment is computed: the assignments are sorted
# by expert, the experts run as grouped matmuls over the ragged groups, and
# each result is gathered back to its token.  Both permutations are gathers
# whose cotangents are gathers by the inverse permutation, so neither
# direction scatters.

def _float0(x):
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, token, inv, K):
    """xs[j] = x[token[j]]: sorted assignment j reads its token's row.  A
    token's cotangent sums its K assignments', read back through ``inv``
    (the sorted position of each assignment, in token-major order)."""
    return x[token]


def _dispatch_fwd(x, token, inv, K):
    return x[token], (token, inv)


def _dispatch_bwd(K, res, dxs):
    token, inv = res
    d = dxs[inv].reshape(-1, K, dxs.shape[-1])
    dx = d.astype(jnp.float32).sum(axis=1).astype(dxs.dtype)
    return dx, _float0(token), _float0(inv)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unpermute(ys, inv, order):
    """ya[a] = ys[inv[a]]: each assignment's result back in token-major
    order; the cotangent is the gather by ``order``, the inverse of inv."""
    return ys[inv]


def _unpermute_fwd(ys, inv, order):
    return ys[inv], (inv, order)


def _unpermute_bwd(res, dya):
    inv, order = res
    return dya[order], _float0(inv), _float0(order)


_unpermute.defvjp(_unpermute_fwd, _unpermute_bwd)


def _moe_ffn(cfg, x, w, pol, experts=None):
    """Dropless top-K mixture of SwiGLU experts; returns (y, load).

    Routing is over all E experts: router logits in f32, softmax, top-K,
    the K gates renormalised to sum to 1 (the softmax over the K chosen
    logits).  ``experts`` = (first, count) are the experts whose weights
    ``w`` holds (default all E, first 0): the layer computes their part of
    the result, as one expert-parallel share does; the assignments of the
    other experts sort last, ride in the last group and get gate 0.
    ``load`` (E,) int32 counts the assignments of every expert.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    first, count = experts or (0, E)
    assert w["we_gate"].shape[0] == count, (w["we_gate"].shape, experts)
    cd = dtype_of(cfg.compute_dtype)
    T, A = B * S, B * S * K
    # a Mosaic kernel cannot be partitioned by GSPMD: on a mesh the
    # experts run on the jnp route
    impl = "jnp" if pol is not None and pol.mesh is not None else None
    xt = x.reshape(T, D).astype(cd)
    with jax.named_scope("lm.moe_route"):
        logits = jnp.dot(xt, w["router"], preferred_element_type=jnp.float32)
        gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        expert = idx.reshape(A)
        load = jnp.sum(expert[:, None] == jnp.arange(E), axis=0,
                       dtype=jnp.int32)
        local = expert - first
        mine = (local >= 0) & (local < count)
        order = jnp.argsort(jnp.where(mine, local, count),
                            stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        sizes = load[first:first + count]
        sizes = sizes.at[-1].add(A - sizes.sum())
        xs = _dispatch(xt, order // K, inv, K)
        gw = jnp.where(mine, gate.reshape(A), 0.0)

    # the expert weights constrained in the forward: their gradients then
    # keep the experts' sharding instead of a whole f32 (E, D, F)
    # cotangent a layer (16.6 GiB at arctic's size)
    kinds = {"we_gate": "moe_w_in", "we_up": "moe_w_in",
             "we_down": "moe_w_out"}

    def gmm(a, name):
        return ops.grouped_matmul(a, constrain(pol, w[name], kinds[name]),
                                  sizes, impl=impl,
                                  preferred_element_type=jnp.float32)

    with jax.named_scope("lm.moe_experts"):
        h = (jax.nn.silu(gmm(xs, "we_gate")) * gmm(xs, "we_up")).astype(cd)
        ys = gmm(h, "we_down").astype(cd)
    with jax.named_scope("lm.moe_combine"):
        ya = _unpermute(ys, inv, order).reshape(T, K, D)
        y = (ya.astype(jnp.float32) * gw.reshape(T, K, 1)).sum(axis=1)
        y = y.astype(cd).reshape(B, S, D)
    if cfg.moe_dense_ff:  # arctic dense-residual branch (parallel)
        y = y + _dense_ffn(cfg, x, w, pol)
    return constrain(pol, y, "residual"), load


def _ffn(cfg, h, w, pol):
    """(FFN output, per-expert load or None)."""
    if cfg.num_experts:
        return _moe_ffn(cfg, h, w, pol)
    return _dense_ffn(cfg, h, w, pol), None


def _residual(cfg, x, y):
    """x + y, the branch scaled by ``residual_multiplier`` where set."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _softmax_scale(cfg):
    """``attention_multiplier`` where set, else the kernels' 1/sqrt(Dh)."""
    return cfg.attention_multiplier or None


def _block(cfg, pol, carry, w, *, causal=True, mem_kv=None):
    x, positions = carry
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    with jax.named_scope("lm.attn"):
        attn_out, _ = _attention(cfg, h, w, pol, positions, causal=causal,
                                 window=cfg.window)
    x = _residual(cfg, x, attn_out)
    if mem_kv is not None and "wxq" in w:
        h = rms_norm(x, w["lnx"], cfg.norm_eps)
        x = _residual(cfg, x, _cross_attention(cfg, h, w, pol, mem_kv))
    h = rms_norm(x, w["ln2"], cfg.norm_eps)
    with jax.named_scope("lm.mlp"):
        ffn, load = _ffn(cfg, h, w, pol)
    x = _residual(cfg, x, ffn)
    return (constrain(pol, x, "residual"), positions), load


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def _embed_tokens(cfg, params, tokens):
    x = params["emb"][tokens].astype(dtype_of(cfg.compute_dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _embed(cfg, params, batch, pol):
    with jax.named_scope("lm.embed"):
        x = _embed_tokens(cfg, params, batch["tokens"])
        if cfg.frontend == "vit_stub":
            P = cfg.frontend_tokens
            pe = batch["patch_embeds"].astype(x.dtype)
            x = jnp.concatenate([pe, x[:, P:]], axis=1)
        return constrain(pol, x, "residual")


def _encode(cfg, params, frames, pol):
    x = constrain(pol, frames.astype(dtype_of(cfg.compute_dtype)), "residual")
    positions = jnp.arange(x.shape[1])
    body = functools.partial(_block, cfg, pol, causal=False, mem_kv=None)
    if cfg.remat:
        body = jax.checkpoint(body)
    (x, _), _ = jax.lax.scan(body, (x, positions), params["enc_layers"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _logits(cfg, params, x, pol):
    with jax.named_scope("lm.head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["emb"].T if cfg.tie_embeddings
                else params["out_head"])
        logits = x @ head.astype(x.dtype)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        logits = softcap(logits, cfg.logits_softcap)
        if cfg.vocab_padded != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
            logits = jnp.where(pad_mask, logits,
                               jnp.asarray(-1e30, logits.dtype))
        return constrain(pol, logits, "logits")


def forward(cfg: ModelConfig, params, batch, policy=None):
    """Teacher-forced full-sequence logits. batch: tokens (B,S) [+ frontend]."""
    return _forward(cfg, params, batch, policy)[0]


def _forward(cfg, params, batch, policy):
    """(logits, per-layer expert load (L, E) int32 or None)."""
    pol = policy
    x = _embed(cfg, params, batch, pol)
    positions = jnp.arange(x.shape[1])
    mem_kv = None
    if cfg.enc_layers:
        mem = _encode(cfg, params, batch["frames"], pol)
        # precompute cross K/V once per layer inside the scan from mem
        mem_kv = mem
    def body(carry, w):
        if cfg.enc_layers:
            B = mem_kv.shape[0]
            KH, Dh = cfg.num_kv_heads, cfg.head_dim
            cd = dtype_of(cfg.compute_dtype)
            xk = (mem_kv @ w["wxk"]).astype(cd).reshape(B, -1, KH, Dh)
            xv = (mem_kv @ w["wxv"]).astype(cd).reshape(B, -1, KH, Dh)
            xk = constrain(pol, xk, "kv_full")
            xv = constrain(pol, xv, "kv_full")
            return _block(cfg, pol, carry, w, causal=True, mem_kv=(xk, xv))
        return _block(cfg, pol, carry, w, causal=True)
    if cfg.remat:
        body = jax.checkpoint(body)
    (x, _), load = jax.lax.scan(body, (x, positions), params["layers"])
    return _logits(cfg, params, x, pol), load


def loss_fn(cfg: ModelConfig, params, batch, policy=None):
    return loss_and_load(cfg, params, batch, policy)[0]


def loss_and_load(cfg: ModelConfig, params, batch, policy=None):
    """(loss, load): ``load`` is the (E,) int32 count of assignments per
    expert summed over the layers, None for a model without experts."""
    logits, load = _forward(cfg, params, batch, policy)
    tokens = batch["tokens"]
    labels = tokens[:, 1:]
    lg = logits[:, :-1]
    mask = jnp.ones_like(labels, jnp.float32)
    if cfg.frontend == "vit_stub":
        pos = jnp.arange(labels.shape[1])
        mask = mask * (pos[None, :] >= cfg.frontend_tokens)
    with jax.named_scope("lm.head"):
        loss = cross_entropy(lg, labels, mask)
    return loss, None if load is None else load.sum(axis=0)


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               enc_len: int = 0):
    L, KH, Dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    cd = dtype_of(cfg.compute_dtype)
    cache = {
        "k": jnp.zeros((L, batch_size, max_len, KH, Dh), cd),
        "v": jnp.zeros((L, batch_size, max_len, KH, Dh), cd),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.enc_layers:
        cache["xk"] = jnp.zeros((L, batch_size, enc_len, KH, Dh), cd)
        cache["xv"] = jnp.zeros((L, batch_size, enc_len, KH, Dh), cd)
    return cache


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     kv_dtype: str = None):
    """Paged KV pool (DESIGN.md §Serving contract): one (L, num_pages,
    page_size, KH, Dh) buffer per K/V, page 0 reserved as the null page.
    ``kv_dtype="int8"`` stores block-scaled int8 values plus one f32
    scale per (page, position, head) head_dim block."""
    L, KH, Dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    cd = dtype_of(cfg.compute_dtype)
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype {kv_dtype!r} not in (None, 'int8')")
    vd = jnp.int8 if kv_dtype == "int8" else cd
    cache = {
        "k": jnp.zeros((L, num_pages, page_size, KH, Dh), vd),
        "v": jnp.zeros((L, num_pages, page_size, KH, Dh), vd),
    }
    if kv_dtype == "int8":
        cache["k_scale"] = jnp.zeros((L, num_pages, page_size, KH),
                                     jnp.float32)
        cache["v_scale"] = jnp.zeros((L, num_pages, page_size, KH),
                                     jnp.float32)
    return cache


def prefill_paged(cfg: ModelConfig, params, batch, cache, page_table,
                  prompt_len, policy=None):
    """Prompt prefill writing KV through the page table.

    batch["tokens"]: (B, S_pad) right-padded prompts with S_pad a
    multiple of the page size; page_table: (B, P) physical page ids;
    prompt_len: (B,) true prompt lengths.  Returns (logits at position
    prompt_len-1 per row (B, 1, V), updated cache).

    Positions >= prompt_len hold pad garbage in the written pages: reads
    are masked by kv_len and decode overwrites them position-by-position
    as the request grows, so they are never observed (§Serving contract).
    """
    pol = policy
    quant = "k_scale" in cache
    x = _embed(cfg, params, batch, pol)
    B, S, D = x.shape
    ps = cache["k"].shape[2]
    assert S % ps == 0, (S, ps)
    positions = jnp.arange(S)

    def body(carry, w):
        (x, positions) = carry
        h = rms_norm(x, w["ln1"], cfg.norm_eps)
        attn_out, (k_new, v_new) = _attention(
            cfg, h, w, pol, positions, causal=True, window=cfg.window)
        x = _residual(cfg, x, attn_out)
        h = rms_norm(x, w["ln2"], cfg.norm_eps)
        x = _residual(cfg, x, _ffn(cfg, h, w, pol)[0])
        return (constrain(pol, x, "residual"), positions), (k_new, v_new)

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, _), (k_st, v_st) = jax.lax.scan(body, (x, positions),
                                        params["layers"])
    idx = (prompt_len - 1).astype(jnp.int32)[:, None, None]
    logits = _logits(cfg, params, jnp.take_along_axis(x, idx, axis=1), pol)

    # scatter the prompt's pages into the pool (whole pages at a time)
    L = cfg.num_layers
    Pp = S // ps
    KH, Dh = cfg.num_kv_heads, cfg.head_dim
    phys = page_table[:, :Pp]  # (B, Pp)
    out = dict(cache)
    kc = k_st.reshape(L, B, Pp, ps, KH, Dh)
    vc = v_st.reshape(L, B, Pp, ps, KH, Dh)
    if quant:
        kq, ks = kv_quantize_int8(kc)
        vq, vs = kv_quantize_int8(vc)
        out["k"] = cache["k"].at[:, phys].set(kq)
        out["v"] = cache["v"].at[:, phys].set(vq)
        out["k_scale"] = cache["k_scale"].at[:, phys].set(ks)
        out["v_scale"] = cache["v_scale"].at[:, phys].set(vs)
    else:
        out["k"] = cache["k"].at[:, phys].set(kc.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[:, phys].set(vc.astype(cache["v"].dtype))
    return logits, out


def decode_step_paged(cfg: ModelConfig, params, cache, tokens, page_table,
                      kv_len, policy=None, contiguous=False):
    """One-token decode through the page table. tokens: (B, 1); kv_len:
    (B,) per-request lengths (0 for empty decode slots — their reads are
    fully masked and their writes land on the null page).  Returns
    (logits (B, 1, V), cache).

    Same pre-update-attend + analytic-combine structure as the dense
    ``decode_step`` (the page write stays write-only => in place under
    XLA), but positions, rope and the cache view are per-request, so any
    mix of requests at different lengths decodes in one batch.
    """
    pol = policy
    B = tokens.shape[0]
    H, KH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = cfg.num_layers
    cd = dtype_of(cfg.compute_dtype)
    quant = "k_scale" in cache
    ps = cache["k"].shape[2]
    kv_len = kv_len.astype(jnp.int32)
    positions = kv_len[:, None]  # (B, 1) per-request rope positions
    x = _embed_tokens(cfg, params, tokens)
    pj = kv_len // ps
    phys = jnp.take_along_axis(page_table, pj[:, None], axis=1)[:, 0]
    off = kv_len % ps

    def body(l, carry):
        x, c = carry
        w = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
            params["layers"])
        h = rms_norm(x, w["ln1"], cfg.norm_eps)
        q = (h @ w["wq"]).astype(cd)
        k = (h @ w["wk"]).astype(cd)
        v = (h @ w["wv"]).astype(cd)
        if cfg.qkv_bias:
            q, k, v = q + w["bq"].astype(cd), k + w["bk"].astype(cd), \
                v + w["bv"].astype(cd)
        q = rope(q.reshape(B, 1, H, Dh), positions, cfg.rope_theta)
        k = rope(k.reshape(B, 1, KH, Dh), positions, cfg.rope_theta)
        v = v.reshape(B, 1, KH, Dh)
        kp = jax.lax.dynamic_index_in_dim(c["k"], l, 0, keepdims=False)
        vp = jax.lax.dynamic_index_in_dim(c["v"], l, 0, keepdims=False)
        scales = {}
        if quant:
            scales = dict(
                k_scale=jax.lax.dynamic_index_in_dim(c["k_scale"], l, 0,
                                                     keepdims=False),
                v_scale=jax.lax.dynamic_index_in_dim(c["v_scale"], l, 0,
                                                     keepdims=False))
        o_old, m_old, l_old = ops.paged_decode_attention(
            q, kp, vp, page_table, kv_len, contiguous=contiguous,
            softmax_scale=_softmax_scale(cfg), **scales)
        o = ops.decode_attention_combine(q, o_old, m_old, l_old, k, v,
                                         softmax_scale=_softmax_scale(cfg))
        c = dict(c)
        if quant:
            kq, ks = kv_quantize_int8(k[:, 0])
            vq, vs = kv_quantize_int8(v[:, 0])
            c["k"] = c["k"].at[l, phys, off].set(kq)
            c["v"] = c["v"].at[l, phys, off].set(vq)
            c["k_scale"] = c["k_scale"].at[l, phys, off].set(ks)
            c["v_scale"] = c["v_scale"].at[l, phys, off].set(vs)
        else:
            c["k"] = c["k"].at[l, phys, off].set(
                k[:, 0].astype(c["k"].dtype))
            c["v"] = c["v"].at[l, phys, off].set(
                v[:, 0].astype(c["v"].dtype))
        x = _residual(cfg, x, o.reshape(B, 1, H * Dh) @ w["wo"])
        h = rms_norm(x, w["ln2"], cfg.norm_eps)
        x = _residual(cfg, x, _ffn(cfg, h, w, pol)[0])
        return (x, c)

    x, out = jax.lax.fori_loop(0, L, body, (x, dict(cache)))
    logits = _logits(cfg, params, x, pol)
    return logits, out


def prefill(cfg: ModelConfig, params, batch, cache, policy=None):
    """Run the prompt, fill the cache, return last-position logits + cache."""
    pol = policy
    x = _embed(cfg, params, batch, pol)
    B, S, D = x.shape
    positions = jnp.arange(S)
    mem = _encode(cfg, params, batch["frames"], pol) if cfg.enc_layers else None

    def body(carry, wkv):
        w, k_l, v_l = wkv["w"], wkv["k"], wkv["v"]
        (x, positions) = carry
        h = rms_norm(x, w["ln1"], cfg.norm_eps)
        attn_out, (k_new, v_new) = _attention(
            cfg, h, w, pol, positions, causal=True, window=cfg.window)
        x = _residual(cfg, x, attn_out)
        k_l = jax.lax.dynamic_update_slice_in_dim(k_l, k_new, 0, axis=1)
        v_l = jax.lax.dynamic_update_slice_in_dim(v_l, v_new, 0, axis=1)
        out_extra = {}
        if cfg.enc_layers:
            KH, Dh = cfg.num_kv_heads, cfg.head_dim
            cd = dtype_of(cfg.compute_dtype)
            xk = (mem @ w["wxk"]).astype(cd).reshape(B, -1, KH, Dh)
            xv = (mem @ w["wxv"]).astype(cd).reshape(B, -1, KH, Dh)
            h = rms_norm(x, w["lnx"], cfg.norm_eps)
            x = _residual(cfg, x, _cross_attention(cfg, h, w, pol, (xk, xv)))
            out_extra = {"xk": xk, "xv": xv}
        h = rms_norm(x, w["ln2"], cfg.norm_eps)
        x = _residual(cfg, x, _ffn(cfg, h, w, pol)[0])
        return (constrain(pol, x, "residual"), positions), {
            "k": k_l, "v": v_l, **out_extra}

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, _), new_cache = jax.lax.scan(
        body, (x, positions),
        {"w": params["layers"], "k": cache["k"], "v": cache["v"]})
    logits = _logits(cfg, params, x[:, -1:], pol)
    out_cache = {"k": new_cache["k"], "v": new_cache["v"],
                 "pos": jnp.asarray(S, jnp.int32)}
    if cfg.enc_layers:
        out_cache["xk"] = new_cache["xk"]
        out_cache["xv"] = new_cache["xv"]
    return logits, out_cache


def decode_step(cfg: ModelConfig, params, cache, tokens, policy=None):
    """One-token decode. tokens: (B, 1). Returns (logits (B,1,V), cache).

    The layer loop is a fori_loop carrying the full stacked KV cache so XLA
    updates it IN PLACE (a scan emitting stacked ys would double-buffer the
    entire cache — 2x HBM at decode_32k scale)."""
    pol = policy
    B = tokens.shape[0]
    H, KH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = cfg.num_layers
    cd = dtype_of(cfg.compute_dtype)
    pos = cache["pos"]
    x = _embed_tokens(cfg, params, tokens)
    positions = jnp.full((B, 1), pos, jnp.int32)

    def body(l, carry):
        x, k_all, v_all = carry
        w = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
            params["layers"])
        h = rms_norm(x, w["ln1"], cfg.norm_eps)
        q = (h @ w["wq"]).astype(cd)
        k = (h @ w["wk"]).astype(cd)
        v = (h @ w["wv"]).astype(cd)
        if cfg.qkv_bias:
            q, k, v = q + w["bq"].astype(cd), k + w["bk"].astype(cd), \
                v + w["bv"].astype(cd)
        q = rope(q.reshape(B, 1, H, Dh), positions, cfg.rope_theta)
        k = rope(k.reshape(B, 1, KH, Dh), positions, cfg.rope_theta)
        v = v.reshape(B, 1, KH, Dh)
        if cfg.window:
            slot = jnp.mod(pos, k_all.shape[2])
        else:
            slot = pos
        # Attend over the PRE-update cache, then fold the new token's (k, v)
        # in analytically (logsumexp combine): the cache update below is
        # write-only, so XLA performs it in place (no 2x cache buffering).
        k_l = constrain(pol, k_all[l], "cache")
        v_l = constrain(pol, v_all[l], "cache")
        kv_len = jnp.broadcast_to(
            jnp.minimum(pos, k_all.shape[2]), (B,))
        o_old, m_old, l_old = ops.decode_attention(
            q, k_l, v_l, kv_len=kv_len, softmax_scale=_softmax_scale(cfg),
            return_stats=True)
        o = ops.decode_attention_combine(q, o_old, m_old, l_old, k, v,
                                         softmax_scale=_softmax_scale(cfg))
        k_all = jax.lax.dynamic_update_slice(
            k_all, k[None], (l, 0, slot, 0, 0))
        v_all = jax.lax.dynamic_update_slice(
            v_all, v[None], (l, 0, slot, 0, 0))
        x = _residual(cfg, x, o.reshape(B, 1, H * Dh) @ w["wo"])
        if cfg.enc_layers:
            h = rms_norm(x, w["lnx"], cfg.norm_eps)
            x = _residual(cfg, x, _cross_attention(
                cfg, h, w, pol, (cache["xk"][l], cache["xv"][l])))
        h = rms_norm(x, w["ln2"], cfg.norm_eps)
        x = _residual(cfg, x, _ffn(cfg, h, w, pol)[0])
        return (x, k_all, v_all)

    x, k_all, v_all = jax.lax.fori_loop(
        0, L, body, (x, cache["k"], cache["v"]))
    logits = _logits(cfg, params, x, pol)
    out = dict(cache)
    out["k"], out["v"] = k_all, v_all
    out["pos"] = pos + 1
    return logits, out
