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
                            policy=pol)
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
    o = ops.flash_attention(q, k, v, causal=False, policy=pol)
    o = o.reshape(B, S, H * Dh) @ w["wxo"]
    return constrain(pol, o, "residual")


def _dense_ffn(cfg, x, w, pol, prefix="w"):
    cd = dtype_of(cfg.compute_dtype)
    g = jax.nn.silu((x @ w[prefix + "_gate"]).astype(jnp.float32)).astype(cd)
    u = (x @ w[prefix + "_up"]).astype(cd)
    h = constrain(pol, g * u, "ffn_hidden")
    return constrain(pol, h @ w[prefix + "_down"], "residual")


# --- MoE dispatch gathers with gather-form VJPs -----------------------------
# The backward of take_along_axis is a scatter-add, which GSPMD replicates
# for data-dependent indices.  The MoE dispatch permutations are (masked)
# bijections, so every cotangent is itself a gather with the inverse index
# set — these custom VJPs keep the whole fwd+bwd dispatch scatter-free
# (perf iteration 2, EXPERIMENTS.md §Perf).

def _float0(x):
    import numpy as _onp
    return _onp.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _perm_gather(x, idx_f, mask_f, idx_b, mask_b, pol):
    """y[..., j, :] = x[..., idx_f[j], :] * mask_f[j]; bwd uses (idx_b,
    mask_b) — the inverse (masked) permutation along axis 2.  Both fwd and
    bwd outputs are constrained block-local so GSPMD never replicates the
    data-dependent gathers (the only reshard points are the explicit
    moe_dispatch / moe_return constraints)."""
    y = jnp.take_along_axis(x, idx_f[..., None], axis=2)
    y = y * mask_f[..., None].astype(y.dtype)
    return constrain(pol, y, "moe_tokens")


def _perm_gather_fwd(x, idx_f, mask_f, idx_b, mask_b, pol):
    return _perm_gather(x, idx_f, mask_f, idx_b, mask_b, pol), \
        (idx_f, mask_f, idx_b, mask_b)


def _perm_gather_bwd(pol, res, dy):
    idx_f, mask_f, idx_b, mask_b = res
    dy = constrain(pol, dy, "moe_tokens")
    dx = jnp.take_along_axis(dy, idx_b[..., None], axis=2)
    dx = dx * mask_b[..., None].astype(dx.dtype)
    dx = constrain(pol, dx, "moe_tokens")
    return (dx, _float0(idx_f), _float0(mask_f), _float0(idx_b),
            _float0(mask_b))


_perm_gather.defvjp(_perm_gather_fwd, _perm_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fanout_gather(xb, t_s, inv_order, K, pol):
    """tv[..., a, :] = xb[..., t_s[a], :]; each token is read K times, so the
    cotangent is the K-way gather-sum by inv_order (no scatter)."""
    tv = jnp.take_along_axis(xb, t_s[..., None], axis=2)
    return constrain(pol, tv, "moe_tokens")


def _fanout_fwd(xb, t_s, inv_order, K, pol):
    return _fanout_gather(xb, t_s, inv_order, K, pol), (t_s, inv_order)


def _fanout_bwd(K, pol, res, dtv):
    t_s, inv_order = res
    B, n, A, D = dtv.shape
    dtv = constrain(pol, dtv, "moe_tokens")
    d_orig = jnp.take_along_axis(dtv, inv_order[..., None], axis=2)
    dxb = d_orig.reshape(B, n, A // K, K, D).sum(axis=3)
    return constrain(pol, dxb, "moe_tokens"), _float0(t_s), _float0(inv_order)


_fanout_gather.defvjp(_fanout_fwd, _fanout_bwd)


def _moe_ffn(cfg, x, w, pol):
    """Group-local expert-parallel MoE via double-argsort dispatch
    (perf iterations 1-2, EXPERIMENTS.md §Perf).

    Routing/capacity run WITHIN seq-shard-aligned token blocks (nblk =
    sequence shards) so every intermediate keeps the activations' sharding,
    and the dispatch uses ONLY gathers (argsort + take_along_axis — no
    scatters, which GSPMD replicates for data-dependent indices).  The
    dispatch tensor X (B, nblk, E, cap, D) is then resharded from the block
    dim to the expert dim, which lowers to an all-to-all over the model
    axis: tokens physically travel to their expert's shard (classic EP).
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    cd = dtype_of(cfg.compute_dtype)
    nblk = pol.seq_blocks() if pol is not None else 1
    if S % nblk:
        nblk = 1
    Sb = S // nblk
    A = Sb * K  # assignments per block
    xb = x.reshape(B, nblk, Sb, D)

    logits = jnp.einsum("bnsd,de->bnse", xb, w["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (B, nblk, Sb, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    e_flat = gate_idx.reshape(B, nblk, A)
    t_flat = jnp.broadcast_to(
        jnp.repeat(jnp.arange(Sb, dtype=jnp.int32), K), (B, nblk, A))
    w_flat = gate_vals.reshape(B, nblk, A)

    order = jnp.argsort(e_flat, axis=-1).astype(jnp.int32)
    inv_order = jnp.argsort(order, axis=-1).astype(jnp.int32)
    e_s = jnp.take_along_axis(e_flat, order, -1)
    t_s = jnp.take_along_axis(t_flat, order, -1)
    w_s = jnp.take_along_axis(w_flat, order, -1)

    vv = jax.vmap(jax.vmap(lambda a, v: jnp.searchsorted(
        a, v, side="left").astype(jnp.int32)))
    eids = jnp.broadcast_to(jnp.arange(E, dtype=jnp.int32), (B, nblk, E))
    first = vv(e_s, eids)                       # (B, nblk, E)
    cap = max(8, int(2 * ((A + E - 1) // E)))   # capacity factor 2.0

    # ---- dispatch: X[e, c] = tokens of the c-th assignment of expert e ----
    slot_src = first[..., None] + jnp.arange(cap, dtype=jnp.int32)
    src_e = jnp.take_along_axis(
        e_s, jnp.clip(slot_src, 0, A - 1).reshape(B, nblk, E * cap), -1)
    valid = (slot_src < A) & (src_e.reshape(B, nblk, E, cap)
                              == eids[..., None])
    pos = jnp.arange(A, dtype=jnp.int32)[None, None] \
        - jnp.take_along_axis(first, e_s, -1)
    ok = pos < cap
    slot_of_a = jnp.clip(e_s * cap + pos, 0, E * cap - 1)
    tv = _fanout_gather(xb, t_s, inv_order, K, pol)  # (B,nblk,A,D)
    X = _perm_gather(tv, jnp.clip(slot_src, 0, A - 1).reshape(B, nblk, -1),
                     valid.reshape(B, nblk, -1), slot_of_a, ok, pol)
    X = X.reshape(B, nblk, E, cap, D).astype(cd)
    X = constrain(pol, X, "moe_dispatch")  # block->expert reshard (a2a)

    # constrain expert weights in-forward: their GRADIENTS then inherit the
    # (E->model, D/F->extra) sharding instead of materializing a full f32
    # (E, D, F) cotangent per layer (16.6 GiB at arctic scale).
    we_g = constrain(pol, w["we_gate"], "moe_w_in")
    we_u = constrain(pol, w["we_up"], "moe_w_in")
    we_d = constrain(pol, w["we_down"], "moe_w_out")
    g = jax.nn.silu(jnp.einsum("bnecd,edf->bnecf", X, we_g,
                               preferred_element_type=jnp.float32)).astype(cd)
    u = jnp.einsum("bnecd,edf->bnecf", X, we_u,
                   preferred_element_type=jnp.float32).astype(cd)
    Y = jnp.einsum("bnecf,efd->bnecd", g * u, we_d,
                   preferred_element_type=jnp.float32).astype(cd)
    Y = constrain(pol, Y, "moe_return")  # expert->block reshard (a2a back)

    # ---- combine: pure gathers back to tokens (fwd AND bwd) ----
    Yf = Y.reshape(B, nblk, E * cap, D)
    ya = _perm_gather(Yf, slot_of_a, ok,
                      jnp.clip(slot_src, 0, A - 1).reshape(B, nblk, -1),
                      valid.reshape(B, nblk, -1), pol)
    ya = ya * (w_s * jnp.where(ok, 1.0, 0.0))[..., None].astype(cd)
    ya_orig = _perm_gather(ya, inv_order, jnp.ones_like(ok), order,
                           jnp.ones_like(ok), pol)
    y = ya_orig.reshape(B, nblk, Sb, K, D).sum(axis=3)
    y = y.reshape(B, S, D)
    if cfg.moe_dense_ff:  # arctic dense-residual branch (parallel)
        y = y + _dense_ffn(cfg, x, w, pol)
    return constrain(pol, y, "residual")


def _block(cfg, pol, carry, w, *, causal=True, mem_kv=None):
    x, positions = carry
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    attn_out, _ = _attention(cfg, h, w, pol, positions, causal=causal,
                             window=cfg.window)
    x = x + attn_out
    if mem_kv is not None and "wxq" in w:
        h = rms_norm(x, w["lnx"], cfg.norm_eps)
        x = x + _cross_attention(cfg, h, w, pol, mem_kv)
    h = rms_norm(x, w["ln2"], cfg.norm_eps)
    if cfg.num_experts:
        x = x + _moe_ffn(cfg, h, w, pol)
    else:
        x = x + _dense_ffn(cfg, h, w, pol)
    return (constrain(pol, x, "residual"), positions), None


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def _embed(cfg, params, batch, pol):
    tokens = batch["tokens"]
    x = params["emb"][tokens].astype(dtype_of(cfg.compute_dtype))
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
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (params["emb"].T if cfg.tie_embeddings else params["out_head"])
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits, cfg.logits_softcap)
    if cfg.vocab_padded != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, jnp.asarray(-1e30, logits.dtype))
    return constrain(pol, logits, "logits")


def forward(cfg: ModelConfig, params, batch, policy=None):
    """Teacher-forced full-sequence logits. batch: tokens (B,S) [+ frontend]."""
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
    (x, _), _ = jax.lax.scan(body, (x, positions), params["layers"])
    return _logits(cfg, params, x, pol)


def loss_fn(cfg: ModelConfig, params, batch, policy=None):
    logits = forward(cfg, params, batch, policy)
    tokens = batch["tokens"]
    labels = tokens[:, 1:]
    lg = logits[:, :-1]
    mask = jnp.ones_like(labels, jnp.float32)
    if cfg.frontend == "vit_stub":
        pos = jnp.arange(labels.shape[1])
        mask = mask * (pos[None, :] >= cfg.frontend_tokens)
    return cross_entropy(lg, labels, mask)


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
        x = x + attn_out
        h = rms_norm(x, w["ln2"], cfg.norm_eps)
        if cfg.num_experts:
            x = x + _moe_ffn(cfg, h, w, pol)
        else:
            x = x + _dense_ffn(cfg, h, w, pol)
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
    x = params["emb"][tokens].astype(cd)
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
            q, kp, vp, page_table, kv_len, contiguous=contiguous, **scales)
        o = ops.decode_attention_combine(q, o_old, m_old, l_old, k, v)
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
        x = x + o.reshape(B, 1, H * Dh) @ w["wo"]
        h = rms_norm(x, w["ln2"], cfg.norm_eps)
        if cfg.num_experts:
            x = x + _moe_ffn(cfg, h, w, pol)
        else:
            x = x + _dense_ffn(cfg, h, w, pol)
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
        x = x + attn_out
        k_l = jax.lax.dynamic_update_slice_in_dim(k_l, k_new, 0, axis=1)
        v_l = jax.lax.dynamic_update_slice_in_dim(v_l, v_new, 0, axis=1)
        out_extra = {}
        if cfg.enc_layers:
            KH, Dh = cfg.num_kv_heads, cfg.head_dim
            cd = dtype_of(cfg.compute_dtype)
            xk = (mem @ w["wxk"]).astype(cd).reshape(B, -1, KH, Dh)
            xv = (mem @ w["wxv"]).astype(cd).reshape(B, -1, KH, Dh)
            h = rms_norm(x, w["lnx"], cfg.norm_eps)
            x = x + _cross_attention(cfg, h, w, pol, (xk, xv))
            out_extra = {"xk": xk, "xv": xv}
        h = rms_norm(x, w["ln2"], cfg.norm_eps)
        if cfg.num_experts:
            x = x + _moe_ffn(cfg, h, w, pol)
        else:
            x = x + _dense_ffn(cfg, h, w, pol)
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
    x = params["emb"][tokens].astype(cd)
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
            q, k_l, v_l, kv_len=kv_len, return_stats=True)
        o = ops.decode_attention_combine(q, o_old, m_old, l_old, k, v)
        k_all = jax.lax.dynamic_update_slice(
            k_all, k[None], (l, 0, slot, 0, 0))
        v_all = jax.lax.dynamic_update_slice(
            v_all, v[None], (l, 0, slot, 0, 0))
        x = x + o.reshape(B, 1, H * Dh) @ w["wo"]
        if cfg.enc_layers:
            h = rms_norm(x, w["lnx"], cfg.norm_eps)
            x = x + _cross_attention(cfg, h, w, pol,
                                     (cache["xk"][l], cache["xv"][l]))
        h = rms_norm(x, w["ln2"], cfg.norm_eps)
        if cfg.num_experts:
            x = x + _moe_ffn(cfg, h, w, pol)
        else:
            x = x + _dense_ffn(cfg, h, w, pol)
        return (x, k_all, v_all)

    x, k_all, v_all = jax.lax.fori_loop(
        0, L, body, (x, cache["k"], cache["v"]))
    logits = _logits(cfg, params, x, pol)
    out = dict(cache)
    out["k"], out["v"] = k_all, v_all
    out["pos"] = pos + 1
    return logits, out
