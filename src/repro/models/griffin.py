"""Griffin / RecurrentGemma family: RG-LRU recurrent blocks + local MQA.

Pattern ("rglru", "rglru", "attn") repeating; remainder layers keep the
pattern prefix.  Recurrent state is O(1) and the attention cache is a
rolling ``window``-sized buffer => sub-quadratic, long_500k runs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.models.common import (constrain, cross_entropy, dense_init,
                                 dtype_of, mask_padded_logits, rms_norm, rope,
                                 softcap, split_keys)
from repro.models import lm as lm_mod


def _layout(cfg: ModelConfig):
    pat = cfg.block_pattern
    n_groups = cfg.num_layers // len(pat)
    rem = cfg.block_pattern[: cfg.num_layers % len(pat)]
    rec_per_group = sum(1 for p in pat if p == "rglru")
    attn_per_group = sum(1 for p in pat if p == "attn")
    L_rec = n_groups * rec_per_group + sum(1 for p in rem if p == "rglru")
    L_attn = n_groups * attn_per_group + sum(1 for p in rem if p == "attn")
    return n_groups, rem, rec_per_group, attn_per_group, L_rec, L_attn


def _mlp_shapes(cfg):
    D, F = cfg.d_model, cfg.d_ff
    return {"ln2": (D,), "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}


def _rec_shapes(cfg):
    D, W = cfg.d_model, cfg.lru_width
    return {
        "ln1": (D,), "w_y": (D, W), "w_x": (D, W),
        "conv_w": (cfg.conv_width, W), "conv_b": (W,),
        "wa": (W, W), "wg": (W, W), "log_lambda": (W,),
        "w_out": (W, D), **_mlp_shapes(cfg),
    }


def _attn_shapes(cfg):
    D, H, KH, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {"ln1": (D,), "wq": (D, H * Dh), "wk": (D, KH * Dh),
            "wv": (D, KH * Dh), "wo": (H * Dh, D), **_mlp_shapes(cfg)}


def _stack_init(rng, shapes, L, dtype):
    out = {}
    keys = split_keys(rng, len(shapes))
    for key, (name, shp) in zip(keys, sorted(shapes.items())):
        if name.startswith("ln") or name in ("conv_b",):
            init = jnp.ones if name.startswith("ln") else jnp.zeros
            out[name] = init((L,) + shp, dtype)
        elif name == "log_lambda":
            # a = sigmoid(log_lambda) near 0.9..0.999
            out[name] = jnp.full((L,) + shp, 4.0, jnp.float32)
        else:
            out[name] = dense_init(key, (L,) + shp, dtype)
    return out


def init(cfg: ModelConfig, rng) -> Dict[str, Any]:
    dt = dtype_of(cfg.param_dtype)
    _, _, _, _, L_rec, L_attn = _layout(cfg)
    k1, k2, k3 = split_keys(rng, 3)
    params = {
        "emb": dense_init(k1, (cfg.vocab_padded, cfg.d_model), dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "rec_layers": _stack_init(k2, _rec_shapes(cfg), L_rec, dt),
        "attn_layers": _stack_init(k3, _attn_shapes(cfg), L_attn, dt),
    }
    return params


def _mlp(cfg, x, w, pol):
    cd = dtype_of(cfg.compute_dtype)
    g = jax.nn.gelu((x @ w["w_gate"]).astype(jnp.float32)).astype(cd)
    u = (x @ w["w_up"]).astype(cd)
    h = constrain(pol, g * u, "ffn_hidden")
    return constrain(pol, h @ w["w_down"], "residual")


def _rec_temporal(cfg, h, w, pol, conv_state=None, lru_state=None):
    """Recurrent branch. h: (B, S, D). Returns (out, new_conv, new_lru)."""
    cd = dtype_of(cfg.compute_dtype)
    B, S, _ = h.shape
    y = jax.nn.gelu((h @ w["w_y"]).astype(jnp.float32)).astype(cd)
    xi = (h @ w["w_x"]).astype(cd)  # (B, S, W)
    K = cfg.conv_width
    if conv_state is None:
        xp = jnp.pad(xi, ((0, 0), (K - 1, 0), (0, 0)))
        new_conv = xi[:, -(K - 1):] if S >= K - 1 else None
    else:
        xp = jnp.concatenate([conv_state, xi], axis=1)
        new_conv = xp[:, -(K - 1):]
    conv = sum(xp[:, i:i + S] * w["conv_w"][i][None, None, :]
               for i in range(K)) + w["conv_b"][None, None, :]
    conv = conv.astype(cd)
    log_a, gated = ref.rglru_gates(conv, w["wa"], w["wg"],
                                   w["log_lambda"])
    hs, h_last = ops.rglru(log_a, gated, h0=lru_state)
    out = (y * hs.astype(cd)) @ w["w_out"]
    return constrain(pol, out, "residual"), new_conv, h_last


def _attn_temporal(cfg, h, w, pol, positions):
    out, kv = lm_mod._attention(cfg, h, w, pol, positions, causal=True,
                                window=cfg.window)
    return out, kv


def _rec_block(cfg, pol, x, w, positions):
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    out, _, _ = _rec_temporal(cfg, h, w, pol)
    x = x + out
    x = x + _mlp(cfg, rms_norm(x, w["ln2"], cfg.norm_eps), w, pol)
    return constrain(pol, x, "residual")


def _attn_block(cfg, pol, x, w, positions):
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    out, _ = _attn_temporal(cfg, h, w, pol, positions)
    x = x + out
    x = x + _mlp(cfg, rms_norm(x, w["ln2"], cfg.norm_eps), w, pol)
    return constrain(pol, x, "residual")


def _split_groups(cfg, params):
    """rec stack -> (groups, rec_per_group, ...) + remainder; attn likewise."""
    n_groups, rem, rpg, apg, L_rec, L_attn = _layout(cfg)
    n_rec_main = n_groups * rpg
    rec_main = jax.tree.map(
        lambda a: a[:n_rec_main].reshape((n_groups, rpg) + a.shape[1:]),
        params["rec_layers"])
    rec_rem = jax.tree.map(lambda a: a[n_rec_main:], params["rec_layers"])
    n_attn_main = n_groups * apg
    attn_main = jax.tree.map(
        lambda a: a[:n_attn_main].reshape((n_groups, apg) + a.shape[1:]),
        params["attn_layers"])
    return rec_main, rec_rem, attn_main, rem


def forward(cfg: ModelConfig, params, batch, policy=None):
    pol = policy
    x = params["emb"][batch["tokens"]].astype(dtype_of(cfg.compute_dtype))
    x = constrain(pol, x, "residual")
    positions = jnp.arange(x.shape[1])
    rec_main, rec_rem, attn_main, rem = _split_groups(cfg, params)
    n_rem_rec = sum(1 for p in rem if p == "rglru")

    def group_body(x, grp):
        rec_ws, attn_ws = grp
        for i in range(rec_ws["ln1"].shape[0]):
            w = jax.tree.map(lambda a: a[i], rec_ws)
            x = _rec_block(cfg, pol, x, w, positions)
        for i in range(attn_ws["ln1"].shape[0]):
            w = jax.tree.map(lambda a: a[i], attn_ws)
            x = _attn_block(cfg, pol, x, w, positions)
        return x, None

    body = group_body
    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, (rec_main, attn_main))

    if n_rem_rec:
        def rem_body(x, w):
            return _rec_block(cfg, pol, x, w, positions), None
        if cfg.remat:
            rem_body = jax.checkpoint(rem_body)
        x, _ = jax.lax.scan(rem_body, x, rec_rem)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["emb"].T.astype(x.dtype)
    logits = mask_padded_logits(cfg, softcap(logits, cfg.logits_softcap))
    return constrain(pol, logits, "logits")


def loss_fn(cfg, params, batch, policy=None):
    logits = forward(cfg, params, batch, policy)
    return cross_entropy(logits[:, :-1], batch["tokens"][:, 1:])


def loss_and_load(cfg, params, batch, policy=None):
    """(loss, None): no experts, so no load (``lm.loss_and_load``)."""
    return loss_fn(cfg, params, batch, policy), None


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int = 0,
               enc_len: int = 0):
    _, _, _, _, L_rec, L_attn = _layout(cfg)
    cd = dtype_of(cfg.compute_dtype)
    W = min(cfg.window, max_len) if max_len else cfg.window
    return {
        "conv": jnp.zeros((L_rec, batch_size, cfg.conv_width - 1,
                           cfg.lru_width), cd),
        "lru": jnp.zeros((L_rec, batch_size, cfg.lru_width), jnp.float32),
        "k": jnp.zeros((L_attn, batch_size, W, cfg.num_kv_heads,
                        cfg.head_dim), cd),
        "v": jnp.zeros((L_attn, batch_size, W, cfg.num_kv_heads,
                        cfg.head_dim), cd),
        "pos": jnp.zeros((), jnp.int32),
    }


def _decode_rec(cfg, pol, x, w, conv_st, lru_st):
    cd = dtype_of(cfg.compute_dtype)
    B = x.shape[0]
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    y = jax.nn.gelu((h @ w["w_y"]).astype(jnp.float32)).astype(cd)
    xi = (h @ w["w_x"]).astype(cd)  # (B, 1, W)
    window = jnp.concatenate([conv_st, xi], axis=1)  # (B, K, W)
    conv = jnp.einsum("bkw,kw->bw", window.astype(jnp.float32),
                      w["conv_w"].astype(jnp.float32))
    conv = (conv + w["conv_b"].astype(jnp.float32))[:, None].astype(cd)
    log_a, gated = ref.rglru_gates(conv, w["wa"], w["wg"], w["log_lambda"])
    hs, h_last = ref.rglru_ref(log_a, gated, h0=lru_st)
    out = (y * hs.astype(cd)) @ w["w_out"]
    x = x + out
    x = x + _mlp(cfg, rms_norm(x, w["ln2"], cfg.norm_eps), w, pol)
    return x, window[:, 1:], h_last


def _decode_attn(cfg, pol, x, w, k_l, v_l, pos):
    cd = dtype_of(cfg.compute_dtype)
    B = x.shape[0]
    H, KH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    W = k_l.shape[1]
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = rope((h @ w["wq"]).astype(cd).reshape(B, 1, H, Dh), positions,
             cfg.rope_theta)
    k = rope((h @ w["wk"]).astype(cd).reshape(B, 1, KH, Dh), positions,
             cfg.rope_theta)
    v = (h @ w["wv"]).astype(cd).reshape(B, 1, KH, Dh)
    slot = jnp.mod(pos, W)
    k_l = jax.lax.dynamic_update_slice_in_dim(k_l, k, slot, axis=1)
    v_l = jax.lax.dynamic_update_slice_in_dim(v_l, v, slot, axis=1)
    k_l = constrain(pol, k_l, "cache")
    v_l = constrain(pol, v_l, "cache")
    kv_len = jnp.broadcast_to(jnp.minimum(pos + 1, W), (B,))
    o = ops.decode_attention(q, k_l, v_l, kv_len=kv_len)
    x = x + o.reshape(B, 1, H * Dh) @ w["wo"]
    x = x + _mlp(cfg, rms_norm(x, w["ln2"], cfg.norm_eps), w, pol)
    return x, k_l, v_l


def decode_step(cfg: ModelConfig, params, cache, tokens, policy=None):
    pol = policy
    B = tokens.shape[0]
    cd = dtype_of(cfg.compute_dtype)
    pos = cache["pos"]
    x = params["emb"][tokens].astype(cd)
    rec_main, rec_rem, attn_main, rem = _split_groups(cfg, params)
    n_groups, _, rpg, apg, L_rec, L_attn = _layout(cfg)
    n_rec_main = n_groups * rpg
    n_rem_rec = sum(1 for p in rem if p == "rglru")

    conv_main = jax.tree.map(
        lambda a: a[:n_rec_main].reshape((n_groups, rpg) + a.shape[1:]),
        cache["conv"])
    lru_main = cache["lru"][:n_rec_main].reshape(
        (n_groups, rpg) + cache["lru"].shape[1:])
    conv_rem = cache["conv"][n_rec_main:]
    lru_rem = cache["lru"][n_rec_main:]

    def group_body(x, grp):
        rec_ws, attn_ws, conv_g, lru_g, k_g, v_g = grp
        new_conv, new_lru = [], []
        for i in range(rpg):
            w = jax.tree.map(lambda a: a[i], rec_ws)
            x, c, l = _decode_rec(cfg, pol, x, w, conv_g[i], lru_g[i])
            new_conv.append(c)
            new_lru.append(l)
        new_k, new_v = [], []
        for i in range(apg):
            w = jax.tree.map(lambda a: a[i], attn_ws)
            x, k_l, v_l = _decode_attn(cfg, pol, x, w, k_g[i], v_g[i], pos)
            new_k.append(k_l)
            new_v.append(v_l)
        return x, (jnp.stack(new_conv), jnp.stack(new_lru),
                   jnp.stack(new_k), jnp.stack(new_v))

    x, (nc, nl, nk, nv) = jax.lax.scan(
        group_body, x,
        (rec_main, attn_main, conv_main, lru_main, cache["k"][:, None] if apg == 1
         else cache["k"].reshape((n_groups, apg) + cache["k"].shape[1:]),
         cache["v"][:, None] if apg == 1
         else cache["v"].reshape((n_groups, apg) + cache["v"].shape[1:])))
    new_conv = nc.reshape((n_rec_main,) + nc.shape[2:])
    new_lru = nl.reshape((n_rec_main,) + nl.shape[2:])
    new_k = nk.reshape((L_attn,) + nk.shape[2:])
    new_v = nv.reshape((L_attn,) + nv.shape[2:])

    if n_rem_rec:
        def rem_body(x, scanned):
            w, c, l = scanned
            x, c2, l2 = _decode_rec(cfg, pol, x, w, c, l)
            return x, (c2, l2)
        x, (rc, rl) = jax.lax.scan(rem_body, x, (rec_rem, conv_rem, lru_rem))
        new_conv = jnp.concatenate([new_conv, rc], axis=0)
        new_lru = jnp.concatenate([new_lru, rl], axis=0)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["emb"].T.astype(x.dtype)
    logits = mask_padded_logits(cfg, softcap(logits, cfg.logits_softcap))
    logits = constrain(pol, logits, "logits")
    return logits, {"conv": new_conv, "lru": new_lru, "k": new_k, "v": new_v,
                    "pos": pos + 1}


def prefill(cfg: ModelConfig, params, batch, cache, policy=None):
    """Prefill via teacher-forced forward + state extraction (window cache).

    For simplicity states are rebuilt by running decode semantics over the
    last ``window`` tokens only for attention and a full recurrent pass for
    LRU/conv state; long prompts remain O(S) (sub-quadratic).
    """
    pol = policy
    tokens = batch["tokens"]
    B, S = tokens.shape
    cd = dtype_of(cfg.compute_dtype)
    W = cache["k"].shape[2]
    x = params["emb"][tokens].astype(cd)
    x = constrain(pol, x, "residual")
    positions = jnp.arange(S)
    rec_main, rec_rem, attn_main, rem = _split_groups(cfg, params)
    n_groups, _, rpg, apg, L_rec, L_attn = _layout(cfg)
    n_rec_main = n_groups * rpg
    n_rem_rec = sum(1 for p in rem if p == "rglru")

    def group_body(x, grp):
        rec_ws, attn_ws = grp
        convs, lrus, ks, vs = [], [], [], []
        for i in range(rpg):
            w = jax.tree.map(lambda a: a[i], rec_ws)
            h = rms_norm(x, w["ln1"], cfg.norm_eps)
            out, c, l = _rec_temporal(cfg, h, w, pol)
            x = x + out
            x = x + _mlp(cfg, rms_norm(x, w["ln2"], cfg.norm_eps), w, pol)
            convs.append(c)
            lrus.append(l)
        for i in range(apg):
            w = jax.tree.map(lambda a: a[i], attn_ws)
            h = rms_norm(x, w["ln1"], cfg.norm_eps)
            out, (k, v) = _attn_temporal(cfg, h, w, pol, positions)
            x = x + out
            x = x + _mlp(cfg, rms_norm(x, w["ln2"], cfg.norm_eps), w, pol)
            # roll the last W tokens into slots (pos % W); short prompts
            # (S < W) fill slots [0:S] directly (no wrap yet)
            if S >= W:
                kw = jnp.roll(k[:, -W:], S % W, axis=1)
                vw = jnp.roll(v[:, -W:], S % W, axis=1)
            else:
                pad = ((0, 0), (0, W - S), (0, 0), (0, 0))
                kw, vw = jnp.pad(k, pad), jnp.pad(v, pad)
            ks.append(kw)
            vs.append(vw)
        return x, (jnp.stack(convs), jnp.stack(lrus),
                   jnp.stack(ks) if ks else jnp.zeros((0,)),
                   jnp.stack(vs) if vs else jnp.zeros((0,)))

    if cfg.remat:
        group_body = jax.checkpoint(group_body)
    x, (nc, nl, nk, nv) = jax.lax.scan(group_body, x, (rec_main, attn_main))
    new_conv = nc.reshape((n_rec_main,) + nc.shape[2:])
    new_lru = nl.reshape((n_rec_main,) + nl.shape[2:])
    new_k = nk.reshape((L_attn,) + nk.shape[2:])
    new_v = nv.reshape((L_attn,) + nv.shape[2:])

    if n_rem_rec:
        def rem_body(x, w):
            h = rms_norm(x, w["ln1"], cfg.norm_eps)
            out, c, l = _rec_temporal(cfg, h, w, pol)
            x = x + out
            x = x + _mlp(cfg, rms_norm(x, w["ln2"], cfg.norm_eps), w, pol)
            return x, (c, l)
        if cfg.remat:
            rem_body = jax.checkpoint(rem_body)
        x, (rc, rl) = jax.lax.scan(rem_body, x, rec_rem)
        new_conv = jnp.concatenate([new_conv, rc], axis=0)
        new_lru = jnp.concatenate([new_lru, rl], axis=0)

    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = x @ params["emb"].T.astype(x.dtype)
    logits = mask_padded_logits(cfg, softcap(logits, cfg.logits_softcap))
    return logits, {"conv": new_conv, "lru": new_lru, "k": new_k, "v": new_v,
                    "pos": jnp.asarray(S, jnp.int32)}
