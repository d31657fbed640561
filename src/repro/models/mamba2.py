"""Mamba2 (SSD) family — attention-free LM. [arXiv:2405.21060]

Block: in_proj -> [z | xBC | dt]; causal depthwise conv over xBC; SSD scan;
gated RMSNorm; out_proj.  Train/prefill uses the chunked SSD (Pallas on TPU);
decode carries (conv_state, ssm_state) — O(1) in sequence length, which is
why long_500k runs for this arch.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.models.common import (constrain, cross_entropy, dense_init,
                                 dtype_of, mask_padded_logits, rms_norm,
                                 split_keys)


def _dims(cfg: ModelConfig):
    Din = cfg.d_inner
    G, N = cfg.ssm_groups, cfg.ssm_state
    H = cfg.ssm_heads
    conv_ch = Din + 2 * G * N
    return Din, G, N, H, conv_ch


def init(cfg: ModelConfig, rng) -> Dict[str, Any]:
    dt = dtype_of(cfg.param_dtype)
    D = cfg.d_model
    Din, G, N, H, conv_ch = _dims(cfg)
    L = cfg.num_layers
    keys = split_keys(rng, 6)
    proj_in = Din + conv_ch + H  # z, xBC, dt
    layers = {
        "ln": jnp.ones((L, D), dt),
        "w_in": dense_init(keys[0], (L, D, proj_in), dt),
        "conv_w": dense_init(keys[1], (L, cfg.conv_width, conv_ch), dt, 0.1),
        "conv_b": jnp.zeros((L, conv_ch), dt),
        "dt_bias": jnp.zeros((L, H), jnp.float32),
        "A_log": jnp.zeros((L, H), jnp.float32),  # A = -exp(A_log) = -1
        "D_skip": jnp.ones((L, H), jnp.float32),
        "norm_w": jnp.ones((L, Din), dt),
        "w_out": dense_init(keys[2], (L, Din, D), dt),
    }
    params = {
        "emb": dense_init(keys[3], (cfg.vocab_padded, D), dt),
        "final_norm": jnp.ones((D,), dt),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["out_head"] = dense_init(keys[4], (D, cfg.vocab_padded), dt)
    return params


def _conv1d(x, w, b):
    """Causal depthwise conv. x: (B, S, C); w: (K, C); b: (C,)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :] for i in range(K))
    return out + b[None, None, :]


def _split_proj(cfg, proj):
    Din, G, N, H, conv_ch = _dims(cfg)
    z = proj[..., :Din]
    xBC = proj[..., Din:Din + conv_ch]
    dt_raw = proj[..., Din + conv_ch:]
    return z, xBC, dt_raw


def _block_core(cfg, h, w, pol):
    """Shared projection/conv/split for train & prefill. h: (B, S, D)."""
    Din, G, N, H, conv_ch = _dims(cfg)
    B, S, _ = h.shape
    cd = dtype_of(cfg.compute_dtype)
    proj = (h @ w["w_in"]).astype(cd)
    z, xBC, dt_raw = _split_proj(cfg, proj)
    xBC = jax.nn.silu(_conv1d(xBC, w["conv_w"], w["conv_b"])
                      .astype(jnp.float32)).astype(cd)
    xs = xBC[..., :Din].reshape(B, S, H, cfg.ssm_head_dim)
    Bm = xBC[..., Din:Din + G * N].reshape(B, S, G, N)
    Cm = xBC[..., Din + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + w["dt_bias"])
    return z, xs, Bm, Cm, dt


def _block(cfg, pol, x, w):
    Din, G, N, H, conv_ch = _dims(cfg)
    cd = dtype_of(cfg.compute_dtype)
    h = rms_norm(x, w["ln"], cfg.norm_eps)
    z, xs, Bm, Cm, dt = _block_core(cfg, h, w, pol)
    A = -jnp.exp(w["A_log"])
    xs = constrain(pol, xs, "ssm_x")
    y = ops.ssd(xs, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    y = y + xs * w["D_skip"][None, None, :, None].astype(cd)
    y = y.reshape(*x.shape[:2], Din)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(cd),
                 w["norm_w"], cfg.norm_eps)
    out = y @ w["w_out"]
    return constrain(pol, x + out, "residual")


def forward(cfg: ModelConfig, params, batch, policy=None):
    pol = policy
    x = params["emb"][batch["tokens"]].astype(dtype_of(cfg.compute_dtype))
    x = constrain(pol, x, "residual")

    def body(x, w):
        return _block(cfg, pol, x, w), None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["emb"].T if cfg.tie_embeddings else params["out_head"]
    logits = mask_padded_logits(cfg, x @ head.astype(x.dtype))
    return constrain(pol, logits, "logits")


def loss_fn(cfg, params, batch, policy=None):
    logits = forward(cfg, params, batch, policy)
    return cross_entropy(logits[:, :-1], batch["tokens"][:, 1:])


def loss_and_load(cfg, params, batch, policy=None):
    """(loss, None): no experts, so no load (``lm.loss_and_load``)."""
    return loss_fn(cfg, params, batch, policy), None


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int = 0,
               enc_len: int = 0):
    """O(1)-size decode state: conv window + SSM state (no KV cache)."""
    Din, G, N, H, conv_ch = _dims(cfg)
    L = cfg.num_layers
    cd = dtype_of(cfg.compute_dtype)
    return {
        "conv": jnp.zeros((L, batch_size, cfg.conv_width - 1, conv_ch), cd),
        "ssm": jnp.zeros((L, batch_size, H, cfg.ssm_head_dim, N),
                         jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(cfg: ModelConfig, params, batch, cache, policy=None):
    pol = policy
    tokens = batch["tokens"]
    B, S = tokens.shape
    Din, G, N, H, conv_ch = _dims(cfg)
    cd = dtype_of(cfg.compute_dtype)
    x = params["emb"][tokens].astype(cd)
    x = constrain(pol, x, "residual")

    def body(x, scanned):
        w = scanned["w"]
        h = rms_norm(x, w["ln"], cfg.norm_eps)
        proj = (h @ w["w_in"]).astype(cd)
        z, xBC, dt_raw = _split_proj(cfg, proj)
        conv_state = xBC[:, -(cfg.conv_width - 1):]  # last K-1 pre-conv inputs
        xBC = jax.nn.silu(_conv1d(xBC, w["conv_w"], w["conv_b"])
                          .astype(jnp.float32)).astype(cd)
        xs = xBC[..., :Din].reshape(B, S, H, cfg.ssm_head_dim)
        Bm = xBC[..., Din:Din + G * N].reshape(B, S, G, N)
        Cm = xBC[..., Din + G * N:].reshape(B, S, G, N)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + w["dt_bias"])
        A = -jnp.exp(w["A_log"])
        y, state = ref.ssd_chunked_jnp(xs, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
        y = y + xs * w["D_skip"][None, None, :, None].astype(cd)
        y = y.reshape(B, S, Din)
        y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(cd),
                     w["norm_w"], cfg.norm_eps)
        x = constrain(pol, x + y @ w["w_out"], "residual")
        return x, {"conv": conv_state, "ssm": state}

    if cfg.remat:
        body = jax.checkpoint(body)
    x, new_cache = jax.lax.scan(body, x, {"w": params["layers"]})
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    head = params["emb"].T if cfg.tie_embeddings else params["out_head"]
    logits = mask_padded_logits(cfg, x @ head.astype(x.dtype))
    return logits, {"conv": new_cache["conv"], "ssm": new_cache["ssm"],
                    "pos": jnp.asarray(S, jnp.int32)}


def decode_step(cfg: ModelConfig, params, cache, tokens, policy=None):
    pol = policy
    B = tokens.shape[0]
    Din, G, N, H, conv_ch = _dims(cfg)
    cd = dtype_of(cfg.compute_dtype)
    x = params["emb"][tokens].astype(cd)  # (B, 1, D)

    def body(x, scanned):
        w, conv_st, ssm_st = scanned["w"], scanned["conv"], scanned["ssm"]
        h = rms_norm(x, w["ln"], cfg.norm_eps)
        proj = (h @ w["w_in"]).astype(cd)  # (B, 1, proj)
        z, xBC, dt_raw = _split_proj(cfg, proj)
        # conv via stored window
        window = jnp.concatenate([conv_st, xBC], axis=1)  # (B, K, C)
        conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                              w["conv_w"].astype(jnp.float32))
        conv_out = jax.nn.silu(conv_out + w["conv_b"].astype(jnp.float32))
        conv_out = conv_out.astype(cd)
        xs = conv_out[..., :Din].reshape(B, H, cfg.ssm_head_dim)
        Bm = conv_out[..., Din:Din + G * N].reshape(B, G, N)
        Cm = conv_out[..., Din + G * N:].reshape(B, G, N)
        dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + w["dt_bias"])
        A = -jnp.exp(w["A_log"])
        ssm_st, y = ref.ssd_decode_step(ssm_st, xs, dt, A, Bm, Cm)
        y = y + xs * w["D_skip"][None, :, None].astype(cd)
        y = y.reshape(B, 1, Din)
        y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(cd),
                     w["norm_w"], cfg.norm_eps)
        x = x + y @ w["w_out"]
        return x, {"conv": window[:, 1:], "ssm": ssm_st}

    scanned = {"w": params["layers"], "conv": cache["conv"],
               "ssm": cache["ssm"]}
    x, new_st = jax.lax.scan(body, x, scanned)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["emb"].T if cfg.tie_embeddings else params["out_head"]
    logits = constrain(pol, mask_padded_logits(cfg, x @ head.astype(x.dtype)),
                       "logits")
    return logits, {"conv": new_st["conv"], "ssm": new_st["ssm"],
                    "pos": cache["pos"] + 1}
