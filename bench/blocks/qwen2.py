"""The ``qwen2`` block (``Qwen2ForCausalLM``): RMSNorm, rotary embedding
with halves rotated, q/k/v projections with bias and an output projection
without, softmax attention scaled by 1/sqrt(head_dim) over grouped KV
heads, SwiGLU FFN, output head tied to the embedding.

Per-layer parameter leaves, stacked on a leading layer axis:
``ln1 ln2 wq wk wv wo bq bk bv w_gate w_up w_down``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import counts
from harness.reference import attention, mm, rms_norm, rope

# what the program's configuration has to say of a qwen2 block
PROGRAM_BLOCK = dict(family="dense", qkv_bias=True, num_experts=0,
                     logits_softcap=0.0, window=0)


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def program(c: dict, m):
    """The program's ModelConfig for the file ``c``: the repo
    configuration ``m`` (a qwen2 block) given the file's sizes."""
    bad = {k: (getattr(m, k), v) for k, v in PROGRAM_BLOCK.items()
           if getattr(m, k) != v}
    if bad:
        raise ValueError(f"the program's {c['repo_config']} is not a "
                         f"{c['model_type']} block: {bad}")
    return m.replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], param_dtype=c["param_dtype"],
        compute_dtype=c["compute_dtype"])


def _layer(c, w, x, pos, lowp):
    B, S, D = x.shape
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    Dh = head_dim(c)
    eps = c["rms_norm_eps"]
    h = rms_norm(x, w["ln1"], eps)
    q = mm("bsd,de->bse", h, w["wq"], lowp) + w["bq"]
    k = mm("bsd,de->bse", h, w["wk"], lowp) + w["bk"]
    v = mm("bsd,de->bse", h, w["wv"], lowp) + w["bv"]
    q, k = q.reshape(B, S, H, Dh), k.reshape(B, S, KH, Dh)
    v = v.reshape(B, S, KH, Dh)
    q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    o = attention(q, k, v, pos, 1.0 / Dh ** 0.5, lowp)
    x = x + mm("bse,ed->bsd", o, w["wo"], lowp)
    h = rms_norm(x, w["ln2"], eps)
    a = jax.nn.silu(mm("bsd,df->bsf", h, w["w_gate"], lowp)) \
        * mm("bsd,df->bsf", h, w["w_up"], lowp)
    return x + mm("bsf,fd->bsd", a, w["w_down"], lowp)


def logits(c, params, tokens, lowp=None, positions=None):
    """(B, S, vocab) logits of a causal forward over ``tokens``."""
    V = c["vocab_size"]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    emb = params["emb"][:V].astype(jnp.float32)
    x = emb[tokens]
    B, S = tokens.shape
    pos = (jnp.broadcast_to(jnp.arange(S), (B, S)) if positions is None
           else positions)

    def body(x, w):
        return _layer(c, f32(w), x, pos, lowp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                 c["rms_norm_eps"])
    return mm("bsd,vd->bsv", x, emb, lowp)


def stored_layer_params(c: dict) -> int:
    """The matrices of one layer: q, k, v, o and the gated FFN."""
    return counts.attention_params(c) + counts.ffn_params(c)


def active_layer_params(c: dict) -> int:
    """Every token multiplies through every matrix of a layer."""
    return stored_layer_params(c)


def extra_params(c: dict) -> int:
    """The q, k and v biases and the two norms of one layer."""
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    return (H + 2 * KH) * head_dim(c) + 2 * c["hidden_size"]
