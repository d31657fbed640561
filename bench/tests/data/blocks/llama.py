"""A ``llama`` block (``LlamaForCausalLM``, as SmolLM-135M publishes it):
the qwen2 block without the q, k and v biases.  It lives with the tests,
which point the block directory here: a block is one file, and a new
architecture enters the benchmark as that file and a configuration."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import counts
from harness.reference import attention, mm, rms_norm, rope

PROGRAM_BLOCK = dict(family="dense", qkv_bias=False, num_experts=0,
                     logits_softcap=0.0, window=0)


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def program(c: dict, m):
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
    H, KH, Dh = c["num_attention_heads"], c["num_key_value_heads"], \
        head_dim(c)
    h = rms_norm(x, w["ln1"], c["rms_norm_eps"])
    q = mm("bsd,de->bse", h, w["wq"], lowp).reshape(B, S, H, Dh)
    k = mm("bsd,de->bse", h, w["wk"], lowp).reshape(B, S, KH, Dh)
    v = mm("bsd,de->bse", h, w["wv"], lowp).reshape(B, S, KH, Dh)
    q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    o = attention(q, k, v, pos, 1.0 / Dh ** 0.5, lowp)
    x = x + mm("bse,ed->bsd", o, w["wo"], lowp)
    h = rms_norm(x, w["ln2"], c["rms_norm_eps"])
    a = jax.nn.silu(mm("bsd,df->bsf", h, w["w_gate"], lowp)) \
        * mm("bsd,df->bsf", h, w["w_up"], lowp)
    return x + mm("bsf,fd->bsd", a, w["w_down"], lowp)


def logits(c, params, tokens, lowp=None, positions=None):
    emb = params["emb"][:c["vocab_size"]].astype(jnp.float32)
    B, S = tokens.shape
    pos = (jnp.broadcast_to(jnp.arange(S), (B, S)) if positions is None
           else positions)

    def body(x, w):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        return _layer(c, w, x, pos, lowp), None

    x, _ = jax.lax.scan(body, emb[tokens], params["layers"])
    x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                 c["rms_norm_eps"])
    return mm("bsd,vd->bsv", x, emb, lowp)


def stored_layer_params(c: dict) -> int:
    return counts.attention_params(c) + counts.ffn_params(c)


def active_layer_params(c: dict) -> int:
    return stored_layer_params(c)


def extra_params(c: dict) -> int:
    """The two norms of one layer."""
    return 2 * c["hidden_size"]
