"""The ``granitemoe`` block (``GraniteMoeForCausalLM``, IBM Granite 3.0
MoE): RMSNorm, rotary embedding with halves rotated, q/k/v/o projections
without bias, softmax attention scaled by ``attention_multiplier`` over
grouped KV heads, and in place of the FFN a mixture of SwiGLU experts: a
linear router without bias over all experts, the top
``num_experts_per_tok`` logits of each token, and gates that are the
softmax over those chosen logits; every routed token reaches its experts
(no capacity).  The muP multipliers: token embeddings times
``embedding_multiplier``, each residual branch (attention and experts)
times ``residual_multiplier``, logits divided by ``logits_scaling``.
Output head tied to the embedding.  The source's ``attention_dropout``
acts in training only and is left out here, as the program leaves it out.

Per-layer parameter leaves, stacked on a leading layer axis:
``ln1 ln2 wq wk wv wo router we_gate we_up we_down`` (experts stacked on
their own leading axis)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import counts
from harness.reference import attention, mm, rms_norm, rope

# what the program's configuration has to say of a granitemoe block
PROGRAM_BLOCK = dict(family="moe", qkv_bias=False, moe_dense_ff=0,
                     logits_softcap=0.0, window=0)

MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")

# Expert matmul work of one local step, in units of one forward matmul
# (2 D F FLOPs per assignment; three matmuls a layer: gate, up, down).
# The backward takes two products per matmul (the input's gradient and
# the weight's); under remat it first re-runs all three forward matmuls:
# the gates' gradient needs each expert's output, the down projection's.
EXPERT_GMM_PASSES = {"forward": 3, "backward": 6, "remat": 3}


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def program(c: dict, m):
    """The program's ModelConfig for the file ``c``: the repo
    configuration ``m`` (a granitemoe block) given the file's sizes."""
    bad = {k: (getattr(m, k), v) for k, v in PROGRAM_BLOCK.items()
           if getattr(m, k) != v}
    missing = [k for k in MULTIPLIERS if not hasattr(m, k)]
    if missing:
        raise ValueError(f"the program's ModelConfig has no {missing}: "
                         f"it cannot compute a {c['model_type']} block")
    if bad:
        raise ValueError(f"the program's {c['repo_config']} is not a "
                         f"{c['model_type']} block: {bad}")
    return m.replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        num_experts=c["num_local_experts"],
        experts_per_token=c["num_experts_per_tok"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], param_dtype=c["param_dtype"],
        compute_dtype=c["compute_dtype"], **{k: c[k] for k in MULTIPLIERS})


def experts(c, w, h, lowp):
    """The expert layer on h (B, S, D): every expert on every token,
    weighted by a dense (B, S, E) gate matrix that is zero off each
    token's top-K experts."""
    E, K = c["num_local_experts"], c["num_experts_per_tok"]
    logits = mm("bsd,de->bse", h, w["router"], lowp)
    top, idx = jax.lax.top_k(logits, K)
    gates = jnp.einsum("bsk,bske->bse", jax.nn.softmax(top, axis=-1),
                       jax.nn.one_hot(idx, E, dtype=jnp.float32))
    a = jax.nn.silu(mm("bsd,edf->bsef", h, w["we_gate"], lowp)) \
        * mm("bsd,edf->bsef", h, w["we_up"], lowp)
    return mm("bsef,efd->bsd", a * gates[..., None], w["we_down"], lowp)


def _layer(c, w, x, pos, lowp):
    B, S, D = x.shape
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    Dh = head_dim(c)
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    h = rms_norm(x, w["ln1"], eps)
    q = mm("bsd,de->bse", h, w["wq"], lowp).reshape(B, S, H, Dh)
    k = mm("bsd,de->bse", h, w["wk"], lowp).reshape(B, S, KH, Dh)
    v = mm("bsd,de->bse", h, w["wv"], lowp).reshape(B, S, KH, Dh)
    q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    o = attention(q, k, v, pos, c["attention_multiplier"], lowp)
    x = x + r * mm("bse,ed->bsd", o, w["wo"], lowp)
    return x + r * experts(c, w, rms_norm(x, w["ln2"], eps), lowp)


def logits(c, params, tokens, lowp=None, positions=None):
    """(B, S, vocab) logits of a causal forward over ``tokens``.  Each
    layer is re-run in the backward (``jax.checkpoint``), so the
    all-experts intermediates of one layer are held at a time."""
    V = c["vocab_size"]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    emb = params["emb"][:V].astype(jnp.float32)
    x = emb[tokens] * c["embedding_multiplier"]
    B, S = tokens.shape
    pos = (jnp.broadcast_to(jnp.arange(S), (B, S)) if positions is None
           else positions)

    @jax.checkpoint
    def body(x, w):
        return _layer(c, f32(w), x, pos, lowp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                 c["rms_norm_eps"])
    return mm("bsd,vd->bsv", x, emb, lowp) / c["logits_scaling"]


def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_local_experts"]


def stored_layer_params(c: dict) -> int:
    """The matrices of one layer: q, k, v, o, the router and every
    expert's gate, up and down projections."""
    return counts.attention_params(c) + router_params(c) \
        + c["num_local_experts"] * _expert_params(c)


def active_layer_params(c: dict) -> int:
    """A token multiplies through attention, the router and its top-K
    experts."""
    return counts.attention_params(c) + router_params(c) \
        + c["num_experts_per_tok"] * _expert_params(c)


def extra_params(c: dict) -> int:
    """The two norms of one layer."""
    return 2 * c["hidden_size"]


def expert_gmm_flops(c: dict, tokens: int) -> dict:
    """{pass: FLOPs} of the expert matmuls of local steps whose forwards
    run over ``tokens`` tokens in all: every token's K assignments, 2 D F
    FLOPs a matmul, in every layer, times each pass's matmuls
    (``EXPERT_GMM_PASSES``)."""
    one = (2 * c["hidden_size"] * c["intermediate_size"]
           * c["num_experts_per_tok"] * tokens * c["num_hidden_layers"])
    return {k: n * one for k, n in EXPERT_GMM_PASSES.items()}


def expert_gmm_bytes(c: dict, tokens: int, weight_reads: int) -> dict:
    """{pass: bytes} the same matmuls move at the least, in bf16: each
    product (the forward's, and the backward's input and weight
    gradients alike) reads its two operands and writes its result once,
    which is every assignment's row at the widths D and F, plus the (E, D,
    F) weights once for each of ``weight_reads`` distinct weight sets (one
    a client and local step), in every layer."""
    D, F = c["hidden_size"], c["intermediate_size"]
    rows = c["num_experts_per_tok"] * tokens * (D + F)
    weights = weight_reads * c["num_local_experts"] * D * F
    one = 2 * (rows + weights) * c["num_hidden_layers"]
    return {k: n * one for k, n in EXPERT_GMM_PASSES.items()}
