"""Operations and bytes the algorithms need, from the shapes of a call and
the published configuration alone.  Nothing here reads the HLO or the
kernels' code, so a change that replaces a kernel is judged against the
same yardstick.

``c`` is a configuration file of ``bench/configs`` (Hugging Face key
names).  What a layer holds and what a token goes through in it is its
block's (``bench/blocks/<model_type>.py``); the attention and dense FFN
counts here are what blocks build theirs from.  FLOPs count a
multiply-add as 2.
"""
from __future__ import annotations

from harness import spec


def _dims(c: dict):
    D = c["hidden_size"]
    H = c["num_attention_heads"]
    KH = c["num_key_value_heads"]
    Dh = c.get("head_dim") or D // H
    return D, H, KH, Dh


def attention_params(c: dict) -> int:
    """q, k, v and output projections of one layer."""
    D, H, KH, Dh = _dims(c)
    return 2 * D * H * Dh + 2 * D * KH * Dh


def ffn_params(c: dict) -> int:
    """Gated FFN of one layer (gate, up and down projections)."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def active_matmul_params(c: dict) -> int:
    """Parameters each token multiplies through: what every layer's block
    gives it, and the output head (tied or not).  The embedding lookup is
    a gather and does no matmul work; norms and biases are left out."""
    block = spec.load_block(c["model_type"])
    return c["num_hidden_layers"] * block.active_layer_params(c) \
        + head_params(c)


def total_params(c: dict) -> int:
    """Every stored parameter: the layers (matrices, and the biases and
    norms of the block), embedding, final norm and an untied head if
    there is one."""
    D, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    block = spec.load_block(c["model_type"])
    n = L * (block.stored_layer_params(c) + block.extra_params(c)) \
        + V * D + D
    if not c.get("tie_word_embeddings", True):
        n += V * D
    return n


def attention_fwd_flops(c: dict, seq: int, causal: bool = True) -> int:
    """QK^T and PV of one layer over one sequence of ``seq`` tokens; a
    causal mask does the S(S+1)/2 query-key pairs at or below the
    diagonal."""
    _, H, _, Dh = _dims(c)
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    return 4 * H * Dh * pairs


def attention_fwd_bytes(c: dict, seq: int, itemsize: int = 2) -> int:
    """Read q, k, v and write the output of one layer over one sequence."""
    _, H, KH, Dh = _dims(c)
    return itemsize * seq * Dh * (2 * H + 2 * KH)


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward FLOPs per trained token: 6 N_active plus the
    causal attention term, 3 x (forward attention FLOPs / seq).  Recompute
    (remat, a backward that re-runs the forward) does not count."""
    L = c["num_hidden_layers"]
    attn = 3 * attention_fwd_flops(c, seq) / seq
    return 6.0 * active_matmul_params(c) + L * attn


def topk_compress_bytes(n_elems: int, itemsize: int = 2) -> int:
    """Block top-k with error feedback over ``n_elems`` coordinates: read
    the delta and the error feedback, write the kept values and the new
    error feedback."""
    return 4 * itemsize * n_elems


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
