"""The system under test as the benchmark builds it from a configuration
file: the program's own block for the file's ``model_type`` (the repo
configuration named by ``repo_config``), at the published widths, depth
and dtypes the file states."""
from __future__ import annotations

# model_type -> what the program's configuration has to say of the block
BLOCKS = {"qwen2": dict(family="dense", qkv_bias=True, num_experts=0,
                        logits_softcap=0.0, window=0)}


def model_config(c: dict):
    """The program's ModelConfig for a configuration file: the repo
    configuration of the same block, given the file's sizes."""
    from repro.configs import get_config
    m = get_config(c["repo_config"]).model
    block = BLOCKS.get(c["model_type"])
    if block is None:
        raise ValueError(f"model_type {c['model_type']!r} not in {BLOCKS}")
    bad = {k: (getattr(m, k), v) for k, v in block.items()
           if getattr(m, k) != v}
    if bad:
        raise ValueError(f"the program's {c['repo_config']} is not a "
                         f"{c['model_type']} block: {bad}")
    return m.replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or
        c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], param_dtype=c["param_dtype"],
        compute_dtype=c["compute_dtype"])
