"""CPU fixtures for the benchmark's own tests: a tiny cell of each kind at
the program's CPU test size, driven through the same harness as a chip
run (the device check is the one thing skipped)."""
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_QWEN = {
    "repo_config": "qwen2_7b", "model_type": "qwen2",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 257, "tie_word_embeddings": True, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "initializer_range": 0.02,
    "param_dtype": "float32", "compute_dtype": "float32",
}

TINY_FL = {
    "kind": "fl", "clusters": 2, "devices_per_cluster": 2,
    "seq_len": 32, "tau": 2, "seqs_per_step": 2, "q": 2, "gossip_phase": 1,
    "eta": 0.05, "momentum": 0.9, "block_size": 1024, "theta_min": 0.05,
    "controller": "cef_c", "time_budget_s": 1e-06, "energy_budget_j": 1e-06,
    "expect_theta": 0.05, "noniid_beta": 0.5,
    "vocab_bands": 8, "check_rounds": 2, "trace_rounds": 2,
}


def make_cell(config, traffic, limits, name="tiny", per_layer=False):
    from harness import spec
    bench = spec.load_benchmark()
    same_kind = [w["name"] for w in bench["workloads"]
                 if spec.load_json(spec.traffic_path(w["traffic"]))["kind"]
                 == traffic["kind"]]
    e2e, per = (spec.cell_metrics(bench, same_kind[0]) if same_kind
                else ([], []))
    return spec.Cell(name=name, chips=1, config_name="tiny", config=config,
                     traffic_name="tiny", traffic=traffic,
                     limits={"numbers": limits}, end_to_end=e2e,
                     per_layer=per)


@pytest.fixture
def tiny_fl_cell():
    return lambda limits, **kw: make_cell(TINY_QWEN, dict(TINY_FL, **kw),
                                          limits)
