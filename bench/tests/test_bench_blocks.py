"""Architectures found by name: ``bench/blocks/<model_type>.py`` gives a
block's program configuration, its plain reference forward and its
parameter counts, so a new architecture enters the benchmark as new
files.  The qwen2 block reads, bit for bit, what the harness read before
its code moved there; a llama block kept with the tests runs a tiny fl
cell through the whole harness with no other file of the harness changed;
a model_type with no block is refused by its path."""
import hashlib
import json

import numpy as np
import pytest

from conftest import TINY_FL, TINY_QWEN, make_cell
from harness import counts, spec

DATA = spec.BENCH_DIR / "tests" / "data"
RECORDED = DATA / "tiny_qwen2_readings.json"
TEST_BLOCKS = DATA / "blocks"
TINY_LLAMA = dict(TINY_QWEN, repo_config="smollm_135m", model_type="llama")


@pytest.fixture(scope="module")
def thmin_limits():
    return spec.load_json(spec.limits_path("qwen0.5b-fl-thmin"))["numbers"]


def _digest(samples) -> str:
    h = hashlib.sha256()
    for k in sorted(samples):
        h.update(k.encode())
        h.update(np.ascontiguousarray(samples[k], np.float32).tobytes())
    return h.hexdigest()


def _part(r) -> dict:
    return {"loss": r["loss"], "grad": r["grad"], "update": r["update"],
            "grad_sample_sha256": _digest(r["grad_sample"])}


@pytest.fixture(scope="module")
def qwen2_readings(thmin_limits):
    """The tiny qwen2 cell's readings, as the recorded ones were made."""
    from harness.program import model_config
    rec = spec.load_json(RECORDED)
    kind = spec.load_kind("fl")
    cell = make_cell(TINY_QWEN, dict(TINY_FL), thmin_limits)
    fed = kind.Federation(cell, rec["seed"])
    n = cell.traffic["check_rounds"]
    prog = kind.program_readings(fed, n)
    fed.free()
    ref = kind.reference_readings(fed, n, prog["theta"], prog["gossip"])
    ctl = kind.reference_readings(fed, n, prog["theta"], prog["gossip"],
                                  lowp="fp8")
    got = {"model_config": repr(model_config(TINY_QWEN)),
           "program": _part(prog), "reference": _part(ref),
           "control": _part(ctl), "checks": kind.check_numbers(prog, ref)}
    # through JSON, as the recorded values were written
    return json.loads(json.dumps(got)), rec


@pytest.mark.parametrize("part", ["model_config", "program", "reference",
                                  "control", "checks"])
def test_qwen2_block_reads_as_recorded(qwen2_readings, part):
    got, rec = qwen2_readings
    assert got[part] == rec[part]


def test_qwen2_counts_are_the_blocks():
    c = spec.load_json(spec.BENCH_DIR / "configs" / "qwen2.5-0.5b_l8.json")
    block = spec.load_block("qwen2")
    assert block.stored_layer_params(c) == block.active_layer_params(c) \
        == counts.attention_params(c) + counts.ffn_params(c)
    assert block.extra_params(c) == (14 + 2 * 2) * 64 + 2 * 896


@pytest.fixture
def test_blocks(monkeypatch):
    monkeypatch.setattr(spec, "BLOCKS_DIR", TEST_BLOCKS)


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_llama_block_is_one_file(test_blocks, thmin_limits, fault):
    """The tiny fl cell on a llama block (no q/k/v bias, the program's
    smollm_135m configuration) reads correct, and each fault planted under
    the timed path reads not correct."""
    import jax
    import run
    cell = make_cell(TINY_LLAMA, dict(TINY_FL), thmin_limits)
    out = run.execute(cell, 2**31 + 11, 0.5, False, jax.devices("cpu")[:1],
                      fault=fault, t_start=0.0)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["_compiles_in_window"] == 0


def test_llama_block_counts_what_the_program_holds(test_blocks):
    import jax
    from harness.program import model_config
    from repro.models.registry import get_model
    cfg = model_config(TINY_LLAMA)
    assert not cfg.qkv_bias and cfg.d_model == 64 and cfg.num_layers == 2
    shapes = jax.eval_shape(lambda: get_model(cfg).init(
        cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == counts.total_params(TINY_LLAMA) + (512 - 257) * 64
    assert counts.active_matmul_params(TINY_LLAMA) == 2 * (
        counts.attention_params(TINY_LLAMA)
        + counts.ffn_params(TINY_LLAMA)) + 64 * 257


def test_block_refuses_another_blocks_program(test_blocks):
    """The llama block checks that the repo configuration it is given is
    a llama block: qwen2_7b has q/k/v biases."""
    from harness.program import model_config
    with pytest.raises(ValueError, match="qkv_bias"):
        model_config(dict(TINY_LLAMA, repo_config="qwen2_7b"))


def test_unknown_model_type_names_the_missing_block(tmp_path):
    with pytest.raises(spec.SpecError, match="bench/blocks/nosuch.py"):
        spec.load_block("nosuch")
    with pytest.raises(spec.SpecError, match="bench/blocks/nosuch.py"):
        counts.total_params(dict(TINY_QWEN, model_type="nosuch"))
    bench = spec.load_benchmark()
    w = bench["workloads"][0]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg["file"] = "cfg.json"
    config = dict(spec.load_json(spec.ROOT / "bench" / "configs"
                                 / f"{w['config']}.json"),
                  model_type="nosuch")
    (tmp_path / "cfg.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError, match="bench/blocks/nosuch.py"):
        spec.resolve(w["name"], root=tmp_path)
