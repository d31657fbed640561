"""FLOP and byte counts by hand for the configuration, and the peaks
table's refusals."""
from types import SimpleNamespace

import pytest

from harness import counts, device, spec

CONFIGS = spec.BENCH_DIR / "configs"


def _cfg(name):
    return spec.load_json(CONFIGS / f"{name}.json")


CONFIG = "qwen2.5-0.5b_l8"
ATTN = 896 * 896 + 896 * 128 + 896 * 128 + 896 * 896  # q, k, v, o
FFN = 3 * 896 * 4864
BIAS = 896 + 128 + 128  # q, k, v
EMB = 151936 * 896


def test_qwen_whole_model_is_494m_params():
    c = dict(_cfg(CONFIG), num_hidden_layers=24)
    assert counts.attention_params(c) == ATTN == 1_835_008
    assert counts.ffn_params(c) == FFN == 13_074_432
    # the published count of Qwen2.5-0.5B: 24 layers with norms and q/k/v
    # biases, the tied embedding and the final norm
    assert counts.total_params(c) == 24 * (ATTN + FFN + BIAS + 2 * 896) \
        + EMB + 896 == 494_032_768


def test_qwen_l8_active_params():
    c = _cfg(CONFIG)
    assert counts.active_matmul_params(c) == 8 * (ATTN + FFN) + EMB \
        == 255_410_176


def test_program_holds_the_configured_parameters():
    """The program's parameters at the file's sizes are the count's, with
    the vocabulary padded to a multiple of 256 rows."""
    import jax
    from harness.program import model_config
    from repro.models.registry import get_model
    c = _cfg(CONFIG)
    cfg = model_config(c)
    shapes = jax.eval_shape(lambda: get_model(cfg).init(
        cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == counts.total_params(c) + (152064 - 151936) * 896


def test_attention_and_train_flops():
    c = _cfg(CONFIG)
    # causal: 513*512/2 query-key pairs, QK^T and PV at 2 FLOPs per MAC
    assert counts.attention_fwd_flops(c, 512) == \
        4 * 14 * 64 * (512 * 513 // 2) == 470_679_552
    assert counts.attention_fwd_bytes(c, 512) == \
        2 * 512 * 64 * (2 * 14 + 2 * 2) == 2_097_152
    per_tok = counts.train_flops_per_token(c, 512)
    assert per_tok == 6 * 255_410_176 + 8 * 3 * 470_679_552 / 512
    assert counts.topk_compress_bytes(1000) == 8000


def test_roofline_names_its_bound():
    p = device.peaks_for("TPU v5 lite")
    t, bound = counts.roofline_seconds(197e12, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = counts.roofline_seconds(1.0, 819e9, p)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_peaks_table_is_keyed_and_sourced():
    table = spec.load_json(device.PEAKS_FILE)
    assert "Google Cloud" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9


def test_unknown_device_kind_is_refused():
    with pytest.raises(device.DeviceError):
        device.peaks_for("TPU v9 imaginary")
    chip = SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary")
    with pytest.raises(device.DeviceError):
        device.require_chips([chip], 1)


def test_cpu_and_too_few_chips_are_refused():
    import jax
    with pytest.raises(device.DeviceError, match="needs a TPU"):
        device.require_chips(jax.devices("cpu"), 1)
    chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    with pytest.raises(device.DeviceError, match="asks for 4"):
        device.require_chips([chip], 4)
    assert device.require_chips([chip] * 4, 4) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}
