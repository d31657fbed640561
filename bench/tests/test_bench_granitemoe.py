"""The ``granitemoe`` block (Granite 3.0 MoE): a tiny fl cell through the
whole harness, its parameter and expert-matmul counts by hand, the cell
found by name, and the program's smoke model against the block's plain
reference forward."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_FL, make_cell
from harness import counts, device, reference, scopes, spec
from harness import trace as tr

CELL = "granite-fl-thmin"
TINY_GRANITE = {
    "repo_config": "granite_moe_1b_a400m", "model_type": "granitemoe",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 32,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 257,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "embedding_multiplier": 12.0,
    "attention_multiplier": 0.0625, "residual_multiplier": 0.22,
    "logits_scaling": 6.0, "initializer_range": 0.02,
    "param_dtype": "float32", "compute_dtype": "float32",
}


@pytest.fixture(scope="module")
def config():
    return spec.resolve(CELL).config


@pytest.fixture(scope="module")
def limits():
    return spec.load_json(spec.limits_path(CELL))["numbers"]


def test_cell_resolves():
    cell = spec.resolve(CELL)
    assert cell.config["model_type"] == "granitemoe" and cell.chips == 1
    assert cell.traffic_name == "fl_r4_thmin"
    assert {m.name for m in cell.per_layer} == {
        "moe_dispatch_ms.train", "expert_gmm_roofline.train"}


@pytest.mark.parametrize("reading,correct", [
    ("program_max", True), ("control_fp8_min", False),
    ("half_batch_min", False)])
def test_chip_readings_against_the_limits(reading, correct):
    """The readings the limits file records from the chip, judged by the
    harness's own comparison: the program's largest passes, the fp8
    control's and the half batch's smallest do not."""
    from harness import compare
    d = spec.load_json(spec.limits_path(CELL))
    numbers = {k: v[reading] for k, v in d["readings"].items()
               if isinstance(v, dict)}
    ok, rows = compare.judge(numbers, d["numbers"])
    assert ok is correct, rows


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_tiny_cell_through_the_harness(limits, fault):
    """The tiny fl cell on the granitemoe block reads correct; each fault
    planted under the timed path reads not correct."""
    import run
    cell = make_cell(TINY_GRANITE, dict(TINY_FL), limits)
    out = run.execute(cell, 2**31 + 29, 0.5, False, jax.devices("cpu")[:1],
                      fault=fault, t_start=0.0)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["_compiles_in_window"] == 0


def test_counts_by_hand(config):
    block = spec.load_block("granitemoe")
    D, F, E, K = 1024, 512, 32, 8
    attn = 2 * D * 16 * 64 + 2 * D * 8 * 64
    assert attn == 3_145_728
    assert block.stored_layer_params(config) \
        == attn + D * E + E * 3 * D * F == 3_145_728 + 32_768 + 50_331_648
    assert block.active_layer_params(config) \
        == attn + D * E + K * 3 * D * F == 15_761_408
    assert block.extra_params(config) == 2 * D
    # the model held: 4 layers, the embedding at 49,155 rows, final norm
    assert counts.total_params(config) == 4 * (53_510_144 + 2048) \
        + 49_155 * D + D
    # one round of the cell: 4 clients x tau 4 x 2 sequences of 513
    tokens = 4 * 4 * 2 * 513
    one = 2 * D * F * K * tokens * 4
    assert block.expert_gmm_flops(config, tokens) == {
        "forward": 3 * one, "backward": 6 * one, "remat": 3 * one}
    # bf16: each product's rows in and out (D + F per assignment) and the
    # 32 experts' weights once per client and step (4 x tau 4), 4 layers
    moved = 2 * (K * tokens * (D + F) + 16 * E * D * F) * 4
    assert block.expert_gmm_bytes(config, tokens, 16) == {
        "forward": 3 * moved, "backward": 6 * moved, "remat": 3 * moved}


def test_program_holds_what_the_block_counts(config):
    """At the cell's sizes the program's parameters are the block's count
    plus the embedding's padding to 256 rows: 264,643,584."""
    from harness.program import model_config
    from repro.models.registry import get_model
    cfg = model_config(config)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.d_ff) == (32, 8, 512)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
        12.0, 1 / 64, 0.22, 6.0)
    shapes = jax.eval_shape(lambda: get_model(cfg).init(
        cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == counts.total_params(config) + (49_408 - 49_155) * 1024 \
        == 264_643_584


def test_block_refuses_a_dense_program():
    from harness.program import model_config
    with pytest.raises(ValueError, match="family"):
        model_config(dict(TINY_GRANITE, repo_config="smollm_135m"))


def test_smoke_model_matches_the_reference():
    """The program's forward, loss and gradients at the tiny size, in
    float32, against the block's plain forward (float32 at HIGHEST).
    Both route by their own logits; only the order of the sums differs,
    so the tolerances are a few hundred float32 ulps."""
    from harness.program import model_config
    from repro.models import lm
    c = TINY_GRANITE
    cfg = model_config(c).replace(remat=False)
    params = lm.init(cfg, jax.random.PRNGKey(7))
    params = jax.tree.map(lambda a: a if a.ndim < 2 else 5 * a, params)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 33), 0,
                                c["vocab_size"])
    V = c["vocab_size"]
    got = lm.forward(cfg, params, {"tokens": tokens[:, :-1]})[..., :V]
    want = reference.logits(c, params, tokens[:, :-1])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    lp, gp = jax.value_and_grad(
        lambda p: lm.loss_fn(cfg, p, {"tokens": tokens}))(params)
    lr, gr = jax.value_and_grad(
        lambda p: reference.loss(c, p, tokens))(params)
    np.testing.assert_allclose(lp, lr, rtol=1e-5)
    for path, a in jax.tree_util.tree_flatten_with_path(gp)[0]:
        b = gr
        for k in path:
            b = b[k.key]
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


# --- the two readers, on a window whose ops are named as the program names
# them, and on the recorded Qwen window, whose program has no expert layer

PLANE = "/device:TPU:0"
STEP = ("jit(round_step)/vmap(hcef.local_step)/while/body/closed_call/"
        "jvp()/while/body/closed_call/lm.mlp/")
KERNEL = 'custom_call_target="tpu_custom_call"'


def _ctx(config, rounds=2):
    return SimpleNamespace(
        kind="fl", chips=1, rounds=rounds, config=config,
        peaks=device.peaks_for("TPU v5 lite"),
        traffic=spec.load_json(spec.traffic_path("fl_r4_thmin")),
        trace=SimpleNamespace(events=[], t0=0, t1=1e9, planes=[PLANE]))


def _op(name, ns, op_name):
    return (tr.Event(PLANE, tr.OPS_LINE, name, 0.0, ns), ns, op_name)


def test_readers_on_named_expert_ops(config, monkeypatch):
    bwd = STEP.replace("jvp()", "transpose(jvp())")
    ops = [
        _op(f"%gmm.1 = f32[32896,512] custom-call(), {KERNEL}", 3e6,
            STEP + "lm.moe_experts/pallas_call"),
        _op(f"%gmm.2 = bf16[128,1024,512] custom-call(), {KERNEL}", 5e6,
            bwd + "lm.moe_experts/pallas_call"),
        _op("%fusion.3 = bf16[32896,512] fusion()", 7e6,
            STEP + "lm.moe_experts/mul"),
        _op("%sort.4 = s32[4,8208] sort()", 2e6, STEP + "lm.moe_route/sort"),
        _op("%gather.5 = bf16[4,8208,1024] gather()", 1e6,
            bwd + "lm.moe_route/gather"),
        _op("%fusion.6 = bf16[4,1026,1024] fusion()", 4e6,
            STEP + "lm.moe_combine/reduce_sum"),
        _op(f"%lm.attn.7 = bf16[4,2,16,640,64] custom-call(), {KERNEL}",
            9e6, STEP.replace("lm.mlp/", "lm.attn/") + "pallas_call"),
    ]
    monkeypatch.setattr(scopes, "window_ops",
                        lambda ctx: scopes.NamedOps(ops, 1, 1))
    ctx = _ctx(config)
    dispatch = spec.load_reader("moe_dispatch_ms.train")(ctx)
    assert dispatch == pytest.approx((2e6 + 1e6 + 4e6) * 1e-6 / 2)
    share = spec.load_reader("expert_gmm_roofline.train")(ctx)
    tokens = 4 * 4 * 2 * 513
    flops = 2 * 12 * 2 * 1024 * 512 * 8 * tokens * 4
    nbytes = 2 * 12 * 2 * (8 * tokens * 1536 + 16 * 32 * 1024 * 512) * 4
    # at about 256 rows a group the bytes bound the matmuls
    assert nbytes / 819e9 > flops / 197e12
    assert share == pytest.approx(
        100 * nbytes / 819e9 / 8e-3, rel=1e-9)
    assert ctx.peaks["bf16_flops_per_s"] == 197e12
    assert ctx.peaks["hbm_bytes_per_s"] == 819e9


def test_readers_read_nothing_without_the_expert_layer(config,
                                                       monkeypatch):
    """The recorded Qwen window: its program has no expert scopes, as a
    program without the dropless layer has none."""
    d = spec.load_json(spec.BENCH_DIR / "tests" / "data"
                       / "trace_v5e_scopes.json")
    names = d["op_names"]
    maps = [{k: (v[0], names[v[1]], v[2]) for k, v in m.items()}
            for m in d["programs"].values()]
    monkeypatch.setattr(scopes, "fl_round_maps", lambda ctx: maps)
    ctx = _ctx(config)
    w = list(d["windows"].values())
    ctx.trace = SimpleNamespace(
        events=[tr.Event(**e) for e in d["events"]], planes=[PLANE],
        t0=min(x[0] for x in w), t1=max(x[1] for x in w))
    assert scopes.scope_ms(ctx, "lm.attn") is not None
    for name in ("moe_dispatch_ms.train", "expert_gmm_roofline.train"):
        assert spec.load_reader(name)(ctx) is None
