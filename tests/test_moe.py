"""The dropless expert layer (``models/lm._moe_ffn``) and the grouped
matmul under it (``kernels/ops.grouped_matmul``) against plain f32
references on seeded random weights.

Every comparison here runs in float32 on the CPU on both sides, so the
two differ only in the order of their sums: each tolerance is a few
hundred float32 ulps of the largest value compared."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_model
from repro.kernels import ops, ref
from repro.models import lm

# f32 on both sides: only the order of the sums differs
RTOL = ATOL = 2e-5


def _cfg(E=8, K=2):
    return smoke_model(get_config("granite_moe_1b_a400m").model).replace(
        num_experts=E, experts_per_token=K)


def _weights(cfg, key, scale=0.1):
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    k = jax.random.split(key, 4)
    n = lambda i, s: scale * jax.random.normal(k[i], s, jnp.float32)
    return {"router": n(0, (D, E)), "we_gate": n(1, (E, D, F)),
            "we_up": n(2, (E, D, F)), "we_down": n(3, (E, F, D))}


def all_experts_ref(cfg, x, w):
    """Every expert on every token, weighted by a dense gate matrix that
    is zero off each token's top K (softmax over the K chosen logits)."""
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("bsd,de->bse", x, w["router"], precision=hi)
    top, idx = jax.lax.top_k(logits, cfg.experts_per_token)
    gates = jnp.einsum("bsk,bske->bse", jax.nn.softmax(top, axis=-1),
                       jax.nn.one_hot(idx, cfg.num_experts))
    a = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, w["we_gate"],
                               precision=hi)) \
        * jnp.einsum("bsd,edf->bsef", x, w["we_up"], precision=hi)
    return jnp.einsum("bsef,efd->bsd", a * gates[..., None], w["we_down"],
                      precision=hi)


def _layer(cfg, impl, x, w, experts=None):
    # the layer routes by the call-time route, as on the chip
    with ops.forced_route(impl):
        return lm._moe_ffn(cfg, x, w, None, experts=experts)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_grouped_matmul_vs_ref(impl):
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (256, 64))
    w = jax.random.normal(k[1], (5, 64, 32))
    gs = jnp.array([60, 0, 100, 1, 95], jnp.int32)
    dy = jax.random.normal(k[2], (256, 32))

    def loss(fn, x, w):
        return jnp.sum(fn(x, w, gs) * dy)

    got = jax.value_and_grad(functools.partial(
        loss, functools.partial(ops.grouped_matmul, impl=impl)),
        argnums=(0, 1))(x, w)
    want = jax.value_and_grad(functools.partial(loss, ref.grouped_matmul_ref),
                              argnums=(0, 1))(x, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL * 16)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_dropless_layer_vs_all_experts(impl):
    cfg = _cfg()
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (2, 32, cfg.d_model))
    w = _weights(cfg, kw)
    y, load = _layer(cfg, impl, x, w)
    np.testing.assert_allclose(y, all_experts_ref(cfg, x, w), rtol=RTOL,
                               atol=ATOL)
    assert int(load.sum()) == 2 * 32 * cfg.experts_per_token


def test_skewed_router_drops_nothing():
    """Every token routes to expert 0 (and one other): 64 assignments of
    one expert in a sequence of 64, twice the capacity of 32 that the
    earlier capacity dispatch gave it (max(8, 2 ceil(S K / E)))."""
    cfg = _cfg(E=8, K=2)
    S = 64
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (1, S, cfg.d_model)).at[..., 0].set(4.0)
    w = _weights(cfg, kw)
    w["router"] = w["router"].at[0, 0].set(10.0)
    y, load = _layer(cfg, "jnp", x, w)
    assert int(load[0]) == S and int(load.sum()) == S * cfg.experts_per_token
    np.testing.assert_allclose(y, all_experts_ref(cfg, x, w), rtol=RTOL,
                               atol=ATOL)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_expert_halves_add_up_to_the_layer(impl):
    """Two shares of the experts, each told its first expert and count,
    route over all of them and compute only their own experts' part: the
    parts add up to the whole layer."""
    cfg = _cfg()
    E = cfg.num_experts
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (2, 16, cfg.d_model))
    w = _weights(cfg, kw)
    whole, load = _layer(cfg, impl, x, w)
    parts = []
    for first in (0, E // 2):
        share = dict(w, **{k: w[k][first:first + E // 2]
                           for k in ("we_gate", "we_up", "we_down")})
        y, share_load = _layer(cfg, impl, x, share, experts=(first, E // 2))
        np.testing.assert_array_equal(share_load, load)
        parts.append(y)
    np.testing.assert_allclose(parts[0] + parts[1], whole, rtol=RTOL,
                               atol=ATOL)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_layer_under_vmap_over_clients(impl):
    """The round step's vmap over clients: each client's output, load and
    gradients equal its own call's."""
    cfg = _cfg()
    N = 3
    keys = jax.random.split(jax.random.PRNGKey(4), 2 * N)
    xs = jnp.stack([jax.random.normal(k, (2, 16, cfg.d_model))
                    for k in keys[:N]])
    ws = jax.tree.map(lambda *a: jnp.stack(a),
                      *[_weights(cfg, k) for k in keys[N:]])

    def f(x, w):
        y, load = lm._moe_ffn(cfg, x, w, None)
        return jnp.sum(y * y), load

    with ops.forced_route(impl):
        fn = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
        (loss, load), grads = jax.vmap(fn)(xs, ws)
        for i in range(N):
            one = jax.tree.map(lambda a: a[i], (xs, ws))
            (l1, load1), g1 = fn(*one)
            np.testing.assert_allclose(loss[i], l1, rtol=RTOL)
            np.testing.assert_array_equal(load[i], load1)
            for a, b in zip(jax.tree.leaves(g1),
                            jax.tree.leaves(jax.tree.map(lambda a: a[i],
                                                         grads))):
                np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


def test_round_step_reports_expert_load():
    """``device_round`` returns the last local step's assignments per
    expert, summed over layers; a dense model returns none."""
    from repro.configs.base import FLTopology, HCEFConfig
    from repro.core.round import init_state, make_round_step
    topo = FLTopology(clusters=2, devices_per_cluster=1)
    hcef = HCEFConfig(tau=2)
    R, S, b = 2, 16, 1
    tokens = jax.random.randint(jax.random.PRNGKey(5), (R * 2 * b, S + 1),
                                0, 257)
    ctl = (jnp.ones((R,)), jnp.ones((R,)),
           jax.random.split(jax.random.PRNGKey(6), R))
    for arch, has in (("granite_moe_1b_a400m", True), ("qwen2_7b", False)):
        cfg = smoke_model(get_config(arch).model)
        state = init_state(cfg, hcef, topo, jax.random.PRNGKey(0))
        _, m = jax.jit(make_round_step(cfg, hcef, topo, gossip=False))(
            state, {"tokens": tokens}, *ctl)
        assert ("moe_load" in m) is has
        if has:
            assert m["moe_load"].shape == (R, cfg.num_experts)
            assert m["moe_load"].dtype == jnp.int32
            np.testing.assert_array_equal(
                m["moe_load"].sum(axis=1),
                cfg.num_layers * b * (S + 1) * cfg.experts_per_token)
