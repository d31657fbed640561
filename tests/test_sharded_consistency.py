"""Sharded-vs-unsharded consistency: the production round step on a fake
8-device mesh (conftest's xla_force_host_platform_device_count) must produce
the same numbers as the single-device path — exercising the fused per-leaf
shard_map compress + mix_local pipeline end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_model
from repro.configs.base import FLTopology, HCEFConfig
from repro.core.round import FLState, init_state, make_round_step
from repro.dist.compat import make_mesh
from repro.dist.policies import make_train_policy

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (fake) devices")


def _setup():
    cfg = smoke_model(get_config("smollm_135m").model).replace(
        d_model=64, d_ff=128)
    topo = FLTopology(clusters=2, devices_per_cluster=2)
    hcef = HCEFConfig(tau=2, q=2, eta=0.1, momentum=0.0)
    R = topo.num_devices
    state = init_state(cfg, hcef, topo, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (R * 2 * 2, 32), 0, cfg.vocab_size)}
    keys = jax.random.split(jax.random.PRNGKey(2), R)
    return cfg, topo, hcef, state, batch, keys


@pytest.mark.parametrize("gossip", [True, False])
def test_sharded_round_matches_unsharded(gossip):
    cfg, topo, hcef, state, batch, keys = _setup()
    R = topo.num_devices
    rho = jnp.ones(R)
    theta = jnp.full(R, 0.25)

    # --- unsharded reference ---
    step0 = jax.jit(make_round_step(cfg, hcef, topo, policy=None,
                                    gossip=gossip))
    s_ref, m_ref = step0(state, batch, rho, theta, keys)

    # --- sharded: mesh (4 data, 2 model), R=4 over data ---
    mesh = make_mesh((4, 2), ("data", "model"))
    policy = make_train_policy(mesh, topo, dp_axes=("data",))
    step1 = jax.jit(make_round_step(cfg, hcef, topo, policy=policy,
                                    gossip=gossip))
    state_sh = FLState(
        params=jax.tree.map(lambda x, s: jax.device_put(x, s), state.params,
                            policy.param_shardings(state.params,
                                                   stacked=True)),
        momentum=None,
        ef=jax.tree.map(lambda x, s: jax.device_put(x, s), state.ef,
                        policy.param_shardings(state.ef, stacked=True)),
        round_idx=state.round_idx)
    with mesh:
        s_sh, m_sh = step1(state_sh, batch, rho, theta, keys)

    assert abs(float(m_ref["loss"].mean()) - float(m_sh["loss"].mean())) \
        < 1e-3
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(s_ref.params)[0],
            jax.tree_util.tree_flatten_with_path(s_sh.params)[0]):
        err = float(jnp.abs(jnp.asarray(a, jnp.float32)
                            - jnp.asarray(b, jnp.float32)).max())
        assert err < 5e-3, (str(kp), err)
    # error-feedback buffers must agree too (compression ran shard-local)
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(s_ref.ef)[0],
            jax.tree_util.tree_flatten_with_path(s_sh.ef)[0]):
        err = float(jnp.abs(jnp.asarray(a, jnp.float32)
                            - jnp.asarray(b, jnp.float32)).max())
        assert err < 5e-3, (str(kp), err)


@pytest.mark.parametrize("gossip", [True, False])
def test_sharded_round_pallas_attention_matches_unsharded(gossip):
    """On a mesh the Pallas flash kernel runs inside a shard_map over the
    policy's attention specs (Mosaic kernels cannot be partitioned
    automatically); heads split over the model axis here.  Interpret mode,
    same bounds as the jnp route above."""
    from repro.kernels import ops
    cfg, topo, hcef, state, batch, keys = _setup()
    R = topo.num_devices
    rho, theta = jnp.ones(R), jnp.full(R, 0.25)
    mesh = make_mesh((4, 2), ("data", "model"))
    policy = make_train_policy(mesh, topo, dp_axes=("data",))
    with ops.forced_route("pallas"):
        step0 = jax.jit(make_round_step(cfg, hcef, topo, policy=None,
                                        gossip=gossip))
        step1 = jax.jit(make_round_step(cfg, hcef, topo, policy=policy,
                                        gossip=gossip))
        s_ref, m_ref = step0(state, batch, rho, theta, keys)
        shd = policy.param_shardings(state, stacked=True)
        with mesh:
            s_sh, m_sh = step1(jax.device_put(state, shd), batch, rho,
                               theta, keys)
    assert abs(float(m_ref["loss"].mean()) - float(m_sh["loss"].mean())) \
        < 1e-3
    for a, b in zip(jax.tree.leaves((s_ref.params, s_ref.ef)),
                    jax.tree.leaves((s_sh.params, s_sh.ef))):
        err = float(jnp.abs(jnp.asarray(a, jnp.float32)
                            - jnp.asarray(b, jnp.float32)).max())
        assert err < 5e-3, err


def test_fused_path_emits_no_full_leaf_allgather():
    """The compiled round step must never re-materialize a model-sharded
    leaf: all aggregation traffic is shard-sized (collective-permute/psum),
    which is the whole point of the dist layer (DESIGN.md §Dist-layer)."""
    from repro.dist.hlo_analysis import (analyze_hlo,
                                         check_no_full_leaf_allgather,
                                         sharded_leaf_bytes)
    cfg, topo, hcef, state, batch, keys = _setup()
    R = topo.num_devices
    mesh = make_mesh((4, 2), ("data", "model"))
    policy = make_train_policy(mesh, topo, dp_axes=("data",))
    step = jax.jit(make_round_step(cfg, hcef, topo, policy=policy,
                                   gossip=True))
    shd = policy.param_shardings(state.params, stacked=True)
    state_sh = FLState(
        params=jax.tree.map(jax.device_put, state.params, shd),
        momentum=None,
        ef=jax.tree.map(jax.device_put, state.ef,
                        policy.param_shardings(state.ef, stacked=True)),
        round_idx=state.round_idx)
    rho = jnp.ones(R)
    theta = jnp.full(R, 0.25)
    with mesh:
        hlo = step.lower(state_sh, batch, rho, theta,
                         keys).compile().as_text()
    sharded_bytes = sharded_leaf_bytes(state.params, shd)
    assert sharded_bytes, "policy sharded no leaf over the model axis?"
    chk = check_no_full_leaf_allgather(hlo, sharded_bytes)
    assert chk["ok"], chk
    stats = analyze_hlo(hlo)
    assert stats["coll_total"] > 0  # the mix really runs as collectives


def test_train_policy_topology_tiling():
    """inner_dp > 1 topologies (arctic-style) get a REPLICATED replica dim;
    genuinely mis-sized topologies fail at policy construction."""
    mesh = make_mesh((4, 2), ("data", "model"))
    topo = FLTopology(clusters=2, devices_per_cluster=1, inner_dp=2)
    p = make_train_policy(mesh, topo, dp_axes=("data",))
    assert p.replica_axes == ()
    with pytest.raises(ValueError, match="do not tile"):
        make_train_policy(mesh, FLTopology(clusters=3,
                                           devices_per_cluster=1),
                          dp_axes=("data",))


def test_sparse_gossip_round_step_hlo_and_equivalence():
    """sparse_gossip=True: (a) the lowered HLO's lax.switch branches carry
    collective-permute payloads that scale with the theta level (the
    static-k contract, DESIGN.md §Static-k); (b) at theta = 1 with the f32
    wire the sparse round step matches the dense-gossip round step."""
    import dataclasses

    from repro.dist.hlo_analysis import check_gossip_bytes_scale_with_theta

    cfg, topo, hcef, state, batch, keys = _setup()
    levels = (0.25, 1.0)
    hcef_sp = dataclasses.replace(hcef, sparse_gossip=True,
                                  theta_levels=levels)
    R = topo.num_devices
    mesh = make_mesh((4, 2), ("data", "model"))
    policy = make_train_policy(mesh, topo, dp_axes=("data",))

    def sharded(st):
        shd = policy.param_shardings(st.params, stacked=True)
        return FLState(
            params=jax.tree.map(jax.device_put, st.params, shd),
            momentum=None,
            ef=jax.tree.map(jax.device_put, st.ef,
                            policy.param_shardings(st.ef, stacked=True)),
            round_idx=st.round_idx)

    state_sh = sharded(state)
    rho = jnp.ones(R)
    step_sp = jax.jit(make_round_step(cfg, hcef_sp, topo, policy=policy,
                                      gossip=True))
    step_dn = jax.jit(make_round_step(cfg, hcef, topo, policy=policy,
                                      gossip=True))

    # (a) wire bytes scale with the quantized theta level
    theta = jnp.full(R, 0.25)
    with mesh:
        hlo = step_sp.lower(state_sh, batch, rho, theta,
                            keys).compile().as_text()
    chk = check_gossip_bytes_scale_with_theta(hlo, levels)
    assert chk["ok"], chk

    # (b) theta = 1 (k = d), f32 wire: sparse == dense gossip round
    theta1 = jnp.ones(R)
    with mesh:
        s_sp, m_sp = step_sp(state_sh, batch, rho, theta1, keys)
        s_dn, _ = step_dn(sharded(state), batch, rho, theta1, keys)
    assert float(m_sp["theta_wire"]) == 1.0
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(s_sp.params)[0],
            jax.tree_util.tree_flatten_with_path(s_dn.params)[0]):
        err = float(jnp.abs(jnp.asarray(a, jnp.float32)
                            - jnp.asarray(b, jnp.float32)).max())
        assert err < 1e-5, (str(kp), err)


def test_per_cluster_round_step_equivalence_and_bytes():
    """The per-cluster static dispatch (cluster_levels): (a) all-levels=1
    matches the dense-gossip round step AND ships exactly its gossip
    bytes (no 2x offset overhead at theta=1 — the dense-wire fallback);
    (b) a heterogeneous assignment's gossip permute bytes beat the
    all-max baseline and track the level-vector sum
    (check_cluster_gossip_bytes, the §Static-k per-cluster contract)."""
    import dataclasses

    from repro.dist.hlo_analysis import (analyze_hlo,
                                         check_cluster_gossip_bytes)

    cfg, topo, hcef, state, batch, keys = _setup()
    levels = (0.05, 0.8, 1.0)
    hcef_sp = dataclasses.replace(hcef, sparse_gossip=True,
                                  theta_levels=levels)
    R, C = topo.num_devices, topo.clusters
    mesh = make_mesh((4, 2), ("data", "model"))
    policy = make_train_policy(mesh, topo, dp_axes=("data",))

    def sharded(st):
        shd = policy.param_shardings(st.params, stacked=True)
        return FLState(
            params=jax.tree.map(jax.device_put, st.params, shd),
            momentum=None,
            ef=jax.tree.map(jax.device_put, st.ef,
                            policy.param_shardings(st.ef, stacked=True)),
            round_idx=st.round_idx)

    mk = lambda **kw: jax.jit(make_round_step(cfg, hcef_sp, topo,
                                              policy=policy, **kw))
    rho = jnp.ones(R)

    # (a) all-ones assignment == dense round step, same gossip bytes
    step_pc1 = mk(gossip=True, cluster_levels=(1.0,) * C)
    step_dn = jax.jit(make_round_step(cfg, hcef, topo, policy=policy,
                                      gossip=True))
    theta1 = jnp.ones(R)
    with mesh:
        s_pc, m_pc = step_pc1(sharded(state), batch, rho, theta1, keys)
        s_dn, _ = step_dn(sharded(state), batch, rho, theta1, keys)
    assert float(m_pc["theta_wire"]) == 1.0
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(s_pc.params)[0],
            jax.tree_util.tree_flatten_with_path(s_dn.params)[0]):
        err = float(jnp.abs(jnp.asarray(a, jnp.float32)
                            - jnp.asarray(b, jnp.float32)).max())
        assert err < 1e-5, (str(kp), err)
    with mesh:
        hlo_pc1 = step_pc1.lower(sharded(state), batch, rho, theta1,
                                 keys).compile().as_text()
        hlo_dn = step_dn.lower(sharded(state), batch, rho, theta1,
                               keys).compile().as_text()
    b_pc1 = analyze_hlo(hlo_pc1)["gossip_wire_bytes"]
    b_dn = analyze_hlo(hlo_dn)["gossip_wire_bytes"]
    assert b_pc1 == b_dn, (b_pc1, b_dn)  # exactly dense bytes at theta=1

    # (b) heterogeneous assignment: byte win vs all-max, level-vector share
    hetero = (0.05, 0.8)
    step_het = mk(gossip=True, cluster_levels=hetero)
    step_base = mk(gossip=True, cluster_levels=(0.8,) * C)
    step_intra = jax.jit(make_round_step(cfg, hcef_sp, topo, policy=policy,
                                         gossip=False))
    theta = jnp.full(R, 0.05)
    with mesh:
        lower = lambda st: st.lower(sharded(state), batch, rho, theta,
                                    keys).compile().as_text()
        hlo_het, hlo_base, hlo_intra = map(lower, (step_het, step_base,
                                                   step_intra))
    chk = check_cluster_gossip_bytes(
        hlo_het, hlo_base, hetero, wire_dtype=hcef_sp.wire_dtype,
        wire_block=hcef_sp.wire_block, dense_itemsize=2,
        intra_hlo=hlo_intra)
    assert chk["ok"], chk
    assert chk["permute_bytes"] < chk["baseline_permute_bytes"]


def test_round_step_cluster_levels_validation():
    """Misuse fails loudly: off-grid levels, wrong length, missing
    sparse_gossip, and the mesh-less path all raise at build time."""
    cfg, topo, hcef, state, batch, keys = _setup()
    import dataclasses
    hcef_sp = dataclasses.replace(hcef, sparse_gossip=True,
                                  theta_levels=(0.25, 1.0))
    mesh = make_mesh((4, 2), ("data", "model"))
    policy = make_train_policy(mesh, topo, dp_axes=("data",))
    with pytest.raises(ValueError, match="sparse_gossip"):
        make_round_step(cfg, hcef, topo, policy=policy,
                        cluster_levels=(1.0, 1.0))
    with pytest.raises(ValueError, match="mesh"):
        make_round_step(cfg, hcef_sp, topo, policy=None,
                        cluster_levels=(1.0, 1.0))
    with pytest.raises(ValueError, match="entries for"):
        make_round_step(cfg, hcef_sp, topo, policy=policy,
                        cluster_levels=(1.0,))
    with pytest.raises(ValueError, match="not in theta_levels"):
        make_round_step(cfg, hcef_sp, topo, policy=policy,
                        cluster_levels=(0.5, 1.0))
