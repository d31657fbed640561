"""The program's phase names (README, "Tracing").

Device scopes: every op of the compiled round step that does work (dot,
fusion, custom-call, scatter) carries an ``hcef.*`` or ``lm.*`` scope in
its ``op_name`` as the benchmark resolves it (``bench/harness/scopes.py``
gives a TPU trace's device time to phases by these names), and every
scope appears.  Host spans: the launcher's loop writes them into a
profiler trace.  The scopes are metadata only; the bit-identity tests of
``test_round.py``, ``test_chaos.py``, ``test_population.py`` and
``test_overlap.py`` pin that they change no number.
"""
import collections
import glob
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, smoke_model
from repro.configs.base import FLTopology, HCEFConfig
from repro.core.round import abstract_state, make_round_step

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from harness import scopes  # noqa: E402

WORK = ("dot", "fusion", "custom-call", "scatter")
HOST_PATH_SCOPES = {"lm.embed", "lm.attn", "lm.mlp", "lm.head",
                    "hcef.local_step", "hcef.grad_stats", "hcef.sgd",
                    "hcef.delta", "hcef.compress", "hcef.aggregate"}
SPANS = ("hcef.round", "hcef.feed", "hcef.budget", "hcef.controller",
         "hcef.reports", "hcef.compile")


def _compiled(cfg, hcef, topo, gossip, policy=None, shard=None):
    R = topo.num_devices
    state = abstract_state(cfg, hcef, topo)
    if shard is not None:
        state = shard(state)
    vec = jax.ShapeDtypeStruct((R,), jnp.float32)
    args = (state, {"tokens": jax.ShapeDtypeStruct(
        (R * hcef.tau * 2, 33), jnp.int32)}, vec, vec,
        jax.ShapeDtypeStruct((R, 2), jnp.uint32))
    step = make_round_step(cfg, hcef, topo, policy, gossip=gossip)
    return jax.jit(step, donate_argnums=0).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def host_programs():
    """The host-path round step as the benchmark's cells run it (a Qwen2
    block, remat on) at the test size, in f32 (the CPU compiler rewrites
    bf16 math into converts of its own): {gossip: HLO text}."""
    cfg = smoke_model(get_config("qwen2_7b").model).replace(remat=True)
    topo = FLTopology(clusters=2, devices_per_cluster=2)
    hcef = HCEFConfig(tau=2, q=2, eta=0.1, momentum=0.9)
    return {g: _compiled(cfg, hcef, topo, g) for g in (False, True)}


@pytest.mark.parametrize("gossip", [False, True], ids=["intra", "gossip"])
def test_every_working_op_carries_a_scope(host_programs, gossip):
    """Ops that the compiler made and that name no program code (an empty
    ``op_name``, or a parameter's) are left out: no scope can name them.
    So are scalar ops (loop counters, the round index)."""
    ops = scopes.hlo_op_names(host_programs[gossip])
    work = {n: v for n, v in ops.items()
            if v[2] in WORK and v[1].startswith("jit(")
            and not v[0].endswith("[]")}
    assert len(work) > 100
    bare = {n: v[1] for n, v in work.items()
            if scopes.phase_of(v[1]) == "unscoped"}
    assert not bare, sorted(bare.items())[:10]


def test_every_host_path_scope_appears(host_programs):
    for gossip, text in host_programs.items():
        found = set(scopes.SCOPE.findall(text))
        assert HOST_PATH_SCOPES <= found, (gossip, HOST_PATH_SCOPES - found)


def test_backward_ops_keep_their_scope_inside_transpose(host_programs):
    names = [on for _, on, _ in
             scopes.hlo_op_names(host_programs[False]).values()]
    bwd = [on for on in names if "transpose(" in on]
    assert any("lm.attn" in on for on in bwd)
    assert any("lm.mlp" in on for on in bwd)
    assert any(re.search(r"transpose\(jvp\(lm\.head\)\)", on) for on in bwd)
    assert {scopes.phase_of(on) for on in bwd
            if scopes.SCOPE.findall(on)[-1] in ("lm.attn", "lm.mlp")} == {
        "local_bwd"}
    assert any(scopes.REMAT in on for on in bwd)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (fake) devices")
def test_mesh_gossip_scopes_appear():
    """The mesh path (sparse gossip on a (4, 2) mesh): compress and the
    mix inside the per-leaf shard_map, the wire exchange under
    ``hcef.gossip``."""
    import dataclasses

    from repro.dist.compat import make_mesh
    from repro.dist.policies import make_train_policy

    cfg = smoke_model(get_config("smollm_135m").model)
    topo = FLTopology(clusters=2, devices_per_cluster=2)
    hcef = dataclasses.replace(HCEFConfig(tau=2, q=2, eta=0.1, momentum=0.0),
                               sparse_gossip=True, theta_levels=(0.25, 1.0))
    mesh = make_mesh((4, 2), ("data", "model"))
    policy = make_train_policy(mesh, topo, dp_axes=("data",))

    def shard(st):
        put = lambda t: jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            t, policy.param_shardings(t, stacked=True))
        return st._replace(params=put(st.params), ef=put(st.ef))

    with mesh:
        text = _compiled(cfg, hcef, topo, True, policy, shard)
    found = set(scopes.SCOPE.findall(text))
    assert {"hcef.gossip", "hcef.compress", "hcef.aggregate",
            "hcef.local_step"} <= found


def test_launcher_loop_writes_its_host_spans(tmp_path, monkeypatch):
    from repro.launch import train
    # JAX read the variable at start-up: set now, it only keeps the
    # launcher from putting a compile cache in the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    with jax.profiler.trace(str(tmp_path)):
        train.run(["--mesh", "host", "--rounds", "2", "--seq", "16",
                   "--controller", "cef"])
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    pd = jax.profiler.ProfileData.from_file(path[-1])
    names = collections.Counter(
        e.name for p in pd.planes if p.name.startswith("/host:")
        for line in p.lines for e in line.events
        if e.name.startswith("hcef."))
    assert names["hcef.round"] == 2
    for span in SPANS:
        assert names[span] >= 1, (span, names)
