import os
# MUST precede every other import (jax locks its platform and device count
# on first init).  This AOT tool compiles on 512 fake CPU devices and never
# takes an accelerator, for itself or the cell subprocesses it spawns (they
# inherit the environment): a chip belongs to one process at a time.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512"
                           ).strip()

import argparse
import dataclasses
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import FLTopology, HCEFConfig, ShapeConfig
from repro.core.round import (FLState, OverlapState, abstract_state,
                              make_overlap_round_step, make_prefill_step,
                              make_round_step, make_serve_step)
from repro.dist.hlo_analysis import (analyze_hlo,
                                     check_cluster_gossip_bytes,
                                     check_gossip_bytes_scale_with_theta,
                                     check_gossip_overlap,
                                     check_no_full_leaf_allgather,
                                     sharded_leaf_bytes)
from repro.dist.policies import Policy, make_serve_policy, make_train_policy
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models.registry import cache_specs, get_model, input_specs

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def _cache_shardings(policy: Policy, cache_abs):
    """Name-based sharding rules for decode caches (divisibility-guarded)."""
    mesh = policy.mesh
    nf = int(np.prod([mesh.shape[a] for a in policy.fsdp_axes], initial=1))
    nb = int(np.prod([mesh.shape[a] for a in policy.batch_axes], initial=1))

    def rule(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        shape = leaf.shape
        spec = [None] * len(shape)
        b = tuple(policy.batch_axes)
        f = tuple(policy.fsdp_axes)
        s = tuple(policy.seq_axes)
        if name in ("k", "v", "xk", "xv") and len(shape) == 5:
            # (L, B, S, KH, Dh): batch + sequence sharding (flash-decode)
            if b and shape[1] % nb == 0:
                spec[1] = b
            if shape[2] % nf == 0:
                spec[2] = s
        elif name == "conv" and len(shape) == 4:
            if b and shape[1] % nb == 0:
                spec[1] = b
            if shape[3] % nf == 0:
                spec[3] = f
        elif name == "ssm" and len(shape) == 5:
            if b and shape[1] % nb == 0:
                spec[1] = b
            if shape[2] % nf == 0:
                spec[2] = f
        elif name == "lru" and len(shape) == 3:
            if b and shape[1] % nb == 0:
                spec[1] = b
            if shape[2] % nf == 0:
                spec[2] = f
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(rule, cache_abs)


def _batch_shardings(policy: Policy, batch_abs):
    mesh = policy.mesh
    axes = tuple(policy.replica_axes) + tuple(policy.batch_axes)
    n = int(np.prod([mesh.shape[a] for a in axes], initial=1))

    def rule(leaf):
        spec = [None] * leaf.ndim
        if axes and leaf.shape[0] % n == 0:
            spec[0] = axes
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(rule, batch_abs)


def overlap_equivalence_smoke():
    """Executed staleness=0 contract (DESIGN.md §Overlap): the overlapped
    engine's synchronous-delegation path must reproduce the plain round
    step BIT-FOR-BIT on a small sharded smoke cell."""
    from repro.configs import smoke_model
    from repro.core.round import init_state
    from repro.dist.compat import make_mesh

    cfg = smoke_model(get_config("smollm_135m").model).replace(
        d_model=64, d_ff=128)
    topo = FLTopology(clusters=2, devices_per_cluster=2)
    hcef = HCEFConfig(tau=2, q=2, eta=0.1, momentum=0.0, sparse_gossip=True)
    R = topo.num_devices
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (R * 2 * 2, 32), 0, cfg.vocab_size)}
    keys = jax.random.split(jax.random.PRNGKey(2), R)
    rho = jnp.ones(R)
    theta = jnp.full(R, 0.25)
    mesh = make_mesh((4, 2), ("data", "model"))
    policy = make_train_policy(mesh, topo, dp_axes=("data",))
    levels = (0.1, 1.0)

    state = init_state(cfg, hcef, topo, jax.random.PRNGKey(0))
    put = lambda t: jax.tree.map(
        lambda x, s: jax.device_put(x, s), t,
        policy.param_shardings(t, stacked=True))
    state = FLState(params=put(state.params), momentum=None,
                    ef=put(state.ef), round_idx=state.round_idx)
    hcef_ov = dataclasses.replace(hcef, overlap=True, staleness=0)
    step_sync = jax.jit(make_round_step(cfg, hcef, topo, policy,
                                        gossip=True, cluster_levels=levels))
    step_ov = jax.jit(make_overlap_round_step(cfg, hcef_ov, topo, policy,
                                              gossip=True,
                                              cluster_levels=levels))
    with mesh:
        s_ref, _ = step_sync(state, batch, rho, theta, keys)
        o, _ = step_ov(OverlapState(fl=state, pending=state.params),
                       batch, rho, theta, keys)
    equal = all(
        bool(jnp.array_equal(a, b))
        for ra, rb in ((s_ref.params, o.fl.params), (s_ref.ef, o.fl.ef),
                       (o.fl.params, o.pending))
        for a, b in zip(jax.tree.leaves(ra), jax.tree.leaves(rb)))
    return {"ok": equal}


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               verbose: bool = True, sparse_gossip: bool = False,
               theta_spread: str = None, overlap: bool = False,
               wire_dtype: str = None):
    """``theta_spread``: comma-separated theta levels assigned round-robin
    to the clusters (e.g. "0.05,0.8") — lowers the train cell with the
    PER-CLUSTER static dispatch, plus an all-max baseline and a
    gossip=False (intra-only) program, and emits the
    ``cluster_gossip_bytes`` verdict: the heterogeneous program's gossip
    collective-permute bytes must beat the baseline and track the
    level-vector sum (DESIGN.md §Static-k).

    ``overlap``: additionally lowers the OVERLAPPED staleness=1 round
    (all clusters stale, static per-cluster dispatch — the traced-theta
    lax.switch would drag the permutes into the conditional) next to the
    synchronous gossip round, and emits the ``gossip_overlap`` verdict:
    the overlap program's gossip collective-permutes must carry no data
    dependence on the local-step loop while the synchronous program's all
    do (DESIGN.md §Overlap contract), plus an executed staleness=0
    bit-for-bit equivalence smoke."""
    bundle = get_config(arch)
    cfg = bundle.model
    hcef = bundle.hcef
    if sparse_gossip or theta_spread:
        hcef = dataclasses.replace(hcef, sparse_gossip=True)
    if wire_dtype:
        # wire value format only matters on the sparse gossip payload path
        hcef = dataclasses.replace(hcef, sparse_gossip=True,
                                   wire_dtype=wire_dtype)
    shapes = {s.name: s for s in bundle.shapes}
    shape = shapes[shape_name]
    if shape_name in bundle.skip_shapes:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": bundle.skip_reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    dpx = dp_axes(mesh)
    t0 = time.time()

    # very large models need weights sharded beyond the model axis when
    # serving (one 16-way shard per chip would blow HBM) — arctic-480b.
    model0 = get_model(cfg)
    pcount = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
        jax.eval_shape(lambda: model0.init(cfg, jax.random.PRNGKey(0)))))
    serve_extra = dpx if pcount * 2 / 16 > 12e9 else ()

    cluster_levels = extra_jits = overlap_jits = None
    if shape.kind == "train":
        topo = bundle.fl_multi if multi_pod else bundle.fl_single
        topo.validate(int(np.prod([mesh.shape[a] for a in dpx])))
        policy = make_train_policy(mesh, topo, dp_axes=dpx)
        state_abs = abstract_state(cfg, hcef, topo)
        state_sh = FLState(
            params=policy.param_shardings(state_abs.params, stacked=True),
            momentum=(policy.param_shardings(state_abs.momentum, stacked=True)
                      if state_abs.momentum is not None else None),
            ef=policy.param_shardings(state_abs.ef, stacked=True),
            round_idx=policy.replicated())
        batch_abs = input_specs(cfg, shape)
        batch_sh = _batch_shardings(policy, batch_abs)
        R = topo.num_devices
        rep = tuple(policy.replica_axes) or None
        ctl_sh = NamedSharding(mesh, P(rep))
        key_sh = NamedSharding(mesh, P(rep, None))
        rho_abs = jax.ShapeDtypeStruct((R,), jnp.float32)
        key_abs = jax.ShapeDtypeStruct((R, 2), jnp.uint32)

        def mk_jitted(gossip=True, levels=None):
            step = make_round_step(cfg, hcef, topo, policy, gossip=gossip,
                                   cluster_levels=levels)
            return jax.jit(step,
                           in_shardings=(state_sh, batch_sh, ctl_sh, ctl_sh,
                                         key_sh),
                           out_shardings=(state_sh, None),
                           donate_argnums=(0,))

        if theta_spread and multi_pod:
            # multi-axis replica dims collapse per-cluster levels to the
            # max (sparse_neighbor_exchange's conservative fallback), so
            # the byte-win verdict is single-pod only.
            print(f"NOTE {arch}/{shape_name}: --theta-spread skipped on "
                  f"the multi-pod mesh (per-cluster levels collapse to "
                  f"max over multi-axis replica dims)")
        elif theta_spread:
            spread = [float(t) for t in theta_spread.split(",")]
            C = topo.clusters
            cluster_levels = tuple(spread[i % len(spread)]
                                   for i in range(C))
            # extra programs for the byte-win verdict: all-max baseline
            # and the intra-only (gossip=False) level-independent floor.
            extra_jits = {
                "baseline": mk_jitted(levels=(max(cluster_levels),) * C),
                "intra": mk_jitted(gossip=False),
            }
        jitted = mk_jitted(levels=cluster_levels)
        args = (state_abs, batch_abs, rho_abs, rho_abs, key_abs)
        if overlap:
            # overlap verdict programs: staleness=1 all-stale vs the
            # synchronous gossip round, both sparse + static per-cluster
            # levels (a traced-theta switch would make every permute
            # conditional-dependent and defeat the taint analysis).
            C = topo.clusters
            grid = sorted(hcef.theta_levels)
            ov_levels = cluster_levels or tuple(
                grid[i % len(grid)] for i in range(C))
            hcef_sp = dataclasses.replace(hcef, sparse_gossip=True)
            hcef_ov = dataclasses.replace(hcef_sp, overlap=True, staleness=1)
            ov_state_abs = OverlapState(fl=state_abs,
                                        pending=state_abs.params)
            ov_state_sh = OverlapState(fl=state_sh, pending=state_sh.params)
            overlap_jits = {
                "overlap": (jax.jit(
                    make_overlap_round_step(cfg, hcef_ov, topo, policy,
                                            gossip=True,
                                            cluster_levels=ov_levels),
                    in_shardings=(ov_state_sh, batch_sh, ctl_sh, ctl_sh,
                                  key_sh),
                    out_shardings=(ov_state_sh, None),
                    donate_argnums=(0,)),
                    (ov_state_abs, batch_abs, rho_abs, rho_abs, key_abs)),
                "sync": (jax.jit(
                    make_round_step(cfg, hcef_sp, topo, policy, gossip=True,
                                    cluster_levels=ov_levels),
                    in_shardings=(state_sh, batch_sh, ctl_sh, ctl_sh,
                                  key_sh),
                    out_shardings=(state_sh, None),
                    donate_argnums=(0,)), args),
            }
    elif shape.kind == "prefill":
        policy = make_serve_policy(mesh, dp_axes=dpx, kind="prefill",
                                   extra_fsdp=serve_extra)
        model = get_model(cfg)
        params_abs = jax.eval_shape(
            lambda: model.init(cfg, jax.random.PRNGKey(0)))
        params_sh = policy.param_shardings(params_abs, stacked=False)
        cache_abs = cache_specs(cfg, shape)
        cache_sh = _cache_shardings(policy, cache_abs)
        batch_abs = input_specs(cfg, shape)
        batch_sh = _batch_shardings(policy, batch_abs)
        step = make_prefill_step(cfg, policy)
        jitted = jax.jit(step, in_shardings=(params_sh, batch_sh, cache_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,))
        args = (params_abs, batch_abs, cache_abs)
    else:  # decode
        policy = make_serve_policy(mesh, dp_axes=dpx, kind="decode",
                                   extra_fsdp=serve_extra)
        model = get_model(cfg)
        params_abs = jax.eval_shape(
            lambda: model.init(cfg, jax.random.PRNGKey(0)))
        params_sh = policy.param_shardings(params_abs, stacked=False)
        cache_abs = cache_specs(cfg, shape)
        cache_sh = _cache_shardings(policy, cache_abs)
        tok_abs = input_specs(cfg, shape)["tokens"]
        tok_sh = _batch_shardings(policy, {"tokens": tok_abs})["tokens"]
        step = make_serve_step(cfg, policy)
        jitted = jax.jit(step, in_shardings=(params_sh, cache_sh, tok_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))
        args = (params_abs, cache_abs, tok_abs)

    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        extra_hlo = {}
        if extra_jits:
            for name, j in extra_jits.items():
                extra_hlo[name] = j.lower(*args).compile().as_text()
        overlap_hlo = {}
        if overlap_jits:
            for name, (j, a) in overlap_jits.items():
                overlap_hlo[name] = j.lower(*a).compile().as_text()

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    hstats = analyze_hlo(hlo)
    n_chips = int(np.prod(list(mesh.shape.values())))

    agcheck = gossipcheck = clustercheck = overlapcheck = ovsmoke = None
    if shape.kind == "train":
        # the fused compress+mix path must never re-materialize a
        # model-sharded leaf: no single all-gather the size of a full leaf.
        agcheck = check_no_full_leaf_allgather(
            hlo, sharded_leaf_bytes(state_abs.params, state_sh.params))
        if not agcheck["ok"]:
            print(f"WARNING {arch}/{shape_name}: all-gather of "
                  f"{agcheck['allgather_max_bytes']:.3e} B >= half the "
                  f"largest model-sharded leaf "
                  f"{agcheck['largest_sharded_leaf_bytes']:.3e} B")
        dense_itemsize = jnp.zeros((), cfg.param_dtype).dtype.itemsize
        wire_kw = dict(wire_dtype=hcef.wire_dtype,
                       wire_block=hcef.wire_block,
                       dense_itemsize=dense_itemsize)
        if cluster_levels is not None:
            # per-cluster static-k contract: the heterogeneous program's
            # gossip permute bytes must beat the all-max baseline and
            # track the level-vector sum.
            clustercheck = check_cluster_gossip_bytes(
                hlo, extra_hlo["baseline"], cluster_levels,
                intra_hlo=extra_hlo["intra"], **wire_kw)
            if not clustercheck["ok"]:
                print(f"WARNING {arch}/{shape_name}: per-cluster gossip "
                      f"bytes do not track the level vector: "
                      f"{clustercheck}")
        elif hcef.sparse_gossip:
            # the static-k lowering contract: the lax.switch branches'
            # collective-permute payloads must scale with the theta level
            # (capped by the dense-wire fallback).
            gossipcheck = check_gossip_bytes_scale_with_theta(
                hlo, hcef.theta_levels, **wire_kw)
            if not gossipcheck["ok"]:
                print(f"WARNING {arch}/{shape_name}: gossip wire bytes do "
                      f"not scale with theta: {gossipcheck['switches']}")
        if overlap_hlo:
            overlapcheck = check_gossip_overlap(overlap_hlo["overlap"],
                                                sync_hlo=overlap_hlo["sync"])
            if not overlapcheck["ok"]:
                print(f"WARNING {arch}/{shape_name}: gossip permutes are "
                      f"NOT off the local-step critical path: "
                      f"{overlapcheck}")
            ovsmoke = overlap_equivalence_smoke()
            if not ovsmoke["ok"]:
                print(f"WARNING {arch}/{shape_name}: staleness=0 overlapped "
                      f"round is not bit-for-bit the synchronous round")

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "wire_dtype": hcef.wire_dtype,
        "status": "ok", "kind": shape.kind, "param_count": pcount,
        "n_chips": n_chips,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_est_bytes": int(ma.argument_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  + ma.output_size_in_bytes
                                  - ma.alias_size_in_bytes),
        },
        "cost_raw": {k: ca.get(k) for k in ("flops", "bytes accessed")
                     if k in ca},
        "hlo": {k: float(v) for k, v in hstats.items()},
        "hlo_chars": len(hlo),
    }
    if agcheck is not None:
        result["no_full_leaf_allgather"] = agcheck
    if gossipcheck is not None:
        result["gossip_bytes_scale_with_theta"] = gossipcheck
    if overlapcheck is not None:
        result["gossip_overlap"] = overlapcheck
        result["overlap_equivalence"] = ovsmoke
        if verbose:
            print(f"  gossip overlap: "
                  f"free={overlapcheck['free_permute_bytes']:.3e} / "
                  f"{overlapcheck['total_permute_bytes']:.3e} B "
                  f"({100 * overlapcheck['free_fraction']:.1f}% off the "
                  f"local-step path; sync free="
                  f"{overlapcheck['sync_free_permute_bytes']:.3e}) "
                  f"ok={overlapcheck['ok']} "
                  f"staleness0_bitwise={ovsmoke['ok']}")
    if clustercheck is not None:
        result["cluster_gossip_bytes"] = clustercheck
        if verbose:
            print(f"  cluster gossip: levels={clustercheck['cluster_levels']}"
                  f" share={clustercheck['share']:.3f} "
                  f"bytes={clustercheck['permute_bytes']:.3e} vs baseline "
                  f"{clustercheck['baseline_permute_bytes']:.3e} "
                  f"(win {100 * clustercheck['byte_win']:.1f}%) "
                  f"ok={clustercheck['ok']}")
    if verbose:
        print(f"== {arch} x {shape_name} x "
              f"{'multi' if multi_pod else 'single'} ==")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s; "
              f"chips={n_chips}")
        print(f"  memory/device: args={ma.argument_size_in_bytes/2**30:.2f}"
              f"GiB temp={ma.temp_size_in_bytes/2**30:.2f}GiB "
              f"out={ma.output_size_in_bytes/2**30:.2f}GiB")
        print(f"  hlo: flops={hstats['flops']:.3e} "
              f"dot_bytes={hstats['dot_bytes']:.3e} "
              f"coll_bytes={hstats['coll_total']:.3e}")
        for k, v in sorted(hstats.items()):
            if k.startswith("coll:"):
                print(f"    {k} = {v:.3e}")
    return result


def run_cell_subprocess(arch, shape, mesh_kind, out_dir: Path,
                        sparse_gossip: bool = False,
                        theta_spread: str = None,
                        overlap: bool = False,
                        wire_dtype: str = None) -> dict:
    """Run one cell in an isolated subprocess (memory isolation) + cache."""
    tag = ".sparse" if sparse_gossip else ""
    if theta_spread:
        tag += ".spread" + theta_spread.replace(",", "_")
    if overlap:
        tag += ".overlap"
    if wire_dtype:
        tag += f".wd{wire_dtype}"
    out = out_dir / f"{arch}.{shape}.{mesh_kind}{tag}.json"
    if out.exists():
        return json.loads(out.read_text())
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh_kind, "--out", str(out)]
    if sparse_gossip:
        cmd.append("--sparse-gossip")
    if theta_spread:
        cmd += ["--theta-spread", theta_spread]
    if overlap:
        cmd.append("--overlap")
    if wire_dtype:
        cmd += ["--wire-dtype", wire_dtype]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[2])
    t0 = time.time()
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=3600)
    if r.returncode != 0:
        res = {"arch": arch, "shape": shape, "mesh": mesh_kind,
               "status": "error", "stderr": r.stderr[-4000:],
               "wall_s": time.time() - t0}
        out.write_text(json.dumps(res, indent=1))
        return res
    return json.loads(out.read_text())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sparse-gossip", action="store_true",
                    help="lower train cells with HCEFConfig.sparse_gossip "
                         "and emit the gossip_bytes_scale_with_theta verdict")
    ap.add_argument("--theta-spread", default=None,
                    help="comma-separated theta levels assigned round-robin "
                         "to clusters (e.g. 0.05,0.8): lowers the "
                         "PER-CLUSTER dispatch plus an all-max baseline "
                         "and emits the cluster_gossip_bytes byte-win "
                         "verdict")
    ap.add_argument("--overlap", action="store_true",
                    help="lower train cells with the overlapped staleness=1 "
                         "round engine next to the synchronous one and emit "
                         "the gossip_overlap verdict (permutes off the "
                         "local-step critical path) plus a staleness=0 "
                         "bit-for-bit equivalence smoke")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["f32", "bf16", "int8", "int4", "fp8"],
                    help="wire value encoding for the sparse gossip "
                         "payload (implies --sparse-gossip); the "
                         "gossip_bytes_scale_with_theta verdict sizes the "
                         "expected permute bytes from the v2 wire format")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    if args.all:
        ok = err = skip = 0
        for arch in ARCH_IDS:
            bundle = get_config(arch)
            for s in bundle.shapes:
                for mesh_kind in ("single", "multi"):
                    res = run_cell_subprocess(
                        arch, s.name, mesh_kind, RESULTS_DIR,
                        sparse_gossip=args.sparse_gossip,
                        theta_spread=args.theta_spread,
                        overlap=args.overlap,
                        wire_dtype=args.wire_dtype)
                    tag = res["status"]
                    ok += tag == "ok"
                    err += tag == "error"
                    skip += tag == "skipped"
                    print(f"{arch:24s} {s.name:12s} {mesh_kind:6s} -> {tag}",
                          flush=True)
        print(f"TOTAL ok={ok} err={err} skipped={skip}")
        sys.exit(1 if err else 0)

    res = lower_cell(args.arch, args.shape, args.mesh == "multi",
                     sparse_gossip=args.sparse_gossip,
                     theta_spread=args.theta_spread,
                     overlap=args.overlap,
                     wire_dtype=args.wire_dtype)
    if args.out:
        Path(args.out).write_text(json.dumps(res, indent=1))
    # gate CI on the HLO verdicts: a lowered-but-wrong wire path must fail
    # the cell, not just print a warning.
    bad = [k for k in ("no_full_leaf_allgather",
                       "gossip_bytes_scale_with_theta",
                       "cluster_gossip_bytes",
                       "gossip_overlap",
                       "overlap_equivalence")
           if isinstance(res.get(k), dict) and not res[k]["ok"]]
    if bad:
        print(f"VERDICT FAILED: {bad}")
        sys.exit(1)


if __name__ == "__main__":
    main()
