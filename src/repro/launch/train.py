"""Federated training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm_135m \
        --mesh host --smoke --rounds 8 --controller hcef

--mesh host   : single-device run (R=4 stacked replicas on one device, no
                mesh); reduced config unless --full.
--mesh local  : mesh over this host's devices, (n, 1) as ("data", "model"),
                R=4 as 2 clusters x 2 tiling it (one replica per chip on a
                four-chip host).
--mesh single : 16x16 production mesh (on TPU hardware; on CPU this requires
                xla_force_host_platform_device_count and is what
                launch/dryrun.py exercises AOT).
Ties together: mesh + policy + HCEF round steps + online controller +
heterogeneity/budget accounting + checkpointing.  ``run`` returns the
losses, timings and compiled programs (chip_smoke.py drives it).
"""
from __future__ import annotations

import argparse
import time
from collections import OrderedDict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, smoke_model
from repro.core.compression import cluster_levels_from_theta, quantize_theta
from repro.core.controller import BudgetState, population_energy_caps
from repro.core.round import (client_template, init_overlap_state,
                              init_state, make_overlap_round_step,
                              make_round_step, merge_state, split_state)
from repro.data.synthetic import client_token_shard, synthetic_tokens
from repro.dist.policies import make_train_policy
from repro.fl.baselines import make_controller
from repro.fl.cost_model import (decide_stale_clusters, overlap_round_time,
                                 per_device_energy, round_energy, round_time)
from repro.fl.heterogeneity import HeterogeneityModel
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (dp_axes, make_local_mesh,
                               make_production_mesh)
from repro.runtime.checkpoint import save_pytree
from repro.runtime.chaos import ChaosConfig, FaultPlan, controls_on_live
from repro.runtime.elastic import cohort_swap
from repro.runtime.population import PopulationStore


def make_topology(mesh_kind: str, bundle):
    """(mesh, topo, policy) for a --mesh choice."""
    from repro.configs.base import FLTopology
    if mesh_kind == "host":
        return None, FLTopology(clusters=2, devices_per_cluster=2), None
    if mesh_kind == "local":
        mesh = make_local_mesh()
        topo = FLTopology(clusters=2, devices_per_cluster=2)
    else:
        mesh = make_production_mesh(multi_pod=mesh_kind == "multi")
        topo = bundle.fl_multi if mesh_kind == "multi" else bundle.fl_single
    return mesh, topo, make_train_policy(mesh, topo, dp_axes=dp_axes(mesh))


def init_placed_state(cfg, hcef, topo, policy, seed: int = 0):
    """Round-0 state, built in place: with a policy every leaf is created
    directly in its policy sharding (nothing is built whole on one chip
    first)."""
    init = init_overlap_state if hcef.overlap else init_state
    make = lambda: init(cfg, hcef, topo, jax.random.PRNGKey(seed))
    if policy is None:
        return jax.jit(make)()
    shd = policy.param_shardings(jax.eval_shape(make), stacked=True)
    return jax.jit(make, out_shardings=shd)()


def compile_step(step, args):
    """AOT-compile a round step for ``args`` with the state (argument 0)
    donated.  Returns (compiled, seconds)."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("hcef.compile"):
        compiled = jax.jit(step, donate_argnums=0).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _rounds(n: int):
    """range(n), each round's body inside the profiler step ``hcef.round``
    (``step_num`` = the round), so a trace's step view shows the rounds."""
    for rnd in range(n):
        with jax.profiler.StepTraceAnnotation("hcef.round", step_num=rnd):
            yield rnd


def _gb(n):
    return f"{n / 2**30:.2f}GiB"


def main(argv=None):
    run(argv)


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m", choices=ARCH_IDS)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "local", "single", "multi"])
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--controller", default="hcef",
                    choices=["hcef", "cef", "cef_f", "cef_c", "mll_sgd"])
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--sparse-gossip", action="store_true",
                    help="route gossip through the theta-scaled wire path")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["f32", "bf16", "int8", "int4", "fp8"])
    ap.add_argument("--wire-ef", action="store_true",
                    help="CHOCO-style wire error feedback: gossip payloads "
                         "carry the difference to a shared neighbor "
                         "estimate (requires --sparse-gossip and a mesh)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped round engine (DESIGN.md §Overlap): "
                         "hide gossip behind local compute with "
                         "bounded-staleness mixing")
    ap.add_argument("--staleness", type=int, default=1, choices=[0, 1],
                    help="staleness bound for --overlap: 0 reproduces the "
                         "synchronous engine bit-for-bit, 1 lets behind "
                         "clusters ship their stale-by-1 model")
    ap.add_argument("--stale-quantile", type=float, default=0.9,
                    help="straggler-deadline quantile deciding which "
                         "clusters run stale on gossip rounds")
    ap.add_argument("--population", type=int, default=0,
                    help="logical clients behind the R-slot mesh (DESIGN.md "
                         "§Cohort contract): each round draws a cohort of R "
                         "from N clients whose per-client state pages "
                         "through a PopulationStore; 0 disables, "
                         "population == R pages without sampling (bitwise "
                         "identical to 0)")
    ap.add_argument("--cohort-seed", type=int, default=0,
                    help="seed for the per-round cohort draw")
    ap.add_argument("--store-root", default="",
                    help="page directory for the population store (default: "
                         "a temp dir; small populations stay resident)")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded fault injection: device dropout, deadline "
                         "misses, cluster partitions, coordinator churn")
    ap.add_argument("--chaos-dropout", type=float, default=0.2)
    ap.add_argument("--chaos-partition", type=float, default=0.1)
    ap.add_argument("--chaos-coord-fail", type=float, default=0.2)
    ap.add_argument("--chaos-seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    bundle = get_config(args.arch)
    cfg = smoke_model(bundle.model) if args.smoke else bundle.model
    hcef = bundle.hcef
    if args.sparse_gossip or args.wire_dtype or args.overlap or args.wire_ef:
        import dataclasses
        hcef = dataclasses.replace(
            hcef, sparse_gossip=hcef.sparse_gossip or args.sparse_gossip,
            wire_dtype=args.wire_dtype or hcef.wire_dtype,
            wire_ef=hcef.wire_ef or args.wire_ef,
            overlap=args.overlap,
            staleness=args.staleness if args.overlap else 0)

    mesh, topo, policy = make_topology(args.mesh, bundle)

    R = topo.num_devices
    cluster_of = np.repeat(np.arange(topo.clusters),
                           topo.devices_per_cluster)
    state = init_placed_state(cfg, hcef, topo, policy)
    # Per-assignment jit cache (DESIGN.md §Static-k): gossip steps are
    # keyed by the static per-cluster level assignment so each distinct
    # (cluster -> level) vector lowers ONE program with sender-sized
    # payloads.  LRU-bounded: a drifting heterogeneity model could
    # otherwise visit up to |levels|^C assignments and pin every compiled
    # executable in host memory (evicting recompiles — the price of a
    # genuinely new assignment, not of revisiting a recent one).  The
    # overlapped engine adds the static stale-cluster set to the key
    # (DESIGN.md §Overlap) — one program per (levels, stale) assignment.
    # Programs are compiled ahead of time for the call's arguments (the
    # state donated), so each new one's memory analysis is printed before
    # it first runs; the key also holds the argument shardings and the
    # call's arity (chaos rounds pass the fault masks).
    step_cache: OrderedDict = OrderedDict()
    STEP_CACHE_MAX = 32

    def get_step(call_args, gossip_round: bool, cluster_levels=None,
                 stale_clusters=None):
        key = (gossip_round, cluster_levels, stale_clusters,
               tuple(x.sharding for x in jax.tree.leaves(call_args)))
        secs = 0.0
        if key not in step_cache:
            if hcef.overlap:
                step = make_overlap_round_step(
                    cfg, hcef, topo, policy, gossip=gossip_round,
                    cluster_levels=cluster_levels,
                    stale_clusters=stale_clusters)
            else:
                step = make_round_step(
                    cfg, hcef, topo, policy, gossip=gossip_round,
                    cluster_levels=cluster_levels)
            compiled, secs = compile_step(step, call_args)
            ma = compiled.memory_analysis()
            print(f"compiled step gossip={gossip_round} in {secs:.1f}s: "
                  f"args={_gb(ma.argument_size_in_bytes)} "
                  f"out={_gb(ma.output_size_in_bytes)} "
                  f"temp={_gb(ma.temp_size_in_bytes)} "
                  f"alias={_gb(ma.alias_size_in_bytes)}", flush=True)
            step_cache[key] = compiled
            if len(step_cache) > STEP_CACHE_MAX:
                step_cache.popitem(last=False)
        step_cache.move_to_end(key)
        return step_cache[key], secs

    controller = make_controller(args.controller, hcef.tau)
    fl0 = state.fl if hcef.overlap else state
    n_params = sum(int(x.size) for x in jax.tree.leaves(fl0.params)) // R
    if args.population and args.population < R:
        raise SystemExit(f"--population {args.population} smaller than the "
                         f"mesh cohort R={R}")
    if args.population > R and hcef.wire_ef:
        # CHOCO wire-EF estimates are SHARED between gossip neighbors; a
        # rotating cohort would desync them (the neighbor that holds the
        # other copy left the mesh).  Paged fine at population == R.
        raise SystemExit("--wire-ef is incompatible with cohort sampling "
                         "(--population > R): neighbor estimates desync "
                         "under churn")
    het = HeterogeneityModel(num_devices=R, model_bits=n_params * 16,
                             population=args.population)
    budget = BudgetState(
        time_budget=hcef.time_budget or np.inf,
        energy_budget=hcef.energy_budget or np.inf,
        phi=max(args.rounds // hcef.q, 1), q=hcef.q,
        backhaul_time=het.backhaul_time(),
        population=args.population, cohort=R if args.population else 0)
    pop_store = None
    cohort_ids = None
    if args.population:
        if args.store_root:
            store_root = Path(args.store_root)
        else:
            import tempfile
            store_root = Path(tempfile.mkdtemp(prefix="pop_store_"))
        pop_store = PopulationStore(args.population, client_template(fl0),
                                    root=store_root, resident_max=4 * R)

    plan = None
    if args.chaos:
        plan = FaultPlan(ChaosConfig(
            seed=args.chaos_seed, dropout_prob=args.chaos_dropout,
            partition_prob=args.chaos_partition,
            coordinator_fail_prob=args.chaos_coord_fail),
            num_devices=R, num_clusters=topo.clusters)

    n_seq = 32
    if args.population:
        # per-client shards generated by id (data/synthetic): nothing
        # O(population) in memory; LRU over recent cohorts.  With
        # population == R the shards ARE synthetic_tokens' rows, so the
        # batch stream below is bit-identical to the legacy corpus.
        from functools import lru_cache

        @lru_cache(maxsize=4 * R)
        def _shard(cid: int) -> np.ndarray:
            return client_token_shard(cfg.vocab_size, n_seq=n_seq,
                                      seq_len=args.seq + 1, client_id=cid,
                                      beta=0.5)
    else:
        corpus = synthetic_tokens(cfg.vocab_size, n_seq=n_seq,
                                  seq_len=args.seq + 1, n_devices=R,
                                  beta=0.5)
    rng = np.random.default_rng(0)
    b_per_dev = hcef.tau * 2

    print(f"arch={args.arch} mesh={args.mesh} R={R} controller="
          f"{args.controller} params/replica={n_params:,}")
    result = {"losses": [], "step_s": [], "compile_s": [], "gossip": [],
              "programs": step_cache}
    ctx = mesh or _null()
    with ctx:
        for rnd in _rounds(args.rounds):
            t0 = time.time()
            if pop_store is not None:
                # rotate this round's cohort into the mesh: scatter the
                # previous cohort's client half (EF, momentum, wire-EF)
                # back to the store, gather the new cohort's into the same
                # slots (elastic.cohort_swap — EF aggregate conserved
                # exactly; at population == R this is an identity
                # round-trip).
                new_ids = (het.sample_cohort(rnd, R, seed=args.cohort_seed)
                           if args.population > R
                           else np.arange(R, dtype=np.int64))
                fl = state.fl if hcef.overlap else state
                mesh_half, client_half = split_state(fl)
                if cohort_ids is None:
                    # round 0: mesh slots hold exact zeros — every
                    # client's implicit initial state; nothing to scatter.
                    client_half = pop_store.gather(new_ids)
                else:
                    client_half = cohort_swap(
                        jax.device_get(client_half), cohort_ids, new_ids,
                        pop_store)
                fl = merge_state(mesh_half,
                                 jax.tree.map(jnp.asarray, client_half))
                state = (state._replace(fl=fl) if hcef.overlap else fl)
                cohort_ids = new_ids
            reports = het.sample_round(rnd, ids=cohort_ids)
            if pop_store is not None and args.population > R:
                import dataclasses as _dc
                reports = _dc.replace(
                    reports, energy_cap=population_energy_caps(
                        budget,
                        pop_store.rounds_participated[cohort_ids],
                        pop_store.energy_spent[cohort_ids]))
            if plan is not None:
                alive0 = plan.sample_available(rnd)
                rho, theta = controls_on_live(controller, reports, budget,
                                              alive0)
            else:
                rho, theta = controller.controls(reports, budget)
            gossip_round = (rnd + 1) % hcef.q == 0
            cluster_levels = None
            if hcef.sparse_gossip:
                # static-k contract (DESIGN.md §Static-k): the wire only
                # ships grid levels, so the theta the devices run must be
                # a level — round UP, conservative; gossip rounds on a
                # mesh also get the per-cluster assignment (sender-sized
                # payloads, one cached program per distinct assignment).
                theta = quantize_theta(theta, hcef.theta_levels)
                if gossip_round and policy is not None:
                    cluster_levels = cluster_levels_from_theta(
                        theta, hcef.theta_levels, cluster_of)
            with jax.profiler.TraceAnnotation("hcef.feed"):
                idx = rng.integers(0, n_seq, (R, b_per_dev))
                if pop_store is not None:
                    batch = {"tokens": jnp.asarray(np.concatenate(
                        [_shard(int(cohort_ids[d]))[idx[d]]
                         for d in range(R)]))}
                else:
                    batch = {"tokens": jnp.asarray(np.concatenate(
                        [corpus[d, idx[d]] for d in range(R)]))}
                keys = jax.random.split(jax.random.PRNGKey(1000 + rnd), R)
            # dense_bits=16: het's model_bits above is n_params * 16 (bf16).
            wire_kw = (dict(wire_dtype=hcef.wire_dtype,
                            wire_block=hcef.wire_block, dense_bits=16)
                       if hcef.sparse_gossip else {})
            stale_cl = None
            if hcef.overlap and hcef.staleness and gossip_round:
                # who runs stale this round: clusters whose backhaul gossip
                # does not fit in the straggler-deadline compute window.
                stale_cl = decide_stale_clusters(
                    rho, theta, reports.mu, reports.nu, hcef.tau,
                    cluster_of, backhaul=het.backhaul_time(),
                    alive=alive0 if plan is not None else None,
                    quantile=args.stale_quantile, **wire_kw)
            faults = None
            alive = conn = None
            if plan is not None:
                from repro.fl.cost_model import per_device_time
                faults = plan.step(
                    rnd, gossip_round=gossip_round,
                    per_device_time=per_device_time(
                        rho, theta, reports.mu, reports.nu, hcef.tau,
                        **wire_kw),
                    alive=alive0)
                alive, conn = faults.alive, faults.cluster_conn
            degraded = faults is not None and (not alive.all()
                                               or not conn.all())
            call_args = (state, batch, jnp.asarray(rho, jnp.float32),
                         jnp.asarray(theta, jnp.float32), keys)
            # fault-free rounds take the EXACT unmasked trace (bitwise
            # contract: chaos at zero faults == no chaos).
            if degraded:
                from repro.dist.collectives import participation_weights
                aw = participation_weights(
                    alive, clusters=topo.clusters,
                    dev=topo.devices_per_cluster)
                call_args += (jnp.asarray(alive, jnp.float32),
                              jnp.asarray(aw, jnp.float32),
                              jnp.asarray(conn, jnp.float32))
            fn, csecs = get_step(call_args, gossip_round, cluster_levels,
                                 stale_cl)
            del state  # donated to the step
            t_step = time.perf_counter()
            state, m = fn(*call_args)
            del call_args
            jax.block_until_ready(state)
            step_s = time.perf_counter() - t_step
            loss = float(m["loss"].mean())
            result["losses"].append(loss)
            result["step_s"].append(step_s)
            result["compile_s"].append(csecs)
            result["gossip"].append(gossip_round)
            with jax.profiler.TraceAnnotation("hcef.budget"):
                if stale_cl:
                    # overlapped accounting: a stale cluster's gossip transfer
                    # hides behind its tau local steps — max, not sum.
                    t, _ = overlap_round_time(
                        rho, theta, reports.mu, reports.nu, hcef.tau,
                        cluster_of, gossip=gossip_round,
                        backhaul=het.backhaul_time(), alive=alive, conn=conn,
                        stale_clusters=stale_cl, **wire_kw)
                else:
                    t, _ = round_time(rho, theta, reports.mu, reports.nu,
                                      hcef.tau, cluster_of,
                                      gossip=gossip_round,
                                      backhaul=het.backhaul_time(),
                                      alive=alive, conn=conn, **wire_kw)
                e = round_energy(rho, theta, reports.mu, reports.nu,
                                 reports.alpha, reports.p, hcef.tau,
                                 alive=alive, **wire_kw)
                if pop_store is not None:
                    pop_store.record_round(
                        cohort_ids, rnd,
                        energy=per_device_energy(
                            rho, theta, reports.mu, reports.nu, reports.alpha,
                            reports.p, hcef.tau, alive=alive, **wire_kw))
                budget.time_spent_this += t
                budget.energy_spent_this += e
                budget.r += 1
                if gossip_round:
                    budget.time_spent_prev += budget.time_spent_this
                    budget.energy_spent_prev += budget.energy_spent_this
                    budget.time_spent_this = budget.energy_spent_this = 0.0
                    budget.r = 0
                    budget.l += 1
            chaos_str = ""
            if pop_store is not None and args.population > R:
                chaos_str += (f" cohort[{int(cohort_ids.min())}.."
                              f"{int(cohort_ids.max())}] "
                              f"res={pop_store.resident_count}")
            if stale_cl is not None:
                chaos_str += f" stale={len(stale_cl)}/{topo.clusters}"
            if faults is not None:
                chaos_str = (f" part={faults.participation:.2f} "
                             f"coord={faults.coordinator}"
                             + (f" cut={int((~faults.cluster_conn).sum())}"
                                if not faults.cluster_conn.all() else ""))
            print(f"round {rnd:3d} loss={loss:7.4f} "
                  f"rho={np.mean(rho):.2f} theta={np.mean(theta):.2f} "
                  f"sim_t={budget.time_spent_prev + budget.time_spent_this:9.0f}s "
                  f"wall={time.time()-t0:5.1f}s step={step_s:.3f}s"
                  + chaos_str, flush=True)
            if args.ckpt_dir:
                fl = state.fl if hcef.overlap else state
                meta = {"round": rnd}
                if pop_store is not None:
                    meta["cohort_ids"] = [int(c) for c in cohort_ids]
                    pop_store.save(Path(args.ckpt_dir)
                                   / f"ckpt_{rnd:06d}.pop.npz")
                save_pytree(Path(args.ckpt_dir) / f"ckpt_{rnd:06d}.npz",
                            fl._asdict(), meta=meta)
    result["state"] = state
    return result


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
