"""Federated rounds: the HCEF round step driven by its controller on the
host path (every client's replica stacked on one chip), on weights and
non-IID tokens made from ``--seed``.  The loop is the benchmark's own
copy of ``repro.launch.train``'s round loop, cut to what the window and
the check need: the controller's solve, the feed, the compiled round
program and the loss read back.  (``train.run`` fixes its seeds and runs
a count of rounds, not a window.)

Set-up builds the state and the compiled intra and gossip round programs,
then runs the first ``check_rounds`` rounds through the same loop the
window uses; their losses, the momentum after round 1 and the parameters'
change after the last of them are the program's readings for ``correct``.
The same state then goes on into the window, which runs whole cycles of
``q`` rounds until ``--seconds`` have passed.  Once the window is closed
and the program's state is freed, the plain reference replays the checked
rounds from the same seed and the gaps are judged against the cell's
limits.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
import zlib
from types import SimpleNamespace
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, reference, weights
from harness.program import model_config
from harness.device import memory_peak_bytes

FAULTS = ("unchanged", "half_batch")


def hcef_config(bundle_hcef, t: dict):
    return dataclasses.replace(
        bundle_hcef, tau=t["tau"], q=t["q"], eta=t["eta"],
        momentum=t["momentum"], block_size=t["block_size"],
        theta_min=t["theta_min"], controller=t["controller"],
        time_budget=t.get("time_budget_s"),
        energy_budget=t.get("energy_budget_j"))


@jax.jit
def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]


def leaf_norms(tree) -> Dict[str, float]:
    """{leaf path: l2 norm over the whole (stacked) leaf}.  A client's
    tree and the stacked tree of all clients have the same paths."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    vals = _norms([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, vals)}


SAMPLE = 65536  # coordinates per leaf for the gradient's relative error


def _sample_index(name: str, n: int) -> np.ndarray:
    seed = zlib.crc32(name.encode())
    return np.random.default_rng(seed).integers(0, n, SAMPLE).astype(
        np.int32)


@jax.jit
def _gather(xs, idxs):
    return [x.reshape(x.shape[0], -1)[:, i].astype(jnp.float32)
            for x, i in zip(xs, idxs)]


def leaf_samples(tree, clients: int = 0) -> Dict[str, np.ndarray]:
    """{leaf path: (clients, SAMPLE) f32 values at fixed coordinates of
    each client's leaf}; ``tree`` is stacked over clients, or one
    client's (``clients=0``)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    xs = [x if clients else x[None] for _, x in flat]
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    idxs = [jnp.asarray(_sample_index(n, int(np.prod(x.shape[1:]))))
            for n, x in zip(names, xs)]
    return {n: np.asarray(v) for n, v in zip(names, _gather(xs, idxs))}


class Federation:
    """One cell's program: state, compiled round programs, controller and
    feed, and the round loop that drives them."""

    def __init__(self, cell, seed: int, fault: Optional[str] = None):
        from repro.configs import get_config
        from repro.configs.base import FLTopology
        from repro.core.controller import BudgetState
        from repro.core.round import FLState, init_state
        from repro.fl.baselines import make_controller
        from repro.fl.heterogeneity import HeterogeneityModel
        from repro.models.registry import get_model

        if fault is not None and fault not in FAULTS:
            raise ValueError(f"fault {fault!r} not in {FAULTS}")
        t, c = cell.traffic, cell.config
        self.t, self.c, self.seed, self.fault = t, c, seed, fault
        bundle = get_config(c["repo_config"])
        self.cfg = model_config(c)
        self.hcef = hcef_config(bundle.hcef, t)
        self.topo = FLTopology(clusters=t["clusters"],
                               devices_per_cluster=t["devices_per_cluster"])
        self.R = R = self.topo.num_devices
        self.rows = t["tau"] * t["seqs_per_step"]
        self.tokens_per_round = R * self.rows * t["seq_len"]

        # weights from the seed, in the program's layout, made on the
        # device in one jitted call; the FL state around them likewise.
        model = get_model(self.cfg)
        self.abstract_params = jax.eval_shape(
            lambda: model.init(self.cfg, jax.random.PRNGKey(0)))
        self.param_key = weights.key_from_seed(seed, 0)
        self.make_params = jax.jit(weights.param_maker(
            self.abstract_params, c["initializer_range"]))
        abstract_state = jax.eval_shape(lambda: init_state(
            self.cfg, self.hcef, self.topo, jax.random.PRNGKey(0)))

        def make_state(key):
            p = weights.param_maker(self.abstract_params,
                                    c["initializer_range"])(key)
            stack = lambda x: jnp.broadcast_to(x[None], (R,) + x.shape)
            zeros = lambda t_: jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), t_)
            return FLState(params=jax.tree.map(stack, p),
                           momentum=zeros(abstract_state.momentum),
                           ef=zeros(abstract_state.ef),
                           round_idx=jnp.zeros((), jnp.int32),
                           wire_ef=zeros(abstract_state.wire_ef))

        self.state = jax.jit(make_state)(self.param_key)

        # traffic: per-client non-IID token mixtures, one batch per round
        self.data_key = weights.key_from_seed(seed, 1)
        self.step_key = weights.key_from_seed(seed, 2)
        self.logmix = jnp.asarray(weights.noniid_mixtures(
            seed, R, t["vocab_bands"], t["noniid_beta"]))
        self.batcher = jax.jit(weights.token_batcher(
            c["vocab_size"], t["vocab_bands"], R, self.rows,
            t["seq_len"] + 1))

        n_params = sum(int(np.prod(x.shape))
                       for x in jax.tree.leaves(self.abstract_params))
        self.controller = make_controller(t["controller"], self.hcef.tau,
                                          theta_min=self.hcef.theta_min)
        self.het = HeterogeneityModel(num_devices=R, seed=seed,
                                      model_bits=n_params * 16)
        # a window runs no fixed number of rounds: the budgets are spread
        # over a campaign far longer than any run (phi global rounds); the
        # controller's solve reads them, and no spending is booked, since
        # the traffic's budgets already put every client at its expected
        # theta (checked each round)
        self.budget = BudgetState(
            time_budget=self.hcef.time_budget or np.inf,
            energy_budget=self.hcef.energy_budget or np.inf,
            phi=1_000_000, q=self.hcef.q,
            backhaul_time=self.het.backhaul_time())
        self.programs: Dict = {}
        self.rnd = 0

    # -- the round loop ----------------------------------------------------
    def controls(self, rnd: int):
        reports = self.het.sample_round(rnd)
        rho, theta = self.controller.controls(reports, self.budget)
        gossip = (rnd - self.t["gossip_phase"]) % self.hcef.q == 0
        want = self.t.get("expect_theta")
        if want is not None and not np.allclose(theta, want):
            raise RuntimeError(f"round {rnd}: the controller chose theta "
                               f"{np.round(theta, 4).tolist()}, the traffic "
                               f"file expects {want}")
        return rho, theta, gossip

    def feed(self, rnd: int, rho, theta):
        batch = {"tokens": self.batcher(jax.random.fold_in(self.data_key,
                                                           rnd), self.logmix)}
        keys = jax.random.split(jax.random.fold_in(self.step_key, rnd),
                                self.R)
        return (self.state, batch, jnp.asarray(rho, jnp.float32),
                jnp.asarray(theta, jnp.float32), keys)

    def program(self, args, gossip: bool):
        from repro.core.round import make_round_step
        from repro.launch.train import compile_step
        key = gossip
        if key not in self.programs:
            step = make_round_step(self.cfg, self.hcef, self.topo,
                                   gossip=gossip)
            if self.fault == "unchanged":
                inner = jax.jit(step)

                def fn(state, *rest):
                    _, m = inner(state, *rest)
                    return state, m
            elif self.fault == "half_batch":
                inner = jax.jit(step, donate_argnums=0)
                R, tau, b = self.R, self.hcef.tau, self.t["seqs_per_step"]

                def fn(state, batch, *rest):
                    tok = batch["tokens"]
                    tok = tok.reshape(R, tau, b, -1)[:, :, :b // 2]
                    return inner(state, {"tokens": tok.reshape(
                        R * tau * (b // 2), -1)}, *rest)
            else:
                fn, _ = compile_step(step, args)
            self.programs[key] = fn
        return self.programs[key]

    def round(self, clock=time.perf_counter) -> dict:
        """One round of the loop; returns its record with host timings."""
        ann = jax.profiler.TraceAnnotation
        rnd = self.rnd
        t0 = clock()
        with ann("bench.controller"):
            rho, theta, gossip = self.controls(rnd)
        with ann("bench.feed"):
            args = self.feed(rnd, rho, theta)
        fn = self.program(args, gossip)
        self.state = None  # donated to the step
        t_dispatch = clock()
        with ann("bench.dispatch"):
            state, m = fn(*args)
        del args
        with ann("bench.wait"):
            jax.block_until_ready(state)
        t_ready = clock()
        self.state = state
        with ann("bench.loss"):
            loss = float(jnp.mean(m["loss"]))
        rec = dict(round=rnd, loss=loss, gossip=gossip,
                   theta=float(np.mean(theta)), rho=float(np.mean(rho)),
                   t_start=t0, t_dispatch=t_dispatch, t_ready=t_ready,
                   t_end=clock())
        self.rnd += 1
        return rec

    def batches(self, n: int):
        """The token batches of rounds 0..n-1, regenerated from the seed."""
        return [self.batcher(jax.random.fold_in(self.data_key, r),
                             self.logmix) for r in range(n)]

    def free(self):
        self.state = None
        self.programs.clear()
        gc.collect()


def program_readings(fed: Federation, n: int):
    """Run the first ``n`` rounds; the program's numbers for the check."""
    out = {"loss": [], "theta": [], "gossip": []}
    for r in range(n):
        rec = fed.round()
        out["loss"].append(rec["loss"])
        out["theta"].append(rec["theta"])
        out["gossip"].append(rec["gossip"])
        if r == 0:
            out["grad"] = leaf_norms(fed.state.momentum)
            out["grad_sample"] = leaf_samples(fed.state.momentum, fed.R)
    p0 = fed.make_params(fed.param_key)
    out["update"] = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b[None].astype(jnp.float32),
        fed.state.params, p0))
    del p0
    return out


def reference_readings(fed: Federation, n: int, thetas, gossips,
                       lowp=None, rows=None):
    """The plain reference's numbers for the same first ``n`` rounds, or
    with ``lowp`` the control's (matmul operands in that dtype), or with
    ``rows`` a fault's (each local step on only that many sequences)."""
    c, t = fed.c, fed.t
    R, dev = fed.R, t["devices_per_cluster"]
    p0 = fed.make_params(fed.param_key)
    ps = [p0] * R
    ms = [jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p0)] * R
    efs = [jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), p0)] * R
    local = reference.make_local_steps(c, t["tau"], t["eta"],
                                       t["momentum"], lowp=lowp, rows=rows)
    batches = fed.batches(n)
    out = {"loss": []}
    squash = lambda trees: {k: math.sqrt(sum(
        d[k] ** 2 for d in trees)) for k in trees[0]}
    for rnd in range(n):
        comp = reference.make_compress(thetas[rnd], t["block_size"])
        b = batches[rnd].reshape(R, t["tau"], t["seqs_per_step"], -1)
        comps, losses = [], []
        for r in range(R):
            pt, ms[r], ls = local(ps[r], ms[r], b[r])
            cr, efs[r] = comp(pt, ps[r], efs[r])
            comps.append(cr)
            losses.append(ls)
        ps = reference.aggregate(ps, comps, t["clusters"], dev,
                                 gossips[rnd])
        out["loss"].append(float(jnp.mean(jnp.stack(losses))))
        if rnd == 0:
            out["grad"] = squash([leaf_norms(m) for m in ms])
            per = [leaf_samples(m) for m in ms]
            out["grad_sample"] = {k: np.concatenate([d[k] for d in per])
                                  for k in per[0]}
    out["update"] = squash([leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))
        for p in ps])
    return out


def check_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    keep = compare.moved_leaves(ref["grad"])
    return {"loss_gap": compare.loss_gap(prog["loss"], ref["loss"]),
            "grad_gap": compare.leaf_norm_gap(prog["grad"], ref["grad"]),
            "update_gap": compare.leaf_norm_gap(prog["update"],
                                                ref["update"], keep),
            "grad_rel_err": compare.median_rel_err(prog["grad_sample"],
                                                   ref["grad_sample"])}


def _rounded(d):
    return {k: float(f"{v:.4g}") for k, v in d.items()}


def run(env) -> dict:
    """One run of an fl cell.  ``env``: cell, seed, seconds, trace,
    t_start, devices, fault (None outside the tests), trace_dir."""
    cell, t = env.cell, env.cell.traffic
    clock = time.perf_counter
    fed = Federation(cell, env.seed, fault=env.fault)
    n_check = t["check_rounds"]
    prog = program_readings(fed, n_check)
    # every program of the window has run once: the loop's first rounds
    # cover an intra and a gossip round (gossip_phase < check_rounds)
    if not (any(prog["gossip"]) and not all(prog["gossip"])):
        raise RuntimeError("the check rounds must run both programs")

    compiles = env.compile_counter()
    trace_cm = env.tracer() if env.trace else None
    window_rounds = t["trace_rounds"] if env.trace else None
    if trace_cm is not None:
        trace_cm.__enter__()
    t_w0 = clock()
    setup_s = t_w0 - env.t_start
    recs = []
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            for _ in range(t["q"]):
                recs.append(fed.round(clock))
            done = (len(recs) >= window_rounds if window_rounds
                    else clock() - t_w0 >= env.seconds)
            if done:
                break
    t_w1 = clock()
    if trace_cm is not None:
        trace_cm.__exit__(None, None, None)
    n_compiles = compiles()

    mem = memory_peak_bytes(env.devices[:cell.chips])
    thetas, gossips = prog["theta"], prog["gossip"]
    fed.free()
    t_r0 = clock()
    ref = reference_readings(fed, n_check, thetas, gossips)
    ref_s = clock() - t_r0
    numbers = check_numbers(prog, ref)
    correct, rows = compare.judge(numbers, cell.limits["numbers"])
    losses = [r["loss"] for r in recs] + prog["loss"]
    gaps = [b["t_dispatch"] - a["t_ready"] for a, b in zip(recs, recs[1:])]
    ctx = SimpleNamespace(
        kind="fl", cell=cell, config=cell.config, traffic=t,
        chips=cell.chips, setup_s=setup_s, window_s=t_w1 - t_w0,
        rounds=len(recs), tokens=len(recs) * fed.tokens_per_round,
        host_gaps_s=gaps, round_log=recs, clusters=t["clusters"])
    return dict(ctx=ctx, correct=correct, checks=rows,
                attempted=len(losses),
                failed=sum(not math.isfinite(x) for x in losses),
                memory_peak_bytes=mem, compiles_in_window=n_compiles,
                notes={"round_s": [round(r["t_ready"] - r["t_dispatch"], 4)
                                   for r in recs],
                       "check_losses": [prog["loss"], ref["loss"]],
                       "grad_gap_by_leaf": _rounded(compare.leaf_gaps(
                           prog["grad"], ref["grad"])),
                       "update_gap_by_leaf": _rounded(compare.leaf_gaps(
                           prog["update"], ref["update"])),
                       "leaves_left_out": sorted(
                           set(ref["grad"]) - compare.moved_leaves(
                               ref["grad"])),
                       "reference_s": round(ref_s, 2),
                       "window_s": round(t_w1 - t_w0, 3)})
