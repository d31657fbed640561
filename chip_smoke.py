#!/usr/bin/env python3
"""On-chip smoke run: the HCEF round step and the serving engine at the full
published widths of smollm-135m (30 layers, d_model 576, 9 heads / 3 KV
heads, d_ff 1536, vocab 49152; random weights from a seed).

    python chip_smoke.py            # one chip: training + serving phases
    python chip_smoke.py --chips 4  # four-chip host: the mesh gossip phase

One chip.  Training runs ``repro.launch.train.run`` (``--mesh host --full
--seq 512``, controller hcef, R=4 replicas, a gossip round every q=4) and
serving runs ``repro.launch.serve.run`` (``Engine.serve``, continuous
batching over the bf16 paged KV pool).  Each phase is checked against the
same computation traced with every op on its jnp route, on the same chip
and inputs.

Four chips.  A sparse-gossip int4 round at theta_min on the (4, 1) local
mesh, which must be finite, and a theta=1 f32-wire mesh round, which must
match the same round on the single-device host path within the tolerances
of tests/test_sharded_consistency.py.

The script fails (non-zero exit, no result line) when JAX finds no TPU or
the repository's sources are not beside it.  Its last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
It starts no child processes.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "smollm_135m"

# Agreement tolerances (Pallas route vs jnp route, same chip, same inputs).
# Training: one round, its loss (absolute) and the relative error of its
# parameter update, ||p_pallas - p_jnp|| / ||p_jnp - p_0|| over all leaves.
# The round runs at theta=1 (the top-k kernel runs but keeps every
# coordinate: at theta_min a last-bit difference of a gradient flips which
# near-threshold coordinates ship) in f32 (a bf16 parameter store turns
# last-bit differences of the update into whole-ulp ones), with no momentum
# and seq 256 (two attention blocks) to fit the f32 state in memory.  The
# routes differ in the flash-attention forward (kernel vs XLA summation
# order and matmul passes); a wrong mask or head mapping moves both
# numbers by O(1).
TRAIN_SEQ = 256
TRAIN_LOSS_TOL = 1e-2
TRAIN_UPDATE_REL_TOL = 5e-2
# Serving: ||logits_pallas - logits_jnp|| / ||logits_jnp|| of one decode
# step over the paged KV pool (bf16 attention outputs, 30 layers: last-bit
# differences, about 4e-3 relative each; a wrong page, mask or head mapping
# is O(1)).
DECODE_LOGITS_REL_TOL = 5e-2
# Four chips: tests/test_sharded_consistency.py's bounds.
MESH_SEQ = 128
MESH_LOSS_TOL = 1e-3
MESH_PARAM_TOL = 5e-3


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)
    print(f"  ok: {msg}", flush=True)


def has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def leaf_max_abs(a, b):
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def steady(values):
    values = sorted(values)
    return values[len(values) // 2] if values else float("nan")


# ---------------------------------------------------------------------------
# one chip: training
# ---------------------------------------------------------------------------

def train_phase(dev, rounds: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.round import make_round_step
    from repro.kernels import ops
    from repro.launch import train

    print(f"== train: {ARCH} full width, mesh host, R=4, seq 512, "
          f"{rounds} rounds", flush=True)
    t0 = time.perf_counter()
    res = train.run(["--arch", ARCH, "--mesh", "host", "--full",
                     "--rounds", str(rounds), "--seq", "512",
                     "--controller", "hcef"])
    wall = time.perf_counter() - t0
    losses = res["losses"]
    del res["state"]
    seen, warm = set(), []
    for s, g in zip(res["step_s"], res["gossip"]):
        if g in seen:  # not the first run of this program
            warm.append(s)
        seen.add(g)
    print(f"train losses: {[round(x, 4) for x in losses]}")
    print(f"train compile_s: {[round(c, 2) for c in res['compile_s'] if c]}"
          f" (sum {sum(res['compile_s']):.2f}s)")
    print(f"train step_s per round: {[round(s, 4) for s in res['step_s']]}")
    print(f"train steady round wall (median after warm-up): "
          f"{steady(warm):.4f}s; phase wall {wall:.1f}s")
    check(all(math.isfinite(x) for x in losses), "training losses finite")
    check(losses[-1] < losses[0], f"loss decreased {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")
    check(any(res["gossip"]), "a gossip round ran")
    check(all(has_kernel(c) for c in res["programs"].values()),
          f"tpu_custom_call in all {len(res['programs'])} round-step "
          f"programs")
    res = None

    # agreement: one round, Pallas route vs jnp route, same inputs
    bundle = get_config(ARCH)
    cfg = dataclasses.replace(bundle.model, param_dtype="float32",
                              compute_dtype="float32")
    hcef = dataclasses.replace(bundle.hcef, momentum=0.0)
    _, topo, _ = train.make_topology("host", bundle)
    R = topo.num_devices
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    inputs = (
        {"tokens": jax.random.randint(k1, (R * hcef.tau * 2, TRAIN_SEQ + 1),
                                      0, cfg.vocab_size, jnp.int32)},
        jnp.ones((R,), jnp.float32),
        jnp.ones((R,), jnp.float32),
        jax.random.split(k2, R))
    out = {}
    for route in ("pallas", "jnp"):
        state = train.init_placed_state(cfg, hcef, topo, None)
        with ops.forced_route(route):
            compiled, secs = train.compile_step(
                make_round_step(cfg, hcef, topo, None, gossip=False),
                (state,) + inputs)
        new, m = compiled(state, *inputs)
        out[route] = (jax.device_get(new.params), float(m["loss"].mean()))
        print(f"agreement round [{route}] compiled in {secs:.1f}s, "
              f"loss {out[route][1]:.6f}", flush=True)
        if route == "pallas":
            check(has_kernel(compiled), "tpu_custom_call in the Pallas "
                  "round program")
        del new, m, state
    p0 = jax.device_get(train.init_placed_state(cfg, hcef, topo,
                                                None).params)
    num = den = 0.0
    for a, b, c in zip(jax.tree.leaves(out["pallas"][0]),
                       jax.tree.leaves(out["jnp"][0]), jax.tree.leaves(p0)):
        num += float(np.sum(np.square(a - b, dtype=np.float64)))
        den += float(np.sum(np.square(b - c, dtype=np.float64)))
    rel = math.sqrt(num) / max(math.sqrt(den), 1e-30)
    dloss = abs(out["pallas"][1] - out["jnp"][1])
    print(f"train agreement: |dloss|={dloss:.3e} (tol {TRAIN_LOSS_TOL}), "
          f"update rel err={rel:.3e} (tol {TRAIN_UPDATE_REL_TOL})")
    check(dloss <= TRAIN_LOSS_TOL, "round loss agrees with the jnp route")
    check(rel <= TRAIN_UPDATE_REL_TOL,
          "round parameter update agrees with the jnp route")

    # the top-k kernel at theta_min, where it selects: bit-identical to the
    # jnp bisection by design (same f32 operations), on one stacked leaf
    ka, kb = jax.random.split(jax.random.PRNGKey(9))
    L = 30 * 576 * 1536  # the stacked w_up leaf, per replica
    delta = jax.random.normal(ka, (R, L), jnp.bfloat16)
    ef = 0.1 * jax.random.normal(kb, (R, L), jnp.bfloat16)
    theta = jnp.full((R,), hcef.theta_min, jnp.float32)
    outs = {}
    for route in ("pallas", "jnp"):
        outs[route] = jax.jit(lambda x, t, e, r=route: ops.topk_compress(
            x, t, block=hcef.block_size, impl=r, ef=e))(delta, theta, ef)
    bad = sum(int(jnp.sum(a != b)) for a, b in zip(outs["pallas"],
                                                  outs["jnp"]))
    kept = float(jnp.mean(outs["pallas"][0] != 0))
    print(f"top-k at theta={hcef.theta_min}: {bad} of {2 * R * L} "
          f"masked/residual elements differ from the jnp route, kept "
          f"fraction {kept:.4f}")
    check(bad == 0, "top-k kernel bit-identical to the jnp route")


# ---------------------------------------------------------------------------
# one chip: serving
# ---------------------------------------------------------------------------

def serve_phase(dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.launch import serve
    from repro.models import lm

    n_req, slots, plen, new = 12, 8, 96, 16
    print(f"== serve: {ARCH} full width, Engine.serve, {n_req} requests, "
          f"{slots} slots, bf16 paged KV", flush=True)
    t0 = time.perf_counter()
    res = serve.run(["--arch", ARCH, "--full", "--continuous",
                     "--requests", str(n_req), "--batch", str(slots),
                     "--prompt-len", str(plen), "--new-tokens", str(new),
                     "--temperature", "0", "--rate", "1000"])
    wall = time.perf_counter() - t0
    engine, reqs, outs = res["engine"], res["requests"], res["outputs"]
    n_tok = sum(len(o.tokens) for o in outs.values())
    print(f"serve: {len(outs)} requests answered, {n_tok} tokens, serve "
          f"{res['seconds']:.2f}s (compile included), phase wall "
          f"{wall:.1f}s")
    check(len(outs) == n_req and all(
        len(outs[r.rid].tokens) == r.max_new_tokens for r in reqs),
        "every request answered with its max_new_tokens tokens")
    check(engine.paged.kv_dtype is None and not engine.paged.contiguous,
          "bf16 paged KV through the page-table kernel route")

    # one decode step through the page table: Pallas vs jnp route
    cfg, params = engine.cfg, engine.params
    ps = engine.paged.page_size
    P = -(-(plen + new) // ps)
    rng = np.random.default_rng(3)
    B = slots
    table = jnp.asarray(1 + np.arange(B * P, dtype=np.int32).reshape(B, P))
    plens = jnp.asarray(rng.integers(plen // 2, plen + 1, B), jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, plen)),
                       jnp.int32)
    cache = lm.init_paged_cache(cfg, 1 + B * P, ps)
    _, cache = jax.jit(lambda p, c, t, pt, pl_: lm.prefill_paged(
        cfg, p, {"tokens": t}, c, pt, pl_))(params, cache, toks, table,
                                            plens)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    logits = {}
    for route in ("pallas", "jnp"):
        with ops.forced_route(route):
            compiled = jax.jit(lambda p, c, t, pt, kl: lm.decode_step_paged(
                cfg, p, c, t, pt, kl)[0]).lower(
                    params, cache, nxt, table, plens).compile()
        if route == "pallas":
            check(has_kernel(compiled),
                  "tpu_custom_call in the paged decode program")
        logits[route] = compiled(params, cache, nxt, table, plens)
    lp, lj = (logits[r].astype(jnp.float32) for r in ("pallas", "jnp"))
    rel = float(jnp.linalg.norm(lp - lj)) / max(float(jnp.linalg.norm(lj)),
                                                1e-30)
    agree = float(jnp.mean(jnp.argmax(lp, -1) == jnp.argmax(lj, -1)))
    print(f"decode agreement: ||dlogits||/||logits||={rel:.3e} (tol "
          f"{DECODE_LOGITS_REL_TOL}), max|dlogits|="
          f"{leaf_max_abs(lp, lj):.3e}, max|logits|="
          f"{float(jnp.max(jnp.abs(lj))):.3e}, argmax agreement "
          f"{agree:.3f}")
    check(rel <= DECODE_LOGITS_REL_TOL,
          "decode logits agree with the jnp route")

    # the engine's own decode program (same trace as serve's)
    slot_arr = jnp.zeros((slots,), jnp.int32)
    text_ok = has_kernel(engine._paged_decode.lower(
        params, cache, nxt, table, plens, slot_arr, slot_arr).compile())
    check(text_ok, "tpu_custom_call in Engine.serve's decode program")


# ---------------------------------------------------------------------------
# four chips: mesh gossip
# ---------------------------------------------------------------------------

def mesh_phase():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.round import make_round_step
    from repro.launch import train

    bundle = get_config(ARCH)
    # f32 as in tests/test_sharded_consistency.py (a last-bit difference of
    # a bf16 store, 7.8e-3 at |x| = 1, would swamp its 5e-3 parameter
    # bound), and its tau/q/eta/momentum; seq 128 so that the host path's
    # four f32 replicas fit on one chip.
    cfg = dataclasses.replace(bundle.model, param_dtype="float32",
                              compute_dtype="float32")
    base = dataclasses.replace(bundle.hcef, tau=2, q=2, eta=0.1,
                               momentum=0.0, sparse_gossip=True)
    mesh, topo, policy = train.make_topology("local", bundle)
    R, C = topo.num_devices, topo.clusters
    print(f"== mesh: {ARCH} full width (f32 params), local mesh "
          f"{dict(mesh.shape)}, R={R} as {C} clusters x "
          f"{topo.devices_per_cluster}", flush=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    batch = {"tokens": jax.random.randint(k1, (R * base.tau * 2, MESH_SEQ + 1),
                                          0, cfg.vocab_size, jnp.int32)}
    keys = jax.random.split(k2, R)
    rho = jnp.ones((R,), jnp.float32)

    def mesh_round(hcef, theta):
        state = train.init_placed_state(cfg, hcef, topo, policy)
        th = jnp.full((R,), theta, jnp.float32)
        with mesh:
            compiled, secs = train.compile_step(
                make_round_step(cfg, hcef, topo, policy, gossip=True,
                                cluster_levels=(theta,) * C),
                (state, batch, rho, th, keys))
            t0 = time.perf_counter()
            new, m = compiled(state, batch, rho, th, keys)
            jax.block_until_ready(new)
        print(f"mesh round wire={hcef.wire_dtype} theta={theta}: compile "
              f"{secs:.1f}s, run {time.perf_counter() - t0:.3f}s, loss "
              f"{float(m['loss'].mean()):.6f}", flush=True)
        check(has_kernel(compiled), f"tpu_custom_call in the {hcef.wire_dtype}"
              f" mesh program")
        return new, m

    # sparse-gossip int4 round at theta_min
    int4 = dataclasses.replace(base, wire_dtype="int4")
    new, m = mesh_round(int4, int4.theta_min)
    check(math.isfinite(float(m["loss"].mean())) and all(
        bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
        for x in jax.tree.leaves(new.params)),
        "int4 theta_min mesh round finite")
    del new, m

    # theta = 1, f32 wire: mesh vs host path
    f32 = dataclasses.replace(base, wire_dtype="f32")
    s_mesh, m_mesh = mesh_round(f32, 1.0)
    host = dataclasses.replace(f32, sparse_gossip=False)
    state = train.init_placed_state(cfg, host, topo, None)
    th = jnp.ones((R,), jnp.float32)
    args = (state, batch, rho, th, keys)
    compiled, secs = train.compile_step(
        make_round_step(cfg, host, topo, None, gossip=True), args)
    s_host, m_host = compiled(*args)
    del state, args
    dloss = abs(float(m_host["loss"].mean()) - float(m_mesh["loss"].mean()))
    dev0 = jax.tree.leaves(s_host.params)[0].sharding
    perr = max(leaf_max_abs(a, jax.device_put(b, dev0)) for a, b in zip(
        jax.tree.leaves(s_host.params), jax.tree.leaves(s_mesh.params)))
    eerr = max(leaf_max_abs(a, jax.device_put(b, dev0)) for a, b in zip(
        jax.tree.leaves(s_host.ef), jax.tree.leaves(s_mesh.ef)))
    print(f"mesh vs host (theta=1, f32 wire): |dloss|={dloss:.3e} (tol "
          f"{MESH_LOSS_TOL}), max|dparams|={perr:.3e}, max|def|={eerr:.3e}"
          f" (tol {MESH_PARAM_TOL}); host compile {secs:.1f}s")
    check(dloss < MESH_LOSS_TOL, "mesh round loss matches the host path")
    check(perr < MESH_PARAM_TOL and eerr < MESH_PARAM_TOL,
          "mesh round params and EF match the host path")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: training + serving on one chip; 4: the mesh "
                         "gossip phase on a four-chip host")
    ap.add_argument("--rounds", type=int, default=8,
                    help="training rounds (q=4: every 4th gossips)")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              f"devices", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    print(f"device_kind={dev.device_kind} platform={dev.platform} "
          f"count={len(devs)} jax={jax.__version__} cache={cache_dir}",
          flush=True)
    check(not ops._interp(), "Pallas kernels compiled, not interpreted")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_phase()
        else:
            train_phase(dev, args.rounds)
            serve_phase(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    print(f"peak_bytes_in_use: {[f'{p / 2**30:.2f}GiB' for p in peaks]}; "
          f"smoke wall {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
