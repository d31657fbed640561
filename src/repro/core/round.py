"""HCEF round step (Algorithm 1, lines 4–19) as a single jit-able function.

Stacked-replica layout: every FL device's state is one slice of a leading R
dim sharded over the mesh's data axes; all FL algebra (intra-cluster
averaging, inter-cluster gossip) is plain jnp on that dim, which GSPMD lowers
to the corresponding collectives.

One call = one edge round:
  tau masked local SGD steps  ->  delta = x_tau - x_0
  -> Q(delta + ef) block-top-k with error feedback (theta_n per device)
  -> intra-cluster mean (devices -> edge model)
  -> [every q-th round] gossip mix with H over clusters
  -> broadcast edge models back to devices.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FLTopology, HCEFConfig, ModelConfig
from repro.core import mixing
from repro.core.compression import compress_delta
from repro.models.registry import get_model
from repro.optim.sgd import sgd_init, sgd_update


class FLState(NamedTuple):
    params: Any     # pytree, leaves (R, *shape)
    momentum: Any   # pytree or None
    ef: Any         # error-feedback pytree, leaves (R, *shape)
    round_idx: jnp.ndarray  # scalar int32
    # CHOCO-style wire-EF estimates (hcef.wire_ef; DESIGN.md §Wire format
    # v2): {"est_self": pytree, "est_wsum": pytree} of f32 leaves shaped
    # like params, or None.  Last field so every keyword-based
    # construction (and old checkpoints) default it.
    wire_ef: Any = None


# --- FLState split (DESIGN.md §Cohort contract) -------------------------
# The round state divides into two halves with different ownership:
#   * MESH-RESIDENT: shared by every logical client — the cluster edge
#     models (broadcast over the R slots) and the round counter.  They
#     persist in the mesh across cohorts (edge servers outlive devices).
#   * PER-CLIENT: each R-slot's slice belongs to the LOGICAL CLIENT the
#     cohort mapped into that slot this round — error feedback, optimizer
#     momentum, wire-EF estimates.  Between rounds these slices page
#     against runtime/population.PopulationStore via elastic.cohort_swap.
MESH_FIELDS = ("params", "round_idx")
CLIENT_FIELDS = ("ef", "momentum", "wire_ef")


def split_state(state: "FLState"):
    """FLState -> (mesh_half, client_half) dicts (pure views, no copies)."""
    mesh = {f: getattr(state, f) for f in MESH_FIELDS}
    client = {f: getattr(state, f) for f in CLIENT_FIELDS}
    return mesh, client


def merge_state(mesh, client) -> "FLState":
    """Inverse of split_state: (mesh_half, client_half) -> FLState."""
    return FLState(**mesh, **client)


def client_template(state: "FLState"):
    """Per-client page template for the paged half: the client_half with
    each leaf's leading R (cohort-slot) dim stripped — what one logical
    client's page in the population store holds."""
    _, client = split_state(state)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape[1:]), x.dtype),
        client)


class OverlapState(NamedTuple):
    """Double-buffered state for the overlapped round engine (DESIGN.md
    §Overlap contract).

    ``fl`` is the working buffer (buffer B: the tau local SGD steps run
    against it); ``pending`` is the gossip payload buffer (buffer A: the
    model snapshot the in-flight gossip ppermutes read).  At every round
    boundary ``pending`` is refreshed to the new params, so on entry to a
    gossip round it holds the START-of-round model — stale by exactly one
    edge round relative to the fold.  ``params`` and ``pending`` diverge
    only INSIDE a staleness=1 gossip step, between the local-step stage
    and the fold; with staleness=0 the fold waits for fresh means and the
    two buffers never carry different models (bit-for-bit the synchronous
    engine)."""
    fl: FLState
    pending: Any    # params-shaped pytree, leaves (R, *shape)


def _global_norm2(tree):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree.leaves(tree))


def init_state(cfg: ModelConfig, hcef: HCEFConfig, topo: FLTopology,
               rng) -> FLState:
    model = get_model(cfg)
    params = model.init(cfg, rng)
    R = topo.num_devices
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), t)
    params_r = stack(params)
    mom = None
    if hcef.momentum and cfg.state_dtype:
        mom = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.dtype(
            cfg.state_dtype)), params_r)
    ef = jax.tree.map(lambda x: jnp.zeros_like(x), params_r)
    wef = None
    if hcef.wire_ef:
        # zero estimates: round 0's payload is the full mean (q = x - 0),
        # so the network's estimates converge from the first gossip.
        z = lambda: jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params_r)
        wef = {"est_self": z(), "est_wsum": z()}
    return FLState(params=params_r, momentum=mom, ef=ef,
                   round_idx=jnp.zeros((), jnp.int32), wire_ef=wef)


def abstract_state(cfg: ModelConfig, hcef: HCEFConfig,
                   topo: FLTopology) -> FLState:
    """ShapeDtypeStruct version of init_state (no allocation) for lowering."""
    return jax.eval_shape(lambda: init_state(cfg, hcef, topo,
                                             jax.random.PRNGKey(0)))


def init_overlap_state(cfg: ModelConfig, hcef: HCEFConfig, topo: FLTopology,
                       rng) -> OverlapState:
    """Both buffers start at the same model: the first gossip round's
    payload is the (identical) initial model, so round 0 is a fixed point
    of the stale mix exactly like it is of the synchronous one."""
    fl = init_state(cfg, hcef, topo, rng)
    return OverlapState(fl=fl, pending=fl.params)


def abstract_overlap_state(cfg: ModelConfig, hcef: HCEFConfig,
                           topo: FLTopology) -> OverlapState:
    return jax.eval_shape(lambda: init_overlap_state(
        cfg, hcef, topo, jax.random.PRNGKey(0)))


def _split_batch(batch: Dict[str, jnp.ndarray], R: int, tau: int):
    """(global_batch, ...) -> (R, tau, b_local, ...)."""
    def split(x):
        B = x.shape[0]
        assert B % (R * tau) == 0, (B, R, tau)
        return x.reshape(R, tau, B // (R * tau), *x.shape[1:])
    return {k: split(v) for k, v in batch.items()}


def _check_cluster_levels(cluster_levels, hcef, C, policy, gossip):
    """Shared static-k validation for the sync and overlapped factories."""
    if cluster_levels is None:
        return None
    if not (hcef.sparse_gossip and gossip):
        raise ValueError("cluster_levels requires sparse_gossip and a "
                         "gossip round step")
    if policy is None or policy.mesh is None:
        raise ValueError("cluster_levels requires a mesh policy (the "
                         "non-fused path has no wire)")
    cluster_levels = tuple(float(t) for t in cluster_levels)
    if len(cluster_levels) != C:
        raise ValueError(f"cluster_levels has {len(cluster_levels)} "
                         f"entries for {C} clusters")
    grid = {float(t) for t in hcef.theta_levels}
    bad = [t for t in cluster_levels if t not in grid]
    if bad:
        raise ValueError(f"cluster_levels {bad} not in theta_levels "
                         f"{sorted(grid)} (the static-k contract only "
                         f"lowers grid levels)")
    return cluster_levels


def make_round_step(cfg: ModelConfig, hcef: HCEFConfig, topo: FLTopology,
                    policy=None, *, gossip: bool = True, impl=None,
                    cluster_levels=None):
    """Returns round_step(state, batch, rho, theta, keys) -> (state, metrics).

    batch: dict of (global_batch, ...) arrays; rho/theta: (R,) controls;
    keys: (R, 2) uint32 per-device PRNG keys.
    ``gossip`` statically selects whether the inter-cluster mixing (Eq. 5)
    runs at the end of the round (the driver uses it every q-th edge round).
    ``cluster_levels``: optional STATIC per-cluster theta levels (length
    ``topo.clusters``, each a ``hcef.theta_levels`` entry) for the sparse
    gossip path — each cluster's outgoing band payload is then sized by
    its OWN level (sender-sized edges, Algorithm 3's heterogeneous
    ratios) instead of one global ``max(theta)`` switch.  The assignment
    is static per lowered program; call sites compute it on the host from
    the quantized theta (``core.compression.cluster_levels_from_theta``)
    and jit-cache one step per distinct assignment (DESIGN.md §Static-k).
    Requires ``hcef.sparse_gossip`` and a mesh policy (fails loudly
    otherwise — a silently ignored level assignment would un-FL the run).
    """
    model = get_model(cfg)
    C, Dev = topo.clusters, topo.devices_per_cluster
    R = topo.num_devices
    cluster_levels = _check_cluster_levels(cluster_levels, hcef, C, policy,
                                           gossip)
    if hcef.wire_ef and gossip and (policy is None or policy.mesh is None):
        raise ValueError("wire_ef requires a mesh policy: the non-fused "
                         "aggregation path has no wire to feed back on")
    H_np = mixing.make_mixing(topo.backhaul, C)
    # Paper Appendix A: the whole aggregation (intra-cluster averaging +
    # gossip + broadcast-back) is one linear operator on the device dim,
    #   W = B^T diag(1/Dev) H B   (gossip)  /  B^T diag(1/Dev) B  (intra).
    # It is applied FACTORIZED (per-cluster mean -> (C, C) H matmul ->
    # broadcast), O(R d) instead of the dense einsum's O(R^2 d).  The
    # reshape to (C, Dev, ...) is only safe off-mesh: under GSPMD it
    # destroys the replica dim's sharding (DESIGN.md §Reshape-pitfall), so
    # the mesh path runs shard-locally via dist.collectives.mix_local.
    H = jnp.asarray(H_np, jnp.float32)

    def device_round(params, mom, batch_tau, key, rho_r):
        """One device's tau local iterations. All args UNSTACKED."""
        x0 = params
        with jax.named_scope("hcef.grad_stats"):
            bits = jax.random.bernoulli(
                key, jnp.clip(rho_r, 0.0, 1.0),
                (hcef.tau,)).astype(jnp.float32)

        def step(carry, inp):
            p, m = carry
            batch_s, bit = inp
            loss, g = jax.value_and_grad(
                lambda pp: model.loss_fn(cfg, pp, batch_s, policy))(p)
            with jax.named_scope("hcef.grad_stats"):
                gn2 = _global_norm2(g)
                g = jax.tree.map(lambda a: a * bit.astype(a.dtype), g)
            with jax.named_scope("hcef.sgd"):
                p, m = sgd_update(p, g, m, lr=hcef.eta, momentum=hcef.momentum)
            return (p, m), (loss, gn2, bit)

        # the scope covers the loop as well as its body: the loop's carry,
        # the zeros autodiff makes for cotangents, the stacked outputs
        with jax.named_scope("hcef.local_step"):
            (params, mom), (losses, gn2s, bits_out) = jax.lax.scan(
                step, (params, mom), (batch_tau, bits))
        with jax.named_scope("hcef.delta"):
            delta = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32)
                              - b.astype(jnp.float32)).astype(a.dtype),
                params, x0)
        # Algorithm-2 style statistics (norm-based proxies; DESIGN.md):
        with jax.named_scope("hcef.grad_stats"):
            g2_est = jnp.min(gn2s)
            sigma2_est = jnp.maximum(jnp.mean(gn2s) - g2_est, 0.0)
            metrics = {"loss": jnp.mean(losses), "g2": g2_est,
                       "sigma2": sigma2_est, "steps": jnp.sum(bits_out)}
        return delta, mom, metrics

    spmd = tuple(policy.replica_axes) if (
        policy is not None and policy.replica_axes) else None

    def round_step(state: FLState, batch, rho, theta, keys,
                   alive=None, alive_w=None, conn=None):
        """``alive``/``alive_w``/``conn`` are the chaos masks (all None on
        fault-free rounds — the unmasked trace below is then byte-identical
        to the pre-chaos step, which is what keeps it bit-for-bit):

          alive   (R,) 0/1 — device made this round's deadline.  A dropped
                  device's compressed contribution is folded back into its
                  error feedback (``runtime.chaos.fold_dropped_updates``'s
                  conservation invariant), so nothing is silently lost.
          alive_w (R,) f32 HOST-computed ``dist.collectives.
                  participation_weights`` — renormalizes the unchanged
                  sum/Dev intra mean to the mean over live devices.
          conn    (C,) 0/1 — cluster backhaul up; gossip applies
                  ``mixing.participation_mixing`` (partitioned clusters
                  keep their intra model, mix stale-by-1 on reconnect).
        """
        chaos = alive is not None
        if chaos:
            if alive_w is None:
                raise ValueError("alive requires alive_w (host-computed "
                                 "participation_weights)")
            if hcef.wire_ef and conn is not None and gossip:
                raise ValueError(
                    "wire_ef is incompatible with chaos cluster "
                    "partitions (conn): a partitioned sender's neighbors "
                    "would zero its contribution while its own estimate "
                    "advances — the shared estimates desync")
            alive_f = jnp.asarray(alive, jnp.float32)
            alive_wf = jnp.asarray(alive_w, jnp.float32)
            conn_f = (jnp.asarray(conn, jnp.float32)
                      if conn is not None else None)
        batch_r = _split_batch(batch, R, hcef.tau)
        if R == 1:
            # No vmap: a batched-by-1 tracer would have an extra leading dim
            # and the policy's activation constraints (fixed ndim) would
            # silently no-op — catastrophic at arctic-480b scale.
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            delta, mom, metrics = device_round(
                sq(state.params), sq(state.momentum), sq(batch_r), keys[0],
                rho[0])
            delta = jax.tree.map(lambda x: x[None], delta)
            mom = jax.tree.map(lambda x: x[None], mom)
            metrics = jax.tree.map(lambda x: x[None], metrics)
        else:
            vkw = {"spmd_axis_name": spmd} if spmd else {}
            delta, mom, metrics = jax.vmap(
                device_round, in_axes=(0, 0, 0, 0, 0), **vkw)(
                    state.params, state.momentum, batch_r, keys, rho)

        # --- compression Q + aggregation (Sec. 3.2 / lines 16, 18) ---
        new_wef = state.wire_ef  # advanced only by sparse gossip rounds
        mesh = policy.mesh if policy is not None else None
        if mesh is not None:
            # Fused per-leaf shard_map: each chip compresses the blocks of
            # its own shard, then the W operator runs as shard-sized
            # recursive-doubling + ring ppermutes (dist/collectives.py).
            from jax.sharding import PartitionSpec as PS
            from repro.dist.compat import shard_map
            from repro.dist.collectives import (mix_local,
                                                sparse_neighbor_exchange)
            from repro.core.compression import _compress_flat

            shd = policy.param_shardings(state.params, stacked=True)
            specs = jax.tree.map(lambda s: s.spec, shd)
            rep_axes = tuple(policy.replica_axes)
            if R == 1:
                rep_axes = ()  # inner_dp-only topologies: nothing to mix
            elif rep_axes and R % policy.axis_size(rep_axes):
                raise ValueError(  # fail loudly: skipping W would silently
                    f"R={R} does not tile replica axes {rep_axes}")  # un-FL
            rspec = PS(rep_axes or None)
            hkind = topo.backhaul if gossip else "none"
            # Sparse wire path (DESIGN.md §Static-k): the level-independent
            # work (compress + intra mean + broadcast-back) runs ONCE with
            # hkind="none"; the gossip bands then run per quantized theta
            # level inside a lax.switch, so each branch's only collectives
            # are band-rotation ppermutes of the compact wire payload.
            # At theta < 1 the NEIGHBOR terms of the mix are top-k
            # approximations of the gossiped edge models (self term exact),
            # i.e. a sparsified application of H.  With hcef.wire_ef the
            # payload is the difference to a CHOCO-style shared estimate
            # (FLState.wire_ef), so the truncation error scales with the
            # consensus gap instead of the mean's norm (DESIGN.md §Wire
            # format v2).
            sparse = hcef.sparse_gossip and gossip and R > 1
            use_wef = bool(hcef.wire_ef) and sparse

            def per_leaf(x0l, dl, el, spec, mix_hkind):
                pass_conn = chaos and conn is not None and mix_hkind != "none"

                def local(x0s, ds, es, ts, *cargs):
                    # No caller-side f32 upcast: the top-k kernel adds the
                    # error feedback and thresholds in f32 internally, per
                    # VMEM block (bf16-native path).
                    Rl = ds.shape[0]
                    flat = ds.reshape(Rl, -1)
                    ef_flat = (es.reshape(Rl, -1) if hcef.error_feedback
                               else None)
                    with jax.named_scope("hcef.compress"):
                        masked, resid = _compress_flat(flat, ts,
                                                       hcef.block_size,
                                                       impl, ef=ef_flat)
                    mix_kw = {}
                    if chaos:
                        # EF conservation fold: a dropped device's split is
                        # routed whole into its residual, so per device
                        # contribution + ef_out == delta + ef_old exactly.
                        a = (cargs[0] > 0)[:, None]
                        masked, resid = (
                            jnp.where(a, masked, jnp.zeros_like(masked)),
                            jnp.where(a, resid, masked + resid))
                        mix_kw = dict(alive=cargs[1],
                                      conn=cargs[2] if pass_conn else None)
                    # rep_axes == () with R > 1 means the replica dim is
                    # fully replicated per shard; mix_local then runs the
                    # dense-local factorization — never skip W silently.
                    with jax.named_scope("hcef.aggregate"):
                        upd = x0s + masked.reshape(ds.shape).astype(
                            x0s.dtype)
                        y = mix_local(upd, clusters=C, dev=Dev,
                                      axes=rep_axes, hkind=mix_hkind,
                                      **mix_kw) if R > 1 else upd
                    return (y.astype(x0s.dtype),
                            resid.reshape(es.shape).astype(es.dtype))

                in_specs = (spec, spec, spec, rspec)
                args = (x0l, dl, el, theta)
                if chaos:
                    in_specs += (rspec, rspec)
                    args += (alive_f, alive_wf)
                    if pass_conn:
                        in_specs += (PS(None),)
                        args += (conn_f,)
                fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                               out_specs=(spec, spec), check_vma=False)
                return fn(*args)

            flat_x, treedef = jax.tree.flatten(state.params)
            flat_d = treedef.flatten_up_to(delta)
            flat_e = treedef.flatten_up_to(state.ef)
            flat_s = treedef.flatten_up_to(specs)
            outs = [per_leaf(x, d, e, s, "none" if sparse else hkind)
                    for x, d, e, s in zip(flat_x, flat_d, flat_e, flat_s)]
            new_flat = [p for p, _ in outs]
            ef = treedef.unflatten([r for _, r in outs])

            if use_wef:
                flat_es = treedef.flatten_up_to(state.wire_ef["est_self"])
                flat_ew = treedef.flatten_up_to(state.wire_ef["est_wsum"])

            if sparse and cluster_levels is not None:
                # Per-CLUSTER static dispatch: one program per distinct
                # (cluster -> level) assignment (the call site jit-caches
                # them); every cluster's outgoing band payload is sized
                # by its own level via partial-perm level groups inside
                # sparse_neighbor_exchange — no switch, no dead branches.
                gossip_conn = chaos and conn is not None

                def gossip_leaf_pc(ml, spec, ef=None):
                    def local_g(ms, *rest):
                        wef, cargs = None, rest
                        if ef is not None:
                            wef, cargs = (rest[0], rest[1]), rest[2:]
                        return sparse_neighbor_exchange(
                            ms, clusters=C, dev=Dev, axes=rep_axes,
                            cluster_theta=cluster_levels, hkind=hkind,
                            wire_dtype=hcef.wire_dtype,
                            wire_block=hcef.wire_block, intra_done=True,
                            wire_ef=wef,
                            wire_ef_gamma=hcef.wire_ef_gamma,
                            conn=cargs[0] if gossip_conn else None)

                    nio = 1 if ef is None else 3  # (y[, est_self, est_wsum])
                    gspecs = (spec,) * nio + ((PS(None),) if gossip_conn
                                              else ())
                    gargs = (ml,) + (tuple(ef) if ef else ()) + (
                        (conn_f,) if gossip_conn else ())
                    with jax.named_scope("hcef.gossip"):
                        return shard_map(local_g, mesh=mesh,
                                         in_specs=gspecs,
                                         out_specs=(spec,) * nio if ef
                                         else spec,
                                         check_vma=False)(*gargs)

                if use_wef:
                    outs = [gossip_leaf_pc(m, s, (es, ew))
                            for m, es, ew, s in zip(new_flat, flat_es,
                                                    flat_ew, flat_s)]
                    new_flat = [o[0] for o in outs]
                    flat_es = [o[1] for o in outs]
                    flat_ew = [o[2] for o in outs]
                else:
                    new_flat = [gossip_leaf_pc(m, s)
                                for m, s in zip(new_flat, flat_s)]
                metrics["theta_wire"] = jnp.float32(max(cluster_levels))
            elif sparse:
                # Fallback for callers that only pass a traced theta: a
                # lax.switch over the level grid dispatched on the GLOBAL
                # max (uniform — every cluster ships at the ceiling;
                # per-cluster savings need the static assignment above).
                levels = tuple(sorted({float(t)
                                       for t in hcef.theta_levels}))
                lv = jnp.asarray(levels, jnp.float32)
                # smallest level >= max per-device theta (conservative:
                # the wire never ships fewer coordinates than Q kept).
                idx = jnp.minimum(
                    jnp.searchsorted(lv, jnp.max(theta), side="left"),
                    len(levels) - 1).astype(jnp.int32)

                gossip_conn = chaos and conn is not None

                def gossip_leaf(ml, spec, level, ef=None):
                    def local_g(ms, *rest):
                        wef, cargs = None, rest
                        if ef is not None:
                            wef, cargs = (rest[0], rest[1]), rest[2:]
                        return sparse_neighbor_exchange(
                            ms, clusters=C, dev=Dev, axes=rep_axes,
                            theta=level, hkind=hkind,
                            wire_dtype=hcef.wire_dtype,
                            wire_block=hcef.wire_block, intra_done=True,
                            wire_ef=wef,
                            wire_ef_gamma=hcef.wire_ef_gamma,
                            conn=cargs[0] if gossip_conn else None)

                    nio = 1 if ef is None else 3
                    gspecs = (spec,) * nio + ((PS(None),) if gossip_conn
                                              else ())
                    gargs = (ml,) + (tuple(ef) if ef else ()) + (
                        (conn_f,) if gossip_conn else ())
                    with jax.named_scope("hcef.gossip"):
                        return shard_map(local_g, mesh=mesh,
                                         in_specs=gspecs,
                                         out_specs=(spec,) * nio if ef
                                         else spec,
                                         check_vma=False)(*gargs)

                if use_wef:
                    def branch(level):
                        def run(op):
                            ms, ess, ews = op
                            return [gossip_leaf(m, s, level, (es, ew))
                                    for m, es, ew, s in zip(ms, ess, ews,
                                                            flat_s)]
                        return run

                    outs = jax.lax.switch(idx, [branch(l) for l in levels],
                                          (new_flat, flat_es, flat_ew))
                    new_flat = [o[0] for o in outs]
                    flat_es = [o[1] for o in outs]
                    flat_ew = [o[2] for o in outs]
                else:
                    def branch(level):
                        return lambda ms: [gossip_leaf(m, s, level)
                                           for m, s in zip(ms, flat_s)]

                    new_flat = jax.lax.switch(
                        idx, [branch(l) for l in levels], new_flat)
                metrics["theta_wire"] = jnp.take(lv, idx)
            new_params = treedef.unflatten(new_flat)
            if use_wef:
                new_wef = {"est_self": treedef.unflatten(flat_es),
                           "est_wsum": treedef.unflatten(flat_ew)}
        else:
            comp, ef = compress_delta(delta, state.ef, theta,
                                      block=hcef.block_size,
                                      error_feedback=hcef.error_feedback,
                                      impl=impl)
            if chaos:
                from repro.runtime.chaos import fold_dropped_updates
                comp, ef = fold_dropped_updates(comp, ef, alive_f)

            # gossip rounds fold the per-cluster mean and the (C, C) H
            # matmul into ONE (C, R) x (R, d) GEMM: M = H diag(1/Dev) B,
            # Dev x less compute than the dense (R, R) einsum at identical
            # memory traffic; intra rounds are just the per-cluster mean.
            # Under chaos the same GEMM absorbs the whole degraded-mode
            # contract: H -> participation_mixing(H, conn) and diag(1/Dev)
            # -> diag(alive_w/Dev) (the live-count-renormalized mean).
            with jax.named_scope("hcef.aggregate"):
                Hg = H
                if chaos and conn is not None and gossip:
                    Hg = mixing.participation_mixing(H, conn_f).astype(
                        jnp.float32)
                M = jnp.repeat(Hg / Dev, Dev, axis=1)  # (C, R)
                if chaos:
                    M = M * alive_wf[None, :]

            def aggregate(x0_leaf, comp_leaf):
                upd = (x0_leaf.astype(jnp.float32)
                       + comp_leaf.astype(jnp.float32))
                if R > 1:
                    dims = upd.shape[1:]
                    if gossip:
                        yc = (M @ upd.reshape(R, -1)).reshape((C,) + dims)
                    else:
                        uw = upd
                        if chaos:
                            uw = upd * alive_wf.reshape(
                                (R,) + (1,) * len(dims))
                        yc = uw.reshape((C, Dev) + dims).mean(axis=1)
                    upd = jnp.broadcast_to(
                        yc[:, None], (C, Dev) + dims).reshape(upd.shape)
                return upd.astype(x0_leaf.dtype)

            with jax.named_scope("hcef.aggregate"):
                new_params = jax.tree.map(aggregate, state.params, comp)
        new_state = FLState(params=new_params, momentum=mom, ef=ef,
                            round_idx=state.round_idx + 1,
                            wire_ef=new_wef)
        out_metrics = {k: v for k, v in metrics.items()}
        return new_state, out_metrics

    return round_step


def make_overlap_round_step(cfg: ModelConfig, hcef: HCEFConfig,
                            topo: FLTopology, policy=None, *,
                            gossip: bool = True, impl=None,
                            cluster_levels=None, stale_clusters=None):
    """Overlapped round step (DESIGN.md §Overlap contract):
    round_step(state: OverlapState, ...) -> (OverlapState, metrics).

    Staleness semantics (``hcef.staleness``):

      0: the fold waits for this round's gossip — the step DELEGATES to
         the synchronous ``make_round_step`` program (bit-for-bit
         identical by construction; the fl buffer sees the exact same jit
         graph) and only additionally refreshes the pending buffer.
      1: gossip rounds run as two stages.  Stage 1 is the synchronous
         intra-only step (tau local steps + compress + EF fold + intra
         mean).  Stage 2 folds the gossip mix where every cluster in the
         STATIC ``stale_clusters`` set (default: all clusters) ships its
         PENDING (start-of-round) model over the wire while the self term
         stays fresh (``sparse_neighbor_exchange(stale=...)``).  The stale
         payload is a step INPUT, so its encode + band-rotation ppermutes
         carry no data dependence on the local-step scan — XLA can issue
         them while the tau steps run, which is exactly what the dryrun
         overlap verdict (``hlo_analysis.check_gossip_overlap``) checks.
         Non-gossip rounds delegate to the synchronous gossip=False step.

    ``stale_clusters``: static cluster ids that run stale, from
    ``fl.cost_model.decide_stale_clusters`` (clusters whose backhaul
    gossip time exceeds the straggler-deadline compute window).  An empty
    tuple means nobody is behind — the step degrades to the synchronous
    gossip program.  Partial sets keep fresh senders' payloads dependent
    on this round's compute (documented reduced overlap).

    Chaos masks work in both modes exactly like the sync engine:
    ``alive``/``alive_w`` mask the intra stage (EF-conserving fold),
    ``conn`` applies participation mixing to the gossip fold.
    """
    if not hcef.overlap:
        raise ValueError("make_overlap_round_step requires hcef.overlap "
                         "(use make_round_step for the synchronous engine)")
    C, Dev = topo.clusters, topo.devices_per_cluster
    R = topo.num_devices
    if stale_clusters is not None:
        stale_clusters = tuple(sorted({int(c) for c in stale_clusters}))
        if any(not 0 <= c < C for c in stale_clusters):
            raise ValueError(
                f"stale_clusters {stale_clusters} out of range({C})")
    sync_like = (hcef.staleness == 0 or not gossip
                 or stale_clusters == () or R == 1)
    if sync_like:
        inner = make_round_step(
            cfg, hcef, topo, policy, gossip=gossip, impl=impl,
            cluster_levels=cluster_levels if gossip else None)

        def round_step(state: OverlapState, batch, rho, theta, keys,
                       alive=None, alive_w=None, conn=None):
            fl, metrics = inner(state.fl, batch, rho, theta, keys,
                                alive=alive, alive_w=alive_w, conn=conn)
            return OverlapState(fl=fl, pending=fl.params), metrics

        return round_step

    # staleness == 1 gossip round: two-stage bounded-stale program.
    from repro.dist.collectives import sparse_neighbor_exchange

    cluster_levels = _check_cluster_levels(cluster_levels, hcef, C, policy,
                                           gossip=True)
    if stale_clusters is None:
        stale_clusters = tuple(range(C))
    inner = make_round_step(cfg, hcef, topo, policy, gossip=False, impl=impl)
    hkind = topo.backhaul
    mesh = policy.mesh if policy is not None else None
    # the wire format only exists on the sparse mesh path; the dense fold
    # ships the full rows (theta=1.0 f32 wire == the dense-wire fallback).
    sparse = hcef.sparse_gossip and mesh is not None
    wire_kw = (dict(wire_dtype=hcef.wire_dtype, wire_block=hcef.wire_block)
               if sparse else dict(wire_dtype="f32"))
    rep_axes = tuple(policy.replica_axes) if (
        policy is not None and policy.replica_axes) else ()

    def round_step(state: OverlapState, batch, rho, theta, keys,
                   alive=None, alive_w=None, conn=None):
        fl_mid, metrics = inner(state.fl, batch, rho, theta, keys,
                                alive=alive, alive_w=alive_w, conn=conn)
        conn_f = (jnp.asarray(conn, jnp.float32) if conn is not None
                  else None)

        if mesh is not None:
            from jax.sharding import PartitionSpec as PS
            from repro.dist.compat import shard_map

            shd = policy.param_shardings(state.fl.params, stacked=True)
            specs = jax.tree.map(lambda s: s.spec, shd)
            flat_m, treedef = jax.tree.flatten(fl_mid.params)
            flat_p = treedef.flatten_up_to(state.pending)
            flat_s = treedef.flatten_up_to(specs)
            gossip_conn = conn is not None

            def gossip_leaf(ml, pl, spec, level):
                def local_g(ms, ps, *cargs):
                    kw = dict(clusters=C, dev=Dev, axes=rep_axes,
                              hkind=hkind, intra_done=True, stale=ps,
                              stale_clusters=stale_clusters,
                              conn=cargs[0] if gossip_conn else None,
                              **wire_kw)
                    if cluster_levels is not None:
                        return sparse_neighbor_exchange(
                            ms, cluster_theta=cluster_levels, **kw)
                    return sparse_neighbor_exchange(ms, theta=level, **kw)

                gspecs = (spec, spec) + ((PS(None),) if gossip_conn
                                         else ())
                gargs = (ml, pl) + ((conn_f,) if gossip_conn else ())
                with jax.named_scope("hcef.gossip"):
                    return shard_map(local_g, mesh=mesh, in_specs=gspecs,
                                     out_specs=spec,
                                     check_vma=False)(*gargs)

            if cluster_levels is not None or not sparse:
                new_flat = [gossip_leaf(m, p, s, 1.0)
                            for m, p, s in zip(flat_m, flat_p, flat_s)]
                if sparse:
                    metrics["theta_wire"] = jnp.float32(max(cluster_levels))
            else:
                # traced-theta fallback: one lax.switch branch per level,
                # dispatched on the global max (same contract as the sync
                # engine's sparse path).
                levels = tuple(sorted({float(t)
                                       for t in hcef.theta_levels}))
                lv = jnp.asarray(levels, jnp.float32)
                idx = jnp.minimum(
                    jnp.searchsorted(lv, jnp.max(theta), side="left"),
                    len(levels) - 1).astype(jnp.int32)

                def branch(level):
                    return lambda op: [gossip_leaf(m, p, s, level)
                                       for m, p, s in zip(op[0], op[1],
                                                          flat_s)]

                new_flat = jax.lax.switch(idx, [branch(l) for l in levels],
                                          (flat_m, flat_p))
                metrics["theta_wire"] = jnp.take(lv, idx)
            new_params = treedef.unflatten(new_flat)
        else:
            # off-mesh: dense fold through the same stale-select operator
            # (theta=1.0 f32 wire ships the dense rows bit-exactly).
            with jax.named_scope("hcef.gossip"):
                new_params = jax.tree.map(
                    lambda ml, pl: sparse_neighbor_exchange(
                        ml, clusters=C, dev=Dev, axes=(), hkind=hkind,
                        theta=1.0, intra_done=True, stale=pl,
                        stale_clusters=stale_clusters, conn=conn_f,
                        wire_dtype="f32"),
                    fl_mid.params, state.pending)
        metrics["stale_frac"] = jnp.float32(len(stale_clusters) / C)
        fl = FLState(params=new_params, momentum=fl_mid.momentum,
                     ef=fl_mid.ef, round_idx=fl_mid.round_idx,
                     wire_ef=fl_mid.wire_ef)
        return OverlapState(fl=fl, pending=new_params), metrics

    return round_step


def make_serve_step(cfg: ModelConfig, policy=None):
    """serve_step(params, cache, tokens) -> (logits, cache) for dry-run and
    the serving engine (one decode token across the whole batch)."""
    model = get_model(cfg)

    def serve_step(params, cache, tokens):
        return model.decode_step(cfg, params, cache, tokens, policy)

    return serve_step


def make_prefill_step(cfg: ModelConfig, policy=None):
    model = get_model(cfg)

    def prefill_step(params, batch, cache):
        return model.prefill(cfg, params, batch, cache, policy)

    return prefill_step
