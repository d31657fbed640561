"""The paper's compression operator Q applied to stacked-replica pytrees.

Each leaf of the per-replica delta (R, *shape) is compressed with the
block-local top-k kernel with fused error feedback.  Block-locality preserves
the contraction property (Eq. 7) while keeping compression embarrassingly
shardable.

Sharding note (critical at 480B scale): flattening a sharded leaf to (R, L)
is a sharding-destroying reshape — GSPMD would materialize the full leaf on
every device.  When (mesh, specs) are provided, compression therefore runs
inside a per-leaf ``shard_map``: every device compresses the blocks of its
OWN shard (top-k is block-local anyway, so shard-locality changes nothing
semantically — blocks never span shards).  Without a mesh (CPU tests) the
plain path is used.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.wire_format import compression_ratio_bytes  # noqa: F401
from repro.dist.compat import shard_map

from repro.kernels import ops


def _compress_flat(flat, theta, block, impl, ef=None):
    """flat (and optional ef): (R_local, L_local) already local; theta:
    (R_local,).  The EF add is fused into the kernel (f32 per VMEM tile),
    so callers pass storage-dtype arrays and never upcast a whole shard.

    (A slab-chunked lax.map variant was tried to bound the kernel's f32
    working set but measured WORSE — the map double-buffers transposed
    copies of the whole leaf; see EXPERIMENTS.md §Perf iteration log.)"""
    L = flat.shape[1]
    pad = (-L) % block
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
        if ef is not None:
            ef = jnp.pad(ef, ((0, 0), (0, pad)))
    masked, resid = ops.topk_compress(flat, theta, block=block, impl=impl,
                                      ef=ef)
    return masked[:, :L], resid[:, :L]


def _leaf_plain(d, e, theta, block, error_feedback, impl):
    R = d.shape[0]
    flat = d.reshape(R, -1)
    ef = (e.reshape(R, -1) if error_feedback and e is not None else None)
    masked, resid = _compress_flat(flat, theta, block, impl, ef=ef)
    return (masked.reshape(d.shape).astype(d.dtype),
            resid.reshape(d.shape).astype(e.dtype if e is not None
                                          else d.dtype))


def compress_delta(delta, ef, theta, *, block: int = 1024,
                   error_feedback: bool = True, impl=None,
                   mesh=None, specs=None,
                   replica_spec=None) -> Tuple[Any, Any]:
    """delta, ef: pytrees of (R, *shape); theta: (R,) in (0, 1].

    Returns (compressed_delta, new_ef) with
      compressed + new_ef == delta + ef   (exact, tested).

    mesh/specs: optional mesh and same-structure tree of PartitionSpec for
    the leaves (including the leading R dim) -> shard_map per-shard path.
    replica_spec: PartitionSpec for the (R,) theta vector.
    """
    with jax.named_scope("hcef.compress"):
        if mesh is None or specs is None:
            fn = functools.partial(_leaf_plain, theta=theta, block=block,
                                   error_feedback=error_feedback, impl=impl)
            flat_d, treedef = jax.tree.flatten(delta)
            flat_e = (treedef.flatten_up_to(ef) if ef is not None
                      else [None] * len(flat_d))
            out = [fn(d, e) for d, e in zip(flat_d, flat_e)]
            return (treedef.unflatten([m for m, _ in out]),
                    treedef.unflatten([r for _, r in out]))

        rspec = replica_spec if replica_spec is not None else P(None)

        def per_leaf(d, e, spec):
            def local(dl, el, tl):
                Rl = dl.shape[0]
                flat = dl.reshape(Rl, -1)
                ef = el.reshape(Rl, -1) if error_feedback else None
                masked, resid = _compress_flat(flat, tl, block, impl, ef=ef)
                return (masked.reshape(dl.shape).astype(dl.dtype),
                        resid.reshape(dl.shape).astype(el.dtype))

            fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, rspec),
                           out_specs=(spec, spec), check_vma=False)
            return fn(d, e if e is not None else jnp.zeros_like(d), theta)

        flat_d, treedef = jax.tree.flatten(delta)
        flat_e = (treedef.flatten_up_to(ef) if ef is not None
                  else [None] * len(flat_d))
        flat_s = treedef.flatten_up_to(specs)
        out = [per_leaf(d, e, s) for d, e, s in zip(flat_d, flat_e, flat_s)]
        return (treedef.unflatten([m for m, _ in out]),
                treedef.unflatten([r for _, r in out]))


# Bits per kept entry of the FIXED-WIDTH v1 wire formats: (value_bits,
# offset_bits, per-wire-block scale_bits).  Documentation only — the v2
# formats (int4/fp8) pack offsets to a (wb, k_b)-dependent width, so every
# byte computation goes through ``core.wire_format`` (the single source of
# truth shared with dist/collectives and dist/hlo_analysis).
WIRE_FORMAT_BITS = {"f32": (32, 32, 0), "bf16": (16, 32, 0),
                    "int8": (8, 16, 32)}


def quantize_theta(theta, levels):
    """Round each theta UP to the nearest level (conservative: the wire
    never ships fewer coordinates than the controller asked for).  A theta
    ABOVE the largest level is an out-of-grid error — clamping it down
    would silently ship fewer coordinates than Q kept, so the level grid
    must cover the controller's range (validated at ``HCEFConfig`` /
    ``FedSimConfig`` construction: ``max(theta_levels) >= 1.0``).  numpy
    in / numpy out — used at the round-step call sites (launch/train.py,
    runtime/driver.py) so the static-k branch lowered for a level matches
    the Q the devices ran."""
    lv = np.sort(np.unique(np.asarray(levels, np.float64)))
    th = np.asarray(theta, np.float64)
    if np.any(th > lv[-1] + 1e-9):
        raise ValueError(
            f"theta {float(np.max(th))} above the largest level "
            f"{float(lv[-1])}: the theta_levels grid must cover every "
            f"theta the controller can emit (rounding DOWN would ship "
            f"fewer coordinates than Q kept)")
    idx = np.minimum(np.searchsorted(lv, th, side="left"), len(lv) - 1)
    return lv[idx].astype(np.float32)


def cluster_levels_from_theta(theta, levels, cluster_of):
    """Static per-CLUSTER wire levels for the sparse gossip path.

    Quantizes each device's theta UP to the level grid, then takes the max
    level within each cluster: the cluster's outgoing gossip payload must
    carry every coordinate any of its members shipped.  Returns a plain
    tuple of EXACT grid floats (not float32 round-trips — the round-step
    validates membership in ``theta_levels`` and the call sites key their
    per-assignment jit cache on the tuple, DESIGN.md §Static-k)."""
    q = quantize_theta(theta, levels)  # float32, validated in-grid
    lv = np.sort(np.unique(np.asarray(levels, np.float64)))
    cl = np.asarray(cluster_of)
    out = []
    for c in range(int(cl.max()) + 1):
        m = np.max(q[cl == c])
        out.append(float(lv[int(np.argmin(np.abs(lv - m)))]))
    return tuple(out)
