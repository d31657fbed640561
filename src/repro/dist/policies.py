"""Sharding policies: mesh axes -> parameter shardings + activation rules.

A ``Policy`` is the single object the models, round step and launchers see;
mesh axis names never leak past this module.  Three axis roles:

  replica_axes  the stacked FL replica dim R (train only) — the axes
                ``mix_local`` runs its ppermute chains over
  batch_axes    request batch dim (serve only)
  tensor_axes   within-layer model parallelism ("model")
  fsdp_axes     parameter sharding for serving (model axis, plus data axes
                for models too big for one 16-way shard)
  seq_axes      sequence dim of decode KV caches (flash-decode sharding)

Parameter-sharding rule (stacked=True, the FL train state): the leading R
dim goes to ``replica_axes``; ONE more dim is sharded over ``tensor_axes``.
Preferred: the LAST dim whose per-shard contiguous run length
((shape[i]/n) * prod(shape[i+1:])) is a multiple of ``block_align`` (the
top-k compression block).  Block-aligned runs mean the shard-local (R, -1)
flattening partitions into EXACTLY the same compression blocks as the
unsharded flattening, so the fused shard_map path is bit-compatible with
the reference (DESIGN.md §Reshape-pitfall).  Latest-dim preference keeps
the scan/layer dim (dim 1 of stacked layer leaves) unsharded — sharding it
would force a cross-shard gather per scan step.  If no dim aligns, the
last divisible dim is used anyway (Q's block partition then shifts, which
preserves the paper's contraction property but not bitwise equality).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import FLTopology


@dataclasses.dataclass(frozen=True)
class Policy:
    mesh: jax.sharding.Mesh
    replica_axes: Tuple[str, ...] = ()
    batch_axes: Tuple[str, ...] = ()
    tensor_axes: Tuple[str, ...] = ()
    fsdp_axes: Tuple[str, ...] = ()
    seq_axes: Tuple[str, ...] = ()
    kind: str = "train"
    block_align: int = 1024  # top-k compression block (HCEFConfig.block_size)

    # -- axis arithmetic ----------------------------------------------------

    def axis_size(self, axes) -> int:
        return int(np.prod([self.mesh.shape[a] for a in axes], initial=1))

    # -- shardings ----------------------------------------------------------

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def _leaf_spec(self, shape, *, stacked: bool) -> P:
        spec = [None] * len(shape)
        if stacked:
            rsize = self.axis_size(self.replica_axes)
            if self.replica_axes and shape and shape[0] % rsize == 0:
                spec[0] = self.replica_axes
            shard_axes, start = self.tensor_axes, 1
        else:
            shard_axes, start = self.fsdp_axes, 0
        size = self.axis_size(shard_axes)
        if not shard_axes or size <= 1:
            return P(*spec)
        divisible = [i for i in range(start, len(shape))
                     if shape[i] % size == 0 and shape[i] >= size]
        aligned = [i for i in divisible
                   if (shape[i] // size) * int(np.prod(shape[i + 1:],
                                                       initial=1))
                   % self.block_align == 0]
        pick = aligned[-1] if aligned else (divisible[-1] if divisible
                                            else None)
        if pick is not None:
            spec[pick] = shard_axes
        return P(*spec)

    def param_shardings(self, tree, *, stacked: bool):
        """NamedSharding tree for a parameter/state pytree.

        stacked=True: leaves are (R, *shape) FL train state; stacked=False:
        plain serving parameters (FSDP over ``fsdp_axes``).
        """
        return jax.tree.map(
            lambda x: NamedSharding(self.mesh,
                                    self._leaf_spec(x.shape, stacked=stacked)),
            tree)

    def attention_specs(self, q_shape, kv_shape):
        """(q_spec, kv_spec) for an attention kernel on (B, S, heads, Dh)
        operands run under shard_map: batch over the batch axes, heads over
        the tensor axes only where both head counts divide (each shard
        then keeps whole GQA groups); sequence and head_dim whole."""
        b = self.batch_axes or None
        if not (b and q_shape[0] % self.axis_size(self.batch_axes) == 0):
            b = None
        n = self.axis_size(self.tensor_axes)
        t = (self.tensor_axes or None) if (
            n > 1 and q_shape[2] % n == 0 and kv_shape[2] % n == 0) else None
        return P(b, None, t, None), P(b, None, t, None)

    # -- activation constraints --------------------------------------------

    def _dim_ok(self, shape, i, axes) -> bool:
        return bool(axes) and shape[i] % self.axis_size(axes) == 0

    def act(self, x, kind: str):
        """``with_sharding_constraint`` by activation kind (models/*.py).

        Called from inside ``jax.vmap(..., spmd_axis_name=replica_axes)``
        during training, so specs here describe the UNBATCHED view; vmap
        inserts the replica axes at the vmapped dim.
        """
        b = self.batch_axes or None
        t = self.tensor_axes or None
        s = self.seq_axes or None
        shape = x.shape
        spec = [None] * x.ndim
        if x.ndim and b and shape[0] % self.axis_size(self.batch_axes) == 0:
            spec[0] = b

        if kind in ("residual", "logits", "ffn_hidden") and x.ndim >= 3:
            if kind != "residual" and self._dim_ok(shape, x.ndim - 1,
                                                  self.tensor_axes):
                spec[x.ndim - 1] = t  # vocab / FFN-hidden over model
        elif kind in ("heads", "ssm_x") and x.ndim >= 3:
            if self._dim_ok(shape, x.ndim - 2, self.tensor_axes):
                spec[x.ndim - 2] = t  # head dim over model
        elif kind == "kv_full":
            pass  # fully gathered over seq for flash attention
        elif kind == "cache" and x.ndim >= 2:
            if self._dim_ok(shape, 1, self.seq_axes):
                spec[1] = s  # flash-decode: KV sequence over seq shards
        elif kind in ("moe_w_in", "moe_w_out") and x.ndim == 3:
            if self._dim_ok(shape, 0, self.tensor_axes):
                spec[0] = t  # experts over model

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))


def make_train_policy(mesh, topo: FLTopology, *, dp_axes) -> Policy:
    """FL training policy: replica dim over the data axes, tensor over model.

    The stacked R dim must tile the data axes: R_local = R / |dp| replicas
    per data slot.  ``inner_dp > 1`` topologies (each FL replica spanning
    inner_dp data slots, e.g. arctic_480b) keep the replica dim REPLICATED
    instead — mix_local then runs dense-locally on every shard.  Anything
    else is a mis-sized topology and fails here, not inside a shard_map.
    """
    dp = tuple(dp_axes)
    dp_size = int(np.prod([mesh.shape[a] for a in dp], initial=1))
    R = topo.num_devices
    if dp and R > 1 and R % dp_size != 0:
        if R * topo.inner_dp == dp_size:
            dp = ()  # replicated replica dim (inner_dp consumes the slots)
        else:
            raise ValueError(
                f"R={R} FL replicas do not tile dp axes {dp} of size "
                f"{dp_size} (inner_dp={topo.inner_dp})")
    tensor = ("model",) if "model" in mesh.axis_names else ()
    return Policy(mesh=mesh, replica_axes=dp, tensor_axes=tensor,
                  fsdp_axes=tensor, seq_axes=tensor, kind="train")


def make_serve_policy(mesh, *, dp_axes, kind: str = "decode",
                      extra_fsdp=()) -> Policy:
    """Serving policy: batch over data axes, FSDP over model (+ extra)."""
    dp = tuple(dp_axes)
    tensor = ("model",) if "model" in mesh.axis_names else ()
    return Policy(mesh=mesh, batch_axes=dp, tensor_axes=tensor,
                  fsdp_axes=tensor + tuple(extra_fsdp), seq_axes=tensor,
                  kind=kind)
