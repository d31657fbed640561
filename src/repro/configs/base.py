"""Configuration dataclasses for models, shapes, FL topology and runs.

Every assigned architecture gets a ``src/repro/configs/<id>.py`` exporting
``CONFIG: ArchBundle``.  ``repro.configs.get_config(name)`` resolves them.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters (superset over all supported families)."""

    name: str
    family: str  # dense | moe | ssm | hybrid | encdec
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_dense_ff: int = 0  # arctic-style parallel dense residual FFN width
    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    expand: int = 2
    conv_width: int = 4
    ssm_chunk: int = 256
    # --- hybrid (recurrentgemma / griffin) ---
    block_pattern: Tuple[str, ...] = ()  # e.g. ("rglru", "rglru", "attn")
    window: int = 0  # local-attention window (0 = full/global)
    lru_width: int = 0
    # --- encoder-decoder ---
    enc_layers: int = 0
    cross_attention: bool = False
    # --- modality frontend stubs ---
    frontend: str = ""  # "" | "vit_stub" | "audio_stub"
    frontend_tokens: int = 0  # number of precomputed embedding positions
    # --- misc ---
    qkv_bias: bool = False
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    rope_theta: float = 10_000.0
    logits_softcap: float = 0.0
    # --- muP-style multipliers (Granite); the defaults add no operation:
    # token embeddings times embedding_multiplier, softmax scale
    # attention_multiplier (0: 1/sqrt(head_dim)), each residual branch
    # times residual_multiplier, logits divided by logits_scaling.  The
    # repr shows one only where it is set (``__repr__``).
    embedding_multiplier: float = field(default=1.0, repr=False)
    attention_multiplier: float = field(default=0.0, repr=False)
    residual_multiplier: float = field(default=1.0, repr=False)
    logits_scaling: float = field(default=1.0, repr=False)
    # --- dtypes ---
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    state_dtype: str = "float32"  # optimizer momentum dtype ("" = no momentum)
    remat: bool = True

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to a multiple of 256 so the embedding table can be
        FSDP-sharded on any mesh axis (MaxText-style padding); padded logit
        columns are masked to -inf before the softmax/CE."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def __repr__(self) -> str:
        """The dataclass repr, with each multiplier shown only where it
        differs from its neutral default: a configuration without them
        reads as before they existed (recorded readings compare it)."""
        shown = [f"{f.name}={getattr(self, f.name)!r}"
                 for f in dataclasses.fields(self)
                 if f.repr or getattr(self, f.name) != f.default]
        return f"{type(self).__name__}({', '.join(shown)})"

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell: (kind, seq, batch)."""

    name: str  # train_4k | prefill_32k | decode_32k | long_500k
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


LM_SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", "train", 4_096, 256),
    ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    ShapeConfig("decode_32k", "decode", 32_768, 128),
    ShapeConfig("long_500k", "decode", 524_288, 1),
)


@dataclass(frozen=True)
class FLTopology:
    """Mapping of the CFEL cluster/device structure onto mesh data axes.

    ``clusters * devices_per_cluster * inner_dp`` must equal the product of
    the mesh's data-parallel axis sizes (|pod| * |data|).
    """

    clusters: int
    devices_per_cluster: int
    inner_dp: int = 1
    backhaul: str = "ring"  # ring | complete | erdos_renyi

    @property
    def num_devices(self) -> int:
        return self.clusters * self.devices_per_cluster

    def validate(self, dp_size: int) -> None:
        tot = self.clusters * self.devices_per_cluster * self.inner_dp
        if tot != dp_size:
            raise ValueError(
                f"FLTopology {self} covers {tot} dp slots, mesh has {dp_size}")


def validate_theta_levels(theta_levels) -> None:
    """Shared sparse-gossip level-grid contract (HCEFConfig and
    runtime.driver.FedSimConfig): non-empty, every level in (0, 1], and
    the largest level exactly covering an uncompressed round —
    ``quantize_theta`` rounds UP and RAISES out-of-grid, so a grid that
    stops short of 1.0 cannot represent any controller theta above its
    max without shipping fewer coordinates than Q kept."""
    if not theta_levels:
        raise ValueError("sparse_gossip requires theta_levels")
    if any(not 0.0 < float(t) <= 1.0 for t in theta_levels):
        raise ValueError(
            f"theta_levels must lie in (0, 1], got {theta_levels}")
    if max(float(t) for t in theta_levels) < 1.0:
        raise ValueError(
            f"theta_levels {theta_levels} do not cover [theta_min, 1.0]: "
            f"the largest level must be 1.0")


@dataclass(frozen=True)
class HCEFConfig:
    """Round structure + controller knobs (paper Sec. 3/5)."""

    tau: int = 4  # local iterations per edge round
    q: int = 4  # edge rounds per global round
    eta: float = 0.05  # local learning rate
    momentum: float = 0.9
    controller: str = "hcef"  # hcef | cef | cef_f | cef_c | mll_sgd
    # compression
    block_size: int = 1024  # block-local top-k block length
    theta_min: float = 0.05
    rho_min: float = 0.1
    # budgets (seconds / joules); None = un-budgeted
    time_budget: Optional[float] = None
    energy_budget: Optional[float] = None
    # --- sparse gossip wire path (DESIGN.md §Static-k) ---
    # Route the fused round step's gossip through sparse_neighbor_exchange:
    # the per-device theta is quantized to theta_levels, one program branch
    # is lowered per level (k must be static under jit), and jax.lax.switch
    # dispatches at runtime, so gossip wire bytes scale with theta.
    sparse_gossip: bool = False
    theta_levels: Tuple[float, ...] = (0.05, 0.1, 0.2, 0.4, 0.6, 0.8, 1.0)
    # f32 | bf16 | int8 | int4 | fp8 (dist/collectives.Wire; the v2
    # formats int4/fp8 ship packed ascending offsets, DESIGN.md §Wire
    # format v2)
    wire_dtype: str = "f32"
    wire_block: int = 1024  # wire-encode slab length (block-local offsets)
    error_feedback: bool = True
    # CHOCO-style wire-side error feedback: gossip payloads carry the
    # difference to a shared neighbor estimate, so wire quantization
    # error scales with the compressed DIFFERENCE rather than ||params||.
    # Requires sparse_gossip; incompatible with overlap staleness and
    # with chaos cluster partitions (the estimates would desync — the
    # round step raises).
    wire_ef: bool = False
    wire_ef_gamma: float = 1.0  # consensus step size (1.0 = plain mix)
    # --- overlapped round engine (DESIGN.md §Overlap contract) ---
    # overlap=True double-buffers the edge models so gossip ppermutes on the
    # PENDING buffer run concurrently with the next round's local steps.
    # staleness=0 waits at the fold boundary (bit-for-bit the synchronous
    # engine); staleness=1 lets stale clusters mix neighbors' stale-by-1
    # means (bounded-stale semi-async).
    overlap: bool = False
    staleness: int = 0

    def __post_init__(self):
        if self.wire_dtype not in ("f32", "bf16", "int8", "int4", "fp8"):
            raise ValueError(f"wire_dtype {self.wire_dtype!r}")
        if self.wire_dtype == "int8" and self.wire_block > 32768:
            raise ValueError(  # int16 block-local offsets wrap past 2^15-1
                f"int8 wire needs wire_block <= 32768, got {self.wire_block}")
        if self.sparse_gossip:
            validate_theta_levels(self.theta_levels)
        if self.staleness not in (0, 1):
            raise ValueError(
                f"staleness must be 0 (synchronous fold) or 1 (bounded "
                f"stale), got {self.staleness}")
        if self.staleness and not self.overlap:
            raise ValueError("staleness > 0 requires overlap=True")
        if self.wire_ef:
            if not self.sparse_gossip:
                raise ValueError("wire_ef requires sparse_gossip=True (the "
                                 "estimates track wire-encoded payloads)")
            if self.staleness:
                raise ValueError(
                    "wire_ef is incompatible with overlap staleness: a "
                    "stale payload would update neighbors' estimates with "
                    "a buffer the sender's own estimate never saw")
        if self.wire_ef_gamma <= 0.0 or self.wire_ef_gamma > 1.0:
            raise ValueError(
                f"wire_ef_gamma must lie in (0, 1], got "
                f"{self.wire_ef_gamma}")


@dataclass(frozen=True)
class ArchBundle:
    """Everything the launcher needs for one assigned architecture."""

    model: ModelConfig
    fl_single: FLTopology  # single-pod (16 data rows)
    fl_multi: FLTopology  # multi-pod (2 pods x 16 data rows)
    shapes: Tuple[ShapeConfig, ...] = LM_SHAPES
    skip_shapes: Tuple[str, ...] = ()  # e.g. ("long_500k",) with reason in notes
    skip_reason: str = ""
    hcef: HCEFConfig = field(default_factory=HCEFConfig)
    source: str = ""


# Skip reason shared by all pure full-attention archs (spec: long_500k is run
# only for sub-quadratic families).
FULL_ATTN_LONG_SKIP = (
    "long_500k requires sub-quadratic attention; this arch is pure "
    "full-attention (see DESIGN.md Arch-applicability)")
