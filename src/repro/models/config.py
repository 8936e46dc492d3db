"""Model + parallelism configuration shared by every architecture."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from repro.core.groups import DiompGroup
from repro.distributed.buckets import DEFAULT_BUCKET_BYTES

__all__ = ["ModelConfig", "ParallelCtx"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One architecture.  Field names follow the assignment table."""

    name: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int               # 0 for attention-free archs
    kv_heads: int = 0
    head_dim: int = 0            # derived if 0: d_model // num_heads
    d_ff: int = 0
    vocab_size: int = 0

    # attention flavor
    attention: str = "gqa"       # gqa | mla | none
    causal: bool = True          # False for encoder-only
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0   # partial rotary (stablelm/glm)

    # MoE
    moe: bool = False
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    shared_experts: int = 0
    first_k_dense: int = 0       # deepseek: leading dense layers
    capacity_factor: float = 1.25
    mtp: bool = False            # deepseek multi-token prediction head

    # MLA (deepseek)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0

    # SSM / RWKV / hybrid
    ssm_state: int = 0
    ssm_heads: int = 0
    conv_width: int = 4
    attn_every: int = 0          # zamba2: shared attn block period
    rwkv_head_dim: int = 64

    # VLM / audio frontends are STUBS: input_specs() hands pre-computed
    # patch/frame embeddings of this many prefix positions.
    prefix_tokens: int = 0

    # numerics
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.num_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # -- derived sizes ---------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    def param_count(self) -> int:
        """Total parameters (exact, from the schema)."""
        from . import schema  # local import to avoid cycle

        total = 0
        for s in schema.build_schema(self).values():
            n = 1
            for d in s.shape:
                n *= int(d)
            total += n
        return total

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top-k + shared only)."""
        if not self.moe:
            return self.param_count()
        from . import schema

        total = 0
        for s in schema.build_schema(self).values():
            n = 1
            for d in s.shape:
                n *= d
            if s.per_expert:
                n = n // max(self.num_experts, 1) * (
                    self.experts_per_token + self.shared_experts
                )
            total += n
        return total


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Static parallel layout for one mesh — sizes + DiOMP group handles.

    Built once per (mesh, config); passed into the shard_map'd step so every
    layer knows its local tile sizes *statically* and which group each
    collective targets.
    """

    tp: int                       # size of the "model" axis
    fsdp: int                     # size of the "data" axis (ZeRO-3 shard)
    dp: int                       # total data parallel = pod * data
    pods: int
    tp_group: DiompGroup
    fsdp_group: DiompGroup
    dp_group: DiompGroup
    ep_group: DiompGroup
    world: DiompGroup
    device: object                # a device of the mesh: a layer loop that
    #                               carries a cache keeps it in the layout
    #                               this device stores it in
    pod_group: Optional[DiompGroup] = None

    # knobs (the §Perf hillclimb surface)
    dp_backend: str = "hierarchical"   # flat | hierarchical
    grad_codec: str = "none"           # none | int8 | topk
    bucket_bytes: int = DEFAULT_BUCKET_BYTES  # DP grad bucket size; grads
    #                                    are packed into flat f32 buckets of
    #                                    this many bytes per (group, dtype,
    #                                    dup) partition and reduced whole-
    #                                    bucket through one communicator
    #                                    handle.  0 disables bucketing (the
    #                                    per-param baseline path).
    overlap_grad_reduce: bool = True   # reduce-scatter bucket partial sums
    #                                    inside the microbatch accumulation
    #                                    scan (carry holds 1/|group| shards),
    #                                    one invariant all-gather per bucket
    #                                    after the scan; requires bucketing,
    #                                    microbatch > 1 and grad_codec="none"
    use_ring_matmul: bool = False      # Cannon-style TP matmul overlap
    ring_impl: str = "auto"            # auto | fused (bidirectional, planner-
    #                                    scheduled) | host (unidirectional XLA-
    #                                    overlap loop); resolved by the step
    #                                    builders via plan.resolve_ring_impl
    dispatch_impl: str = "auto"        # MoE dispatch: auto (-> a2a, the host
    #                                    collective capacity path) | a2a |
    #                                    fused (dropless one-sided ring,
    #                                    combine overlapped under the expert
    #                                    GEMMs) | host (same puts serialized);
    #                                    resolved by the step builders via
    #                                    plan.resolve_dispatch_impl.  The
    #                                    dropless modes are opt-in: they keep
    #                                    tokens the capacity path would drop,
    #                                    so they change the numbers.
    seq_parallel: str = "auto"         # self-attention context strategy:
    #                                    auto (-> allgather) | allgather
    #                                    (materialize full K/V per rank, one
    #                                    bulk collective) | ring (fused ring
    #                                    attention: K/V stripes rotate as
    #                                    one-sided puts folded with the
    #                                    online-softmax merge, O(T/n) memory);
    #                                    resolved by the step builders via
    #                                    plan.resolve_seq_parallel
    remat: bool = True
    microbatch: int = 1                # grad-accumulation factor
    seq_shard: bool = False            # sequence parallelism for norms/residual
    explicit_dp: bool = True           # DP reduction through OMPCCL (DiOMP)
    #                                    vs XLA-implicit (the MPI+X baseline)
    inference: bool = False            # serve steps: no AD; gathers use the
    #                                    invariant all-gather (exact vma typing)
    expert2d: bool = False             # MoE experts sharded over model x data
    #                                    (combined-group a2a; no d-gathers)
    fsdp_params: bool = True           # False (inference): dense weights stay
    #                                    TP-sharded only — no ZeRO-3 gathers
    gather_codec: str = "none"         # "int8": quantize ZeRO-3 weight
    #                                    gathers (2x wire; straight-through
    #                                    estimator keeps grads flowing)
    layout: str = "tp"                 # "tp" (default) | "dp_only" (no TP:
    #                                    batch over every axis; small models)

    @classmethod
    def from_mesh(cls, mesh: Mesh, **knobs) -> "ParallelCtx":
        from repro.core.groups import standard_groups

        g = standard_groups(mesh)
        shape = dict(mesh.shape)
        knobs["device"] = mesh.devices.flat[0]
        tp = shape.get("model", 1)
        fsdp = shape.get("data", 1)
        pods = shape.get("pod", 1)
        if knobs.get("layout") == "dp_only":
            # no TP: the model axis joins the data-parallel domain
            dp_axes = tuple(a for a in ("pod", "data", "model")
                            if a in shape)
            return cls(
                tp=1,
                fsdp=fsdp,
                dp=fsdp * pods * tp,
                pods=pods,
                tp_group=DiompGroup((), name="self"),
                fsdp_group=g.get("dp_inner",
                                 DiompGroup(("data",), name="dp_inner")),
                dp_group=DiompGroup(dp_axes, name="dp_all"),
                ep_group=DiompGroup((), name="self"),
                world=g["world"],
                pod_group=g.get("pod"),
                **knobs,
            )
        if knobs.get("expert2d"):
            knobs = dict(knobs)
            knobs["ep_group"] = DiompGroup(("model", "data"), name="ep2d")
            return cls(
                tp=tp, fsdp=fsdp, dp=fsdp * pods, pods=pods,
                tp_group=g.get("tp", DiompGroup(("model",), name="tp")),
                fsdp_group=g.get("dp_inner",
                                 DiompGroup(("data",), name="dp_inner")),
                dp_group=g["dp"],
                world=g["world"],
                pod_group=g.get("pod"),
                **knobs,
            )
        return cls(
            tp=tp,
            fsdp=fsdp,
            dp=fsdp * pods,
            pods=pods,
            tp_group=g.get("tp", DiompGroup(("model",), name="tp")),
            fsdp_group=g.get("dp_inner", DiompGroup(("data",), name="dp_inner")),
            dp_group=g["dp"],
            ep_group=g.get("ep", DiompGroup(("model",), name="ep")),
            world=g["world"],
            pod_group=g.get("pod"),
            **knobs,
        )

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return self.dp_group.axes

    @property
    def ep_size(self) -> int:
        n = 1
        from jax import lax  # static under trace: mesh sizes are known
        # group sizes are static: derive from the stored dp/tp/fsdp counts
        for ax in self.ep_group.axes:
            n *= {"model": self.tp, "data": self.fsdp,
                  "pod": self.pods}[ax]
        return n

    def local_heads(self, cfg: ModelConfig) -> int:
        assert cfg.num_heads % self.tp == 0, (cfg.num_heads, self.tp)
        return cfg.num_heads // self.tp

    def local_kv_heads(self, cfg: ModelConfig) -> int:
        """KV heads per device; GQA groups with kv < tp replicate."""
        return max(1, cfg.kv_heads // self.tp)

    def kv_shard(self, cfg: ModelConfig) -> int:
        """How many ways the kv heads are actually sharded (≤ tp)."""
        return min(cfg.kv_heads, self.tp) if cfg.kv_heads else 1
