"""Logical-axis sharding rules (MaxText-style) for the DiOMP-JAX runtime.

Model code annotates every tensor with *logical* axis names ("embed", "mlp",
"heads", "vocab", "expert", "batch", "seq", ...).  The runtime translates
those to *mesh* axes via a rule table — this is the TPU counterpart of
DiOMP's PGAS placement decisions: the centralized mapping table stores the
logical spec, and placement onto the pod topology is one rule lookup.

Rules are ordered: the first mesh axis in a rule's list that exists in the
mesh AND is not already taken by another tensor dim wins.  ``None`` = +
replicated.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "ShardingRules",
    "DEFAULT_RULES",
    "logical_to_spec",
    "named_sharding",
    "param_bytes_per_device",
]


# mesh axes, in the order the production meshes define them
POD, DATA, MODEL = "pod", "data", "model"


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis -> candidate mesh axes (first available wins)."""

    rules: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...]

    def lookup(self, logical: Optional[str], mesh: Mesh, taken: set) -> Optional[object]:
        if logical is None:
            return None
        for name, candidates in self.rules:
            if name != logical:
                continue
            picked: List[str] = []
            for cand in candidates:
                if cand is None:
                    continue
                # a size-1 axis shards nothing: leaving it out keeps the
                # value invariant over it, as the collectives (which skip
                # size-1 groups) expect
                if cand in mesh.shape and mesh.shape[cand] > 1 \
                        and cand not in taken:
                    picked.append(cand)
            if not picked:
                return None
            taken.update(picked)
            return picked[0] if len(picked) == 1 else tuple(picked)
        return None

    def replace(self, logical: str, candidates: Tuple[Optional[str], ...]) -> "ShardingRules":
        """Return a copy with one rule overridden (hillclimb knob)."""
        new = []
        replaced = False
        for name, cands in self.rules:
            if name == logical:
                new.append((name, candidates))
                replaced = True
            else:
                new.append((name, cands))
        if not replaced:
            new.append((logical, candidates))
        return ShardingRules(tuple(new))


# The default placement, mirroring MaxText conventions on a
# ("pod", "data", "model") mesh:
#   * batch over pod+data (hierarchical DP),
#   * d_model ("embed") replicated for activations, FSDP-sharded for weights,
#   * heads / mlp / vocab / expert over "model" (TP / EP),
#   * seq over "model" only for sequence-parallel paths (explicit opt-in).
DEFAULT_RULES = ShardingRules(
    rules=(
        ("batch", (POD, DATA)),
        ("seq", (None,)),
        ("seq_shard", (MODEL,)),        # sequence parallelism (opt-in)
        ("embed", (None,)),             # activations keep d_model whole
        ("embed_fsdp", (DATA,)),        # ZeRO-3 weight shard over data axis
        ("heads", (MODEL,)),
        ("kv_heads", (MODEL,)),
        ("mlp", (MODEL,)),
        ("vocab", (MODEL,)),
        ("expert", (MODEL,)),
        ("expert_mlp", (None,)),
        ("conv_state", (None,)),
        ("ssm_state", (None,)),
        ("stage", (None,)),             # pipeline stages (unused on 2-pod mesh)
    )
)


# Beyond-paper layout variants (the §Perf hillclimb surface):
#
# * EXPERT2D — MoE expert weights sharded over BOTH "model" and "data" on the
#   expert dim (256-way for DeepSeek's 256 experts): each chip owns whole
#   experts with full d/ff, so the per-microbatch ZeRO-3 d-gathers vanish;
#   dispatch runs one all-to-all over the combined (model×data) EP group.
# * DP_ONLY — no tensor parallelism: batch over every mesh axis.  For small
#   dense models whose TP activation all-reduces dominate the roofline.
EXPERT2D_RULES = DEFAULT_RULES.replace("expert", (MODEL, DATA))

DP_ONLY_RULES = ShardingRules(rules=tuple(
    (name, (POD, DATA, MODEL)) if name == "batch" else
    (name, (None,)) if cands and set(cands) <= {MODEL} else
    (name, cands)
    for name, cands in DEFAULT_RULES.rules
))


def rules_for_ctx(ctx) -> ShardingRules:
    """Pick the placement-rule table for a ParallelCtx's layout knobs."""
    if getattr(ctx, "layout", "tp") == "dp_only":
        return DP_ONLY_RULES
    rules = DEFAULT_RULES
    if getattr(ctx, "expert2d", False):
        rules = rules.replace("expert", (MODEL, DATA))
    if not getattr(ctx, "fsdp_params", True):
        # inference weight-stationary: dense weights TP-sharded only
        rules = rules.replace("embed_fsdp", (None,))
    return rules


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
) -> PartitionSpec:
    """Translate a tuple of logical axis names into a PartitionSpec."""
    taken: set = set()
    parts = [rules.lookup(ax, mesh, taken) for ax in logical_axes]
    # trim trailing Nones (canonical PartitionSpec form)
    while parts and parts[-1] is None:
        parts.pop()
    return PartitionSpec(*parts)


def named_sharding(
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, mesh, rules))


def param_bytes_per_device(
    shape: Sequence[int],
    dtype_bytes: int,
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
) -> int:
    """Local shard size in bytes — what GlobalMemory charges the arena."""
    spec = logical_to_spec(logical_axes, mesh, rules)
    parts = list(spec) + [None] * (len(shape) - len(spec))
    n = 1
    for dim, part in zip(shape, parts):
        div = 1
        if part is not None:
            axes = part if isinstance(part, tuple) else (part,)
            for ax in axes:
                div *= mesh.shape[ax]
        n *= -(-dim // div)  # ceil-div: padded shard
    return n * dtype_bytes
