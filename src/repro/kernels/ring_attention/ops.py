"""Public wrapper for sequence-parallel ring attention.

Called inside shard_map with per-rank shards: ``q (B, tq_loc, H, D)``,
``k/v (B, tk_loc, KH, D/Dv)`` -> ``(B, tq_loc, H, Dv)``.  The usual knob
conventions apply: ``plan=None`` asks the shared
:class:`~repro.kernels.plan.OverlapPlanner` for slot/block sizes
(``StreamPool.plan_slots`` contract), ``impl`` resolves ``"auto"``/None to
the ``"fused"`` overlap order (``"host"`` is the serialized listing), and
``interpret=None`` resolves from the backend at call time — compiled on
TPU, the differentiable ``ompx_put`` emulation elsewhere.  Shapes whose
VMEM-resident bytes exceed ``VMEM_LIMIT_BYTES`` take the emulation on TPU
too (XLA compiles it there).

Traced ``q_offset``/``valid_len`` (dynamic chunked prefill) are legal:
the plan then disables static causal step-skipping and the masks handle
everything — but only the emulation can run them; the TPU kernel bakes
static offsets and raises otherwise.

Deliberately not jitted here: the callers (model steps) are jitted.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.core.groups import DiompGroup
from repro.kernels.plan import VMEM_LIMIT_BYTES, AttentionRingPlan, \
    default_planner, resolve_interpret
from .fused import (fused_ring_attention_interpret,
                    fused_ring_attention_resident_bytes,
                    fused_ring_attention_tpu)

__all__ = ["ring_attention", "resolve_attention_impl"]


def resolve_attention_impl(impl: Optional[str]) -> str:
    """``"auto"``/None pick the fused overlap order; explicit ``"host"``
    (serialized put-fence-compute listing) and ``"fused"`` pass through —
    the same convention as the ring matmul's knob."""
    if impl in (None, "auto"):
        return "fused"
    if impl in ("host", "fused"):
        return impl
    raise ValueError(f"unknown ring attention impl {impl!r}")


def _static_int(val) -> bool:
    return val is not None and not isinstance(val, jax.core.Tracer)


def ring_attention(
    q, k, v, group: DiompGroup, *,
    causal: bool = True,
    q_offset=0,
    valid_len=None,
    scale: Optional[float] = None,
    q_sharded: bool = True,
    plan: Optional[AttentionRingPlan] = None,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
):
    """The fused ring attention entry point (inside shard_map)."""
    from repro.core.compat import axis_size

    if len(group.axes) != 1:
        raise ValueError(
            f"ring attention needs a single-axis group, got {group.axes}")
    n = axis_size(group.axes[0])
    B, tq, H, D = q.shape
    tk, KH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    if H % KH:
        raise ValueError(f"H={H} not divisible by kv heads {KH}")
    mode = resolve_attention_impl(impl)
    if plan is None:
        plan = default_planner().plan_ring_attention(
            B, tq, tk, H, KH, D, Dv, q.dtype, n,
            causal=causal, q_sharded=q_sharded,
            q_offset=int(q_offset) if _static_int(q_offset) else None,
            valid_len=int(valid_len) if _static_int(valid_len) else None,
            overlap=mode == "fused")
    if plan.n != n:
        raise ValueError(f"plan for n={plan.n} used on a ring of {n}")
    if plan.overlap != (mode == "fused"):
        plan = dataclasses.replace(plan, overlap=mode == "fused")
    if resolve_interpret(interpret) or fused_ring_attention_resident_bytes(
            plan, q.dtype) > VMEM_LIMIT_BYTES:
        return fused_ring_attention_interpret(
            q, k, v, group, plan=plan, scale=scale,
            q_offset=q_offset, valid_len=valid_len)
    if not _static_int(q_offset) or (valid_len is not None
                                     and not _static_int(valid_len)):
        raise ValueError(
            "the TPU ring attention kernel bakes q_offset/valid_len into "
            "its masks at trace time; traced offsets need interpret=True "
            "(the ompx_put emulation)")
    return fused_ring_attention_tpu(q, k, v, axis=group.axes[0], plan=plan,
                                    scale=scale)
