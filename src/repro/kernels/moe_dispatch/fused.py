"""Fused dropless MoE dispatch — in-kernel all-to-all over asymmetric regions.

The host collective path (``moe_block``'s ``"a2a"`` mode) exposes the full
token exchange on both sides of the expert GEMMs and silently drops
capacity overflow.  This module is the DiOMP treatment of the same traffic:

* token→expert routing scatters rows into per-expert landing layouts whose
  capacities are **asymmetric** — sized per expert from measured load by
  :meth:`~repro.kernels.plan.OverlapPlanner.plan_alltoall` (largest-
  remainder split, the Minimod decomposition), so the dispatch is
  **dropless** by construction (``caps[e] >= load[e]``);
* the exchange is a ring of one-sided ``ompx_put``\\ s: step ``s`` puts the
  block for the rank ``s + 1`` ahead, runs the expert GEMMs on the block
  that landed from the rank ``s`` behind, and puts the *previous* result
  straight back to its source — the return combine rides UNDER the current
  GEMM;
* every put is recorded against both the OMPCCL byte log and the
  RMATracker's MoE dispatch/combine windows
  (:func:`repro.core.rma.dispatch_window_names`), so tests assert exact
  put-traffic parity like the Minimod driver does.

Two executions of ONE schedule (:meth:`~repro.kernels.plan.AllToAllPlan.
schedule`): the compiled TPU kernel (``pltpu.make_async_remote_copy``
started before each step's GEMMs) and the differentiable interpret
emulation every CPU CI run and training step traces through.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backends import payload_bytes
from repro.core.groups import DiompGroup
from repro.core.rma import dispatch_window_names, ompx_fence, ompx_put
from repro.core.vma import out_struct, zeros_varying
from repro.kernels.plan import VMEM_LIMIT_BYTES, AllToAllPlan
from .ref import expert_mlp_ref

__all__ = [
    "dispatch_buffers",
    "fused_moe_dispatch_interpret",
    "fused_moe_dispatch_tpu",
]


# ---------------------------------------------------------------------------
# shared routing -> buffer layout (both executions, and the oracle tests)
# ---------------------------------------------------------------------------


def dispatch_buffers(toks, top_e, top_w, plan: AllToAllPlan):
    """Scatter routed rows into the padded per-destination wire blocks.

    Slot assignment is ``moe_block``'s running-index cumsum, but checked
    against the plan's per-expert **asymmetric** capacity instead of one
    global ``cap`` — with capacities sized from measured load the ``keep``
    mask is all-true and the dispatch drops nothing.  Returns

    * ``buf (ep, E_loc, cap_pad, d)`` — destination-rank-major wire
      blocks (global expert order; rows beyond ``caps[e]`` stay zero),
    * ``addr (t_loc·k,)`` — flat row address of each (token, choice) in
      the global ``(E·cap_pad, d)`` landing layout (combine unpermute),
    * ``gates (t_loc·k, 1)`` — combine weights, zeroed for dropped rows,
    * ``dropped ()`` — f32 count of capacity-overflow drops (0 when the
      plan is dropless).
    """
    t_loc, d = toks.shape
    k = top_e.shape[-1]
    E, C = plan.E, plan.cap_pad

    e_flat = top_e.reshape(-1)                                # (t_loc*k,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    slot = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    slot = slot.sum(-1)
    caps = jnp.asarray(plan.caps, dtype=jnp.int32)[e_flat]
    keep = slot < caps
    addr = e_flat * C + jnp.clip(slot, 0, C - 1)

    buf = zeros_varying((E * C, d), toks.dtype, toks)
    src = jnp.repeat(toks, k, axis=0)
    buf = buf.at[jnp.where(keep, addr, E * C - 1)].add(
        jnp.where(keep[:, None], src, 0.0).astype(toks.dtype), mode="drop")
    gates = (keep[:, None] * top_w.reshape(-1)[:, None]).astype(toks.dtype)
    dropped = jnp.sum(~keep).astype(jnp.float32)
    return buf.reshape(plan.ep, plan.E_loc, C, d), addr, gates, dropped


def _combine(full, addr, gates, t_loc: int, d: int):
    """Unpermute the landed expert outputs back to (token, choice) order
    and gate-combine: ``full (ep, E_loc, C, d)`` -> ``(t_loc, d)``."""
    ret = full.reshape(-1, d)
    picked = ret[addr] * gates
    return picked.reshape(t_loc, -1, d).sum(axis=1)


# ---------------------------------------------------------------------------
# the interpret / CPU emulation: identical schedule over ompx_put
# ---------------------------------------------------------------------------


def fused_moe_dispatch_interpret(
    toks, top_e, top_w, wg, wu, wd, group: DiompGroup, *,
    plan: AllToAllPlan, mlp: Optional[Callable] = None,
):
    """Execute :meth:`AllToAllPlan.schedule` with ``ompx_put`` as the RDMA.

    Every dispatch put starts BEFORE the GEMM it overlaps and every
    combine put rides under the next step's GEMM — the same order the TPU
    kernel hard-codes, which is what lets XLA's async collective-permute
    hide the exchange.  Differentiable end to end (ppermute, scatter-add,
    gather and the fence's identity-JVP all transpose), so this is the
    path the training step traces on CPU.  Returns ``(combined (t_loc,
    d), dropped ())``.
    """
    if mlp is None:
        mlp = expert_mlp_ref
    from repro.core.context import default_context

    ax = group.axes[0]
    ep, E_loc, C = plan.ep, plan.E_loc, plan.cap_pad
    t_loc, d = toks.shape
    me = lax.axis_index(ax)

    buf, addr, gates, dropped = dispatch_buffers(toks, top_e, top_w, plan)

    tracker = default_context().rma
    dwin, cwin = dispatch_window_names(group, ep)

    landed = {0: lax.dynamic_slice(
        buf, (me, 0, 0, 0), (1, E_loc, C, d))[0]}
    outs = {}
    rets = {}
    for phase, s in plan.schedule():
        if phase == "put":
            # my block for the rank s ahead, started before this step's GEMM
            blk = lax.dynamic_slice(
                buf, (lax.rem(me + s, ep), 0, 0, 0), (1, E_loc, C, d))[0]
            tracker.ensure(dwin[s - 1])
            tracker.on_put(dwin[s - 1], payload_bytes(blk))
            landed[s] = ompx_put(blk, group, shift=s)
        elif phase == "fence":
            landed[s] = ompx_fence(landed[s])
            tracker.on_fence(dwin[s - 1])
            tracker.on_read(dwin[s - 1])
        elif phase == "gemm":
            outs[s] = mlp(landed[s], wg, wu, wd).astype(toks.dtype)
        elif phase == "ret":
            # previous result back to its source, under the next GEMM
            tracker.ensure(cwin[s - 1])
            tracker.on_put(cwin[s - 1], payload_bytes(outs[s]))
            rets[s] = ompx_put(outs[s], group, shift=-s)
        elif phase == "fence_ret":
            if rets:
                order = sorted(rets)
                fenced = ompx_fence(*[rets[s] for s in order])
                if len(order) == 1:
                    fenced = (fenced,)
                rets = dict(zip(order, fenced))
                tracker.on_fence(*cwin)
                for w in cwin:
                    tracker.on_read(w)
        else:  # pragma: no cover - schedule() emits only the above
            raise ValueError(phase)

    # assemble the landed returns in home-rank-major (global expert) order
    full = zeros_varying((ep, E_loc, C, d), toks.dtype, toks)
    full = lax.dynamic_update_slice(full, outs[0][None], (me, 0, 0, 0))
    for s, blk in rets.items():
        full = lax.dynamic_update_slice(
            full, blk[None], (lax.rem(me + s, ep), 0, 0, 0))
    return _combine(full, addr, gates, t_loc, d), dropped


# ---------------------------------------------------------------------------
# the TPU kernel: one pallas_call for dispatch + GEMMs + combine
# ---------------------------------------------------------------------------


def _grouped_mlp(x, wg_ref, wu_ref, wd_ref):
    """In-kernel grouped expert MLP on one landed block (E_loc, C, d)."""
    outs = []
    for e in range(wg_ref.shape[0]):
        g = jnp.dot(x[e], wg_ref[e], preferred_element_type=jnp.float32)
        u = jnp.dot(x[e], wu_ref[e], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        outs.append(jnp.dot(h, wd_ref[e],
                            preferred_element_type=jnp.float32))
    return jnp.stack(outs).astype(x.dtype)


def _fused_dispatch_kernel(buf_ref, wg_ref, wu_ref, wd_ref, o_ref,
                           stage, ret_stage, send_sems, recv_sems,
                           ret_send_sems, ret_recv_sems,
                           *, axis: str, plan: AllToAllPlan, slots: int):
    """Kernel body; the schedule is baked statically, ranks are traced.

    ``stage``: VMEM (slots, E_loc, C, d) landing slots for the dispatch
    ring (slot ``s % slots`` holds the block from the rank ``s`` behind);
    ``ret_stage`` the symmetric combine staging.  Every device runs the
    same code, so one ``make_async_remote_copy`` per step realizes both my
    outgoing put (to ``me + s``) and the incoming landing (from
    ``me - s``); combine copies write the remote ``o_ref`` at *my* rank
    index — the home-rank-major return layout the host-side combine reads.
    """
    ep = plan.ep
    me = lax.axis_index(axis)

    # startup barrier: every peer entered the kernel before any RDMA
    # touches its stage buffers
    barrier = pltpu.get_barrier_semaphore()
    for r in range(1, ep):
        pltpu.semaphore_signal(barrier, inc=1,
                               device_id={axis: lax.rem(me + r, ep)},
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, ep - 1)

    in_flight = {}      # ring offset -> dispatch rdma (my landing from me-s)
    ret_flight = {}     # staging slot -> combine rdma (waited before slot
    #                     reuse and at the final fence)
    for phase, s in plan.schedule():
        if phase == "put":
            slot = s % slots
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf_ref.at[lax.rem(me + s, ep)],
                dst_ref=stage.at[slot],
                send_sem=send_sems.at[slot], recv_sem=recv_sems.at[slot],
                device_id={axis: lax.rem(me + s, ep)},
                device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            in_flight[s] = rdma
        elif phase == "fence":
            # ONLY step s's landing: the put for s+1 stays in flight under
            # this step's GEMM — that is the overlap
            in_flight.pop(s).wait()
        elif phase == "gemm":
            slot = s % slots
            if slot in ret_flight:   # combine still reading this slot
                ret_flight.pop(slot).wait()
            x = buf_ref[me] if s == 0 else stage[slot]
            y = _grouped_mlp(x, wg_ref, wu_ref, wd_ref)
            if s == 0:
                o_ref[me] = y
            else:
                ret_stage[slot] = y
        elif phase == "ret":
            slot = s % slots
            rdma = pltpu.make_async_remote_copy(
                src_ref=ret_stage.at[slot],
                dst_ref=o_ref.at[me],
                send_sem=ret_send_sems.at[slot],
                recv_sem=ret_recv_sems.at[slot],
                device_id={axis: lax.rem(me - s + ep, ep)},
                device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            ret_flight[slot] = rdma
        elif phase == "fence_ret":
            for rdma in ret_flight.values():
                rdma.wait()
            ret_flight = {}


def fused_moe_dispatch_tpu(toks, top_e, top_w, wg, wu, wd,
                           group: DiompGroup, *, plan: AllToAllPlan):
    """The compiled fused kernel (requires a real TPU backend).

    Restriction recorded here rather than hidden: the EP group must be a
    single mesh axis (``device_id`` names the peer's index along it).  The
    routing scatter and the gated combine stay outside the kernel (cheap,
    token-local); the kernel owns the overlapped exchange + GEMMs.
    """
    ep, E_loc, C = plan.ep, plan.E_loc, plan.cap_pad
    t_loc, d = toks.shape
    f = wg.shape[2]
    slots = max(plan.slots, min(ep, 3))

    buf, addr, gates, dropped = dispatch_buffers(toks, top_e, top_w, plan)
    full = pl.pallas_call(
        functools.partial(_fused_dispatch_kernel, axis=group.axes[0],
                          plan=plan, slots=slots),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM)] * 4,
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
        out_shape=out_struct((ep, E_loc, C, d), toks.dtype, buf, wg, wu,
                             wd),
        scratch_shapes=[
            pltpu.VMEM((slots, E_loc, C, d), toks.dtype),
            pltpu.VMEM((slots, E_loc, C, d), toks.dtype),
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=1, vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(buf, wg, wu, wd)
    return _combine(full, addr, gates, t_loc, d), dropped
