"""Fused in-kernel ring collective matmul (paper §4.4, done below the runtime).

The host-level ring in :mod:`.ops` leaves the overlap to the XLA scheduler:
every step is a separate ``dot`` + ``collective-permute`` HLO and the compiler
*may* run them concurrently.  This module is the schedule made explicit — the
same move the PGAS distributed-OpenMP line of work makes to hide latency below
the runtime layer: ONE ``pallas_call`` executes the whole ring, each step's
remote copy of the next X stripe is an ``pltpu.make_async_remote_copy`` into a
planned VMEM slot, and the copy is started *before* the step's GEMM so the DMA
engines and the MXU run concurrently by construction.

Two executions of ONE schedule (:meth:`repro.kernels.plan.RingPlan.schedule`):

* ``fused_ring_allgather_matmul_tpu`` — the real kernel: double/multi-buffered
  stripe slots per ring direction (slot count from ``OverlapPlanner`` /
  ``StreamPool.plan_slots``, floored at the reuse-safe minimum — see
  ``_ring_slots``), bidirectional RDMA (clockwise stream serves sources behind
  me, counter-clockwise the sources ahead) so the ring finishes in
  ``ceil((n - 1) / 2)`` exchange steps with both ICI directions busy.
* ``fused_ring_allgather_matmul_interpret`` — the CPU-CI emulation: iterates
  the IDENTICAL step records, with each RDMA realized as the one-sided
  ``ompx_put`` (a ``collective-permute`` remote DMA) started before the step's
  GEMM.  Differentiable, runs under ``shard_map`` on any backend, and is what
  the train/serve layers use.

Layout contract matches :func:`.ops.ring_allgather_matmul`: inside shard_map,
``x_local (T/n, K)``, ``w_local (K, N/n)`` -> ``(T, N/n)``.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.groups import DiompGroup
from repro.core.rma import ompx_put
from repro.core.vma import out_struct, zeros_varying
from repro.kernels.plan import (VMEM_LIMIT_BYTES, RingPlan, default_planner,
                                resolve_interpret)
from .ref import matmul_ref

__all__ = [
    "fused_ring_allgather_matmul",
    "fused_ring_resident_bytes",
    "fused_ring_allgather_matmul_interpret",
    "fused_ring_allgather_matmul_tpu",
]


# ---------------------------------------------------------------------------
# the TPU kernel: one pallas_call for the whole ring
# ---------------------------------------------------------------------------


def _ring_slots(plan: RingPlan) -> int:
    """The slot count the TPU kernel actually allocates.

    Slot reuse is made safe by *count*, not by per-step barriers (a shared
    counting barrier semaphore cannot attribute signals to senders, so a
    fast neighbor's step-``s+1`` signal could stand in for the slow
    neighbor's step-``s`` one).  The per-step ``rdma.wait()`` bounds
    neighbor skew on the bidirectional ring to one step — a device cannot
    enter step ``s+1`` before both neighbors' step-``s`` stripes landed —
    so a neighbor reads slot ``(s-1..s) % slots`` while my step-``s`` send
    writes slot ``(s+1) % slots``: three buffers suffice.  Unidirectional
    rings only chain the skew one way around the ring, so they take one
    slot per step (no reuse) — they exist for benchmarking, the fused
    default is bidirectional.
    """
    steps = plan.exchange_steps
    need = min(steps + 1, 3) if plan.direction == "bidi" else steps + 1
    return max(plan.slots, need)


def _fused_ring_kernel(x_ref, w_ref, o_ref, bufs, send_sems, recv_sems,
                       *, axis: str, plan: RingPlan, t_loc: int):
    """Kernel body; the schedule is baked statically, ranks are traced.

    ``bufs``: VMEM (2, slots, t_loc, K) — stripe slots per direction
    (0 = clockwise stream, 1 = counter-clockwise).  Slot ``s % slots``
    holds step ``s``'s stripes; the RDMA for step ``s + 1`` lands in the
    next slot while this step's GEMMs run.
    """
    n, slots = plan.n, _ring_slots(plan)
    my = lax.axis_index(axis)
    right = lax.rem(my + 1, n)
    left = lax.rem(my + n - 1, n)

    # startup barrier: both neighbors entered the kernel before any RDMA
    # touches their buffers (over-signaling from a fast neighbor is benign
    # here — slot 0 is seeded locally, never remotely written)
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id={axis: left},
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_signal(barrier, inc=1, device_id={axis: right},
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 2)

    # seed both streams' slot 0 with the local stripe
    bufs[0, 0] = x_ref[...]
    bufs[1, 0] = x_ref[...]

    def gemm(stream: int, slot: int, src):
        y = lax.dot_general(
            bufs[stream, slot], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[pl.ds(src * t_loc, t_loc), :] = y.astype(o_ref.dtype)

    for st in plan.schedule():
        slot = st.index % slots
        nxt = (st.index + 1) % slots
        rdmas = []
        if st.send_cw:        # my cw stripe -> right neighbor's next cw slot
            rdma = pltpu.make_async_remote_copy(
                src_ref=bufs.at[0, slot], dst_ref=bufs.at[0, nxt],
                send_sem=send_sems.at[0, slot], recv_sem=recv_sems.at[0, nxt],
                device_id={axis: right},
                device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            rdmas.append(rdma)
        if st.send_ccw:       # my ccw stripe -> left neighbor's next ccw slot
            rdma = pltpu.make_async_remote_copy(
                src_ref=bufs.at[1, slot], dst_ref=bufs.at[1, nxt],
                send_sem=send_sems.at[1, slot], recv_sem=recv_sems.at[1, nxt],
                device_id={axis: left},
                device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            rdmas.append(rdma)

        # GEMMs on the CURRENT slot overlap the in-flight stripe transfers
        if st.compute_cw:
            gemm(0, slot, lax.rem(my - st.index + n, n))
        if st.compute_ccw:
            gemm(1, slot, lax.rem(my + st.index, n))

        for rdma in rdmas:    # next step's stripes must have landed
            rdma.wait()


def fused_ring_resident_bytes(t_loc: int, k: int, n_loc: int, dtype,
                              plan: RingPlan) -> int:
    """VMEM the compiled kernel holds at once: the stripe slots of both
    streams, the resident X stripe, W block and (n·t_loc, n_loc) output,
    and one step's f32 GEMM result."""
    item = jnp.dtype(dtype).itemsize
    slots = _ring_slots(plan)
    return ((2 * slots + 1) * t_loc * k + k * n_loc
            + plan.n * t_loc * n_loc) * item + t_loc * n_loc * 4


def fused_ring_allgather_matmul_tpu(x_local, w_local, *, axis: str,
                                    plan: RingPlan):
    """The compiled fused kernel (requires a real TPU backend).

    Restrictions recorded here rather than hidden: the ring must be a single
    mesh axis (``device_id`` names the peer's index along it), and every
    operand stays resident in VMEM (:func:`fused_ring_resident_bytes` must
    fit ``VMEM_LIMIT_BYTES``; the dispatcher routes larger shapes to the
    emulation).
    """
    t_loc, k = x_local.shape
    n_loc = w_local.shape[1]
    slots = _ring_slots(plan)
    return pl.pallas_call(
        functools.partial(_fused_ring_kernel, axis=axis, plan=plan,
                          t_loc=t_loc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
        out_shape=out_struct((plan.n * t_loc, n_loc), x_local.dtype,
                             x_local, w_local),
        scratch_shapes=[
            pltpu.VMEM((2, slots, t_loc, k), x_local.dtype),
            pltpu.SemaphoreType.DMA((2, slots)),
            pltpu.SemaphoreType.DMA((2, slots)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=0, vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(x_local, w_local)


# ---------------------------------------------------------------------------
# the interpret / CPU emulation: identical schedule over ompx_put
# ---------------------------------------------------------------------------


def fused_ring_allgather_matmul_interpret(
    x_local, w_local, group: DiompGroup, *, plan: RingPlan,
    dot: Optional[Callable] = None,
):
    """Execute :meth:`RingPlan.schedule` with ``ompx_put`` as the remote copy.

    Every step starts its forwards BEFORE its GEMMs — the same
    DMA-then-compute order as the kernel, which is exactly what lets XLA's
    async collective-permute overlap the dots.  Differentiable (ppermute,
    dynamic_update_slice and dot all transpose), so this is also the path
    the TP layers train through on CPU.
    """
    if dot is None:
        dot = matmul_ref
    ax = group.axes[0]
    n = plan.n
    idx = lax.axis_index(ax)
    t_loc = x_local.shape[0]
    out = zeros_varying((n * t_loc, w_local.shape[1]), x_local.dtype, x_local)

    cw = ccw = x_local
    for st in plan.schedule():
        # forwards first: step s+1's stripes are in flight during step s's GEMMs
        cw_next = ompx_put(cw, group, shift=1) if st.send_cw else cw
        ccw_next = ompx_put(ccw, group, shift=-1) if st.send_ccw else ccw
        if st.compute_cw:
            src = (idx - st.index) % n
            y = dot(cw, w_local).astype(out.dtype)
            out = lax.dynamic_update_slice(out, y, (src * t_loc, 0))
        if st.compute_ccw:
            src = (idx + st.index) % n
            y = dot(ccw, w_local).astype(out.dtype)
            out = lax.dynamic_update_slice(out, y, (src * t_loc, 0))
        cw, ccw = cw_next, ccw_next
    return out


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def fused_ring_allgather_matmul(
    x_local, w_local, group: DiompGroup, *,
    plan: Optional[RingPlan] = None,
    direction: str = "bidi",
    dot: Optional[Callable] = None,
    interpret: Optional[bool] = None,
):
    """The fused collective matmul entry point (inside shard_map).

    ``plan`` defaults to the process planner's
    :meth:`~repro.kernels.plan.OverlapPlanner.plan_ring_matmul` for the
    traced shapes; ``interpret=None`` resolves from the backend at call
    time (compiled on TPU, emulated elsewhere).  A caller-supplied ``dot``
    carries custom GEMM semantics the in-kernel ``lax.dot_general`` cannot
    honor, and shapes whose resident bytes exceed ``VMEM_LIMIT_BYTES``
    cannot be held by the kernel; both route through the emulation — which
    XLA still compiles (and overlaps) on TPU.
    """
    from repro.core.compat import axis_size

    n = axis_size(group.axes[0])
    if plan is None:
        plan = default_planner().plan_ring_matmul(
            x_local.shape[0], x_local.shape[1], w_local.shape[1],
            x_local.dtype, n, direction=direction)
    if plan.n != n:
        raise ValueError(f"plan for n={plan.n} used on a ring of {n}")
    resident = fused_ring_resident_bytes(
        x_local.shape[0], x_local.shape[1], w_local.shape[1], x_local.dtype,
        plan)
    if resolve_interpret(interpret) or dot is not None \
            or resident > VMEM_LIMIT_BYTES:
        return fused_ring_allgather_matmul_interpret(
            x_local, w_local, group, plan=plan, dot=dot)
    return fused_ring_allgather_matmul_tpu(
        x_local, w_local, axis=group.axes[0], plan=plan)
