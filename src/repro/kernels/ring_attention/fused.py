"""Fused sequence-parallel ring attention (ROADMAP item 3, paper §4.4).

The all-gather path in :mod:`repro.models.layers` materializes the FULL
K/V on every rank before one local flash pass — O(T) memory per rank and
a bulk collective the scheduler may or may not hide.  This module is the
DiOMP treatment of the same traffic: K/V *stripes* rotate through the
bidirectional ring as one-sided puts while each rank folds
partial-softmax states (:mod:`.kernel`) for the stripes it holds, so
peak memory stays O(T/n) and the exchange of step ``s + 1``'s stripes
rides under step ``s``'s flash block by construction.

Two executions of ONE schedule (:meth:`~repro.kernels.plan.
AttentionRingPlan.schedule` — the matmul ring's step records):

* ``fused_ring_attention_tpu`` — one ``pallas_call`` for the whole ring:
  per-direction VMEM stripe slots, each step's
  ``pltpu.make_async_remote_copy`` started BEFORE the step's flash block,
  a startup neighbor barrier, and ``pl.when`` causal step-skipping —
  ranks holding an only-future stripe spend no FLOPs, which is bitwise
  sound because a fully masked stripe's state is the merge identity.
* ``fused_ring_attention_interpret`` — the CPU-CI emulation: iterates the
  IDENTICAL step records with each RDMA realized as ``ompx_put`` and each
  landing completed by ``ompx_fence`` (differentiable, so the training
  step traces through it).  Every put is recorded against the
  RMATracker's attention windows (:func:`repro.core.rma.
  attention_window_names`) with the same bytes the OMPCCL communicator
  logs — exact put-traffic parity, the Minimod/MoE discipline.

Both fold stripe states in schedule-arrival order, the same chain
:func:`~.ref.ring_attention_ref` replays on one device — so the
equivalence suite asserts bit-equality, not tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backends import payload_bytes
from repro.core.groups import DiompGroup
from repro.core.rma import attention_window_names, ompx_fence, ompx_put
from repro.core.vma import out_struct
from repro.kernels.plan import LANES, VMEM_LIMIT_BYTES, AttentionRingPlan
from .kernel import chain_grads, empty_state, finalize_state, merge_states, \
    scaled_queries, stripe_mask, stripe_state

__all__ = [
    "fused_ring_attention_interpret",
    "fused_ring_attention_tpu",
]


# ---------------------------------------------------------------------------
# the interpret / CPU emulation: identical schedule over ompx_put
# ---------------------------------------------------------------------------


def fused_ring_attention_interpret(
    q, k, v, group: DiompGroup, *, plan: AttentionRingPlan,
    scale=None, q_offset=0, valid_len=None,
):
    """Execute :meth:`AttentionRingPlan.schedule` with ``ompx_put`` as the
    remote copy (inside shard_map; ``q (B, tq_loc, H, D)``, ``k/v
    (B, tk_loc, KH, D/Dv)`` per-rank shards).

    ``plan.overlap=True`` (the fused order): both directions' forwards
    start BEFORE the step's flash block and fence after it — the next
    stripes are in flight during compute, which is what lets XLA's async
    collective-permute hide them.  ``overlap=False`` is the serialized
    "host" listing: put, fence, then compute — same traffic, same merge
    chain, nothing hidden.  Stripes the plan's causal skip would drop are
    folded anyway: fully masked states are the merge identity, so the
    result is bit-identical to the skipping kernel.

    The whole schedule carries a hand-written VJP (:func:`~.kernel.
    chain_grads`): autodiff's ring transpose would accumulate each K/V
    shard's cotangent in a different f32 add order than the oracle's
    slice transpose, breaking the gradient bit contract.  The backward
    replays the arrivals with plain ``lax.ppermute`` (no tracker
    double-count, no chaos reinjection), routes each stripe's cotangent
    back to its owner, and every execution sums contributions in ONE
    canonical order — own stripe, then clockwise deliveries by ascending
    step, then counter-clockwise.
    """
    from repro.core.context import default_context

    ax = group.axes[0]
    n = plan.n
    B, tq, H, D = q.shape
    tk = k.shape[1]
    KH = plan.kh
    if scale is None:
        scale = D ** -0.5
    me = lax.axis_index(ax)
    q0 = jnp.asarray(q_offset) + (me * tq if plan.q_sharded else 0)
    q_pos = q0.reshape(-1, 1) + jnp.arange(tq)[None, :]
    folds = plan.fold_steps()
    fidx = {f: i for i, f in enumerate(folds)}
    # Fold-order visibility masks: exact boolean math, built outside the
    # custom-VJP boundary and passed as an aux input with zero cotangent
    # (they absorb the possibly-traced q_offset/valid_len).
    masks = []
    for dirn, s in folds:
        src = lax.rem(me - s + n, n) if dirn == "cw" else lax.rem(me + s, n)
        vis = stripe_mask(tk, q_pos=q_pos, k_start=src * tk,
                          causal=plan.causal, valid_len=valid_len)
        masks.append(jnp.broadcast_to(vis, (B, tq, tk)))
    masks = jnp.stack(masks).astype(jnp.float32)

    def run(q, k, v, masks):
        qg = scaled_queries(q, KH, scale)
        state = empty_state(qg, v)

        def fold(state, k_str, v_str, i):
            blk = stripe_state(qg, k_str, v_str, vis=masks[i])
            return merge_states(state, blk)

        if n == 1:
            return finalize_state(fold(state, k, v, 0), q.dtype)

        tracker = default_context().rma
        cw_w, ccw_w = attention_window_names(group, n, plan.direction)

        def put(win, k_str, v_str, shift):
            tracker.ensure(win)
            tracker.on_put(win, payload_bytes(k_str))
            tracker.on_put(win, payload_bytes(v_str))
            return ompx_put(k_str, group, shift=shift), \
                ompx_put(v_str, group, shift=shift)

        def land(win, k_str, v_str):
            k_str, v_str = ompx_fence(k_str, v_str)
            tracker.on_fence(win)
            tracker.on_read(win)
            return k_str, v_str

        kcw = kccw = k
        vcw = vccw = v
        for st in plan.schedule():
            s = st.index
            # forwards first: step s+1's stripes fly under this step's block
            kcw_n, vcw_n = put(cw_w[s], kcw, vcw, 1) if st.send_cw \
                else (kcw, vcw)
            kccw_n, vccw_n = put(ccw_w[s], kccw, vccw, -1) if st.send_ccw \
                else (kccw, vccw)
            if not plan.overlap:  # serialized listing: land before computing
                if st.send_cw:
                    kcw_n, vcw_n = land(cw_w[s], kcw_n, vcw_n)
                if st.send_ccw:
                    kccw_n, vccw_n = land(ccw_w[s], kccw_n, vccw_n)
            if st.compute_cw:
                state = fold(state, kcw, vcw, fidx[("cw", s)])
            if st.compute_ccw:
                state = fold(state, kccw, vccw, fidx[("ccw", s)])
            if plan.overlap:      # next step's stripes must have landed
                if st.send_cw:
                    kcw_n, vcw_n = land(cw_w[s], kcw_n, vcw_n)
                if st.send_ccw:
                    kccw_n, vccw_n = land(ccw_w[s], kccw_n, vccw_n)
            kcw, vcw = kcw_n, vcw_n
            kccw, vccw = kccw_n, vccw_n
        return finalize_state(state, q.dtype)

    @jax.custom_vjp
    def ring(q, k, v, masks):
        return run(q, k, v, masks)

    def ring_fwd(q, k, v, masks):
        return run(q, k, v, masks), (q, k, v, masks)

    def ring_bwd(res, ct):
        q, k, v, masks = res
        G = H // KH
        Dv = v.shape[-1]
        ct32 = ct.astype(jnp.float32).reshape(B, tq, KH, G, Dv)
        qg = scaled_queries(q, KH, scale)
        # replay the arrivals (same values the forward folded)
        stripes = [None] * len(folds)
        if n == 1:
            stripes[0] = (k, v, masks[0])
        else:
            perm_cw = [(j, (j + 1) % n) for j in range(n)]
            perm_ccw = [(j, (j - 1) % n) for j in range(n)]
            kcw = kccw = k
            vcw = vccw = v
            for st in plan.schedule():
                s = st.index
                if st.compute_cw:
                    i = fidx[("cw", s)]
                    stripes[i] = (kcw, vcw, masks[i])
                if st.compute_ccw:
                    i = fidx[("ccw", s)]
                    stripes[i] = (kccw, vccw, masks[i])
                if st.send_cw:
                    kcw = lax.ppermute(kcw, ax, perm_cw)
                    vcw = lax.ppermute(vcw, ax, perm_cw)
                if st.send_ccw:
                    kccw = lax.ppermute(kccw, ax, perm_ccw)
                    vccw = lax.ppermute(vccw, ax, perm_ccw)
        gqg, gks, gvs = chain_grads(qg, stripes, ct32)
        gq = (gqg.reshape(B, tq, H, D) * scale).astype(q.dtype)
        # canonical owner-side accumulation (mirrored by the oracle's VJP)
        gk32, gv32 = gks[folds.index(("cw", 0))], gvs[folds.index(("cw", 0))]
        for want in ("cw", "ccw"):
            for i, (dirn, s) in enumerate(folds):
                if dirn != want or s == 0:
                    continue
                sign = -s if dirn == "cw" else s
                perm = [(j, (j + sign) % n) for j in range(n)]
                gk32 = gk32 + lax.ppermute(gks[i], ax, perm)
                gv32 = gv32 + lax.ppermute(gvs[i], ax, perm)
        return (gq, gk32.astype(k.dtype), gv32.astype(v.dtype),
                jnp.zeros_like(masks))

    ring.defvjp(ring_fwd, ring_bwd)
    return ring(q, k, v, masks)


# ---------------------------------------------------------------------------
# the TPU kernel: one pallas_call for the whole ring
# ---------------------------------------------------------------------------


def _ring_slots(plan: AttentionRingPlan) -> int:
    """Slot count the kernel allocates — same skew argument as the matmul
    ring (``ring_matmul.fused._ring_slots``): the per-step ``rdma.wait()``
    bounds bidirectional neighbor skew to one step, so three buffers
    suffice; unidirectional rings take one slot per step."""
    steps = plan.exchange_steps
    need = min(steps + 1, 3) if plan.direction == "bidi" else steps + 1
    return max(plan.slots, need)


def _fold_stripe(q2, k2, v2, m, l, acc, *, q0, tq: int, k_start,
                 causal: bool, valid_len):
    """One (batch, kv-head) pair's queries against one stripe, merged into
    its (m, l, acc) carry — :func:`~.kernel.stripe_state` then
    :func:`~.kernel.merge_states` (``exact=False``) on 2-D operands, so
    Mosaic sees plain matmuls.

    ``q2 (G·tq, D)`` pre-scaled f32 queries, row ``g·tq + t`` at position
    ``q0 + t``; ``k2/v2 (tk, D/Dv)``; ``m, l (G·tq, 1)``; ``acc (G·tq, Dv)``.
    """
    f32 = jnp.float32
    s = lax.dot_general(q2, k2.astype(f32), (((1,), (1,)), ((), ())),
                        preferred_element_type=f32)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    vis = None
    if valid_len is not None:
        vis = k_pos < valid_len
    if causal:
        q_pos = q0 + lax.rem(lax.broadcasted_iota(jnp.int32, s.shape, 0), tq)
        vis = k_pos <= q_pos if vis is None else vis & (k_pos <= q_pos)
    if vis is not None:
        s = jnp.where(vis, s, -jnp.inf)
    mb = s.max(axis=-1, keepdims=True)           # -inf on fully masked rows
    p = jnp.exp(s - jnp.where(mb == -jnp.inf, 0.0, mb))
    if vis is not None:
        p = jnp.where(vis, p, 0.0)
    lb = p.sum(axis=-1, keepdims=True)
    ab = lax.dot_general(p, v2.astype(f32), (((1,), (0,)), ((), ())),
                         preferred_element_type=f32)
    mn = jnp.maximum(m, mb)
    mn_safe = jnp.where(mn == -jnp.inf, 0.0, mn)
    c1 = jnp.where(m == -jnp.inf, 0.0, jnp.exp(m - mn_safe))
    c2 = jnp.where(mb == -jnp.inf, 0.0, jnp.exp(mb - mn_safe))
    return mn, l * c1 + lb * c2, acc * c1 + ab * c2


def _fused_attention_kernel(q_ref, k_ref, v_ref, o_ref,
                            kbufs, vbufs, macc, lacc, oacc,
                            ksend, krecv, vsend, vrecv,
                            *, axis: str, plan: AttentionRingPlan,
                            scale: float):
    """Kernel body; the schedule is baked statically, ranks are traced.

    Head-major operands: ``q_ref (B, KH, G·tq, D)`` (GQA groups stacked
    along the rows), ``k_ref/v_ref (B, KH, tk, D/Dv)``.  ``kbufs/vbufs``:
    VMEM (2, slots, B, KH, tk, D/Dv) stripe slots per direction
    (0 = clockwise, 1 = counter-clockwise); ``macc/lacc/oacc`` the f32
    (m, l, acc) merge carry.  Step ``s + 1``'s RDMAs start before step
    ``s``'s stripe folds; ``pl.when`` skips the folds of stripes the
    causal plan proves fully masked (their states are the merge identity,
    so the carry equals the non-skipping emulation's).
    """
    n, slots = plan.n, _ring_slots(plan)
    B, KH, rows, _ = q_ref.shape
    tq = rows // (plan.h // plan.kh)
    tk = k_ref.shape[2]
    me = lax.axis_index(axis)
    right = lax.rem(me + 1, n)
    left = lax.rem(me + n - 1, n)

    if n > 1:
        # startup barrier: both neighbors entered the kernel before any
        # RDMA touches their buffers (slot 0 is seeded locally)
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis: left},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis: right},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 2)

        kbufs[0, 0] = k_ref[...]
        kbufs[1, 0] = k_ref[...]
        vbufs[0, 0] = v_ref[...]
        vbufs[1, 0] = v_ref[...]

    q0 = (jnp.int32(plan.q_offset or 0)
          + (me * tq if plan.q_sharded else 0))
    macc[...] = jnp.full(macc.shape, -jnp.inf, jnp.float32)
    lacc[...] = jnp.zeros(lacc.shape, jnp.float32)
    oacc[...] = jnp.zeros(oacc.shape, jnp.float32)

    def fold(stream: int, slot: int, src):
        def pair(i, carry):
            b, h = i // KH, i % KH
            k2 = k_ref[b, h] if n == 1 else kbufs[stream, slot, b, h]
            v2 = v_ref[b, h] if n == 1 else vbufs[stream, slot, b, h]
            m, l, a = _fold_stripe(
                q_ref[b, h].astype(jnp.float32) * scale, k2, v2,
                macc[b, h], lacc[b, h], oacc[b, h], q0=q0, tq=tq,
                k_start=src * tk, causal=plan.causal,
                valid_len=plan.valid_len)
            macc[b, h] = m
            lacc[b, h] = l
            oacc[b, h] = a
            return carry
        lax.fori_loop(0, B * KH, pair, 0)

    def wanted(src):
        # the traced twin of plan.computes(me, src): skip only stripes the
        # plan proves fully masked for my (static-offset) query range
        ok = jnp.bool_(True)
        if plan.valid_len is not None:
            ok &= src * tk < plan.valid_len
        if plan.causal and plan.q_offset is not None:
            q_hi = q0 + tq - 1
            ok &= src * tk <= q_hi
        return ok

    for st in plan.schedule():
        slot = st.index % slots
        nxt = (st.index + 1) % slots
        rdmas = []
        if st.send_cw:    # my cw stripes -> right neighbor's next cw slots
            for bufs, ss, rs in ((kbufs, ksend, krecv),
                                 (vbufs, vsend, vrecv)):
                rdma = pltpu.make_async_remote_copy(
                    src_ref=bufs.at[0, slot], dst_ref=bufs.at[0, nxt],
                    send_sem=ss.at[0, slot], recv_sem=rs.at[0, nxt],
                    device_id={axis: right},
                    device_id_type=pltpu.DeviceIdType.MESH)
                rdma.start()
                rdmas.append(rdma)
        if st.send_ccw:   # my ccw stripes -> left neighbor's next ccw slots
            for bufs, ss, rs in ((kbufs, ksend, krecv),
                                 (vbufs, vsend, vrecv)):
                rdma = pltpu.make_async_remote_copy(
                    src_ref=bufs.at[1, slot], dst_ref=bufs.at[1, nxt],
                    send_sem=ss.at[1, slot], recv_sem=rs.at[1, nxt],
                    device_id={axis: left},
                    device_id_type=pltpu.DeviceIdType.MESH)
                rdma.start()
                rdmas.append(rdma)

        # stripe folds on the CURRENT slots overlap the in-flight stripes
        if st.compute_cw:
            src = lax.rem(me - st.index + n, n)
            pl.when(wanted(src))(lambda s=slot, r=src: fold(0, s, r))
        if st.compute_ccw:
            src = lax.rem(me + st.index, n)
            pl.when(wanted(src))(lambda s=slot, r=src: fold(1, s, r))

        for rdma in rdmas:    # next step's stripes must have landed
            rdma.wait()

    # finalize (``finalize_state``, exact=False)
    o_ref[...] = (oacc[...] / jnp.maximum(lacc[...], 1e-30)).astype(
        o_ref.dtype)


def _lanes(d: int) -> int:
    return -(-d // LANES) * LANES


def fused_ring_attention_resident_bytes(plan: AttentionRingPlan,
                                        dtype) -> int:
    """VMEM the compiled kernel holds at once: q, k, v and output, the
    stripe slots of both directions, and the f32 merge carry (``m``/``l``
    one lane-padded column per query row), head dims padded to lanes."""
    item = jnp.dtype(dtype).itemsize
    d, dv = _lanes(plan.d), _lanes(plan.dv)
    rows = plan.b * plan.h * plan.tq_loc
    kv = plan.b * plan.kh * plan.tk_loc * (d + dv)
    return ((rows * (d + dv) + (1 + 2 * _ring_slots(plan)) * kv) * item
            + rows * (2 * LANES + dv) * 4)


def fused_ring_attention_tpu(q, k, v, *, axis: str,
                             plan: AttentionRingPlan, scale=None):
    """The compiled fused kernel (requires a real TPU backend).

    Restrictions recorded here rather than hidden: the ring must be a
    single mesh axis (``device_id`` names the peer's index along it), the
    kernel needs STATIC ``q_offset``/``valid_len`` (they are plan fields
    baked into the masks; traced offsets route to the emulation), and
    every operand stays resident in VMEM
    (:func:`fused_ring_attention_resident_bytes` must fit
    ``VMEM_LIMIT_BYTES``; the dispatcher routes larger shapes to the
    emulation).  The kernel works head-major, with the head dims zero-padded
    to whole lanes (a remote copy cannot slice a partial lane tile; zero
    columns change neither ``q·k`` nor the kept ``Dv`` outputs, but they do
    ride the wire).  The layout changes happen here, outside the kernel.
    """
    B, tq, H, D = q.shape
    tk, KH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    if scale is None:
        scale = D ** -0.5
    slots = _ring_slots(plan)
    G = H // KH
    Dp, Dvp = _lanes(D), _lanes(Dv)

    def lanes(x, width):
        pad = width - x.shape[-1]
        return x if not pad else jnp.pad(x, ((0, 0),) * 3 + ((0, pad),))

    qh = lanes(q.reshape(B, tq, KH, G, D).transpose(0, 2, 3, 1, 4).reshape(
        B, KH, G * tq, D), Dp)
    kh = lanes(k.transpose(0, 2, 1, 3), Dp)
    vh = lanes(v.transpose(0, 2, 1, 3), Dvp)
    out = pl.pallas_call(
        functools.partial(_fused_attention_kernel, axis=axis, plan=plan,
                          scale=scale),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
        out_shape=out_struct((B, KH, G * tq, Dvp), q.dtype, qh, kh, vh),
        scratch_shapes=[
            pltpu.VMEM((2, slots, B, KH, tk, Dp), k.dtype),
            pltpu.VMEM((2, slots, B, KH, tk, Dvp), v.dtype),
            pltpu.VMEM((B, KH, G * tq, 1), jnp.float32),
            pltpu.VMEM((B, KH, G * tq, 1), jnp.float32),
            pltpu.VMEM((B, KH, G * tq, Dvp), jnp.float32),
            pltpu.SemaphoreType.DMA((2, slots)),
            pltpu.SemaphoreType.DMA((2, slots)),
            pltpu.SemaphoreType.DMA((2, slots)),
            pltpu.SemaphoreType.DMA((2, slots)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=2, vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(qh, kh, vh)
    return out[..., :Dv].reshape(B, KH, G, tq, Dv).transpose(
        0, 3, 1, 2, 4).reshape(B, tq, H, Dv)
