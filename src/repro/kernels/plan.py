"""OverlapPlanner — the §3.2 bounded-concurrency contract made concrete.

``StreamPool.plan_slots`` answers ONE question ("how many DMA buffers may a
kernel keep in flight for a given working set?"); this module turns that
answer into the *concrete* slot/tile plans the Pallas kernels execute, so the
documented contract ("plan_slots is queried by the kernels' ops.py wrappers")
is real rather than aspirational:

* :class:`RingPlan` — the full schedule of the fused collective matmul: how
  many VMEM stripe slots per ring direction, which stripe each step computes,
  which buffers each step forwards.  The bidirectional ring covers the
  ``n - 1`` remote stripes in ``ceil((n - 1) / 2)`` exchange steps: the
  clockwise stream serves the "left half" of the ring (sources behind me),
  the counter-clockwise stream the "right half" (sources ahead), and both
  ICI link directions carry one stripe per step.
* matmul tile / flash-attention block / stencil slab planning — each kernel's
  working set is sized against the VMEM budget with ``plan_slots`` buffers
  reserved for the pipeline, replacing the former hardcoded defaults.

The planner is deliberately cheap and deterministic: everything is derived
from static shapes, so plans are computed at trace time and baked into the
unrolled schedules/kernels.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.streams import MAX_ACTIVE_STREAMS_DEFAULT, StreamPool

__all__ = [
    "AllToAllPlan",
    "AttentionRingPlan",
    "RingStep",
    "RingPlan",
    "HaloPlan",
    "OverlapPlanner",
    "default_planner",
    "resolve_interpret",
    "resolve_ring_impl",
    "resolve_dispatch_impl",
    "resolve_seq_parallel",
    "split_extents",
]

# VMEM limits as the TPU compiler enforces them on a v5e TensorCore, which
# has 128 MiB of VMEM (``pltpu.get_tpu_info().vmem_capacity_bytes``):
#
# * a kernel's stack and pipeline buffers get 16 MiB of "scoped" VMEM unless
#   the kernel asks for more (the compiler's refusal names this limit:
#   "Scoped allocation ... limit 16.00M").  Tiled kernels plan against it.
# * a kernel may raise that limit with ``CompilerParams(vmem_limit_bytes)``;
#   scratch buffers and the raised limit together must stay inside the
#   128 MiB.  Kernels that keep whole operands resident ask for
#   ``VMEM_LIMIT_BYTES``, and their dispatchers route any shape whose
#   resident bytes exceed it to the XLA path instead.
VMEM_BUDGET_DEFAULT = 16 * 2**20
VMEM_LIMIT_BYTES = 100 * 2**20

LANES, SUBLANES = 128, 8   # minor-dim tile of a 32-bit VMEM array

# Bytes of one grid step's u_prev / velocity / output block that the
# streamed stencil aims for: enough to amortise a grid step's fixed cost,
# and no deeper, since deeper blocks measured slower on a TPU v5e (whole
# 1024² f32 planes: 13.96 ms a step at one plane a block, 16.05 ms at two).
STENCIL_BLOCK_BYTES = 2**20


def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def stencil_halo_rows(radius: int) -> int:
    """Rows of Y halo a stencil tile stages above and below its core: the
    radius rounded up to the sublane tile, so each plane DMA and the
    tile's core rows stay tile-aligned."""
    return -(-radius // SUBLANES) * SUBLANES


def stencil_tile_bytes(bz: int, by: int, x: int, dtype, radius: int) -> int:
    """VMEM the streamed stencil kernel holds for a ``(bz, by)`` tile: its
    ring of ``2·radius + 2`` halo-extended planes of the Y tile, and the
    double-buffered u_prev / velocity / output blocks of ``bz`` planes,
    each counted at its (8, 128)-tiled size."""
    yp = -(-by // SUBLANES) * SUBLANES
    xp = -(-x // LANES) * LANES
    ring = (2 * radius + 2) * (yp + 2 * stencil_halo_rows(radius)) * xp
    return (ring + 2 * 3 * bz * yp * xp) * _itemsize(dtype)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode resolved from the backend AT CALL TIME.

    ``None`` (the default everywhere) means "compile on TPU, interpret
    elsewhere" — the fast path is never silently interpreted on real
    hardware, and CPU CI exercises the identical kernel bodies in the
    Pallas interpreter.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def resolve_ring_impl(impl: Optional[str]) -> str:
    """Resolve a ring-matmul implementation knob to a concrete mode.

    ``"auto"``/None pick the fused bidirectional schedule; explicit
    ``"host"`` (unidirectional XLA-overlap loop) and ``"fused"`` pass
    through.  The train/serve step builders call this once so the whole
    jitted step traces against one concrete schedule.
    """
    if impl in (None, "auto"):
        return "fused"
    if impl in ("host", "fused"):
        return impl
    raise ValueError(f"unknown ring matmul impl {impl!r}")


def resolve_dispatch_impl(impl: Optional[str]) -> str:
    """Resolve a MoE dispatch implementation knob to a concrete mode.

    ``"auto"``/None keep the host collective ``"a2a"`` path (the status
    quo: GShard capacity dispatch through ``ompccl.alltoall``); the
    dropless one-sided paths — ``"host"`` (puts serialized around the
    expert GEMMs) and ``"fused"`` (combine overlapped under the GEMMs per
    :class:`AllToAllPlan`) — are explicit opt-ins because dropless
    routing changes the numbers whenever the capacity path would have
    dropped tokens.  The train/serve step builders call this once so the
    whole jitted step traces against one concrete dispatch schedule.
    """
    if impl in (None, "auto"):
        return "a2a"
    if impl in ("a2a", "host", "fused"):
        return impl
    raise ValueError(f"unknown moe dispatch impl {impl!r}")


def resolve_seq_parallel(impl: Optional[str]) -> str:
    """Resolve the sequence-parallel attention knob to a concrete mode.

    ``"auto"``/None keep the host collective ``"allgather"`` path (the
    status quo: K/V all-gathered over the model group, then local flash
    attention); ``"ring"`` — K/V stripes rotated through the bidirectional
    one-sided ring while partial softmax accumulates per
    :class:`AttentionRingPlan` — is an explicit opt-in because the
    stripe-merge reduction order changes the numerics at float tolerance
    against the all-gather scan.  The train/serve step builders call this
    once so the whole jitted step traces against one concrete schedule.
    """
    if impl in (None, "auto"):
        return "allgather"
    if impl in ("allgather", "ring"):
        return impl
    raise ValueError(f"unknown seq_parallel mode {impl!r}")


def split_extents(total: int, parts: int,
                  weights: Optional[Sequence[float]] = None,
                  *, minimum: int = 1) -> Tuple[int, ...]:
    """Proportional largest-remainder split of ``total`` into ``parts``.

    The asymmetric-decomposition primitive shared by the Minimod driver
    (per-rank Z extents proportional to device weights) and the MoE
    dispatch planner (per-expert landing capacities proportional to
    measured load).  Every extent is at least ``minimum``; with integral
    weights summing to ``total`` the split reproduces the weights exactly
    (largest-remainder assigns each raw quota its own floor).
    ``weights=None`` degrades to the near-even split, which also covers
    non-divisible grids — a non-divisible symmetric request is just the
    asymmetric path with unit weights.
    """
    if parts < 1:
        raise ValueError("parts must be >= 1")
    weights = tuple(weights) if weights is not None else (1,) * parts
    if len(weights) != parts:
        raise ValueError(f"{len(weights)} weights for {parts} parts")
    if min(weights) <= 0:
        raise ValueError("weights must be positive")
    if minimum * parts > total:
        raise ValueError(
            f"cannot give {parts} ranks at least {minimum} of {total} rows")
    wsum = float(sum(weights))
    raw = [total * w / wsum for w in weights]
    ext = [max(int(r), minimum) for r in raw]
    order = sorted(range(parts), key=lambda i: raw[i] - int(raw[i]),
                   reverse=True)
    i = 0
    while sum(ext) < total:
        ext[order[i % parts]] += 1
        i += 1
    donors = sorted(range(parts), key=lambda i: ext[i] - raw[i], reverse=True)
    i = 0
    while sum(ext) > total:
        j = donors[i % parts]
        if ext[j] > minimum:
            ext[j] -= 1
        i += 1
    return tuple(ext)


# ---------------------------------------------------------------------------
# ring schedule
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RingStep:
    """One compute step of the ring collective matmul.

    ``index`` is the step number ``s``; the clockwise stream holds the
    stripe of rank ``(me - s) % n`` at step ``s``, the counter-clockwise
    stream the stripe of rank ``(me + s) % n``.  ``send_*`` are the
    forwards launched at this step (they deliver step ``s + 1``'s
    stripes and overlap this step's GEMMs); ``slot`` is the VMEM buffer
    slot both streams use for step ``s``.
    """

    index: int
    compute_cw: bool
    compute_ccw: bool
    send_cw: bool
    send_ccw: bool
    slot: int


@dataclasses.dataclass(frozen=True)
class RingPlan:
    """Concrete slot/step plan for one ring collective matmul.

    ``direction``:

    * ``"bidi"`` — the fused default: both link directions carry one stripe
      per step, ``ceil((n - 1) / 2)`` exchange steps;
    * ``"cw"`` / ``"ccw"`` — unidirectional rings (``n - 1`` steps), kept
      for the host-loop benchmark mode and for exercising both directions.
    """

    n: int
    direction: str = "bidi"
    slots: int = 2
    tile: Tuple[int, int, int] = (256, 512, 256)
    stripe_bytes: int = 0
    vmem_bytes: int = 0

    def __post_init__(self):
        if self.direction not in ("bidi", "cw", "ccw"):
            raise ValueError(f"unknown ring direction {self.direction!r}")
        if self.n < 1:
            raise ValueError("group size must be >= 1")

    @property
    def exchange_steps(self) -> int:
        """Ring steps that move data: ceil((n-1)/2) bidi, n-1 one-way."""
        if self.n <= 1:
            return 0
        if self.direction == "bidi":
            return (self.n - 1 + 1) // 2
        return self.n - 1

    def schedule(self) -> Tuple[RingStep, ...]:
        """The per-step schedule both the TPU kernel and the interpret
        emulation execute (compute steps = exchange_steps + 1)."""
        n = self.n
        if n == 1:
            return (RingStep(0, True, False, False, False, 0),)
        steps = []
        if self.direction == "bidi":
            s_cw = (n - 1 + 1) // 2          # cw serves the ring's left half
            s_ccw = (n - 1) // 2             # ccw the right half (no overlap)
            for s in range(s_cw + 1):
                steps.append(RingStep(
                    index=s,
                    compute_cw=s <= s_cw,            # s == 0 is the local stripe
                    compute_ccw=1 <= s <= s_ccw,
                    send_cw=s < s_cw,
                    send_ccw=s < s_ccw,
                    slot=s % self.slots,
                ))
        else:
            cw = self.direction == "cw"
            for s in range(n):
                steps.append(RingStep(
                    index=s,
                    compute_cw=cw or s == 0,
                    compute_ccw=(not cw) and s >= 1,
                    send_cw=cw and s < n - 1,
                    send_ccw=(not cw) and s < n - 1,
                    slot=s % self.slots,
                ))
        return tuple(steps)

    def sources(self, rank: int = 0) -> Tuple[int, ...]:
        """Stripe owners computed by ``rank``, in schedule order (oracle for
        coverage tests: must be a permutation of range(n))."""
        out = []
        for st in self.schedule():
            if st.compute_cw:
                out.append((rank - st.index) % self.n)
            if st.compute_ccw:
                out.append((rank + st.index) % self.n)
        return tuple(out)

    def fold_steps(self) -> Tuple[Tuple[str, int], ...]:
        """Rank-agnostic ``(direction, step)`` of each fold, in schedule
        order — the i-th entry describes where :meth:`sources`' i-th
        stripe came from (``("cw", s)`` = owner ``rank - s``, ``("ccw",
        s)`` = owner ``rank + s``).  The ring-attention backward keys its
        canonical cotangent routing off this list."""
        out = []
        for st in self.schedule():
            if st.compute_cw:
                out.append(("cw", st.index))
            if st.compute_ccw:
                out.append(("ccw", st.index))
        return tuple(out)


# ---------------------------------------------------------------------------
# ring attention schedule (sequence parallelism)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionRingPlan:
    """Concrete schedule for one sequence-parallel ring attention pass.

    The K/V stripes rotate through the same bidirectional ring as the
    collective matmul (the step records ARE :meth:`RingPlan.schedule`),
    but the compute is a flash-attention block per stripe whose partial
    softmax states fold with the :mod:`~repro.kernels.ring_attention.
    kernel` merge operator — so this plan adds the attention-specific
    facts on top of the ring:

    * **causal step skipping** — :meth:`computes` is the static predicate
      for "does ``rank`` spend FLOPs on stripe ``src``".  A stripe whose
      keys all lie in the rank's future (or beyond ``valid_len``) is
      fully masked, its state is the merge identity, and the TPU kernel
      skips it under ``pl.when`` — *bit-identically*, by the identity
      property.  Sends are NEVER skipped (downstream ranks need the
      forwarded stripe), so skipping changes FLOPs, not wire bytes.
      ``q_offset=None`` means the query positions are traced (dynamic
      chunked prefill): nothing can be skipped statically and every
      stripe masks instead.
    * **wire-byte accounting** — K and V are separate one-sided puts, so
      a full pass issues ``2·(n-1)`` puts of ``stripe_bytes`` total wire
      ``(n-1)·stripe_bytes`` per rank, the exact figure the RMATracker
      windows and the OMPCCL byte log must both report.
    * ``q_sharded=True`` is the training layout (rank ``r`` holds queries
      ``q_offset + r·tq_loc ..``); ``False`` the chunked-prefill layout
      (every rank holds the same ``tq_loc`` queries at ``q_offset``).
    """

    n: int
    tq_loc: int
    tk_loc: int
    h: int                      # query heads
    kh: int                     # kv heads (stripe width on the wire)
    d: int
    dv: int
    b: int = 1
    itemsize: int = 4
    causal: bool = True
    q_sharded: bool = True
    q_offset: Optional[int] = 0     # None: traced offsets, no static skip
    valid_len: Optional[int] = None  # None: all n*tk_loc key rows are real
    direction: str = "bidi"
    slots: int = 2
    block: int = 512
    overlap: bool = True            # False: serialized "host" listing
    vmem_bytes: int = 0

    def __post_init__(self):
        if self.n < 1:
            raise ValueError("group size must be >= 1")
        if self.tq_loc < 1 or self.tk_loc < 1:
            raise ValueError("per-rank extents must be >= 1")
        if self.h % self.kh:
            raise ValueError(f"H={self.h} not divisible by KH={self.kh}")
        if self.direction not in ("bidi", "cw", "ccw"):
            raise ValueError(f"unknown ring direction {self.direction!r}")

    @property
    def ring(self) -> RingPlan:
        """The underlying exchange schedule (shared with the matmul ring)."""
        return RingPlan(n=self.n, direction=self.direction, slots=self.slots,
                        stripe_bytes=self.stripe_bytes)

    @property
    def exchange_steps(self) -> int:
        return self.ring.exchange_steps

    def schedule(self) -> Tuple[RingStep, ...]:
        return self.ring.schedule()

    def sources(self, rank: int = 0) -> Tuple[int, ...]:
        """Stripe owners delivered to ``rank``, in schedule (= merge) order."""
        return self.ring.sources(rank)

    def fold_steps(self) -> Tuple[Tuple[str, int], ...]:
        """Per-fold ``(direction, step)`` records (see
        :meth:`RingPlan.fold_steps`)."""
        return self.ring.fold_steps()

    def q_lo(self, rank: int) -> int:
        """First global query position of ``rank`` (static plans only)."""
        if self.q_offset is None:
            raise ValueError("dynamic q_offset has no static query range")
        return self.q_offset + (rank * self.tq_loc if self.q_sharded else 0)

    def computes(self, rank: int, src: int) -> bool:
        """Does ``rank`` spend FLOPs on stripe ``src``?  False only when
        every (query, key) pair of the stripe is masked — beyond
        ``valid_len`` or entirely in the causal future — so skipping is
        sound by the merge-identity property."""
        k_lo = src * self.tk_loc
        if self.valid_len is not None and k_lo >= self.valid_len:
            return False
        if not self.causal or self.q_offset is None:
            return True
        return k_lo <= self.q_lo(rank) + self.tq_loc - 1

    def computed_sources(self, rank: int = 0) -> Tuple[int, ...]:
        return tuple(s for s in self.sources(rank) if self.computes(rank, s))

    @property
    def stripe_bytes(self) -> int:
        """Wire bytes of one K/V stripe (K put + V put)."""
        return self.b * self.tk_loc * self.kh * (self.d + self.dv) \
            * self.itemsize

    @property
    def puts_per_rank(self) -> int:
        """One-sided puts per rank per pass (K and V put separately)."""
        return 2 * (self.n - 1)

    @property
    def wire_bytes(self) -> int:
        """Per-rank put bytes for the whole pass: every remote stripe
        crosses each link once regardless of causal skipping."""
        return (self.n - 1) * self.stripe_bytes

    @property
    def stripe_flops(self) -> int:
        """FLOPs of one stripe's block: QK^T + PV einsums over all local
        queries and ``h`` query heads."""
        return 2 * self.b * self.tq_loc * self.tk_loc * self.h \
            * (self.d + self.dv)

    def flops(self, rank: int) -> int:
        """FLOPs ``rank`` actually spends after causal step skipping."""
        return len(self.computed_sources(rank)) * self.stripe_flops


# ---------------------------------------------------------------------------
# halo schedule (Minimod)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HaloPlan:
    """The halo plan of one fused halo-overlapped stencil step.

    The schedule the fused Minimod step executes (see
    :mod:`repro.kernels.stencil.fused`):

    * **carried halos** (the multi-step time loop): the R-thick *boundary*
      output slabs are computed FIRST (they only need the halos that landed
      last step), their values are immediately put one-sided to the
      neighbors (they are the neighbors' next-step halos), and the
      *interior* — which needs no halo at all — computes under the
      in-flight exchange.  One fence per step.
    * **single step** (no carried halos): the current field's boundary
      slabs are put first, the interior computes under the exchange, and
      the boundary region computes after the fence.

    ``overlap`` holds where some axis exchanges and every exchanging axis
    has an interior to hide the exchange under; ``overlap=False`` is the
    fallback plan (a lone rank, or a local extent of at most 2·halo on an
    exchanging axis): exchange-then-compute, still numerically identical.

    Extents are LOCAL (the per-rank maximum when extents are asymmetric).
    ``slab_bytes``/``strip_bytes`` are the wire sizes of one Z-slab /
    Y-strip halo put.
    """

    nz: int
    ny: int = 1
    halo: int = 4
    z_loc: int = 0
    y_loc: int = 0
    x: int = 0
    slab_bytes: int = 0
    strip_bytes: int = 0
    overlap: bool = True

    def __post_init__(self):
        if self.nz < 1 or self.ny < 1:
            raise ValueError("halo decomposition needs nz, ny >= 1")
        if self.halo < 1:
            raise ValueError("halo must be >= 1")

    @property
    def exchange_axes(self) -> Tuple[str, ...]:
        """Sharded axes that actually exchange (edge groups of 1 don't)."""
        axes = []
        if self.nz > 1:
            axes.append("z")
        if self.ny > 1:
            axes.append("y")
        return tuple(axes)

    @property
    def interior_z(self) -> int:
        return max(self.z_loc - 2 * self.halo, 0) if self.nz > 1 else self.z_loc

    @property
    def interior_y(self) -> int:
        return max(self.y_loc - 2 * self.halo, 0) if self.ny > 1 else self.y_loc

    @property
    def puts_per_step(self) -> int:
        """One-sided puts each step issues (2 per exchanging axis)."""
        return 2 * len(self.exchange_axes)

    @property
    def halo_bytes_per_step(self) -> int:
        return (2 * self.slab_bytes if self.nz > 1 else 0) + \
            (2 * self.strip_bytes if self.ny > 1 else 0)

    def schedule(self, *, carried: bool = True) -> Tuple[str, ...]:
        """Ordered phase names the fused step follows.

        ``carried=True`` is the time-loop order (halos of the current field
        already landed; the step exchanges the freshly computed boundary),
        ``carried=False`` the single-step order (exchange the current
        field's slabs, compute the interior under it).
        """
        if not self.exchange_axes:
            return ("all",)
        if not self.overlap:
            return ("put", "fence", "all")
        if carried:
            return ("boundary", "put", "interior", "fence")
        return ("put", "interior", "fence", "boundary")


# ---------------------------------------------------------------------------
# MoE dispatch schedule (expert-parallel all-to-all)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AllToAllPlan:
    """Concrete schedule for one dropless expert-parallel MoE dispatch.

    The ragged token→expert traffic is realized as a ring of one-sided
    puts: at step ``s`` every rank puts the block destined for the rank
    ``s + 1`` ahead (the exchange that feeds step ``s + 1``), runs the
    expert GEMMs on the block that landed from the rank ``s`` behind
    (step 0 computes the local block), and puts the *previous* GEMM's
    result straight back to its source — the return combine rides under
    the current compute.  One fence per landed block, one final fence for
    the combine windows.  Both the TPU kernel and the differentiable
    interpret emulation execute exactly :meth:`schedule`.

    Capacities are per-expert and **asymmetric** (``caps[e]`` rows per
    source rank, sized from measured load by
    :meth:`OverlapPlanner.plan_alltoall` through :func:`split_extents`);
    the home rank of expert ``e`` registers a PGAS landing region of
    ``ep * caps[e]`` rows while the other ranks register zero bytes —
    the paper's asymmetric-allocation story.  SPMD execution pads every
    wire block to ``cap_pad = max(caps)`` rows per expert (the same
    max-extent-shard trick Minimod uses); :meth:`block_rows` reports the
    *true* per-destination row counts the cost model bills for.
    """

    ep: int                    # EP group size (ring length)
    E: int                     # global expert count
    t_loc: int                 # tokens per rank entering dispatch
    k: int                     # experts per token
    d: int                     # model dim of one token row
    itemsize: int = 4
    caps: Tuple[int, ...] = ()  # per-expert landing rows per source rank
    slots: int = 2             # staging buffers granted by StreamPool
    overlap: bool = True       # False: puts, fence, GEMMs, puts, fence

    def __post_init__(self):
        if self.ep < 1:
            raise ValueError("EP group size must be >= 1")
        if self.E % self.ep != 0:
            raise ValueError(f"E={self.E} not divisible by ep={self.ep}")
        if len(self.caps) != self.E:
            raise ValueError(f"{len(self.caps)} caps for {self.E} experts")
        if self.caps and min(self.caps) < 1:
            raise ValueError("per-expert capacities must be >= 1")

    @property
    def E_loc(self) -> int:
        return self.E // self.ep

    @property
    def cap_pad(self) -> int:
        """Padded per-expert rows of one SPMD wire block (max over experts)."""
        return max(self.caps)

    @property
    def block_bytes(self) -> int:
        """Wire bytes of one padded dispatch/combine put."""
        return self.E_loc * self.cap_pad * self.d * self.itemsize

    def block_rows(self, rank: int) -> int:
        """TRUE rows one source sends to ``rank`` (the asymmetric sizes the
        PGAS regions and the cost model use; the wire block pads to
        ``E_loc * cap_pad``)."""
        lo = rank * self.E_loc
        return sum(self.caps[lo:lo + self.E_loc])

    @property
    def region_rows(self) -> Tuple[int, ...]:
        """Per-expert PGAS landing-region rows on the expert's home rank
        (``ep`` sources × ``caps[e]`` rows each)."""
        return tuple(self.ep * c for c in self.caps)

    @property
    def wire_bytes(self) -> int:
        """Modeled wire bytes per rank per dispatch+combine (true rows,
        remote destinations only)."""
        me = 0  # symmetric in the model: every rank sends all remote blocks
        remote = sum(self.block_rows(r) for r in range(self.ep) if r != me)
        return 2 * remote * self.d * self.itemsize

    @property
    def staging_bytes(self) -> int:
        """VMEM the pipeline pins: ``slots`` in-flight padded blocks."""
        return self.slots * self.block_bytes

    def schedule(self) -> Tuple[Tuple[str, int], ...]:
        """Ordered ``(phase, ring_offset)`` records both executions follow.

        * ``("put", s)``   — one-sided put of my block for the rank ``s``
          ahead (dispatch direction);
        * ``("fence", s)`` — complete the landing of the block from the
          rank ``s`` behind before its GEMM reads it;
        * ``("gemm", s)``  — expert GEMMs on that landed block (``s == 0``
          is the local block);
        * ``("ret", s)``   — one-sided put of that result back to its
          source, overlapped under step ``s + 1``'s GEMM;
        * ``("fence_ret", 0)`` — final fence of the combine windows.

        ``overlap=False`` is the serialized ``"host"`` mode: all dispatch
        puts, one fence, all GEMMs, all combine puts, one fence — the
        same traffic with nothing hidden.
        """
        if self.ep == 1:
            return (("gemm", 0),)
        out = []
        if self.overlap:
            for s in range(self.ep):
                if s + 1 < self.ep:
                    out.append(("put", s + 1))
                if s > 0:
                    out.append(("fence", s))
                out.append(("gemm", s))
                if s > 0:
                    out.append(("ret", s))
            out.append(("fence_ret", 0))
        else:
            for s in range(1, self.ep):
                out.append(("put", s))
            for s in range(1, self.ep):
                out.append(("fence", s))
            for s in range(self.ep):
                out.append(("gemm", s))
            for s in range(1, self.ep):
                out.append(("ret", s))
            out.append(("fence_ret", 0))
        return tuple(out)


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class OverlapPlanner:
    """Converts (StreamPool.plan_slots, VMEM budget, tile shape, group size)
    into the concrete plans the kernels consume.

    ``pool`` supplies the §3.2 bounded-concurrency policy — the number of
    in-flight DMA buffers a kernel may pin is exactly what
    ``StreamPool.plan_slots`` grants for the kernel's working set.
    """

    pool: StreamPool = dataclasses.field(
        default_factory=lambda: StreamPool(MAX_ACTIVE_STREAMS_DEFAULT))
    vmem_budget: int = VMEM_BUDGET_DEFAULT

    def _fits(self, working_set_bytes: int) -> bool:
        """Would the slots plan_slots grants actually fit the budget?

        plan_slots never grants fewer than 2 (double buffering is the point
        of the pipeline), so "fits" means the granted slot count times the
        working set stays inside the budget.
        """
        slots = self.pool.plan_slots(working_set_bytes, self.vmem_budget)
        return slots * working_set_bytes <= self.vmem_budget

    # -- ring collective matmul ---------------------------------------------
    def plan_ring_matmul(self, t_loc: int, k: int, n_loc: int, dtype,
                         n: int, *, direction: str = "bidi") -> RingPlan:
        """Slot/step plan for the fused all-gather matmul.

        Working set: per-slot stripe buffers for BOTH directions, the
        resident W column block, and the f32 output stripe tile.
        """
        item = _itemsize(dtype)
        stripe = max(t_loc * k * item, 1)
        resident = k * n_loc * item + t_loc * n_loc * 4   # W block + f32 out tile
        budget = max(self.vmem_budget - resident, stripe * 2)
        ndir = 2 if direction == "bidi" else 1
        slots = self.pool.plan_slots(ndir * stripe, budget)
        # the grant is a concurrency bound; the pinned bytes must also fit
        slots = min(slots, max(budget // (ndir * stripe), 2))
        plan = RingPlan(n=n, direction=direction,
                        slots=1 if n == 1 else max(2, min(slots, n)),
                        tile=self.plan_matmul_tiles(t_loc, k, n_loc, dtype),
                        stripe_bytes=stripe)
        return dataclasses.replace(
            plan, vmem_bytes=ndir * plan.slots * stripe + resident)

    # -- blocked matmul tiles -----------------------------------------------
    def plan_matmul_tiles(self, m: int, k: int, n: int, dtype,
                          *, bm: int = 256, bk: int = 512, bn: int = 256
                          ) -> Tuple[int, int, int]:
        """MXU-aligned tiles shrunk until plan_slots grants double buffering.

        Working set per pipeline stage: x (bm, bk) + w (bk, bn) in ``dtype``
        + f32 accumulator (bm, bn).  bk halves first (the accumulator is
        bk-independent), then bm/bn together, never below 128.
        """
        item = _itemsize(dtype)
        bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
        while True:
            ws = (bm * bk + bk * bn) * item + bm * bn * 4
            if self._fits(ws) or (bm <= 128 and bk <= 128 and bn <= 128):
                return bm, bk, bn
            if bk > 128:
                bk //= 2
            else:
                bm = max(128, bm // 2)
                bn = max(128, bn // 2)

    # -- flash attention block ----------------------------------------------
    def plan_attention_block(self, tq: int, tk: int, d: int, dv: int, dtype,
                             *, block: int = 512) -> int:
        """Largest block ≤ ``block`` whose tiles double-buffer in budget.

        ``block`` chunks the KV axis (and, in the Pallas kernel, the q axis
        too — both kernels clamp to their actual extents).  Per-step working
        set: q (bq, d) + k/v (bk, d/dv) in ``dtype`` + scores (bq, bk) and
        accumulator (bq, dv) in f32.
        """
        item = _itemsize(dtype)
        b = max(min(block, max(tq, tk)), 1)
        while b > 128:
            bq, bk = min(b, tq), min(b, tk)
            ws = (bq * d + bk * (d + dv)) * item + (bq * bk + bq * dv) * 4
            if self._fits(ws):
                break
            b //= 2
        return b

    # -- ring attention -------------------------------------------------------
    def plan_ring_attention(self, b: int, tq_loc: int, tk_loc: int,
                            h: int, kh: int, d: int, dv: int, dtype, n: int,
                            *, causal: bool = True, q_sharded: bool = True,
                            q_offset: Optional[int] = 0,
                            valid_len: Optional[int] = None,
                            direction: str = "bidi",
                            overlap: bool = True) -> AttentionRingPlan:
        """Slot/block plan for the fused sequence-parallel attention ring.

        Working set: per-slot K+V stripe buffers for BOTH ring directions
        (what ``StreamPool.plan_slots`` bounds), against a budget net of
        the residents — the grouped f32 queries and the (m, l, acc) merge
        carry.  The flash block size reuses :meth:`plan_attention_block`
        on the per-rank extents.  ``q_offset=None`` marks traced query
        offsets (dynamic chunked prefill): the plan then skips nothing
        and every stripe masks.
        """
        item = _itemsize(dtype)
        block = self.plan_attention_block(tq_loc, tk_loc, d, dv, dtype)
        stripe = max(b * tk_loc * kh * (d + dv) * item, 1)
        resident = b * tq_loc * h * (d + 2 + dv) * 4   # qg + m/l + acc, f32
        budget = max(self.vmem_budget - resident, stripe * 2)
        ndir = 2 if direction == "bidi" else 1
        slots = self.pool.plan_slots(ndir * stripe, budget)
        # the grant is a concurrency bound; the pinned bytes must also fit
        slots = min(slots, max(budget // (ndir * stripe), 2))
        plan = AttentionRingPlan(
            n=n, tq_loc=tq_loc, tk_loc=tk_loc, h=h, kh=kh, d=d, dv=dv, b=b,
            itemsize=item, causal=causal, q_sharded=q_sharded,
            q_offset=q_offset, valid_len=valid_len, direction=direction,
            slots=1 if n == 1 else max(2, min(slots, n)), block=block,
            overlap=overlap)
        return dataclasses.replace(
            plan, vmem_bytes=ndir * plan.slots * stripe + resident)

    # -- MoE dispatch all-to-all ----------------------------------------------
    def plan_alltoall(self, t_loc: int, d: int, k: int, E: int, ep: int,
                      dtype, *, loads: Optional[Sequence[int]] = None,
                      slack: float = 1.0, overlap: bool = True
                      ) -> AllToAllPlan:
        """Schedule + asymmetric capacities for one dropless MoE dispatch.

        ``loads`` are measured per-expert row counts — the *maximum over
        source ranks* of rows routed to each expert (what one landing
        region must absorb per source).  The staging budget
        ``ceil(sum(loads) * slack)`` is decomposed over experts by the
        largest-remainder split (:func:`split_extents`, the Minimod
        decomposition); with ``slack == 1.0`` the split reproduces the
        loads exactly, and any split is re-clamped to ``>= loads[e]`` so
        the plan is dropless by construction.  ``loads=None`` is the
        trace-time fallback (no measurement available inside a jitted
        step): every expert gets the worst-case ``t_loc`` rows.

        Slot count is ``StreamPool.plan_slots``' grant for one padded
        wire block against the VMEM budget (the §3.2 bounded-concurrency
        contract), and the plan degrades to ``overlap=False`` when the
        budget cannot double-buffer the staging pipeline.
        """
        if E % ep != 0:
            raise ValueError(f"E={E} not divisible by ep={ep}")
        item = _itemsize(dtype)
        if loads is None:
            caps = (t_loc,) * E
        else:
            loads = tuple(int(l) for l in loads)
            if len(loads) != E:
                raise ValueError(f"{len(loads)} loads for {E} experts")
            total = max(int(-(-sum(loads) * slack // 1)),
                        sum(max(l, 1) for l in loads))
            weights = tuple(max(l, 1e-6) for l in loads)
            caps = split_extents(total, E, weights, minimum=1)
            caps = tuple(max(c, l) for c, l in zip(caps, loads))
        plan = AllToAllPlan(ep=ep, E=E, t_loc=t_loc, k=k, d=d,
                            itemsize=item, caps=caps, overlap=overlap)
        if ep == 1:
            return dataclasses.replace(plan, slots=1)
        block = plan.block_bytes
        slots = self.pool.plan_slots(block, self.vmem_budget)
        slots = max(2, min(slots, max(self.vmem_budget // max(block, 1), 2)))
        slots = min(slots, ep)
        if overlap and 2 * block > self.vmem_budget:
            return dataclasses.replace(plan, overlap=False, slots=1)
        return dataclasses.replace(plan, slots=slots)

    # -- gradient buckets -----------------------------------------------------
    def plan_grad_buckets(self, cfg, mesh, ctx):
        """The DP gradient-reduction schedule (see
        :mod:`repro.distributed.buckets`) — exposed here so every planned
        schedule (ring steps, kernel tiles, reduction buckets) resolves
        through the one planner surface.  Like every other plan it is pure
        static-shape data, cached per (config, mesh, ctx)."""
        from repro.distributed.buckets import plan_for_config

        return plan_for_config(cfg, mesh, ctx)

    # -- stencil tile ---------------------------------------------------------
    def plan_stencil_tile(self, z: int, y: int, x: int, dtype,
                          *, radius: int = 4, bz: int = 8,
                          budget: Optional[int] = None
                          ) -> Optional[Tuple[int, int]]:
        """``(bz, by)`` tile of the streamed stencil kernel, or ``None``.

        ``by`` is the Y tile: the whole plane where its ring fits
        ``budget`` (no Y halo is then read twice), else the largest
        sublane-aligned split of Y that does; ``bz`` is the depth of the
        pipelined u_prev / velocity / output blocks: the fewest planes
        whose block reaches :data:`STENCIL_BLOCK_BYTES` (at most ``bz``
        and ``z``), halved until the tile fits.  ``None`` where not even
        an 8-row tile of one plane fits: the caller then takes the XLA
        path.  ``budget`` defaults to the planner's; the kernel's
        dispatchers pass the VMEM limit it compiles with.
        """
        budget = self.vmem_budget if budget is None else budget
        bz = max(min(bz, z), 1)
        for by in _y_tiles(y):
            plane = (-(-by // SUBLANES) * SUBLANES * -(-x // LANES) * LANES
                     * _itemsize(dtype))
            b = min(bz, -(-STENCIL_BLOCK_BYTES // plane))
            while b > 1 and stencil_tile_bytes(b, by, x, dtype,
                                               radius) > budget:
                b //= 2
            if stencil_tile_bytes(b, by, x, dtype, radius) <= budget:
                return b, by
        return None

    # -- halo exchange (Minimod) ----------------------------------------------
    def plan_halo_slots(self, z_loc: int, y_loc: int, x: int, dtype,
                        nz: int, *, ny: int = 1, halo: int = 4) -> HaloPlan:
        """Halo plan of the fused halo-overlapped stencil step: the wire
        size of each put and whether the step overlaps.

        Falls back to an ``overlap=False`` plan (exchange-then-compute)
        rather than emitting an invalid split when no axis exchanges or
        the local grid has no interior (extent ≤ 2·halo on an exchanging
        axis).  The plan's own ``interior_*`` properties are THE
        definition the step splits by.
        """
        item = _itemsize(dtype)
        plan = HaloPlan(
            nz=nz, ny=ny, halo=halo, z_loc=z_loc, y_loc=y_loc, x=x,
            slab_bytes=halo * y_loc * x * item if nz > 1 else 0,
            strip_bytes=z_loc * halo * x * item if ny > 1 else 0)
        overlap = bool(plan.exchange_axes) and plan.interior_z > 0 and \
            plan.interior_y > 0
        return dataclasses.replace(plan, overlap=overlap)


def _y_tiles(y: int):
    """Y tile heights to try, largest first: the whole plane, then
    ``ceil(y / n)`` rounded up to the sublane tile for n = 2, 3, …,
    down to one sublane tile."""
    yield y
    last, n = y, 2
    while last > SUBLANES:
        rows = -(-y // n)
        by = -(-rows // SUBLANES) * SUBLANES
        if by < last:
            yield by
            last = by
        n += 1


_DEFAULT_PLANNER: Optional[OverlapPlanner] = None


def default_planner() -> OverlapPlanner:
    """The process-default planner, backed by the default DiompContext's
    StreamPool so the §3.2 policy knob (``max_active_streams``) governs
    kernel DMA slots and host async lanes alike."""
    global _DEFAULT_PLANNER
    from repro.core.context import default_context

    pool = default_context().streams
    if _DEFAULT_PLANNER is None or _DEFAULT_PLANNER.pool is not pool:
        _DEFAULT_PLANNER = OverlapPlanner(pool=pool)
    return _DEFAULT_PLANNER
