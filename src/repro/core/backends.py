"""OMPCCL backends — first-class pluggable collective implementations.

The paper's OMPCCL registers one communicator per DiOMP group and dispatches
every collective to the vendor library behind a stable API (NCCL on CUDA,
RCCL on ROCm; §3.3).  Here the "vendor libraries" are backend *classes*
implementing the :class:`CclBackend` protocol:

* :class:`XlaBackend`          — direct ``jax.lax`` collectives (flat
  single-phase algorithms; XLA's collective runtime is the TPU vendor lib);
* :class:`HierarchicalBackend` — pod-aware two-level algorithms from
  :mod:`repro.distributed.hierarchical` (reduce-scatter intra-pod →
  all-reduce inter-pod → all-gather intra-pod), the TPU analogue of NCCL's
  topology-aware trees/rings;
* :class:`CompressedBackend`   — int8 quantization + error feedback around
  the wire collective (:mod:`repro.distributed.compression`);
* :class:`AnalyticBackend`     — the XLA wire path plus a per-call analytic
  cost estimate (the dry-run / roofline napkin math), logged host-side at
  trace time.

Backends register by name in a module registry so new ones plug in without
touching any call site: ``@register_backend`` + ``ctx.communicator(group,
backend="mine")``.  A backend instance never records call counts — that is
the communicator handle's job (:mod:`repro.core.context`); backends own only
the wire lowering, so every method here is safe to call from inside
``shard_map`` tracing.

The analytic link-cost models (ring/hierarchical time bounds) also live
here; :mod:`repro.core.ompccl` re-exports them for the benchmark layer.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp
from jax import lax

from .compat import all_gather_invariant, axis_size, pcast, typeof
from .groups import DiompGroup

__all__ = [
    "CclBackend",
    "XlaBackend",
    "HierarchicalBackend",
    "CompressedBackend",
    "AnalyticBackend",
    "register_backend",
    "get_backend",
    "available_backends",
    "BackendError",
    "ensure_varying",
    "group_rank",
    "group_size",
    "fence",
    "LinkModel",
    "ring_allreduce_time",
    "ring_allgather_time",
    "hierarchical_allreduce_time",
    "per_param_reduce_time",
    "bucketed_reduce_time",
    "overlapped_reduce_time",
]


class BackendError(ValueError):
    """Unknown backend name / invalid backend registration."""


# ---------------------------------------------------------------------------
# trace-level helpers shared by every backend
# ---------------------------------------------------------------------------


def _axes(group: DiompGroup) -> Tuple[str, ...]:
    if group.is_self_group():
        raise ValueError("collective on empty (self) group")
    return group.lax_axes


def ensure_varying(x, axes: Tuple[str, ...]):
    """Promote x to be varying over ``axes`` (vma bookkeeping).

    A collective over a group must see its operand varying on every group
    axis; values that are invariant on some axis (e.g. a loss already
    psum'd over "model") are pvary'd first — a pure type-level operation.
    """
    def promote(v):
        vma = getattr(typeof(v), "vma", frozenset())
        missing = tuple(a for a in axes if a not in vma)
        return pcast(v, missing, to="varying") if missing else v

    return jax.tree.map(promote, x)


def group_rank(group: DiompGroup):
    """Linearized rank of the caller within the group (row-major over axes)."""
    rank = jnp.int32(0)
    for ax in group.axes:
        rank = rank * axis_size(ax) + lax.axis_index(ax)
    return rank


def payload_bytes(x) -> int:
    """Static payload size of a (possibly traced) operand pytree — the ONE
    byte counter behind both the communicator wire-volume log and the
    analytic backend's cost estimates."""
    total = 0
    for leaf in jax.tree.leaves(x):
        n = 1
        for d in getattr(leaf, "shape", ()):
            n *= int(d)
        total += n * jnp.dtype(getattr(leaf, "dtype", jnp.float32)).itemsize
    return total


def group_size(group: DiompGroup) -> int:
    size = 1
    for ax in group.axes:
        size *= axis_size(ax)
    return size


def _ring_axis(group: DiompGroup) -> str:
    if len(group.axes) != 1:
        raise ValueError(
            f"RMA rings need a single-axis group (one ICI ring), got {group.axes}"
        )
    return group.axes[0]


@jax.custom_jvp
def _fence_tuple(arrays):
    return lax.optimization_barrier(arrays)


@_fence_tuple.defjvp
def _fence_tuple_jvp(primals, tangents):
    # the barrier is an ordering property of the PRIMAL program; tangents
    # ride through as the identity (which also makes the reverse-mode
    # transpose trivial), so fenced pipelines stay differentiable — the
    # fused halo-overlapped stencil trains through its per-step fence
    (arrays,), (dots,) = primals, tangents
    return _fence_tuple(arrays), dots


def fence(*arrays):
    """Complete all outstanding RMA before anything downstream runs.

    ``lax.optimization_barrier`` prevents XLA from reordering/fusing across
    the fence — the compiled counterpart of DiOMP's hybrid polling loop that
    waits on both network and device events.  Returns the fenced arrays.
    Backend-independent: the fence is an ordering property of the compiled
    program, not of any one transport — and differentiable (see the custom
    JVP above), so overlapped schedules can sit inside training steps.
    """
    if not arrays:
        return ()
    fenced = _fence_tuple(tuple(arrays))
    return fenced[0] if len(arrays) == 1 else fenced


# ---------------------------------------------------------------------------
# the backend protocol + flat XLA implementation
# ---------------------------------------------------------------------------


class CclBackend:
    """Protocol + default flat-XLA lowering for every OMPCCL verb.

    Subclasses override individual collectives; anything not overridden
    falls through to the flat single-phase algorithm, so a backend only has
    to implement what it actually changes (exactly how OMPCCL falls back to
    the generic path for ops a vendor library lacks).
    """

    #: registry name; subclasses must override.
    name = "xla"

    # -- collectives (usable inside shard_map) ------------------------------
    def allreduce(self, x, group: DiompGroup, *, op: str = "sum"):
        x = ensure_varying(x, _axes(group))
        axes = _axes(group)
        if op == "sum":
            return lax.psum(x, axes)
        if op == "max":
            return lax.pmax(x, axes)
        if op == "min":
            return lax.pmin(x, axes)
        if op == "mean":
            return lax.pmean(x, axes)
        raise ValueError(f"unsupported op {op!r}")

    def bcast(self, x, group: DiompGroup, *, root: int = 0):
        """Root's value delivered to every member.

        SPMD formulation: zero out non-root contributions and sum through
        ``self.allreduce`` — so a backend that only overrides allreduce
        automatically broadcasts over its own wire algorithm (exact because
        non-root terms are literal zeros; on the flat path XLA lowers it to
        one all-reduce whose cost equals a broadcast tree).
        """
        x = ensure_varying(x, _axes(group))
        rank = group_rank(group)
        contribution = jnp.where(rank == root, x, jnp.zeros_like(x))
        return self.allreduce(contribution, group)

    def allgather(self, x, group: DiompGroup, *, axis: int = 0,
                  tiled: bool = True, invariant: bool = False):
        out = ensure_varying(x, _axes(group))
        # gather across each mesh axis of the group, innermost last so that
        # the concatenation order equals the group's row-major rank order
        if invariant:
            for ax in reversed(group.axes):
                out = all_gather_invariant(out, ax, axis=axis, tiled=tiled)
            return out
        for ax in reversed(group.axes):
            out = lax.all_gather(out, ax, axis=axis, tiled=tiled)
        return out

    def reducescatter(self, x, group: DiompGroup, *, axis: int = 0):
        out = ensure_varying(x, _axes(group))
        for ax in group.axes:
            out = lax.psum_scatter(out, ax, scatter_dimension=axis, tiled=True)
        return out

    def alltoall(self, x, group: DiompGroup, *, split_axis: int = 0,
                 concat_axis: int = 0):
        x = ensure_varying(x, _axes(group))
        return lax.all_to_all(
            x, group.lax_axes, split_axis=split_axis, concat_axis=concat_axis,
            tiled=True,
        )

    def permute(self, x, group: DiompGroup, *, shift: int = 1):
        if len(group.axes) != 1:
            raise ValueError("permute requires a single-axis group")
        x = ensure_varying(x, _axes(group))
        ax = group.axes[0]
        n = axis_size(ax)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(x, ax, perm)

    def barrier(self, group: DiompGroup):
        """A collective-ordering token: psum of a zero scalar across the
        group.  Data-depending later ops on this token enforces collective
        completion — the compiled-SPMD analogue of ompx_barrier(group)."""
        return lax.psum(jnp.zeros((), jnp.float32), _axes(group))

    # -- one-sided RMA ------------------------------------------------------
    def put(self, x, group: DiompGroup, *, shift: int = 1):
        """One-sided put of my shard to the rank ``shift`` ahead on the ring.

        SPMD semantics: every rank's window receives the shard of the rank
        ``shift`` *behind* it.  ``shift`` may be negative.  Lowers to a
        single ``collective-permute`` (a remote DMA on ICI).
        """
        ax = _ring_axis(group)
        n = axis_size(ax)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(x, ax, perm)

    def put_perm(self, x, group: DiompGroup, perm: Sequence[Tuple[int, int]]):
        """General one-sided put along an arbitrary (src, dst) permutation."""
        ax = _ring_axis(group)
        return lax.ppermute(x, ax, list(perm))

    def halo_exchange(self, x, group: DiompGroup, *, halo: int,
                      axis: int = 0):
        """Minimod's halo pattern (paper Listing 1) as one fused exchange.

        Every rank puts its *left* boundary slab to the left neighbor's
        right halo and its *right* boundary slab to the right neighbor's
        left halo, then fences.  Returns ``(left_halo, right_halo)``; edge
        ranks receive zeros (non-periodic stencil boundaries).
        """
        # deferred import: rma imports this module at load time
        from .rma import validate_halo

        validate_halo(halo, x.shape[axis], axis)
        ax = _ring_axis(group)
        n = axis_size(ax)
        idx = lax.axis_index(ax)

        left_slab = lax.slice_in_dim(x, 0, halo, axis=axis)
        right_slab = lax.slice_in_dim(
            x, x.shape[axis] - halo, x.shape[axis], axis=axis)

        # put right_slab -> rank+1's left halo; left_slab -> rank-1's right
        # halo.  Non-periodic: drop the wrap-around edge.
        fwd = [(i, i + 1) for i in range(n - 1)]
        bwd = [(i, i - 1) for i in range(1, n)]
        from_left = lax.ppermute(right_slab, ax, fwd)
        from_right = lax.ppermute(left_slab, ax, bwd)

        from_left = jnp.where(idx == 0, jnp.zeros_like(from_left), from_left)
        from_right = jnp.where(idx == n - 1, jnp.zeros_like(from_right),
                               from_right)
        return fence(from_left, from_right)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"{type(self).__name__}({self.name!r})"


class XlaBackend(CclBackend):
    """The flat vendor path: every verb is the base-class XLA lowering."""

    name = "xla"


class HierarchicalBackend(CclBackend):
    """Pod-aware two-level algorithms (NCCL's topology-trees analogue)."""

    name = "hierarchical"

    def allreduce(self, x, group: DiompGroup, *, op: str = "sum"):
        from repro.distributed.hierarchical import hierarchical_allreduce

        x = ensure_varying(x, _axes(group))
        return hierarchical_allreduce(x, group, op=op)

    def reducescatter(self, x, group: DiompGroup, *, axis: int = 0):
        """Fast-axes-first reduce-scatter: the payload is cut to 1/F
        intra-pod before anything crosses the slow inter-pod link.

        Shard order is therefore fast-major — the exact inverse of this
        backend's ``allgather(invariant=True)``, so an RS -> invariant-AG
        pair through one hierarchical handle reconstructs the flat result
        (the bucketed overlap path's contract).  It is NOT the row-major
        shard order of the flat backend, and the handle's *non-invariant*
        allgather keeps the row-major concat order (the standalone
        gather-a-sharded-tensor contract) — pairing RS with
        ``invariant=False`` returns element-permuted data.
        """
        if len(group.axes) < 2:
            return super().reducescatter(x, group, axis=axis)
        slow, fast = group.axes[0], group.axes[1:]
        out = ensure_varying(x, _axes(group))
        for ax in (*fast, slow):
            out = lax.psum_scatter(out, ax, scatter_dimension=axis,
                                   tiled=True)
        return out

    def allgather(self, x, group: DiompGroup, *, axis: int = 0,
                  tiled: bool = True, invariant: bool = False):
        if len(group.axes) < 2 or not tiled:
            return super().allgather(x, group, axis=axis, tiled=tiled,
                                     invariant=invariant)
        x = ensure_varying(x, _axes(group))
        if invariant:
            # slow link first, while the payload is smallest (1/(F·S) ->
            # 1/F crosses inter-pod; the fast axes finish intra-pod) —
            # inverts this backend's reducescatter step for step
            slow, fast = group.axes[0], group.axes[1:]
            out = x
            for ax in (slow, *reversed(fast)):
                out = all_gather_invariant(out, ax, axis=axis, tiled=tiled)
            return out
        from repro.distributed.hierarchical import hierarchical_allgather

        return hierarchical_allgather(x, group, axis=axis)


class CompressedBackend(CclBackend):
    """int8 + error-feedback wire compression around the reduce.

    ``allreduce`` honors the CclBackend contract (returns the reduced
    array); the quantization residual is discarded.  Error-feedback
    training loops need the residual as a traced carry, so they call
    :func:`repro.distributed.compression.compressed_allreduce` directly —
    backend-instance state cannot thread a per-step carry.
    """

    name = "compressed"

    def allreduce(self, x, group: DiompGroup, *, op: str = "sum",
                  error=None):
        from repro.distributed.compression import compressed_allreduce

        if op != "sum":
            raise ValueError(
                f"compressed backend reduces op='sum' only, got {op!r} "
                "(min/max do not decompose through quantized chunks)")
        x = ensure_varying(x, _axes(group))
        # compressed_allreduce returns the group MEAN; scale back to the
        # sum the CclBackend contract promises
        out, _residual = compressed_allreduce(x, group, error=error)
        return jax.tree.map(lambda o: o * group_size(group), out)


class AnalyticBackend(CclBackend):
    """XLA wire path + a host-side analytic cost log per call.

    Each collective traced through this backend appends an estimate row to
    :attr:`estimates` (op, payload bytes, group size, modeled seconds on
    the v5e link model) — the dry-run's napkin math, attached to the same
    call stream the communicator records.  Estimation failures (e.g. a
    pytree operand outside shard_map) degrade to ``est_s=None`` rather than
    perturbing the traced program.
    """

    name = "analytic"

    def __init__(self, link: Optional["LinkModel"] = None):
        self.link = link or LinkModel()
        self.estimates: List[dict] = []

    def _note(self, op: str, x, group: DiompGroup, time_fn) -> None:
        try:
            nbytes = payload_bytes(x)
            ndev = group_size(group)
            est = time_fn(nbytes, ndev)
        except Exception:  # noqa: BLE001 - cost model must never break trace
            nbytes, ndev, est = None, None, None
        self.estimates.append(
            {"op": op, "bytes": nbytes, "ndev": ndev, "est_s": est})

    def allreduce(self, x, group: DiompGroup, *, op: str = "sum"):
        self._note("allreduce", x, group,
                   lambda b, n: ring_allreduce_time(b, n, self.link))
        return super().allreduce(x, group, op=op)

    # bcast needs no override: the base class routes it through
    # self.allreduce, which logs the underlying all-reduce estimate

    def allgather(self, x, group: DiompGroup, *, axis: int = 0,
                  tiled: bool = True, invariant: bool = False):
        self._note("allgather", x, group,
                   lambda b, n: ring_allgather_time(b * n, n, self.link))
        return super().allgather(x, group, axis=axis, tiled=tiled,
                                 invariant=invariant)

    def reducescatter(self, x, group: DiompGroup, *, axis: int = 0):
        self._note("reducescatter", x, group,
                   lambda b, n: ring_allgather_time(b, n, self.link))
        return super().reducescatter(x, group, axis=axis)

    def alltoall(self, x, group: DiompGroup, *, split_axis: int = 0,
                 concat_axis: int = 0):
        self._note("alltoall", x, group,
                   lambda b, n: ring_allgather_time(b, n, self.link))
        return super().alltoall(x, group, split_axis=split_axis,
                                concat_axis=concat_axis)

    def put(self, x, group: DiompGroup, *, shift: int = 1):
        self._note("put", x, group,
                   lambda b, n: b / self.link.bandwidth_Bps
                   + self.link.latency_s)
        return super().put(x, group, shift=shift)


# ---------------------------------------------------------------------------
# backend registry (models OMPCCL's vendor-library dispatch table)
# ---------------------------------------------------------------------------

_BACKENDS: Dict[str, Type[CclBackend]] = {}


def register_backend(cls: Type[CclBackend], *,
                     name: Optional[str] = None,
                     aliases: Sequence[str] = ()) -> Type[CclBackend]:
    """Register a backend class under ``cls.name`` (usable as a decorator).

    New backends plug in without touching a single call site: every
    communicator handle resolves its backend through this table.
    """
    if not (isinstance(cls, type) and issubclass(cls, CclBackend)):
        raise BackendError(f"{cls!r} is not a CclBackend subclass")
    key = name or cls.name
    if not key:
        raise BackendError(f"{cls.__name__} has no backend name")
    _BACKENDS[key] = cls
    for alias in aliases:
        _BACKENDS[alias] = cls
    return cls


def get_backend(name: str) -> Type[CclBackend]:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise BackendError(
            f"unknown OMPCCL backend {name!r}; available: "
            f"{sorted(set(_BACKENDS))}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(set(_BACKENDS)))


register_backend(XlaBackend, aliases=("flat",))
register_backend(HierarchicalBackend)
register_backend(CompressedBackend)
register_backend(AnalyticBackend)


# ---------------------------------------------------------------------------
# analytic cost model (used by benchmarks + the hillclimb napkin math)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinkModel:
    """v5e ICI link model; one link per mesh-torus direction."""

    bandwidth_Bps: float = 50e9  # ~50 GB/s per link direction
    latency_s: float = 1e-6  # per-hop launch latency
    dispatch_s: float = 5e-6  # host/XLA launch overhead per collective

    def collective_time(self, nbytes: int, ndev: int) -> float:
        """One ring all-reduce including the per-call dispatch overhead —
        the unit cost both gradient-reduction schedules are built from."""
        return self.dispatch_s + ring_allreduce_time(nbytes, ndev, self)


def ring_allreduce_time(bytes_: int, ndev: int, link: LinkModel = LinkModel()) -> float:
    """2(n-1)/n · B / bw + 2(n-1) · lat — the classic ring bound."""
    if ndev <= 1:
        return 0.0
    steps = 2 * (ndev - 1)
    return steps * link.latency_s + (steps / ndev) * bytes_ / link.bandwidth_Bps


def ring_allgather_time(bytes_out: int, ndev: int, link: LinkModel = LinkModel()) -> float:
    if ndev <= 1:
        return 0.0
    steps = ndev - 1
    return steps * link.latency_s + (steps / ndev) * bytes_out / link.bandwidth_Bps


def hierarchical_allreduce_time(
    bytes_: int,
    intra: int,
    inter: int,
    intra_link: LinkModel = LinkModel(),
    inter_link: LinkModel = LinkModel(bandwidth_Bps=25e9, latency_s=5e-6),
) -> float:
    """RS(intra) + AR(inter, on 1/intra of the data) + AG(intra)."""
    t_rs = ring_allgather_time(bytes_, intra, intra_link)  # RS cost == AG cost
    t_ar = ring_allreduce_time(bytes_ // max(intra, 1), inter, inter_link)
    t_ag = ring_allgather_time(bytes_, intra, intra_link)
    return t_rs + t_ar + t_ag


def per_param_reduce_time(sizes_bytes: Sequence[int], ndev: int,
                          link: LinkModel = LinkModel(),
                          *, compute_s: float = 0.0) -> float:
    """The per-param issue schedule: the whole backward finishes, then one
    collective per parameter runs back-to-back — nothing overlaps."""
    return compute_s + sum(link.collective_time(b, ndev) for b in sizes_bytes)


def bucketed_reduce_time(bucket_bytes: Sequence[int], ndev: int,
                         link: LinkModel = LinkModel(),
                         *, compute_s: float = 0.0) -> float:
    """The NON-overlap bucketed schedule (``overlap_grad_reduce=False`` or
    ``microbatch == 1``): the whole backward finishes, then every bucket's
    all-reduce runs back-to-back — exactly what ``reduce_bucketed`` issues
    after the scan.  On a layout whose raw parameter count is already
    small (stacked-layer schemas) this *loses* to per-param issue by the
    extra dispatches; the shipped win comes from the overlap pipeline
    (:func:`overlapped_reduce_time`) plus the per-call padding/group-
    resolution overhead the LinkModel does not charge.

    The serial cost model is identical to per-param issue — one collective
    per payload after the compute — so this delegates to
    :func:`per_param_reduce_time`; only the payload list differs.
    """
    return per_param_reduce_time(bucket_bytes, ndev, link,
                                 compute_s=compute_s)


def overlapped_reduce_time(bucket_bytes: Sequence[int], ndev: int,
                           link: LinkModel = LinkModel(),
                           *, compute_s: float = 0.0,
                           microbatches: int = 1) -> float:
    """The backward-overlap schedule build_train_step actually ships with
    ``overlap_grad_reduce`` and ``microbatch = k``: every microbatch's
    buckets reduce-scatter under the NEXT microbatch's backward, and one
    all-gather per bucket trails the scan.

    Wire volume is ``(k + 1)·B·(n-1)/n`` per bucket (k one-phase RS + one
    one-phase AG) vs the single allreduce's ``2B(n-1)/n`` — the price of
    pipelining — so this model, not :func:`bucketed_reduce_time`, is what
    the CI gate must also check: in a wire-bound regime the extra
    reduce-scatters can lose to per-param issue even when the one-shot
    bucketed schedule wins.
    """
    buckets = list(bucket_bytes)
    k = max(microbatches, 1)
    if not buckets:
        return compute_s

    def phase(b):  # one RS or AG pass: half an allreduce + its dispatch
        if ndev <= 1:
            return link.dispatch_s
        return link.dispatch_s + (ndev - 1) * (
            link.latency_s + b / (ndev * link.bandwidth_Bps))

    per_slot_compute = compute_s / (k * len(buckets))
    done = 0.0
    slot = 0
    for _ in range(k):
        for b in buckets:
            slot += 1
            done = max(done, slot * per_slot_compute) + phase(b)
    for b in buckets:            # trailing all-gathers: nothing hides them
        done += phase(b)
    return done
