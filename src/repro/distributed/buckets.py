"""Gradient bucketing — the planned flat-bucket DP reduction subsystem.

Per-parameter gradient reduction is latency-bound: every small tensor pays
a full collective launch (and, on a ring, ``2(n-1)`` per-hop latencies),
and every call re-resolves its group and pads/reshapes its own payload.
:class:`BucketPlanner` turns the parameter schema into a *plan* — the same
"schedule as data" discipline as :class:`repro.kernels.plan.RingPlan`:

* the gradient pytree is partitioned by ``(group-of-unreduced-DP-axes,
  wire dtype, duplication factor)`` — every member of a partition needs the
  exact same collective and the same 1/dup weighting in the global norm;
* each partition is packed, in deterministic name order, into flat buckets
  of at most ``bucket_bytes`` (params split across bucket boundaries, so a
  partition with ``T`` payload bytes issues exactly
  ``ceil(T / bucket_bytes)`` collectives — the bound the call-log test
  asserts);
* every bucket is padded **once, in the layout** to a multiple of its
  group size (times the int8 quantization block when a codec is active),
  so neither :func:`repro.distributed.hierarchical.hierarchical_allreduce`
  nor :func:`repro.distributed.compression.compressed_allreduce` ever pads
  or reshapes per call.

Plans are derived from static shapes only, computed once at trace time
(or ahead of it, from the schema) and identical across traces.  Pack /
unpack are pure reshape/concat index maps baked from the plan.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.context import default_context
from repro.core.groups import DiompGroup, group_for_axes

__all__ = [
    "DEFAULT_BUCKET_BYTES",
    "GRAD_QUANT_BLOCK",
    "BucketSlice",
    "Bucket",
    "BucketPlan",
    "BucketPlanner",
    "unreduced_dp_axes",
    "local_shape",
    "duplication_factor",
    "plan_for_config",
    "pack_buckets",
    "unpack_buckets",
    "backend_for_axes",
    "backend_for_bucket",
    "reduce_bucketed",
]

F32 = jnp.float32
WIRE_ITEMSIZE = 4                  # buckets reduce in f32 (the step's discipline)
DEFAULT_BUCKET_BYTES = 4 * 2**20
GRAD_QUANT_BLOCK = 1024            # int8 per-block scale granularity


def unreduced_dp_axes(pspec, dp_axes) -> Tuple[str, ...]:
    """The DP axes a parameter's sharding does NOT consume — exactly the
    axes its gradient still needs a cross-device reduction over."""
    spec_axes = set()
    for part in pspec:
        if part is None:
            continue
        spec_axes |= set(part if isinstance(part, tuple) else (part,))
    return tuple(a for a in dp_axes if a not in spec_axes)


def local_shape(shape: Sequence[int], pspec,
                mesh_sizes: Mapping[str, int]) -> Tuple[int, ...]:
    """Per-device shard shape of a global tensor under ``pspec``."""
    parts = list(pspec) + [None] * (len(shape) - len(pspec))
    out = []
    for dim, part in zip(shape, parts):
        div = 1
        if part is not None:
            for ax in (part if isinstance(part, tuple) else (part,)):
                div *= mesh_sizes[ax]
        out.append(dim // div)
    return tuple(out)


def duplication_factor(pspec, mesh_sizes: Mapping[str, int]) -> int:
    """Device copies per element: world size / sharded ways — the 1/dup
    weight in the global norm.  The ONE shared implementation (the bucket
    partition key and the per-param norm fallback must agree)."""
    world = 1
    for s in mesh_sizes.values():
        world *= s
    sharded = 1
    for part in pspec:
        if part is None:
            continue
        for ax in (part if isinstance(part, tuple) else (part,)):
            sharded *= mesh_sizes[ax]
    return world // sharded


# ---------------------------------------------------------------------------
# the plan objects
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketSlice:
    """One contiguous run of a parameter's flattened local gradient.

    ``offset`` locates the run inside the bucket, ``start`` inside the
    parameter; a parameter larger than the bucket budget is split across
    consecutive buckets (sum is elementwise, so a split reduces exactly
    like an unsplit tensor).
    """

    name: str
    offset: int
    start: int
    size: int


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One flat wire payload: reduced by ONE collective on ``group``."""

    key: str
    axes: Tuple[str, ...]
    dtype: str
    dup: int
    index: int
    size: int                       # live elements
    padded_size: int                # size rounded up to the layout multiple
    slices: Tuple[BucketSlice, ...]

    @property
    def group(self) -> DiompGroup:
        return group_for_axes(self.axes)

    def group_size(self, mesh_sizes: Mapping[str, int]) -> int:
        g = 1
        for ax in self.axes:
            g *= mesh_sizes[ax]
        return g

    def shard_size(self, mesh_sizes: Mapping[str, int]) -> int:
        """Per-device elements of the reduce-scattered bucket (the overlap
        carry) — exact because ``padded_size`` is a group-size multiple."""
        return self.padded_size // self.group_size(mesh_sizes)

    @property
    def nbytes(self) -> int:
        return self.size * WIRE_ITEMSIZE

    @property
    def padded_nbytes(self) -> int:
        return self.padded_size * WIRE_ITEMSIZE


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """The full reduction schedule for one (config, mesh, ctx)."""

    buckets: Tuple[Bucket, ...]
    local: Tuple[str, ...]          # params needing no cross-device reduce
    shapes: Mapping[str, Tuple[int, ...]]   # local grad shapes, all params
    dups: Mapping[str, int]         # duplication factor, all params
    bucket_bytes: int

    def bucket_count(self) -> Dict[Tuple[str, ...], int]:
        out: Dict[Tuple[str, ...], int] = {}
        for b in self.buckets:
            out[b.axes] = out.get(b.axes, 0) + 1
        return out

    def total_bytes(self) -> int:
        return sum(b.nbytes for b in self.buckets)


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketPlanner:
    """Partition + pack the gradient pytree into planned flat buckets.

    ``quant_block`` > 0 aligns every bucket to ``group_size * quant_block``
    so the blockwise int8 codec's chunking never pads per call (set when
    ``grad_codec="int8"``); otherwise buckets align to the group size,
    which every hierarchical fast-axis reduce-scatter divides.
    """

    bucket_bytes: int = DEFAULT_BUCKET_BYTES
    quant_block: int = 0

    def plan(self, shapes: Mapping[str, Sequence[int]],
             pspecs: Mapping[str, object], dp_axes: Sequence[str],
             mesh_sizes: Mapping[str, int]) -> BucketPlan:
        """Build the plan from static *local* shapes.

        Deterministic: partitions are visited in sorted key order, members
        in sorted name order, so the same inputs always produce the same
        buckets (asserted across traces by the tests).
        """
        dp_axes = tuple(dp_axes)
        parts: Dict[Tuple, list] = {}
        local = []
        loc_shapes = {}
        dups = {}
        for name in sorted(shapes):
            shp = tuple(int(d) for d in shapes[name])
            loc_shapes[name] = shp
            dups[name] = duplication_factor(pspecs[name], mesh_sizes)
            need = unreduced_dp_axes(pspecs[name], dp_axes)
            if not need:
                local.append(name)
                continue
            parts.setdefault((need, "float32", dups[name]), []).append(name)

        # capacity rounds UP to whole elements: flooring would let a
        # bucket_bytes that is not a multiple of the wire itemsize exceed
        # the documented ceil(partition_bytes / bucket_bytes) call bound
        bucket_elems = max(-(-self.bucket_bytes // WIRE_ITEMSIZE), 1)
        buckets = []
        for (axes, dtype, dup) in sorted(parts):
            names = parts[(axes, dtype, dup)]
            gsize = 1
            for ax in axes:
                gsize *= mesh_sizes[ax]
            align = gsize * (self.quant_block or 1)
            index = 0
            pos = 0
            slices: list = []

            def close():
                nonlocal index, pos, slices
                if not slices:
                    return
                padded = -(-pos // align) * align
                key = f"{'+'.join(axes)}|{dtype}|dup{dup}|{index}"
                buckets.append(Bucket(
                    key=key, axes=axes, dtype=dtype, dup=dup, index=index,
                    size=pos, padded_size=padded, slices=tuple(slices)))
                index += 1
                pos = 0
                slices = []

            for name in names:
                left = 1
                for d in loc_shapes[name]:
                    left *= d
                start = 0
                while left > 0:
                    take = min(bucket_elems - pos, left)
                    slices.append(BucketSlice(name, pos, start, take))
                    pos += take
                    start += take
                    left -= take
                    if pos == bucket_elems:
                        close()
            close()
        return BucketPlan(buckets=tuple(buckets), local=tuple(local),
                          shapes=loc_shapes, dups=dups,
                          bucket_bytes=self.bucket_bytes)

    def plan_from_arrays(self, grads: Mapping[str, object],
                         pspecs: Mapping[str, object],
                         dp_axes: Sequence[str],
                         mesh_sizes: Mapping[str, int]) -> BucketPlan:
        """Plan from live (local) gradient arrays at trace time — shapes
        are static under shard_map, so this is identical to :meth:`plan`
        fed the derived local shapes."""
        return self.plan({n: g.shape for n, g in grads.items()},
                         pspecs, dp_axes, mesh_sizes)


@functools.lru_cache(maxsize=64)
def plan_for_config(cfg, mesh, ctx, *,
                    bucket_bytes: Optional[int] = None) -> BucketPlan:
    """The plan for one (ModelConfig, Mesh, ParallelCtx) — cached, so every
    trace of a step (and every bench / test inspecting the schedule) shares
    one plan object."""
    from repro.distributed.sharding import rules_for_ctx
    from repro.models import schema as sch

    pspecs = sch.partition_specs(cfg, mesh, rules_for_ctx(ctx))
    sizes = dict(mesh.shape)
    shapes = {name: local_shape(spec.shape, pspecs[name], sizes)
              for name, spec in sch.build_schema(cfg).items()}
    planner = BucketPlanner(
        bucket_bytes=(ctx.bucket_bytes if bucket_bytes is None
                      else bucket_bytes),
        quant_block=GRAD_QUANT_BLOCK if ctx.grad_codec == "int8" else 0)
    return planner.plan(shapes, pspecs, ctx.dp_group.axes, sizes)


# ---------------------------------------------------------------------------
# pack / unpack (pure index maps baked from the plan)
# ---------------------------------------------------------------------------


def pack_buckets(grads: Mapping[str, jax.Array], plan: BucketPlan,
                 *, vary: Tuple[str, ...] = ()) -> Dict[str, jax.Array]:
    """Flatten + concatenate each bucket's member slices (f32, zero-padded).

    ``vary`` promotes every slice to be varying over those mesh axes before
    the concat — members of one bucket can carry different vma sets (their
    own sharded axes differ), and a concat operand set must agree.
    """
    from repro.core.backends import ensure_varying
    from repro.core.compat import typeof

    out = {}
    for b in plan.buckets:
        pieces = []
        for s in b.slices:
            flat = grads[s.name].astype(F32).reshape(-1)
            if not (s.start == 0 and s.size == flat.size):
                flat = flat[s.start:s.start + s.size]
            pieces.append(flat)
        if vary:
            # the bucket's own axes plus whatever its members already vary
            # over: promoting further would leave the reduced grads varying
            # over axes their parameters are replicated on
            axes = set(b.axes)
            for flat in pieces:
                axes |= set(getattr(typeof(flat), "vma", ()))
            axes = tuple(a for a in vary if a in axes)
            pieces = [ensure_varying(flat, axes) for flat in pieces]
        if b.padded_size > b.size:
            padz = jnp.zeros((b.padded_size - b.size,), F32)
            pieces.append(ensure_varying(padz, axes) if vary else padz)
        out[b.key] = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    return out


def unpack_buckets(bufs: Mapping[str, jax.Array],
                   plan: BucketPlan) -> Dict[str, jax.Array]:
    """Inverse of :func:`pack_buckets`: reassemble per-param f32 grads."""
    pieces: Dict[str, list] = {}
    for b in plan.buckets:
        buf = bufs[b.key]
        for s in b.slices:
            pieces.setdefault(s.name, []).append(
                buf[s.offset:s.offset + s.size])
    out = {}
    for name, ps in pieces.items():
        flat = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
        out[name] = flat.reshape(plan.shapes[name])
    return out


# ---------------------------------------------------------------------------
# the whole-bucket reduction
# ---------------------------------------------------------------------------


def backend_for_axes(axes: Sequence[str], ctx) -> str:
    """The dp_backend dispatch policy — the ONE copy both the bucketed and
    the per-param reduction paths resolve backends through."""
    if (ctx.dp_backend == "hierarchical" and "pod" in axes
            and len(axes) > 1):
        return "hierarchical"
    return "xla"


def backend_for_bucket(bucket: Bucket, ctx) -> str:
    """The OMPCCL backend one bucket's collective dispatches through."""
    return backend_for_axes(bucket.axes, ctx)


def reduce_bucketed(grads: Mapping[str, jax.Array], plan: BucketPlan, ctx,
                    *, errors: Optional[dict] = None, context=None,
                    vary: Tuple[str, ...] = ()):
    """DP mean-reduction of whole buckets, one communicator handle each.

    Mirrors the per-param contract of ``train.step.reduce_gradients``
    (grads divided by ``ctx.dp``, summed over each bucket's group; int8
    buckets reduce through the blockwise compressed codec with ONE
    error-feedback state per bucket), but issues
    ``ceil(partition_bytes / bucket_bytes)`` collectives per partition
    instead of one per parameter.

    Returns ``(reduced_grads, reduced_bufs, new_errors)`` — the reduced
    flat buckets ride along so the caller can compute the global grad norm
    bucket-wise without re-packing.
    """
    from repro.distributed.compression import compressed_allreduce

    dctx = context or default_context()
    dp_axes = tuple(ctx.dp_group.axes)
    if errors and plan.buckets and not any(b.key in errors
                                           for b in plan.buckets):
        # name-keyed residual from a per-param caller: silently reducing
        # with error=None would drop the accumulated int8 feedback — fail
        # loudly instead of degrading convergence
        raise ValueError(
            "error-feedback state keys match no bucket in the plan "
            f"(got {sorted(errors)[:3]}...); carried per-param errors? "
            "pass bucket_bytes=0 / plan=None to stay on the per-param path")
    out = {n: grads[n].astype(F32) / ctx.dp for n in plan.local}
    bufs = pack_buckets(grads, plan, vary=vary)
    new_errors = {}
    red = {}
    for b in plan.buckets:
        if ctx.grad_codec == "int8" and set(b.axes) == set(dp_axes):
            # the codec returns the group MEAN, and the bucket's group IS
            # the dp group here, so the raw sum goes in — no /dp round trip
            err = errors.get(b.key) if errors else None
            buf, e = compressed_allreduce(bufs[b.key], b.group, error=err,
                                          block=GRAD_QUANT_BLOCK)
            new_errors[b.key] = e
        else:
            comm = dctx.communicator(b.group, backend_for_bucket(b, ctx))
            buf = comm.allreduce(bufs[b.key] / ctx.dp)
        red[b.key] = buf
    out.update(unpack_buckets(red, plan))
    return out, red, new_errors
