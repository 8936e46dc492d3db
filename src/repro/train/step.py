"""Train-step builder: grad accumulation + explicit OMPCCL gradient reduction
+ optimizer, all inside one shard_map (the DiOMP unified-runtime discipline).

Gradient-reduction strategy (DESIGN.md §4):

* ``ctx.explicit_dp=True`` (DiOMP mode): parameters are ``pvary``'d over the
  DP axes before differentiation, so AD yields *per-device* gradients with
  no implicit cross-batch collectives; the reduction then runs explicitly
  through OMPCCL with a selectable backend — flat psum / pod-hierarchical /
  int8-compressed with error feedback.  ZeRO-3 params still reduce-scatter
  over "data" inside AD (the all_gather transpose — structurally the
  intra-pod half of the hierarchical algorithm), leaving only the tiny
  inter-pod psum to OMPCCL.
* ``ctx.explicit_dp=False`` (the MPI+X-shaped baseline): AD's automatic
  pvary-transpose psums do the reduction implicitly inside XLA.

Bucketing + backward overlap (the §Perf reduction path):

* With ``ctx.bucket_bytes > 0`` (the default) the per-param reduction is
  replaced by the planned flat-bucket schedule of
  :mod:`repro.distributed.buckets`: the gradient pytree is packed into
  fixed-byte f32 buckets per (group, dtype, dup) partition and each bucket
  reduces through ONE communicator handle — ``ceil(bytes / bucket_bytes)``
  collectives per partition instead of one per parameter.
* With ``ctx.overlap_grad_reduce`` (and ``microbatch > 1``,
  ``grad_codec="none"``) the microbatch ``lax.scan`` carries *reduce-
  scattered* bucket partial sums: each microbatch's bucket gradients
  reduce-scatter inside the accumulation loop (ZeRO-style — the shard is
  1/|group| of the bucket, and the wire work rides under the next
  microbatch's backward), and one invariant all-gather per bucket after
  the scan completes the mean.  Numerically this is the same psum, split
  RS+AG and pipelined.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.compat import shard_map

from repro.core import ompccl
from repro.core.context import default_context
from repro.core.groups import group_for_axes
from repro.distributed import buckets as bk
from repro.distributed.buckets import unreduced_dp_axes as _unreduced_dp_axes
from repro.distributed.compression import compressed_allreduce
from repro.models import api as model_api
from repro.models import schema as sch
from repro.models.config import ModelConfig, ParallelCtx
from .optim import Optimizer, bucketed_sq_norm

__all__ = ["build_train_step", "opt_state_specs", "reduce_gradients",
           "sharded_global_norm"]

F32 = jnp.float32


def _spec_drop_dim(spec: P, rank: int, drop: int) -> P:
    parts = list(spec) + [None] * (rank - len(spec))
    del parts[drop]
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def opt_state_specs(cfg: ModelConfig, mesh: Mesh, optimizer_name: str,
                    rules=None):
    """PartitionSpecs for the optimizer state (mirrors param sharding)."""
    pspecs = sch.partition_specs(cfg, mesh, rules)
    schema = sch.build_schema(cfg)
    if optimizer_name == "adamw":
        return {"m": dict(pspecs), "v": dict(pspecs)}
    out = {}
    for name, spec in pspecs.items():
        rank = len(schema[name].shape)
        shape = schema[name].shape
        if rank >= 2 and shape[-1] > 1 and shape[-2] > 1:
            out[name] = {"vr": _spec_drop_dim(spec, rank, rank - 1),
                         "vc": _spec_drop_dim(spec, rank, rank - 2)}
        else:
            out[name] = {"v": spec}
    return out


def sharded_global_norm(grads, cfg: ModelConfig, ctx: ParallelCtx, mesh: Mesh,
                        pspecs: Optional[dict] = None, *, plan=None,
                        bufs=None):
    """Global L2 norm of a sharded gradient pytree.

    Each param's local sum-of-squares is weighted by 1/duplication (so
    replicated copies count once), then psum'd across the world group.

    When the reduced flat buckets are still at hand (``plan`` + ``bufs``
    from the bucketed reduction), the bucketed local sums are used
    directly — one fused sum per bucket instead of one per parameter; only
    the plan's unbucketed params walk the per-param loop.
    """
    total = jnp.zeros((), F32)
    if plan is not None and bufs is not None:
        total = total + bucketed_sq_norm(bufs, plan)
        for name in plan.local:
            total = total + jnp.sum(grads[name].astype(F32) ** 2) \
                / plan.dups[name]
    else:
        if pspecs is None:
            from repro.distributed.sharding import rules_for_ctx
            pspecs = sch.partition_specs(cfg, mesh, rules_for_ctx(ctx))
        sizes = dict(mesh.shape)
        for name, g in grads.items():
            dup = bk.duplication_factor(pspecs[name], sizes)
            total = total + jnp.sum(g.astype(F32) ** 2) / dup
    total = default_context().communicator(ctx.world).allreduce(total)
    return jnp.sqrt(total)


def reduce_gradients(grads: Dict[str, jax.Array], cfg: ModelConfig,
                     ctx: ParallelCtx, errors: Optional[dict] = None,
                     pspecs: Optional[dict] = None, mesh: Optional[Mesh] = None,
                     plan=None):
    """Explicit DP mean-reduction through OMPCCL.

    Input grads are per-device (params were pvary'd over DP).  A parameter
    needs reduction only over the DP axes its own sharding does NOT use:
    ZeRO-3 / expert2d shards already had their cross-shard sums folded in by
    AD (the all_gather transpose / the all_to_all round trip).  Returns
    (reduced_grads, new_errors).

    Dispatch: with a :class:`~repro.distributed.buckets.BucketPlan` — passed
    in, or derivable (``mesh`` given and ``ctx.bucket_bytes > 0``) — whole
    flat buckets reduce through one communicator handle each (errors keyed
    by bucket).  Otherwise the per-param baseline path runs: one collective
    per parameter, errors keyed by name.
    """
    from repro.distributed.sharding import rules_for_ctx

    if plan is None and mesh is not None and ctx.bucket_bytes:
        plan = bk.plan_for_config(cfg, mesh, ctx)
    if plan is not None:
        # vary over every world axis: bucket members carry different vma
        # sets (their own sharded axes differ) and a concat must agree
        out, _bufs, new_errors = bk.reduce_bucketed(
            grads, plan, ctx, errors=errors, vary=tuple(ctx.world.axes))
        return out, new_errors

    if pspecs is None:
        pspecs = sch.partition_specs(cfg, mesh, rules_for_ctx(ctx))
    dctx = default_context()
    new_errors = {}
    out = {}
    dp_axes = ctx.dp_group.axes
    for name, g in grads.items():
        need = _unreduced_dp_axes(pspecs[name], dp_axes)
        g = g.astype(F32) / ctx.dp
        if not need:
            out[name] = g
            continue
        group = group_for_axes(need)
        if ctx.grad_codec == "int8" and set(need) == set(dp_axes):
            err = errors.get(name) if errors else None
            g, e = compressed_allreduce(g * ctx.dp, group, error=err)
            new_errors[name] = e
        else:
            backend = bk.backend_for_axes(need, ctx)
            g = dctx.communicator(group, backend).allreduce(g)
        out[name] = g
    return out, new_errors


def build_train_step(cfg: ModelConfig, mesh: Mesh, ctx: ParallelCtx,
                     optimizer: Optimizer, *, optimizer_name: str = "adamw",
                     clip_norm: float = 1.0, donate: bool = True,
                     global_batch: int = 0):
    """Returns the jitted step:

    step(params, opt_state, batch, step_idx) ->
        (params', opt_state', metrics{loss, grad_norm[, moe_dropped,
        moe_drop_rate on MoE configs]})

    ``global_batch`` determines the batch sharding (divisibility over the DP
    axes); pass the real batch size — 0 falls back to dp-divisible.
    """
    import dataclasses

    from repro.distributed.sharding import rules_for_ctx
    from repro.kernels.plan import (default_planner, resolve_dispatch_impl,
                                    resolve_ring_impl, resolve_seq_parallel)

    # resolve the ring-matmul schedule ONCE so the whole step traces against
    # one concrete plan (fused bidirectional unless the ctx pins "host");
    # the MoE dispatch mode and the sequence-parallel attention strategy
    # resolve the same way
    ctx = dataclasses.replace(
        ctx, ring_impl=resolve_ring_impl(ctx.ring_impl),
        dispatch_impl=resolve_dispatch_impl(ctx.dispatch_impl),
        seq_parallel=resolve_seq_parallel(ctx.seq_parallel))
    rules = rules_for_ctx(ctx)
    loss_fn = model_api.loss_fn(cfg)
    pspecs = sch.partition_specs(cfg, mesh, rules)
    ospecs = opt_state_specs(cfg, mesh, optimizer_name, rules)
    dp_axes = ctx.dp_group.axes
    # axes a value can vary over: a size-1 axis splits nothing, and the
    # partition specs leave it out (sharding.ShardingRules.lookup)
    all_axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    mesh_sizes = dict(mesh.shape)
    if not global_batch:  # default: assume a dp-divisible batch
        global_batch = ctx.dp
    _, bspecs = model_api.batch_structs(cfg, mesh, global_batch, 1,
                                        dp_axes=dp_axes)

    # the reduction schedule, like the ring schedule, is resolved once at
    # build time: static shapes in, flat-bucket index maps out
    plan = (default_planner().plan_grad_buckets(cfg, mesh, ctx)
            if ctx.explicit_dp and dp_axes and ctx.bucket_bytes else None)

    def step(params, opt_state, batch, step_idx):
        # DiOMP mode: per-device grads, reduction owned by OMPCCL
        p_diff = (ompccl.ensure_varying(params, dp_axes)
                  if ctx.explicit_dp and dp_axes else params)

        def local_loss(p, mb):
            # drop stats are data-dependent (the capacity overflow mask), so
            # they leave the trace as has_aux outputs; the frame must open
            # INSIDE the traced function (DispatchStats is trace-scoped)
            with default_context().dispatch_stats.collect() as ds:
                loss = loss_fn(p, mb, cfg, ctx)
            zero = jnp.zeros((), F32)
            return loss, (ds.get("moe_dropped", zero),
                          ds.get("moe_routed", zero))

        b_local = jax.tree.leaves(batch)[0].shape[0]
        k = max(min(ctx.microbatch, b_local), 1)
        while b_local % k:          # clamp to a divisor of the local batch
            k -= 1
        # buckets RS inside the scan, AG after it (backward overlap)?
        overlap = (plan is not None and plan.buckets and k > 1
                   and ctx.overlap_grad_reduce and ctx.grad_codec == "none")
        bufs = None
        reduced = False
        if k > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)

            # per-leaf carry vma: grads vary over the DP axes (iff params
            # were pvary'd there) plus the param's own sharded axes
            grad_dp = tuple(dp_axes) if ctx.explicit_dp else ()

            def leaf_axes(name):
                spec_axes = []
                for part in pspecs[name]:
                    if part is None:
                        continue
                    spec_axes += list(part if isinstance(part, tuple)
                                      else (part,))
                return tuple(dict.fromkeys(grad_dp + tuple(spec_axes)))

            def norm_g(g):
                return {n: ompccl.ensure_varying(v, leaf_axes(n))
                        for n, v in g.items()}

            def bucket_axes(b):
                # a bucket's reduce axes plus its members' own axes: what
                # the packed bucket (pack_buckets) varies over
                axes = set(b.axes)
                for sl in b.slices:
                    axes.update(leaf_axes(sl.name))
                return tuple(a for a in all_axes if a in axes)

            if overlap:
                # resolved at trace time like every other collective site
                dctx = default_context()
                comms = {b.key: dctx.communicator(
                    b.group, bk.backend_for_bucket(b, ctx))
                    for b in plan.buckets}

                def micro(carry, mb):
                    loss_acc, aux_acc, g_acc, sh_acc = carry
                    (l, aux), g = jax.value_and_grad(
                        local_loss, has_aux=True)(p_diff, mb)
                    # unbucketed params accumulate whole, as before
                    g_acc = {n: g_acc[n] + g[n].astype(F32) for n in g_acc}
                    # bucketed params: pack THIS microbatch's grads and
                    # reduce-scatter each bucket — the collective overlaps
                    # the next microbatch's backward; the carry holds only
                    # the 1/|group| partial-sum shard
                    mb_bufs = bk.pack_buckets(g, plan, vary=all_axes)
                    sh = {}
                    for b in plan.buckets:
                        piece = comms[b.key].reducescatter(mb_bufs[b.key],
                                                           axis=0)
                        sh[b.key] = ompccl.ensure_varying(
                            sh_acc[b.key] + piece, bucket_axes(b))
                    aux_acc = tuple(
                        ompccl.ensure_varying(a + x, all_axes)
                        for a, x in zip(aux_acc, aux))
                    return (ompccl.ensure_varying(loss_acc + l, all_axes),
                            aux_acc, norm_g(g_acc), sh), None

                zero_g = norm_g({n: jnp.zeros(params[n].shape, F32)
                                 for n in plan.local})
                zero_sh = {
                    b.key: ompccl.ensure_varying(
                        jnp.zeros((b.shard_size(mesh_sizes),), F32),
                        bucket_axes(b))
                    for b in plan.buckets}
                loss0 = ompccl.ensure_varying(jnp.zeros((), F32), all_axes)
                aux0 = tuple(ompccl.ensure_varying(jnp.zeros((), F32),
                                                   all_axes)
                             for _ in range(2))
                (loss, aux, g_local, shards), _ = lax.scan(
                    micro, (loss0, aux0, zero_g, zero_sh), mbs)
                loss = loss / k
                # the trailing exchange: ONE invariant all-gather per bucket
                # (the only wire work not hidden behind backward compute)
                bufs = {
                    b.key: comms[b.key].allgather(
                        shards[b.key] / (k * ctx.dp), axis=0, tiled=True,
                        invariant=True)
                    for b in plan.buckets}
                grads = {n: g_local[n] / (k * ctx.dp) for n in plan.local}
                grads.update(bk.unpack_buckets(bufs, plan))
                reduced = True
            else:
                def micro(carry, mb):
                    loss_acc, aux_acc, g_acc = carry
                    (l, aux), g = jax.value_and_grad(
                        local_loss, has_aux=True)(p_diff, mb)
                    g_acc = {n: g_acc[n] + g[n].astype(F32) for n in g_acc}
                    aux_acc = tuple(
                        ompccl.ensure_varying(a + x, all_axes)
                        for a, x in zip(aux_acc, aux))
                    # scalar loss: canonicalize to all mesh axes (an
                    # unsharded-vocab CE stays model-varying; a sharded one
                    # does not)
                    return (ompccl.ensure_varying(loss_acc + l, all_axes),
                            aux_acc, norm_g(g_acc)), None

                zero_g = norm_g({n: jnp.zeros(p.shape, F32)
                                 for n, p in params.items()})
                loss0 = ompccl.ensure_varying(jnp.zeros((), F32), all_axes)
                aux0 = tuple(ompccl.ensure_varying(jnp.zeros((), F32),
                                                   all_axes)
                             for _ in range(2))
                (loss, aux, grads), _ = lax.scan(micro, (loss0, aux0, zero_g),
                                                 mbs)
                loss = loss / k
                grads = jax.tree.map(lambda g: g / k, grads)
        else:
            (loss, aux), grads = jax.value_and_grad(
                local_loss, has_aux=True)(p_diff, batch)

        if ctx.explicit_dp and dp_axes:
            if not reduced:
                if plan is not None:
                    grads, bufs, _ = bk.reduce_bucketed(
                        grads, plan, ctx, vary=all_axes)
                else:
                    grads, _ = reduce_gradients(grads, cfg, ctx,
                                                pspecs=pspecs)
        else:
            grads = jax.tree.map(lambda g: g.astype(F32) / ctx.dp, grads)

        gnorm = sharded_global_norm(grads, cfg, ctx, mesh, pspecs=pspecs,
                                    plan=plan if bufs is not None else None,
                                    bufs=bufs)
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)

        updates, opt_state = optimizer.update(grads, opt_state, params,
                                              step_idx)
        params = jax.tree.map(lambda p, u: (p.astype(F32) + u.astype(F32)
                                            ).astype(p.dtype), params, updates)
        # resolved at trace time like every other collective site, so the
        # whole step records into whichever context is default when traced
        world_comm = default_context().communicator(ctx.world)
        metrics = {
            "loss": world_comm.allreduce(loss, op="mean"),
            "grad_norm": gnorm,
        }
        if cfg.moe:
            # drop counters are per-rank sums over layers x microbatches;
            # the world sum gives the step's global capacity-overflow drops
            # (identically zero under the dropless fused/host dispatch)
            dropped = world_comm.allreduce(aux[0])
            routed = world_comm.allreduce(aux[1])
            metrics["moe_dropped"] = dropped
            metrics["moe_drop_rate"] = dropped / jnp.maximum(routed, 1.0)
        return params, opt_state, metrics

    mspecs = {"loss": P(), "grad_norm": P()}
    if cfg.moe:
        mspecs.update({"moe_dropped": P(), "moe_drop_rate": P()})
    mapped = shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, ospecs, bspecs, P()),
        out_specs=(pspecs, ospecs, mspecs),
    )
    jit_kwargs = {"donate_argnums": (0, 1)} if donate else {}
    return jax.jit(mapped, **jit_kwargs)
