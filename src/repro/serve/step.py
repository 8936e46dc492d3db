"""Serve-step builders: shard_map'd prefill and decode steps per family.

The programs are jitted as ``serve_decode``, ``serve_prefill_chunk`` and
``serve_prefill``, the names a profiler trace and a dump show them under.

The decode step is THE unit the decode_32k / long_500k dry-run cells lower:
one new token against a full KV cache, with the cache sharded per the
runtime's placement rules (heads over "model"; batch over DP axes; the S
axis over "data" for the context-parallel long shapes).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.compat import shard_map

from repro.core import ompccl
from repro.models import api as model_api
from repro.models import schema as sch
from repro.models.config import ModelConfig, ParallelCtx

__all__ = ["build_decode_step", "build_prefill_step",
           "build_chunk_prefill_step"]


def build_decode_step(cfg: ModelConfig, mesh: Mesh, ctx: ParallelCtx, *,
                      B: int, S: int, seq_sharded: bool = False,
                      donate: bool = True, slot_pos: bool = False):
    """jitted (params, tokens (B,1), cache) -> (logits (B,1,V), cache').

    ``slot_pos=True`` (the serving engine) declares ``cache["pos"]`` as a
    per-slot (B,) vector sharded like the batch, so a slot count divisible
    by the DP axes keeps positions aligned with their cache rows.
    """
    import dataclasses

    from repro.distributed.sharding import rules_for_ctx
    from repro.kernels.plan import (resolve_dispatch_impl, resolve_ring_impl,
                                    resolve_seq_parallel)

    ctx = dataclasses.replace(
        ctx, inference=True, remat=False,
        ring_impl=resolve_ring_impl(ctx.ring_impl),
        dispatch_impl=resolve_dispatch_impl(ctx.dispatch_impl),
        seq_parallel=resolve_seq_parallel(ctx.seq_parallel))
    decode = model_api.decode_fn(cfg)
    pspecs = sch.partition_specs(cfg, mesh, rules_for_ctx(ctx))
    _, cspecs = model_api.cache_structs(cfg, mesh, ctx, B, S,
                                        seq_sharded=seq_sharded)
    ba = model_api._batch_axes(mesh, B)
    bpart = ba if ba else None
    if slot_pos:
        cspecs = dict(cspecs)
        cspecs["pos"] = P(bpart)
    vs = "model" if sch.vocab_sharded(cfg) else None

    def serve_decode(params, tokens, cache):
        logits, cache = decode(params, tokens, cfg, ctx, cache,
                               seq_sharded=seq_sharded)
        return logits, cache

    mapped = shard_map(
        serve_decode, mesh=mesh,
        in_specs=(pspecs, P(bpart), cspecs),
        out_specs=(P(bpart, None, vs), cspecs),
    )
    kwargs = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(mapped, **kwargs)


def build_chunk_prefill_step(cfg: ModelConfig, mesh: Mesh, ctx: ParallelCtx,
                             *, C: int, S_cache: int, B: int = 1,
                             donate: bool = False):
    """jitted (params, tokens (B,C), cache, rlen ()) -> (logits (B,1,V), cache').

    The serving engine's chunked-prefill unit (docs/SERVING.md): ``cache``
    is the engine cache sliced to one slot (B=1) with a *scalar* ``pos``;
    the chunk is appended at ``pos`` and the logits of the last real token
    (``rlen - 1``) come back — ONE device call per prompt chunk instead of
    one per prompt token.  Transformer families only (attention caches
    address by position; recurrent-state families prefill token-by-token
    through the decode step).
    """
    import dataclasses

    from repro.distributed.sharding import rules_for_ctx
    from repro.kernels.plan import (resolve_dispatch_impl, resolve_ring_impl,
                                    resolve_seq_parallel)
    from repro.models.transformer import transformer_chunk_prefill

    if cfg.family not in model_api.TRANSFORMER_FAMILIES:
        raise ValueError(
            f"chunked prefill supports transformer families only, "
            f"got {cfg.family!r}")
    ctx = dataclasses.replace(
        ctx, inference=True, remat=False,
        ring_impl=resolve_ring_impl(ctx.ring_impl),
        dispatch_impl=resolve_dispatch_impl(ctx.dispatch_impl),
        seq_parallel=resolve_seq_parallel(ctx.seq_parallel))
    pspecs = sch.partition_specs(cfg, mesh, rules_for_ctx(ctx))
    _, cspecs = model_api.cache_structs(cfg, mesh, ctx, B, S_cache)
    vs = "model" if sch.vocab_sharded(cfg) else None

    def serve_prefill_chunk(params, tokens, cache, rlen):
        return transformer_chunk_prefill(params, tokens, cfg, ctx, cache,
                                         rlen)

    mapped = shard_map(
        serve_prefill_chunk, mesh=mesh,
        in_specs=(pspecs, P(None), cspecs, P()),
        out_specs=(P(None, None, vs), cspecs),
    )
    kwargs = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(mapped, **kwargs)


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, ctx: ParallelCtx, *,
                       B: int, S_prompt: int, S_cache: int,
                       seq_sharded: bool = False, donate: bool = True):
    """jitted (params, tokens (B,Sp), cache) -> (last logits, cache')."""
    import dataclasses

    import jax.numpy as jnp

    from repro.models.transformer import transformer_prefill
    from repro.models.rwkv import rwkv_forward
    from repro.models.ssm import zamba_forward

    from repro.distributed.sharding import rules_for_ctx
    from repro.kernels.plan import (resolve_dispatch_impl, resolve_ring_impl,
                                    resolve_seq_parallel)

    ctx = dataclasses.replace(
        ctx, inference=True, remat=False,
        ring_impl=resolve_ring_impl(ctx.ring_impl),
        dispatch_impl=resolve_dispatch_impl(ctx.dispatch_impl),
        seq_parallel=resolve_seq_parallel(ctx.seq_parallel))
    pspecs = sch.partition_specs(cfg, mesh, rules_for_ctx(ctx))
    _, cspecs = model_api.cache_structs(cfg, mesh, ctx, B, S_cache,
                                        seq_sharded=seq_sharded)
    ba = model_api._batch_axes(mesh, B)
    bpart = ba if ba else None
    vs = "model" if sch.vocab_sharded(cfg) else None

    if cfg.family in model_api.TRANSFORMER_FAMILIES:
        def serve_prefill(params, tokens, cache):
            logits, cache = transformer_prefill(
                params, tokens, cfg, ctx, cache, seq_sharded=seq_sharded)
            return logits, cache
    elif cfg.family == "ssm":
        def serve_prefill(params, tokens, cache):
            h, cache = rwkv_forward(params, tokens, cfg, ctx, cache)
            logits = jnp.dot(h[:, -1:].astype(jnp.float32),
                             params["lm_head"].astype(jnp.float32))
            return logits, cache
    elif cfg.family == "hybrid":
        def serve_prefill(params, tokens, cache):
            h, cache = zamba_forward(params, tokens, cfg, ctx, cache,
                                     seq_sharded=seq_sharded)
            logits = jnp.dot(h[:, -1:].astype(jnp.float32),
                             params["lm_head"].astype(jnp.float32))
            return logits, cache
    else:
        raise ValueError(cfg.family)

    mapped = shard_map(
        serve_prefill, mesh=mesh,
        in_specs=(pspecs, P(bpart), cspecs),
        out_specs=(P(bpart, None, vs), cspecs),
    )
    kwargs = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(mapped, **kwargs)
