"""Transformer forward passes (dense / MoE / MLA / VLM / audio encoder).

All functions run inside shard_map (manual SPMD).  Layer stacks are scanned
(``lax.scan`` over the leading L dim of every stacked param) with optional
remat; heterogeneous stacks (DeepSeek's leading dense layers, the MTP head)
are separate scans.

Caches are dicts of stacked arrays: {"k": (L, B, S, KH_loc, D), "v": …,
"pos": ()}.  The layer scan carries the stacks: each layer reads its slice
where it lies and writes only its new rows, so a decode step moves no
whole layer of the cache.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core import ompccl
from repro.core.compat import typeof
from .config import ModelConfig, ParallelCtx
from .layers import (
    KVCache, MLACache, attention_block, ce_loss, embed_lookup, gelu_mlp_block,
    layernorm, mla_block, mlp_block, moe_block, rmsnorm, row_matmul,
    col_matmul, gather_fsdp, tp_allreduce,
)
from .schema import head_parallel, kv_sharded

__all__ = [
    "transformer_forward", "transformer_loss", "init_cache",
    "transformer_prefill", "transformer_chunk_prefill", "transformer_decode",
]


def _stacked(params: Dict[str, jax.Array], prefix: str) -> Dict[str, jax.Array]:
    plen = len(prefix) + 1
    return {k[plen:]: v for k, v in params.items() if k.startswith(prefix + "/")}


def _sinusoid(T: int, d: int, dtype):
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((T, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang)).at[:, 1::2].set(jnp.cos(ang[:, : d // 2]))
    return pe.astype(dtype)


def _layer_body(x, lp, cfg: ModelConfig, ctx: ParallelCtx, *,
                moe: bool, mla: bool, positions, prefix_len: int,
                cache=None, chunked: bool = False):
    """One decoder block: (attn + residual) then (ffn + residual)."""
    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps, plus_one=(cfg.family == "vlm"))
    with jax.named_scope("attention"):
        if mla:
            attn, new_cache = mla_block(h, lp, cfg, ctx, positions=positions,
                                        cache=cache, chunked=chunked)
        else:
            attn, new_cache = attention_block(
                h, lp, cfg, ctx, positions=positions, prefix_len=prefix_len,
                cache=cache, causal=cfg.causal, chunked=chunked)
    x = x + attn
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps, plus_one=(cfg.family == "vlm"))
    with jax.named_scope("mlp"):
        if moe:
            ffn = moe_block(h, lp, cfg, ctx)
            # deepseek keeps no separate dense FFN on MoE layers (shared
            # experts are inside moe_block)
        elif cfg.family == "audio":
            ffn = gelu_mlp_block(h, lp, ctx)
        else:
            act = "gelu" if cfg.family == "vlm" else "silu"
            ffn = mlp_block(h, lp, ctx, act=act)
    return x + ffn, new_cache


def _scan_stack(x, stack, cfg, ctx, *, moe, mla, positions, prefix_len,
                caches=None, remat=False, chunked=False):
    """Scan a homogeneous layer stack; threads caches if given.

    The carry is normalized to a canonical varying set (vma bookkeeping):
    different layer kinds leave the residual stream with different inferred
    replication (a psum'd dense output is model-invariant, an all-gathered
    MoE output is not), and scan requires a fixed carry type.  Canonical set:
    the input's own varying axes, plus "model" in training (AD-friendly
    gathers are Varying->Varying); inference uses invariant gathers so the
    residual stream stays exactly as replicated as it really is.
    """
    from repro.core.context import default_context
    from repro.core.ompccl import ensure_varying

    in_vma = getattr(typeof(x), "vma", frozenset())
    axes = set(in_vma)
    if not ctx.inference:
        if ctx.tp > 1:
            axes.add("model")       # train-mode TP gathers are Varying->Varying
        if ctx.fsdp > 1:
            axes.add("data")        # ZeRO-3 weight gathers (AD: reduce-scatter)
    world = tuple(a for a in ctx.world.lax_axes if a in axes)

    # dispatch stats recorded inside the scan body are tracers of the inner
    # (scan/remat) trace — they can't escape through the context's side
    # channel.  When a collection frame is open, re-thread them: collect
    # per-layer inside the body, return them as scan outputs, and re-record
    # the layer-summed totals into the outer frame after the scan.
    stats = default_context().dispatch_stats
    thread_stats = stats.active

    def layer(h, lp, cache):
        if thread_stats:
            with stats.collect() as ds:
                h2, new_cache = _layer_body(
                    h, lp, cfg, ctx, moe=moe, mla=mla, positions=positions,
                    prefix_len=prefix_len, cache=cache, chunked=chunked)
            aux = {k: ds[k] for k in sorted(ds)}
        else:
            h2, new_cache = _layer_body(
                h, lp, cfg, ctx, moe=moe, mla=mla, positions=positions,
                prefix_len=prefix_len, cache=cache, chunked=chunked)
            aux = {}
        return ensure_varying(h2, world), new_cache, aux

    if caches is None:
        def body(h, lp):
            h2, _, aux = layer(h, lp, None)
            return h2, aux

        if remat:
            body = jax.checkpoint(body)
        x, aux = lax.scan(body, ensure_varying(x, world), stack)
        new_caches = None
    else:
        # the stacked cache rides in the carry, so a layer's writes land in
        # place; the advanced position leaves as a per-layer output, since
        # every layer starts from the same one
        arrays = {k: getattr(caches, k) for k in caches.ARRAYS}
        stored = {k: _stored_layout(v, ctx.device) for k, v in arrays.items()}
        # the carry's type is fixed: each stack varies over the axes it came
        # in with and those its new rows vary over (a cache made inside the
        # step starts invariant)
        vma = {k: _vma(v) for k, v in arrays.items()}

        def body(carry, xs):
            h, arrs = carry
            lp, l = xs
            h2, new_cache, aux = layer(
                h, lp, dataclasses.replace(caches, layer=l, **arrs))
            # left free, the compiler would relayout the carried stack for
            # the row writes (a padded copy in and out of the loop)
            arrs = {k: ensure_varying(getattr(new_cache, k), vma[k])
                    for k in arrs}
            arrs = {k: with_layout_constraint(a, stored[k])
                    for k, a in arrs.items()}
            return (h2, arrs), (new_cache.pos, aux)

        L = jax.tree.leaves(stack)[0].shape[0]
        xs = (stack, jnp.arange(L, dtype=jnp.int32))
        xs0 = jax.tree.map(lambda a: a[0], xs)
        x = ensure_varying(x, world)
        while True:
            init = {k: ensure_varying(v, vma[k]) for k, v in arrays.items()}
            (_, out), _ = jax.eval_shape(body, (x, init), xs0)
            grown = {k: tuple(sorted(set(vma[k]) | set(_vma(out[k]))))
                     for k in out}
            if grown == vma:
                break
            vma = grown
        (x, arrays), (pos, aux) = lax.scan(body, (x, init), xs)
        new_caches = dataclasses.replace(caches, pos=pos[-1], **arrays)
    stats.record(**{k: jnp.sum(v) for k, v in aux.items()})
    return x, new_caches


def _vma(a) -> tuple:
    return tuple(sorted(getattr(typeof(a), "vma", ())))


def _stored_layout(a, device) -> Layout:
    """The layout ``device`` stores an array shaped like ``a`` in."""
    return Layout.from_pjrt_layout(
        device.client.get_default_layout(a.dtype, a.shape, device))


def _make_layer_cache(cfg: ModelConfig, ctx: ParallelCtx, B: int, S: int, L: int,
                      *, seq_sharded: bool, dtype) -> Dict[str, jax.Array]:
    """Local cache shapes for one layer stack of depth L (stacked)."""
    if cfg.attention == "mla":
        return {
            "c": jnp.zeros((L, B, S, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((L, B, S, cfg.qk_rope_head_dim), dtype),
        }
    from .layers import local_kv_heads

    KH_loc = local_kv_heads(cfg, ctx)
    S_loc = S // ctx.fsdp if seq_sharded else S
    return {
        "k": jnp.zeros((L, B, S_loc, KH_loc, cfg.head_dim), dtype),
        "v": jnp.zeros((L, B, S_loc, KH_loc, cfg.head_dim), dtype),
    }


def init_cache(cfg: ModelConfig, ctx: ParallelCtx, B_loc: int, S: int,
               *, seq_sharded: bool = False, dtype=jnp.bfloat16):
    """Decode cache pytree (local shapes) + position scalar.

    ``seq_sharded`` is a *static* layout property: it must be passed again
    (identically) to transformer_forward / the serve step builder.
    """
    kd = cfg.first_k_dense if cfg.moe else 0
    cache = _make_layer_cache(cfg, ctx, B_loc, S, cfg.num_layers - kd,
                              seq_sharded=seq_sharded, dtype=dtype)
    if kd:
        dpfx = _make_layer_cache(cfg, ctx, B_loc, S, kd,
                                 seq_sharded=seq_sharded, dtype=dtype)
        cache["dense_c"] = dpfx["c"]
        cache["dense_kr"] = dpfx["kr"]
    cache["pos"] = jnp.zeros((), jnp.int32)
    return cache


def _wrap_cache(cfg, raw, pos, seq_sharded):
    """The cache object over whole stacks (pos may be scalar or (B,))."""
    if cfg.attention == "mla":
        return MLACache(raw["c"], raw["kr"], pos)
    return KVCache(raw["k"], raw["v"], pos, seq_sharded=seq_sharded)


def _unwrap_cache(cfg, cache_obj):
    if cfg.attention == "mla":
        return {"c": cache_obj.c, "kr": cache_obj.kr}, cache_obj.pos
    return {"k": cache_obj.k, "v": cache_obj.v}, cache_obj.pos


def transformer_forward(
    params: Dict[str, jax.Array],
    tokens,                      # (B, T) int32 — token ids
    cfg: ModelConfig,
    ctx: ParallelCtx,
    *,
    prefix_embeds=None,          # (B, P, d) — VLM patch / audio frame stubs
    embeds=None,                 # (B, T, d) — direct embedding input (audio)
    cache: Optional[dict] = None,
    positions=None,
    seq_sharded: bool = False,
    chunked: bool = False,
):
    """Returns (hidden (B, T_total, d), new_cache or None)."""
    if embeds is not None:
        x = embeds
    else:
        x = embed_lookup(tokens, params["embed/table"], cfg, ctx)
        if cfg.family == "vlm":
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    prefix_len = 0
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        prefix_len = prefix_embeds.shape[1]
    if "embed_norm" in params:
        x = layernorm(x, params["embed_norm"], cfg.norm_eps)
    if cfg.family == "audio":
        x = x + _sinusoid(x.shape[1], cfg.d_model, x.dtype)[None]

    T = x.shape[1]
    if positions is None:
        positions = jnp.arange(T)

    pos = cache["pos"] if cache is not None else None
    new_pos = pos
    remat = ctx.remat and cache is None
    kd = cfg.first_k_dense if cfg.moe else 0

    if kd:
        dstack = _stacked(params, "dense_layers")
        dcaches = None
        if cache is not None:
            dcaches = _wrap_cache(cfg, {"c": cache["dense_c"],
                                        "kr": cache["dense_kr"]}, pos, False)
        x, new_d = _scan_stack(
            x, dstack, cfg, ctx, moe=False, mla=cfg.attention == "mla",
            positions=positions, prefix_len=prefix_len, caches=dcaches,
            remat=remat, chunked=chunked)
    stack = _stacked(params, "layers")
    caches = None
    if cache is not None:
        raw = {k: v for k, v in cache.items()
               if k in ("k", "v", "c", "kr")}
        caches = _wrap_cache(cfg, raw, pos, seq_sharded)
    x, new_caches = _scan_stack(
        x, stack, cfg, ctx, moe=cfg.moe, mla=cfg.attention == "mla",
        positions=positions, prefix_len=prefix_len, caches=caches,
        remat=remat, chunked=chunked)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps,
                plus_one=(cfg.family == "vlm"))

    new_cache = None
    if cache is not None:
        raw, new_pos = _unwrap_cache(cfg, new_caches)
        new_cache = dict(raw)
        new_cache["pos"] = new_pos
        if kd:
            draw, _ = _unwrap_cache(cfg, new_d)
            new_cache["dense_c"] = draw["c"]
            new_cache["dense_kr"] = draw["kr"]
    return x, new_cache


def _lm_head(params, cfg):
    if cfg.family == "vlm":          # tied embeddings
        return params["embed/table"].T
    return params["lm_head"]


def _logits(h, params, cfg):
    """The output head in float32: hidden (..., d) -> logits (..., V)."""
    with jax.named_scope("lm_head"):
        return jnp.dot(h.astype(jnp.float32),
                       _lm_head(params, cfg).astype(jnp.float32))


def transformer_loss(params, batch, cfg: ModelConfig, ctx: ParallelCtx):
    """Next-token CE (LM) or masked-frame CE (audio).  Scalar f32 loss."""
    if cfg.family == "audio":
        h, _ = transformer_forward(params, None, cfg, ctx,
                                   embeds=batch["embeds"])
        head = gather_fsdp(params["head"], ctx, dim=0)      # (d, V) replicated V
        loss = ce_loss(h, head, batch["targets"], cfg, ctx,
                       weights=batch.get("mask"))
        return loss
    prefix_embeds = batch.get("prefix_embeds")
    h, _ = transformer_forward(params, batch["tokens"], cfg, ctx,
                               prefix_embeds=prefix_embeds)
    if prefix_embeds is not None:
        h = h[:, prefix_embeds.shape[1]:]
    loss = ce_loss(h[:, :-1], _lm_head(params, cfg), batch["tokens"][:, 1:],
                   cfg, ctx)
    if cfg.mtp:  # DeepSeek multi-token prediction auxiliary head
        emb_next = embed_lookup(batch["tokens"][:, 1:], params["embed/table"],
                                cfg, ctx)
        hm = rmsnorm(h[:, :-1], params["mtp/norm_h"], cfg.norm_eps)
        em = rmsnorm(emb_next, params["mtp/norm_e"], cfg.norm_eps)
        z = jnp.concatenate([hm, em], axis=-1)
        z = jnp.dot(z, gather_fsdp(params["mtp/proj"], ctx, dim=0),
                    preferred_element_type=jnp.float32).astype(h.dtype)
        mt_stack = _stacked(params, "mtp/layer")
        z, _ = _scan_stack(z, mt_stack, cfg, ctx, moe=False,
                           mla=cfg.attention == "mla",
                           positions=jnp.arange(z.shape[1]), prefix_len=0,
                           remat=ctx.remat)
        mtp_loss = ce_loss(z[:, :-1], _lm_head(params, cfg),
                           batch["tokens"][:, 2:], cfg, ctx)
        loss = loss + 0.1 * mtp_loss
    return loss


def transformer_prefill(params, tokens, cfg, ctx, cache, *,
                        prefix_embeds=None, seq_sharded: bool = False):
    """Fill the cache from a prompt; returns (last-position logits, cache)."""
    h, cache = transformer_forward(params, tokens, cfg, ctx, cache=cache,
                                   prefix_embeds=prefix_embeds,
                                   seq_sharded=seq_sharded)
    return _logits(h[:, -1:], params, cfg), cache


def transformer_chunk_prefill(params, tokens, cfg, ctx, cache, rlen, *,
                              seq_sharded: bool = False):
    """One chunked-prefill step: append ``tokens`` (B, C) at ``cache['pos']``.

    The serving engine streams a prompt through the cache in fixed-size
    chunks (docs/SERVING.md): each call writes C new K/V rows at the running
    position and attends the chunk's queries over the whole valid prefix.
    ``rlen`` (traced scalar, 1 <= rlen <= C) is the number of REAL tokens in
    the chunk; the tail is padding whose cache rows are overwritten by the
    next chunk / decode write before any query can attend to them (causal
    masking keeps them invisible meanwhile).  Returns the logits at the last
    real position and the cache with ``pos`` advanced by ``rlen``.
    """
    if seq_sharded:
        raise ValueError("chunked prefill does not support seq_sharded caches")
    C = tokens.shape[1]
    p0 = cache["pos"]
    positions = p0 + jnp.arange(C)
    h, cache = transformer_forward(params, tokens, cfg, ctx, cache=cache,
                                   positions=positions, chunked=True)
    last = lax.dynamic_slice_in_dim(h, jnp.maximum(rlen - 1, 0), 1, axis=1)
    logits = _logits(last, params, cfg)
    # the layer scan advanced pos by the full (possibly padded) chunk width;
    # the true advance is the real token count
    cache["pos"] = p0 + rlen
    return logits, cache


def transformer_decode(params, tokens, cfg, ctx, cache, *,
                       seq_sharded: bool = False):
    """One decode step: tokens (B, 1) -> (local logits (B, 1, V/tp), cache).

    cache["pos"] may be a scalar (uniform batch) or (B,) per-slot positions
    (continuous batching).
    """
    pos = cache["pos"]
    positions = (pos[:, None] if jnp.ndim(pos) == 1
                 else jnp.full((1,), pos, jnp.int32))
    h, cache = transformer_forward(
        params, tokens, cfg, ctx, cache=cache,
        positions=positions, seq_sharded=seq_sharded)
    return _logits(h, params, cfg), cache
