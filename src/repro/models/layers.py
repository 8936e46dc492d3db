"""Manual-SPMD layer library.

Every function here runs *inside* ``shard_map`` on device-local shards and
issues all cross-device traffic explicitly through OMPCCL / RMA verbs — the
DiOMP discipline: communication is owned by the runtime's verbs, never
implicit.  Layout conventions (DESIGN.md §4):

* activations: (B_loc, T, d) — batch sharded over (pod, data); d full;
  replicated over "model";
* weights: TP dim sharded over "model" (column/row Megatron style), the
  other big dim sharded over "data" (ZeRO-3 / FSDP) and all-gathered at use
  (optionally via the Cannon-style ring to overlap transfer with compute);
* attention: head-parallel when heads divide MAX_TP, token-parallel
  otherwise; decode caches are head-sharded, context(seq)-sharded, or
  replicated per the same divisibility rules.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ompccl
from repro.core.compat import axis_size
from repro.core.groups import DiompGroup
from repro.core.rma import ompx_put
from repro.kernels.flash_attention.ops import flash_attention
from .config import ModelConfig, ParallelCtx
from .schema import MAX_TP, head_parallel, kv_sharded, vocab_sharded

__all__ = [
    "rmsnorm", "layernorm", "rope", "gather_fsdp", "tp_allreduce",
    "col_matmul", "row_matmul", "embed_lookup", "ce_loss",
    "attention_block", "mla_block", "mlp_block", "moe_block",
    "moe_capacity", "decode_attention",
]

F32 = jnp.float32


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-5, plus_one: bool = False):
    xf = x.astype(F32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    s = scale.astype(F32)
    if plus_one:
        s = 1.0 + s
    return (xf * inv * s).astype(x.dtype)


def layernorm(x, scale_bias, eps: float = 1e-5):
    """scale_bias: (2, d) — row 0 scale, row 1 bias."""
    xf = x.astype(F32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * scale_bias[0].astype(F32) + scale_bias[1].astype(F32)).astype(x.dtype)


def rope(x, positions, *, theta: float = 10_000.0, fraction: float = 1.0):
    """x: (B, T, H, D); positions: (T,) or (B, T) (per-slot decode offsets)."""
    D = x.shape[-1]
    rot = int(D * fraction) // 2 * 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    pos = positions.astype(F32)
    if pos.ndim == 1:
        pos = pos[None, :]                                      # (1, T)
    ang = pos[..., None] * freqs[None, None, :]                 # (B|1, T, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = xr[..., :half].astype(F32), xr[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# communication helpers (all traffic through OMPCCL / RMA)
# ---------------------------------------------------------------------------

def gather_fsdp(w, ctx: ParallelCtx, dim: int = 0):
    """ZeRO-3 weight all-gather over the data axis (no-op if fsdp == 1).

    AD transposes this to a reduce-scatter of the weight gradient over the
    same axis — the intra-pod half of the hierarchical gradient reduction.

    ``ctx.gather_codec == "int8"``: the wire moves int8 + one f32 scale per
    shard (2x fewer bytes than bf16).  Remote shards are dequantized; my own
    shard is spliced back at full precision through a straight-through
    estimator, so gradients flow to the unquantized weights and the grad
    reduce-scatter stays exact.
    """
    if ctx.fsdp <= 1 or not ctx.fsdp_params:
        return w                      # inference WS: weights arrive whole
    if ctx.gather_codec == "int8":
        return _q8_gather(w, ctx, dim)
    return ompccl.allgather(w, ctx.fsdp_group, axis=dim,
                            invariant=ctx.inference)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _q8_gather(w, ctx, dim):
    """int8-wire ZeRO-3 gather (ZeRO++ qwZ-style).

    Forward: quantize the local shard, all-gather int8 + per-shard scales,
    dequantize, splice my own shard back at full precision.  Backward: the
    exact reduce-scatter of the cotangent (identical to plain all_gather's
    transpose) — the grad wire stays uncompressed and exact.
    """
    from repro.distributed.compression import quantize_int8

    q, s = quantize_int8(w)
    qq = ompccl.allgather(q, ctx.fsdp_group, axis=dim,
                          invariant=ctx.inference)
    ss = ompccl.allgather(s.reshape(1), ctx.fsdp_group, axis=0,
                          invariant=ctx.inference)         # (fsdp,)
    n = ss.shape[0]
    shard = qq.shape[dim] // n
    scale_shape = [1] * qq.ndim
    scale_shape[dim] = n
    scales = jnp.repeat(ss.reshape(scale_shape), shard, axis=dim)
    full = (qq.astype(F32) * scales).astype(w.dtype)
    idx = lax.axis_index(ctx.fsdp_group.axes[0])
    return lax.dynamic_update_slice_in_dim(full, w, idx * shard, axis=dim)


def _q8_gather_fwd(w, ctx, dim):
    return _q8_gather(w, ctx, dim), None


def _q8_gather_bwd(ctx, dim, _res, g):
    return (ompccl.reducescatter(g, ctx.fsdp_group, axis=dim)
            .astype(g.dtype),)


_q8_gather.defvjp(_q8_gather_fwd, _q8_gather_bwd)


def ring_fsdp_matmul(x, w_local, ctx: ParallelCtx):
    """Cannon-style overlap of the ZeRO-3 gather: y = x @ W, W row-sharded.

    Instead of all-gathering W then one GEMM, rotate W shards around the
    data-axis ring; each step's ompx_put overlaps the concurrent partial
    GEMM (paper §4.4 generalized to the weight gather).

    The step schedule comes from the shared
    :class:`~repro.kernels.plan.OverlapPlanner`: ``ctx.ring_impl="fused"``
    (the default resolution of ``"auto"``) runs the bidirectional ring —
    W stripes circulate both ways, ``ceil((n-1)/2)`` exchange steps, both
    link directions busy; ``"host"`` keeps the unidirectional ``n-1``-step
    loop.  Both are differentiable (the puts are ppermutes), so this is
    the path the TP layers train through.
    """
    if ctx.fsdp <= 1 or not ctx.fsdp_params:
        return jnp.dot(x, w_local, preferred_element_type=F32).astype(x.dtype)
    from repro.core.vma import zeros_varying
    from repro.kernels.plan import RingPlan, resolve_ring_impl

    group = ctx.fsdp_group
    n = axis_size(group.axes[0])
    idx = lax.axis_index(group.axes[0])
    dshard = w_local.shape[0]
    direction = ("bidi" if resolve_ring_impl(ctx.ring_impl) == "fused"
                 else "cw")
    # only the step schedule matters here: the stripes live as XLA values,
    # not planned VMEM slots (this is the host-level, differentiable form)
    plan = RingPlan(n=n, direction=direction)
    acc = zeros_varying(x.shape[:-1] + (w_local.shape[1],), F32, x)

    def partial_gemm(acc, w_stripe, src):
        xs = lax.dynamic_slice_in_dim(x, src * dshard, dshard, axis=-1)
        return acc + jnp.dot(xs, w_stripe, preferred_element_type=F32)

    cw = ccw = w_local
    for st in plan.schedule():
        # forwards first: the next stripes fly while this step's GEMMs run
        cw_next = ompx_put(cw, group, shift=1) if st.send_cw else cw
        ccw_next = ompx_put(ccw, group, shift=-1) if st.send_ccw else ccw
        if st.compute_cw:
            acc = partial_gemm(acc, cw, (idx - st.index) % n)
        if st.compute_ccw:
            acc = partial_gemm(acc, ccw, (idx + st.index) % n)
        cw, ccw = cw_next, ccw_next
    return acc.astype(x.dtype)


def tp_allreduce(x, ctx: ParallelCtx):
    if ctx.tp <= 1:
        return x
    return ompccl.allreduce(x, ctx.tp_group)


def col_matmul(x, w_local, ctx: ParallelCtx, bias_local=None):
    """Megatron column-parallel: x (…, d) × W (d/fsdp, out/tp) -> (…, out/tp)."""
    if ctx.use_ring_matmul:
        y = ring_fsdp_matmul(x, w_local, ctx)
    else:
        w = gather_fsdp(w_local, ctx, dim=0)
        y = jnp.dot(x, w, preferred_element_type=F32).astype(x.dtype)
    if bias_local is not None:
        y = y + bias_local.astype(y.dtype)
    return y


def row_matmul(x, w_local, ctx: ParallelCtx):
    """Megatron row-parallel: x (…, in/tp) × W (in/tp, d/fsdp) -> allreduced (…, d)."""
    w = gather_fsdp(w_local, ctx, dim=1)
    y = jnp.dot(x, w, preferred_element_type=F32).astype(x.dtype)
    return tp_allreduce(y, ctx)


# ---------------------------------------------------------------------------
# embedding / loss (vocab-sharded over the TP group)
# ---------------------------------------------------------------------------

def embed_lookup(tokens, table_local, cfg: ModelConfig, ctx: ParallelCtx):
    """tokens: (B, T) int32; table_local: (V/tp, d) or (V, d)."""
    if not vocab_sharded(cfg) or ctx.tp <= 1:
        return table_local[tokens]
    vloc = table_local.shape[0]
    off = lax.axis_index(ctx.tp_group.axes[0]) * vloc
    local = tokens - off
    hit = (local >= 0) & (local < vloc)
    e = table_local[jnp.clip(local, 0, vloc - 1)]
    e = jnp.where(hit[..., None], e, jnp.zeros_like(e))
    return tp_allreduce(e, ctx)


def ce_loss(h, head_local, targets, cfg: ModelConfig, ctx: ParallelCtx,
            weights=None):
    """Cross-entropy with vocab-sharded logits.

    h: (B, T, d); head_local: (d, V/tp) (or (d, V) unsharded); targets (B, T).
    The softmax statistics are reduced across the TP group with explicit
    OMPCCL max/sum collectives (the paper's device-side collectives in the
    loss path).  Returns mean loss (f32).
    """
    logits = jnp.dot(h.astype(F32), head_local.astype(F32))   # (B, T, V/tp)
    sharded = vocab_sharded(cfg) and ctx.tp > 1
    m = lax.stop_gradient(logits).max(axis=-1)
    if sharded:
        m = ompccl.allreduce(m, ctx.tp_group, op="max")
    m = lax.stop_gradient(m)  # the max shift carries no gradient (and pmax
    # has no AD rule); the CE gradient is exact regardless of the shift
    z = jnp.exp(logits - m[..., None]).sum(axis=-1)
    if sharded:
        z = ompccl.allreduce(z, ctx.tp_group)
    if sharded:
        vloc = head_local.shape[1]
        off = lax.axis_index(ctx.tp_group.axes[0]) * vloc
        local = targets - off
        hit = (local >= 0) & (local < vloc)
        tgt = jnp.take_along_axis(
            logits, jnp.clip(local, 0, vloc - 1)[..., None], axis=-1
        )[..., 0]
        tgt = jnp.where(hit, tgt, 0.0)
        tgt = ompccl.allreduce(tgt, ctx.tp_group)
    else:
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jnp.log(z) + m - tgt
    if weights is not None:
        return (nll * weights).sum() / jnp.maximum(weights.sum(), 1.0)
    return nll.mean()


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def layer_view(c, layer):
    """One layer of a cache array: ``c`` itself, or with ``layer`` given,
    slice ``layer`` of the stack (L, B, S, ...) read where it lies."""
    if layer is None:
        return c
    return lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)


def write_rows(c, new, pos, layer=None):
    """Write ``new`` (B, T, ...) into the cache array ``c`` at sequence
    position ``pos``: a scalar, or a (B,) vector of per-slot positions
    (continuous batching).  ``c`` is one layer (B, S, ...) or, with
    ``layer`` given, the stack (L, B, S, ...); only the new rows are
    written, the rest of the stack is not rebuilt."""
    new = new.astype(c.dtype)
    lead = () if layer is None else (layer,)
    tail = (0,) * (new.ndim - 2)
    if layer is not None:
        new = new[None]
    if jnp.ndim(pos) == 0:
        return lax.dynamic_update_slice(c, new, lead + (0, pos) + tail)
    # one write per slot: a vmapped write would lower to a scatter, whose
    # expansion forces the whole cache into a padded row-major layout
    for b in range(pos.shape[0]):
        c = lax.dynamic_update_slice(
            c, lax.slice_in_dim(new, b, b + 1, axis=len(lead)),
            lead + (b, pos[b]) + tail)
    return c


@dataclasses.dataclass
class KVCache:
    """Decode-time cache; a pytree (flax-free).  ``pos`` is a traced scalar
    or a (B,) vector of per-slot positions.

    ``k`` / ``v`` hold one layer (B, S_cache_local, KH_local, D), or, with
    ``layer`` (a traced index) set, the whole stack (L, B, S, KH, D) that
    the layer loop carries: the layer reads its slice where it lies
    (:meth:`view`) and writes only its new rows (:func:`write_rows`).
    """

    ARRAYS = ("k", "v")

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    layer: Optional[jax.Array] = None
    seq_sharded: bool = False   # context-parallel cache (S split over a group)

    def view(self):
        """This layer's (k, v), each (B, S, KH, D)."""
        return layer_view(self.k, self.layer), layer_view(self.v, self.layer)

    def write(self, k_new, v_new, pos, new_pos):
        """The cache with ``k_new`` / ``v_new`` (B, T, KH, D) written at
        ``pos`` and its position set to ``new_pos``."""
        return dataclasses.replace(
            self, k=write_rows(self.k, k_new, pos, self.layer),
            v=write_rows(self.v, v_new, pos, self.layer), pos=new_pos)

    def tree_flatten(self):
        return (self.k, self.v, self.pos, self.layer), (self.seq_sharded,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, seq_sharded=aux[0])


jax.tree_util.register_pytree_node(
    KVCache, KVCache.tree_flatten, KVCache.tree_unflatten
)


def decode_attention(q, k, v, valid_len, *, scale,
                     group: Optional[DiompGroup] = None):
    """Attention of one query per slot over the cache as it is stored.

    q: (B, 1, H, D); k: (B, S, KH, D); v: (B, S, KH, Dv); ``valid_len`` a
    scalar or (B,) count of the rows each slot may see.  Two einsums over
    the whole layer, with scores, softmax and accumulation in float32 and
    no reshape of K or V, so the compiler reads them in the cache's own
    layout (the blockwise reference would relayout every layer).

    With ``group`` given, the cache is context(S)-sharded: k / v are this
    member's S-chunk (chunks in member order), and the partial (max, sum,
    acc) are combined with OMPCCL max/sum collectives — distributed
    flash-decode.
    """
    B, _, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KH
    k_off = 0 if group is None else lax.axis_index(group.axes[0]) * S
    s = jnp.einsum("bhgd,bshd->bhgs", q.reshape(B, KH, G, D), k,
                   preferred_element_type=F32) * scale
    vis = k_off + jnp.arange(S) < jnp.reshape(valid_len, (-1, 1, 1, 1))
    s = jnp.where(vis, s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    if group is not None:
        m = ompccl.allreduce(m, group, op="max")
    p = jnp.where(vis, jnp.exp(s - jnp.where(jnp.isneginf(m), 0.0, m)), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, v, preferred_element_type=F32)
    if group is not None:
        l = ompccl.allreduce(l, group)
        acc = ompccl.allreduce(acc, group)
    return (acc / jnp.maximum(l, 1e-30)).reshape(B, 1, H, Dv).astype(q.dtype)


def _update_cache(cache: KVCache, k_new, v_new, group: Optional[DiompGroup]):
    """Write one decode step's K/V at cache.pos (context-sharded aware).

    ``cache.pos`` may be a scalar (uniform batch) or a (B,) vector
    (continuous batching: per-slot positions).
    """
    if jnp.ndim(cache.pos) == 1 or not cache.seq_sharded:
        return cache.write(k_new, v_new, cache.pos, cache.pos + 1)
    assert group is not None
    # the row lands on the member whose S-chunk holds pos; the others
    # write their own row back
    k, v = cache.view()
    s_loc = k.shape[1]
    lo = lax.axis_index(group.axes[0]) * s_loc
    local = jnp.clip(cache.pos - lo, 0, s_loc - 1)
    in_range = (cache.pos >= lo) & (cache.pos < lo + s_loc)
    k_old = lax.dynamic_slice_in_dim(k, local, 1, axis=1)
    v_old = lax.dynamic_slice_in_dim(v, local, 1, axis=1)
    return cache.write(jnp.where(in_range, k_new.astype(k.dtype), k_old),
                       jnp.where(in_range, v_new.astype(v.dtype), v_old),
                       local, cache.pos + 1)


def local_kv_heads(cfg: ModelConfig, ctx: ParallelCtx) -> int:
    """KV heads each device keeps (cache + attention operand width)."""
    if kv_sharded(cfg):
        return cfg.kv_heads // ctx.tp
    if head_parallel(cfg) and ctx.tp > 1:
        H_loc = cfg.num_heads // ctx.tp
        group = cfg.num_heads // cfg.kv_heads
        assert H_loc % group == 0 or group % H_loc == 0, (H_loc, group)
        return max(1, H_loc // group)
    return cfg.kv_heads


def _slice_kv(kv, cfg: ModelConfig, ctx: ParallelCtx):
    """With heads sharded but KV replicated, keep only the KV heads my local
    q-head block maps to (q head h -> kv head h // (H/KV))."""
    KV_keep = local_kv_heads(cfg, ctx)
    if KV_keep == kv.shape[2]:
        return kv
    H_loc = cfg.num_heads // ctx.tp
    group = cfg.num_heads // cfg.kv_heads
    first_q = lax.axis_index(ctx.tp_group.axes[0]) * H_loc
    return lax.dynamic_slice_in_dim(kv, first_q // group, KV_keep, axis=2)


def attention_block(
    x, lp: Dict[str, jax.Array], cfg: ModelConfig, ctx: ParallelCtx,
    *,
    positions=None,
    prefix_len: int = 0,
    cache: Optional[KVCache] = None,
    causal: Optional[bool] = None,
    chunked: bool = False,
):
    """GQA attention with residual-input x (B, T, d); returns (out, cache').

    Four execution strategies (DESIGN.md §5 + chunked serving prefill,
    docs/SERVING.md):
    * head-parallel  — q heads divide MAX_TP: heads sharded over "model";
    * token-parallel — otherwise (e.g. paligemma H=8): weights replicated
      over "model", the T axis is sliced instead;
    * decode         — T == 1 with a cache (decode_attention, over the
      cache as stored): head-sharded, replicated, or context(S)-sharded
      (partials merged over the group);
    * chunked prefill — ``chunked=True`` with a cache and T > 1: the chunk's
      K/V are appended at the running ``cache.pos`` and the queries attend
      over the whole valid prefix (cached + chunk), so a prompt streams
      through the cache in ``ceil(len/chunk)`` device calls.
    """
    B, T, d = x.shape
    hp = head_parallel(cfg)
    kvs = kv_sharded(cfg)
    hd = cfg.head_dim
    H_loc = cfg.num_heads // ctx.tp if hp else cfg.num_heads
    KV_loc = cfg.kv_heads // ctx.tp if kvs else cfg.kv_heads
    causal = cfg.causal if causal is None else causal
    if positions is None:
        positions = jnp.arange(T)

    bq = lp.get("bq")
    bk = lp.get("bk")
    bv = lp.get("bv")

    decode = cache is not None and T == 1
    chunkfill = chunked and cache is not None and not decode
    token_parallel = ((not hp) and (not decode) and (not chunkfill)
                      and T % ctx.tp == 0 and ctx.tp > 1)

    # sequence-parallel context strategy (ctx.seq_parallel, resolved by the
    # step builders; "ring" rotates K/V stripes as one-sided puts folded
    # with the online-softmax merge instead of materializing full K/V)
    ring_attn = False
    if ctx.tp > 1 and not hp and not kvs:
        from repro.kernels.plan import resolve_seq_parallel

        ring_attn = resolve_seq_parallel(ctx.seq_parallel) == "ring"

    if token_parallel:
        t_loc = T // ctx.tp
        t0 = lax.axis_index(ctx.tp_group.axes[0]) * t_loc
        x_me = lax.dynamic_slice_in_dim(x, t0, t_loc, axis=1)
        pos_me = lax.dynamic_slice_in_dim(positions, t0, t_loc, axis=0)
    else:
        x_me, pos_me = x, positions

    q = col_matmul(x_me, lp["wq"], ctx, bq).reshape(*x_me.shape[:2], H_loc, hd)
    k = col_matmul(x_me, lp["wk"], ctx, bk).reshape(*x_me.shape[:2], KV_loc, hd)
    v = col_matmul(x_me, lp["wv"], ctx, bv).reshape(*x_me.shape[:2], KV_loc, hd)
    if hp and not kvs and ctx.tp > 1:
        # heads sharded, KV weights replicated: keep only my groups' KV heads
        k = _slice_kv(k, cfg, ctx)
        v = _slice_kv(v, cfg, ctx)
    if cfg.rope_fraction > 0:
        q = rope(q, pos_me, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
        k = rope(k, pos_me, theta=cfg.rope_theta, fraction=cfg.rope_fraction)

    new_cache = cache
    if decode:
        with jax.named_scope("kv_write"):
            new_cache = _update_cache(
                cache, k, v,
                ctx.fsdp_group if cache.seq_sharded else None,
            )
        # pos may be scalar or (B,)
        attn = decode_attention(
            q, *new_cache.view(), new_cache.pos, scale=hd ** -0.5,
            group=ctx.fsdp_group if cache.seq_sharded else None)
    elif chunkfill:
        # chunked prefill: append this chunk's K/V at the running cache
        # position and attend over the whole valid prefix.  Causal masking
        # with q_offset = pos keeps any padded tail of the chunk invisible
        # (padded keys sit strictly after every real query position), and
        # padded cache rows are overwritten by the next chunk/decode write
        # before any query can reach them.
        assert not cache.seq_sharded, \
            "chunked prefill does not support a context-sharded cache"
        p0 = cache.pos
        with jax.named_scope("kv_write"):
            new_cache = cache.write(k, v, p0, p0 + T)
        k_all, v_all = new_cache.view()
        s_all = k_all.shape[1]
        if ring_attn and s_all % ctx.tp == 0:
            # sequence-parallel chunked prefill: the cache is replicated
            # over "model", so each rank takes its S-stripe and the chunk's
            # (shared) queries ride the ring — every rank folds n stripes
            # of S/n keys instead of scanning the whole prefix.  q_offset /
            # valid_len are traced; the ring emulation masks dynamically.
            s_loc = s_all // ctx.tp
            me = lax.axis_index(ctx.tp_group.axes[0])
            k_str = lax.dynamic_slice_in_dim(k_all, me * s_loc, s_loc, axis=1)
            v_str = lax.dynamic_slice_in_dim(v_all, me * s_loc, s_loc, axis=1)
            attn = flash_attention(
                q, k_str, v_str, causal=True, impl="ring",
                group=ctx.tp_group, q_offset=p0, valid_len=p0 + T,
                q_sharded=False)
        else:
            attn = flash_attention(q, k_all, v_all, causal=True,
                                   q_offset=p0, valid_len=p0 + T)
    elif token_parallel and ring_attn and cache is None and prefix_len == 0:
        # fused ring attention (token-parallel training): the K/V shards
        # never materialize per-rank — stripes rotate through the
        # bidirectional one-sided ring while the online-softmax state
        # accumulates (O(T/n) context memory instead of O(T))
        attn = flash_attention(q, k, v, causal=causal, impl="ring",
                               group=ctx.tp_group, q_sharded=True)
    elif token_parallel:
        # KV must cover the full sequence: gather over the TP group
        k_full = ompccl.allgather(k, ctx.tp_group, axis=1,
                                  invariant=ctx.inference)
        v_full = ompccl.allgather(v, ctx.tp_group, axis=1,
                                  invariant=ctx.inference)
        attn = flash_attention(
            q, k_full, v_full, causal=causal, q_offset=t0,
            prefix_len=prefix_len,
        )
        if cache is not None:  # prefill: persist the gathered KV
            new_cache = cache.write(k_full, v_full, 0, jnp.asarray(T, jnp.int32))
    else:
        attn = flash_attention(q, k, v, causal=causal, prefix_len=prefix_len)
        if cache is not None:  # prefill into a decode cache
            new_cache = cache.write(k, v, 0, jnp.asarray(T, jnp.int32))

    attn2 = attn.reshape(*attn.shape[:2], H_loc * hd)
    if token_parallel:
        out_me = jnp.dot(attn2, gather_fsdp(lp["wo"], ctx, dim=1),
                         preferred_element_type=F32).astype(x.dtype)
        out = ompccl.allgather(out_me, ctx.tp_group, axis=1,
                               invariant=ctx.inference)   # tokens back
    elif hp:
        out = row_matmul(attn2, lp["wo"], ctx)
    else:  # decode on replicated heads: wo replicated over model
        out = jnp.dot(attn2, gather_fsdp(lp["wo"], ctx, dim=1),
                      preferred_element_type=F32).astype(x.dtype)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MLACache:
    """Latent cache: c_kv (B, S, kr) + rope'd shared key (B, S, dr); with
    ``layer`` set, the stacks (L, B, S, ...) the layer loop carries (as
    :class:`KVCache`)."""

    ARRAYS = ("c", "kr")

    c: jax.Array
    kr: jax.Array
    pos: jax.Array
    layer: Optional[jax.Array] = None

    def view(self):
        """This layer's (c, kr), (B, S, kr) and (B, S, dr)."""
        return layer_view(self.c, self.layer), layer_view(self.kr, self.layer)

    def write(self, c_new, kr_new, pos, new_pos):
        return dataclasses.replace(
            self, c=write_rows(self.c, c_new, pos, self.layer),
            kr=write_rows(self.kr, kr_new, pos, self.layer), pos=new_pos)

    def tree_flatten(self):
        return (self.c, self.kr, self.pos, self.layer), ()

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


jax.tree_util.register_pytree_node(
    MLACache, MLACache.tree_flatten, MLACache.tree_unflatten
)


def mla_block(
    x, lp, cfg: ModelConfig, ctx: ParallelCtx,
    *, positions=None, cache: Optional[MLACache] = None,
    chunked: bool = False,
):
    """DeepSeek-V3 multi-head latent attention.  Returns (out, cache').

    Train/prefill: decompress per-head K/V from the latent and run flash
    attention.  Decode: *absorbed* form — attention runs in the latent space
    against the (replicated, tiny) latent cache; only the final per-head
    up-projection touches head dims.  TP: heads sharded (128 % 16 == 0);
    the latent path is replicated (that is MLA's point: the cache is small).
    ``chunked=True`` (serving prefill, docs/SERVING.md): the chunk's latents
    are appended at the running ``cache.pos`` and the chunk's queries attend
    over K/V decompressed from the whole valid latent prefix.
    """
    B, T, d = x.shape
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kr_rank = cfg.kv_lora_rank
    H_loc = cfg.num_heads // ctx.tp if head_parallel(cfg) else cfg.num_heads
    if positions is None:
        positions = jnp.arange(T)
    scale = (dn + dr) ** -0.5

    cq = rmsnorm(col_matmul(x, lp["wq_a"], ctx), lp["q_norm"], cfg.norm_eps)
    q = col_matmul(cq, lp["wq_b"], ctx).reshape(B, T, H_loc, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, theta=cfg.rope_theta)

    ckv = col_matmul(x, lp["wkv_a"], ctx)                     # (B, T, kr+dr)
    c = rmsnorm(ckv[..., :kr_rank], lp["kv_norm"], cfg.norm_eps)
    k_rope = rope(ckv[..., None, kr_rank:], positions, theta=cfg.rope_theta)

    wkv_b = gather_fsdp(lp["wkv_b"], ctx, dim=0)              # (kr, H_loc*(dn+dv))
    wkv_b = wkv_b.reshape(kr_rank, H_loc, dn + dv)

    new_cache = cache
    if cache is not None and T == 1:
        # absorbed decode (pos scalar or per-slot (B,))
        new_cache = cache.write(c, k_rope[:, :, 0], cache.pos, cache.pos + 1)
        c_all, kr_all = new_cache.view()
        q_lat = jnp.einsum("bthn,khn->bthk", q_nope.astype(F32),
                           wkv_b[..., :dn].astype(F32))        # (B,1,H,kr)
        s = jnp.einsum("bthk,bsk->bhs", q_lat,
                       c_all.astype(F32)) + jnp.einsum(
            "bthr,bsr->bhs", q_rope.astype(F32), kr_all.astype(F32))
        s = s * scale
        k_pos = jnp.arange(c_all.shape[1])
        vis = k_pos[None, None, :] < jnp.reshape(new_cache.pos, (-1, 1, 1))
        s = jnp.where(vis, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1, where=vis)
        ctx_lat = jnp.einsum("bhs,bsk->bhk", p, c_all.astype(F32))
        attn = jnp.einsum("bhk,khn->bhn", ctx_lat,
                          wkv_b[..., dn:].astype(F32))         # (B,H,dv)
        attn = attn[:, None].astype(x.dtype)                   # (B,1,H,dv)
    elif chunked and cache is not None:
        # chunked prefill: append latents at cache.pos, attend over the
        # decompressed full prefix (causal + q_offset mask the padded tail
        # and the unwritten suffix, exactly as in attention_block)
        p0 = cache.pos
        new_cache = cache.write(c, k_rope[:, :, 0], p0, p0 + T)
        c_all, kr_all = new_cache.view()
        S_all = c_all.shape[1]
        kv_all = jnp.einsum("bsk,khn->bshn", c_all.astype(F32),
                            wkv_b.astype(F32)).astype(x.dtype)
        k_nope_all, v_all = kv_all[..., :dn], kv_all[..., dn:]
        k_all = jnp.concatenate(
            [k_nope_all,
             jnp.broadcast_to(kr_all[:, :, None].astype(x.dtype),
                              (B, S_all, H_loc, dr))], axis=-1)
        qkr = jnp.concatenate([q_nope, q_rope], axis=-1)
        attn = flash_attention(qkr, k_all, v_all, causal=True, scale=scale,
                               q_offset=p0, valid_len=p0 + T)
    else:
        kv = jnp.einsum("btk,khn->bthn", c.astype(F32),
                        wkv_b.astype(F32)).astype(x.dtype)     # decompress
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, T, H_loc, dr))], axis=-1)
        qkr = jnp.concatenate([q_nope, q_rope], axis=-1)
        attn = flash_attention(qkr, k, v, causal=True, scale=scale)
        if cache is not None:  # prefill the latent cache
            new_cache = cache.write(c, k_rope[:, :, 0], 0,
                                    jnp.asarray(T, jnp.int32))

    out = row_matmul(attn.reshape(B, -1, H_loc * dv), lp["wo"], ctx)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_block(x, lp, ctx: ParallelCtx, *, act: str = "silu",
              names=("w_gate", "w_up", "w_down")):
    """SwiGLU/GeGLU column->row parallel MLP."""
    g, u, dwn = names
    h = col_matmul(x, lp[g], ctx)
    h = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)
    h = h * col_matmul(x, lp[u], ctx)
    return row_matmul(h, lp[dwn], ctx)


def gelu_mlp_block(x, lp, ctx: ParallelCtx):
    """Plain 2-matmul GELU MLP (hubert encoder): reuses w_up/w_down."""
    h = jax.nn.gelu(col_matmul(x, lp["w_up"], ctx))
    return row_matmul(h, lp["w_down"], ctx)


# ---------------------------------------------------------------------------
# MoE (expert-parallel over the "model" axis, all_to_all dispatch)
# ---------------------------------------------------------------------------

def moe_capacity(t_loc: int, k: int, E: int, capacity_factor: float) -> int:
    """Per-expert slot capacity of the GShard dispatch: the TRUE ceiling
    ``ceil((t_loc*k/E) * capacity_factor)``.

    The former ``int(q + 1)`` overshot by one whole slot per expert
    whenever the product was exactly integral (e.g. ``t_loc=64, k=2, E=8,
    factor=1.0`` gave 17 instead of 16 — a 6% buffer and wire overhead for
    nothing).  The quotient is rounded at 1e-9 before the ceiling so
    binary float dust (``0.1 * 3``-style) cannot bump an exact product to
    the next slot.
    """
    q = (t_loc * k / E) * capacity_factor
    return max(int(math.ceil(round(q, 9))), 1)


def moe_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx):
    """Top-k expert-parallel FFN (GShard-style capacity dispatch).

    EP layouts:
    * default    — experts sharded over "model" (E/tp per chip); expert
      weights keep a ZeRO-3 d-shard that is all-gathered at use;
    * expert2d   — experts sharded over ("model","data") (beyond-paper,
      DESIGN.md §Perf): each chip owns whole experts with full d/ff, the
      dispatch all-to-all runs over the combined EP group, and the
      per-microbatch weight gathers disappear.

    Regimes per call:
    * "a2a"        — tokens sliced over "model", one ompx_alltoall out and
      back (train / prefill);
    * "replicated" — few tokens (decode): dispatch replicated across the EP
      group (expert2d first all-gathers the data-sharded tokens), experts
      sliced, partial-combine psum;
    * "local"      — tp == 1 or E unshardable.

    Capacity = ceil((T_loc*k/E)*capacity_factor) (:func:`moe_capacity`);
    overflow drops (combine weights renormalized), with the drop count
    recorded into the context's ``dispatch_stats`` frame when one is open.
    ``ctx.dispatch_impl`` = ``"fused"``/``"host"`` swaps the a2a regime's
    collective for the dropless one-sided ring of
    :mod:`repro.kernels.moe_dispatch` (docs/PERF.md).
    """
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    tp = ctx.tp
    ep2d = ctx.expert2d and E % max(ctx.ep_size, 1) == 0 and ctx.ep_size > 1
    ep = ctx.ep_size if ep2d else tp
    E_loc = E // ep if (E % ep == 0 and ep > 1) else E
    if E % ep == 0 and ep > 1 and (B * T) % tp == 0 and B * T >= tp:
        regime = "a2a"
    elif E % ep == 0 and ep > 1:
        regime = "replicated"
    else:
        regime = "local"
        E_loc = E

    flat = x.reshape(B * T, d)
    toks_local = flat                     # shared-expert input (my tokens)
    if regime == "a2a":
        t_loc = (B * T) // tp             # tokens sliced over "model" only
        t0 = lax.axis_index(ctx.tp_group.axes[0]) * t_loc
        toks = lax.dynamic_slice_in_dim(flat, t0, t_loc, axis=0)
    elif regime == "replicated" and ep2d and ctx.fsdp > 1:
        # decode: tokens are data-sharded; gather so dispatch is identical
        # across the combined EP group (tiny at decode: B*T tokens)
        toks = ompccl.allgather(flat, ctx.fsdp_group, axis=0,
                                invariant=ctx.inference)
        t_loc = B * T * ctx.fsdp
    else:
        toks, t_loc = flat, B * T

    router = lp["router"].astype(F32)                         # (d, E) replicated
    logits = jnp.dot(toks.astype(F32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, k)                        # (t_loc, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # dropless one-sided dispatch (kernels/moe_dispatch): opt-in via the
    # ParallelCtx knob, available whenever the a2a regime holds on a
    # single-axis EP group (the put ring); expert2d's two-axis group and
    # the replicated/local regimes fall through to the host paths below
    impl = "a2a"
    if regime == "a2a" and len(ctx.ep_group.axes) == 1:
        from repro.kernels.plan import resolve_dispatch_impl

        impl = resolve_dispatch_impl(getattr(ctx, "dispatch_impl", "auto"))
    if impl in ("fused", "host"):
        from repro.kernels.moe_dispatch.ops import moe_dispatch

        wg = gather_fsdp(lp["w_gate_e"], ctx, dim=1)          # (E_loc, d, ffm)
        wu = gather_fsdp(lp["w_up_e"], ctx, dim=1)
        wd = gather_fsdp(lp["w_down_e"], ctx, dim=2)          # (E_loc, ffm, d)
        combined = moe_dispatch(toks, top_e, top_w, wg, wu, wd,
                                ctx.ep_group, impl=impl)
        if "w_gate_s" in lp:  # shared experts (DeepSeek): full rows, then
            shared = mlp_block(  # my slice (see the host path below)
                toks_local, lp, ctx, names=("w_gate_s", "w_up_s", "w_down_s"))
            combined = combined + lax.dynamic_slice_in_dim(
                shared, t0, t_loc, axis=0)
        out = ompccl.allgather(combined, ctx.tp_group, axis=0,
                               invariant=ctx.inference)
        return out.reshape(B, T, d)

    cap = max(moe_capacity(t_loc, k, E, cfg.capacity_factor), 4)

    # slot assignment: position of each (token, choice) within its expert
    e_flat = top_e.reshape(-1)                                # (t_loc*k,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)       # (t_loc*k, E)
    slot = (jnp.cumsum(onehot, axis=0) - 1) * onehot          # running index
    slot = slot.sum(-1)                                       # (t_loc*k,)
    keep = slot < cap
    addr = e_flat * cap + jnp.clip(slot, 0, cap - 1)

    # capacity overflow is a silent quality tax; surface it as a traced
    # aux stat when a DispatchStats frame is open (ctx.dispatch_stats —
    # the dropless moe_dispatch path above records identically zero)
    from repro.core.context import default_context

    dropped = jnp.sum(~keep).astype(F32)
    default_context().dispatch_stats.record(
        moe_dropped=dropped,
        moe_routed=dropped * 0 + keep.size)  # varying like dropped

    from repro.core.vma import zeros_varying

    buf = zeros_varying((E * cap, d), x.dtype, x)
    src = jnp.repeat(toks, k, axis=0)                         # (t_loc*k, d)
    buf = buf.at[jnp.where(keep, addr, E * cap - 1)].add(
        jnp.where(keep[:, None], src, 0.0).astype(x.dtype), mode="drop")

    if regime == "a2a":
        sendbuf = buf.reshape(ep, E_loc * cap, d)
        recv = ompccl.alltoall(sendbuf, ctx.ep_group,
                               split_axis=0, concat_axis=0)    # (ep, E_loc*cap, d)
        expert_in = recv.reshape(ep, E_loc, cap, d).transpose(1, 0, 2, 3)
        expert_in = expert_in.reshape(E_loc, ep * cap, d)
    elif regime == "replicated":
        # dispatch is replicated across the EP group; slice my expert block
        off = ompccl.group_rank(ctx.ep_group) * E_loc * cap
        expert_in = lax.dynamic_slice_in_dim(
            buf, off, E_loc * cap, axis=0).reshape(E_loc, cap, d)
    else:
        expert_in = buf.reshape(E_loc, cap, d)

    if ep2d:
        # expert2d: weights already hold full d/ff — no ZeRO-3 gather
        wg, wu, wd = lp["w_gate_e"], lp["w_up_e"], lp["w_down_e"]
    else:
        wg = gather_fsdp(lp["w_gate_e"], ctx, dim=1)          # (E_loc, d, ffm)
        wu = gather_fsdp(lp["w_up_e"], ctx, dim=1)
        wd = gather_fsdp(lp["w_down_e"], ctx, dim=2)          # (E_loc, ffm, d)
    h = jnp.einsum("ecd,edf->ecf", expert_in, wg)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", expert_in, wu)
    out_e = jnp.einsum("ecf,efd->ecd", h, wd).astype(x.dtype)

    gates = (keep[:, None] * top_w.reshape(-1)[:, None]).astype(x.dtype)
    if regime == "a2a":
        back = out_e.reshape(E_loc, ep, cap, d).transpose(1, 0, 2, 3)
        back = back.reshape(ep, E_loc * cap, d)
        ret = ompccl.alltoall(back, ctx.ep_group, split_axis=0, concat_axis=0)
        ret = ret.reshape(E * cap, d)
        picked = ret[addr] * gates
        combined = picked.reshape(t_loc, k, d).sum(axis=1)
    elif regime == "replicated":
        # partial combine: only my experts contribute; psum over the group
        off = ompccl.group_rank(ctx.ep_group) * E_loc * cap
        local = addr - off
        mine = (local >= 0) & (local < E_loc * cap)
        ret_me = out_e.reshape(E_loc * cap, d)
        picked = jnp.where(
            mine[:, None],
            ret_me[jnp.clip(local, 0, E_loc * cap - 1)], 0.0).astype(x.dtype)
        combined = (picked * gates).reshape(t_loc, k, d).sum(axis=1)
        combined = ompccl.allreduce(combined, ctx.ep_group)
        if ep2d and ctx.fsdp > 1:   # back to my data-shard's rows
            r0 = lax.axis_index(ctx.fsdp_group.axes[0]) * (B * T)
            combined = lax.dynamic_slice_in_dim(combined, r0, B * T, axis=0)
    else:
        ret = out_e.reshape(E * cap, d)
        picked = ret[addr] * gates
        combined = picked.reshape(t_loc, k, d).sum(axis=1)

    if "w_gate_s" in lp:  # shared experts (DeepSeek)
        # the TP col->row shared MLP needs the SAME rows on every "model"
        # rank (its row-parallel psum sums feature partials per row), so it
        # runs on the full replicated token set; the a2a regime then takes
        # this rank's slice.  Feeding the a2a path's per-rank token slice
        # in directly would psum partials of DIFFERENT tokens together.
        shared = mlp_block(toks_local, lp, ctx,
                           names=("w_gate_s", "w_up_s", "w_down_s"))
        if regime == "a2a":
            shared = lax.dynamic_slice_in_dim(shared, t0, t_loc, axis=0)
        combined = combined + shared

    if regime == "a2a":
        out = ompccl.allgather(combined, ctx.tp_group, axis=0,
                               invariant=ctx.inference)  # tokens back
    else:
        out = combined
    return out.reshape(B, T, d)
