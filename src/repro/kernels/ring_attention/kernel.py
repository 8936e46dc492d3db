"""Online-softmax partial states and the merge monoid of ring attention.

A flash-attention pass over one K/V *stripe* produces a partial-softmax
state ``(m, l, acc)`` — running row max, normalizer, and unnormalized
value accumulator.  Ring attention never sees the stripes in one scan:
each rank folds the states of the stripes the bidirectional ring delivers,
in schedule-arrival order, with :func:`merge_states`.

The algebra the property tests pin down (``tests/test_attention_props.py``):

* **merge is associative** and (up to float tolerance) permutation-
  invariant, so any delivery order yields the same attention;
* **the masked-empty state** ``(m = -inf, l = 0, acc = 0)`` is the EXACT
  (bitwise) identity of the merge — the empty side is detected by its
  ``-inf`` max and the other side passes through verbatim.  That identity
  is what makes the causal step-skip sound: a stripe entirely in a rank's
  future is fully masked, its state is the identity, and skipping its
  FLOPs (the TPU kernel's ``pl.when``) leaves the merge chain
  bit-identical.

Every execution of the ring (TPU kernel, CPU ``ompx_put`` emulation,
single-device :func:`~.ref.ring_attention_ref` oracle) folds stripe states
with these exact ops in the same schedule order, which is why the
equivalence tests can assert ``==`` rather than ``allclose``.

Shapes (f32 throughout; GQA grouped like the flash oracle):
``m, l: (B, Tq, KH, G)``; ``acc: (B, Tq, KH, G, Dv)``.

Why the ``exact`` path computes on the host
-------------------------------------------
The cross-program bit contract (emulation == oracle, forward and
gradients) cannot be met with jnp math on XLA CPU: the backend emits
*different code for the same op per fusion instance* — ``exp`` compiles
to the vectorized polynomial or a libm call depending on what it fuses
with, ``a*b + c`` is FMA-contracted in one program and not another, and
reductions vectorize with different accumulation orders.
``lax.optimization_barrier`` does not help: a barrier on a value that is
not a program output does not stop a consumer fusion from recompiling
the producer.  So the exact path routes each stripe/merge/finalize
through :func:`jax.pure_callback` into plain numpy.  Host numpy is ONE
implementation — the same routine runs for the oracle, the host listing,
and the fused emulation, so equal inputs give equal bits by
construction, in straight f32 and regardless of how XLA fuses the
surrounding program.  Callbacks are opaque to autodiff, so each piece is
a ``jax.custom_vjp`` whose backward is itself a numpy callback.  The
backward exploits that the finalized output is mathematically invariant
to every ``m`` value (the normalizer cancels between ``l`` and ``acc``),
so all ``m``-channel cotangents are *exactly* zero and the remaining
VJPs are the plain softmax/rescale pullbacks.  The TPU kernel opts out
(``exact=False``): Mosaic compiles one program, host callbacks do not
exist inside Pallas, and the CPU/TPU bit contract is meaningless across
hardware anyway.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import ensure_varying

__all__ = [
    "scaled_queries",
    "empty_state",
    "stripe_mask",
    "stripe_state",
    "merge_states",
    "finalize_state",
    "stripe_bwd",
    "merge_bwd",
    "finalize_bwd",
    "chain_grads",
]

State = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]

_F32 = np.float32


def scaled_queries(q, kh: int, scale) -> jnp.ndarray:
    """(B, Tq, H, D) queries -> pre-scaled f32 (B, Tq, KH, G, D) GQA groups."""
    B, Tq, H, D = q.shape
    if H % kh:
        raise ValueError(f"H={H} not divisible by kv heads {kh}")
    return (q.astype(jnp.float32) * scale).reshape(B, Tq, kh, H // kh, D)


def empty_state(qg, v) -> State:
    """The merge identity: no keys seen yet (``m = -inf, l = 0, acc = 0``).

    Derives from ``qg``/``v`` so the state's varying-manual-axes match the
    stripe states under shard_map (the flash oracle's carry-tag trick).
    """
    B, Tq, KH, G, _ = qg.shape
    Dv = v.shape[-1]
    tag = (qg.reshape(-1)[0] * 0) + (v.reshape(-1)[0] * 0).astype(jnp.float32)
    m = jnp.full((B, Tq, KH, G), -jnp.inf, jnp.float32) + tag
    l = jnp.zeros((B, Tq, KH, G), jnp.float32) + tag
    acc = jnp.zeros((B, Tq, KH, G, Dv), jnp.float32) + tag
    return m, l, acc


# --------------------------------------------------------------------------
# stripe: one rank's queries against one K/V stripe
# --------------------------------------------------------------------------

def _stripe_f32(qg, k_stripe, v_stripe, vis):
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_stripe.astype(jnp.float32))
    s = jnp.where(vis[:, :, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1)                       # -inf on fully masked rows
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(vis[:, :, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bqhgk,bkhd->bqhgd", p, v_stripe.astype(jnp.float32))
    return m, l, acc


def _np_stripe_p(qg, k, mask):
    """Softmax numerator ``p`` and row max ``m`` (both f32 numpy)."""
    vis = mask > 0.5
    s = np.einsum("bqhgd,bkhd->bqhgk", qg, k, dtype=_F32)
    s = np.where(vis[:, :, None, None, :], s, _F32(-np.inf))
    m = s.max(axis=-1)
    m_safe = np.where(np.isneginf(m), _F32(0), m)
    with np.errstate(invalid="ignore", over="ignore", under="ignore"):
        p = np.exp(s - m_safe[..., None], dtype=_F32)   # exactly 0 if masked
    return p, m


def _np_stripe(qg, k, v, mask):
    p, m = _np_stripe_p(qg, k, mask)
    l = p.sum(axis=-1, dtype=_F32)
    acc = np.einsum("bqhgk,bkhd->bqhgd", p, v, dtype=_F32)
    return m, l, acc


def _np_stripe_bwd(qg, k, v, mask, gl, gacc):
    p, _ = _np_stripe_p(qg, k, mask)
    gp = gl[..., None] + np.einsum("bqhgd,bkhd->bqhgk", gacc, v, dtype=_F32)
    ds = p * gp                              # masked rows: p == 0 -> ds == 0
    gqg = np.einsum("bqhgk,bkhd->bqhgd", ds, k, dtype=_F32)
    gk = np.einsum("bqhgk,bqhgd->bkhd", ds, qg, dtype=_F32)
    gv = np.einsum("bqhgk,bqhgd->bkhd", p, gacc, dtype=_F32)
    return gqg, gk, gv


def _callback(fn, shapes, *args):
    """``fn`` on the host with f32 results of ``shapes``.  Under shard_map
    a callback's results vary over no mesh axis; they are promoted to vary
    over every axis its arguments vary over, as the values they stand for
    do."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    out = jax.pure_callback(
        fn, tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes),
        *args)
    return ensure_varying(out, tuple(sorted(vma)))


def _state_shapes(qg, v):
    B, Tq, KH, G, _ = qg.shape
    return (B, Tq, KH, G), (B, Tq, KH, G), (B, Tq, KH, G, v.shape[-1])


@jax.custom_vjp
def _stripe_exact(qg, k32, v32, mask):
    return _callback(_np_stripe, _state_shapes(qg, v32), qg, k32, v32, mask)


def _stripe_exact_fwd(qg, k32, v32, mask):
    return _stripe_exact(qg, k32, v32, mask), (qg, k32, v32, mask)


def _stripe_exact_bwd(res, ct):
    qg, k32, v32, mask = res
    _, gl, gacc = ct                         # gm dies here (see module doc)
    gqg, gk, gv = _callback(_np_stripe_bwd, (qg.shape, k32.shape, v32.shape),
                            qg, k32, v32, mask, gl, gacc)
    return gqg, gk, gv, jnp.zeros_like(mask)


_stripe_exact.defvjp(_stripe_exact_fwd, _stripe_exact_bwd)


def stripe_mask(S: int, *, q_pos, k_start, causal: bool,
                valid_len=None) -> jnp.ndarray:
    """Visibility of one stripe's ``S`` key rows to the ``(B|1, Tq)`` query
    positions — boolean, exact (no float math), so it can be built outside
    the exact path and passed in via ``stripe_state(..., vis=...)``."""
    k_pos = jnp.asarray(k_start) + jnp.arange(S)                  # (S,)
    q_pos = jnp.asarray(q_pos)                                    # (B|1, Tq)
    vis = jnp.ones((1, 1, S), bool)
    if valid_len is not None:
        v_len = jnp.asarray(valid_len)
        vis = vis & (k_pos.reshape(1, 1, -1) < v_len.reshape(-1, 1, 1))
    if causal:
        vis = vis & (k_pos.reshape(1, 1, -1) <= q_pos[:, :, None])
    return vis


def stripe_state(qg, k_stripe, v_stripe, *, q_pos=None, k_start=None,
                 causal: bool = True, valid_len=None, vis=None,
                 exact: bool = True) -> State:
    """Partial-softmax state of ALL my queries against one K/V stripe.

    ``qg (B, Tq, KH, G, D)`` pre-scaled f32 queries; ``k_stripe /
    v_stripe (B, S, KH, D / Dv)`` one rank's K/V rows; ``q_pos (B|1, Tq)``
    global query positions and ``k_start`` the stripe's first global key
    position (both may be traced — dynamic chunked-prefill offsets mask
    instead of skipping); ``valid_len`` masks padded key rows.  A caller
    that already built the visibility (:func:`stripe_mask`) passes ``vis``
    instead.  A fully masked stripe returns exactly :func:`empty_state`'s
    values.
    """
    B, Tq = qg.shape[:2]
    S = k_stripe.shape[1]
    if vis is None:
        vis = stripe_mask(S, q_pos=q_pos, k_start=k_start, causal=causal,
                          valid_len=valid_len)
    vis = jnp.broadcast_to(vis, (B, Tq, S))
    if exact:
        return _stripe_exact(qg, k_stripe.astype(jnp.float32),
                             v_stripe.astype(jnp.float32),
                             vis.astype(jnp.float32))
    return _stripe_f32(qg, k_stripe, v_stripe, vis.astype(bool))


# --------------------------------------------------------------------------
# merge: fold two partial states
# --------------------------------------------------------------------------

def _merge_f32(a: State, b: State) -> State:
    m1, l1, a1 = a
    m2, l2, a2 = b
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    c1 = jnp.where(jnp.isneginf(m1), 0.0, jnp.exp(m1 - m_safe))
    c2 = jnp.where(jnp.isneginf(m2), 0.0, jnp.exp(m2 - m_safe))
    return m, l1 * c1 + l2 * c2, a1 * c1[..., None] + a2 * c2[..., None]


def _np_merge(m1, l1, a1, m2, l2, a2):
    empty1, empty2 = np.isneginf(m1), np.isneginf(m2)
    m = np.maximum(m1, m2)
    m_safe = np.where(np.isneginf(m), _F32(0), m)
    with np.errstate(invalid="ignore", under="ignore"):
        e1 = np.exp(m1 - m_safe, dtype=_F32)             # -inf max -> 0
        e2 = np.exp(m2 - m_safe, dtype=_F32)
    # An empty side passes the other through VERBATIM (bitwise identity,
    # -0.0 included), not as `x * 1.0 + 0.0 * 0.0`.
    l = np.where(empty2, l1, np.where(empty1, l2, l1 * e1 + l2 * e2))
    acc = np.where(empty2[..., None], a1,
                   np.where(empty1[..., None], a2,
                            a1 * e1[..., None] + a2 * e2[..., None]))
    return m, l, acc


def _np_merge_bwd(m1, m2, gl, gacc):
    empty1, empty2 = np.isneginf(m1), np.isneginf(m2)
    m = np.maximum(m1, m2)
    m_safe = np.where(np.isneginf(m), _F32(0), m)
    with np.errstate(invalid="ignore", under="ignore"):
        e1 = np.exp(m1 - m_safe, dtype=_F32)
        e2 = np.exp(m2 - m_safe, dtype=_F32)
    c1 = np.where(empty2, _F32(1), np.where(empty1, _F32(0), e1))
    c2 = np.where(empty2, _F32(0), np.where(empty1, _F32(1), e2))
    return gl * c1, gl * c2, gacc * c1[..., None], gacc * c2[..., None]


@jax.custom_vjp
def _merge_exact(a: State, b: State) -> State:
    m1, l1, a1 = a
    return _callback(_np_merge, (m1.shape, l1.shape, a1.shape), *a, *b)


def _merge_exact_fwd(a, b):
    return _merge_exact(a, b), (a[0], b[0])


def _merge_exact_bwd(res, ct):
    m1, m2 = res
    _, gl, gacc = ct                         # gm dies here (see module doc)
    gl1, gl2, ga1, ga2 = merge_bwd(m1, m2, gl, gacc)
    return (jnp.zeros_like(m1), gl1, ga1), (jnp.zeros_like(m2), gl2, ga2)


_merge_exact.defvjp(_merge_exact_fwd, _merge_exact_bwd)


def merge_states(a: State, b: State, *, exact: bool = True) -> State:
    """Combine two partial-softmax states (associative; identity =
    :func:`empty_state`).

    Each side is rescaled from its own max to the joint max; a ``-inf``
    max (nothing seen) means that side is empty and the other side passes
    through as-is — a bitwise no-op, which is the property the causal
    step-skip relies on.
    """
    if exact:
        return _merge_exact(tuple(a), tuple(b))
    return _merge_f32(a, b)


# --------------------------------------------------------------------------
# finalize: normalize the folded state
# --------------------------------------------------------------------------

def _finalize_f32(state: State):
    m, l, acc = state
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _np_finalize(l, acc):
    return acc / np.maximum(l, _F32(1e-30))[..., None]


def _np_finalize_bwd(l, acc, ct):
    L = np.maximum(l, _F32(1e-30))
    gacc = ct / L[..., None]
    with np.errstate(divide="ignore", invalid="ignore", under="ignore"):
        gl = -(ct * acc).sum(axis=-1, dtype=_F32) / (L * L)
    gl = np.where(l >= _F32(1e-30), gl, _F32(0))         # dead rows
    return gl, gacc


@jax.custom_vjp
def _finalize_exact(state: State):
    m, l, acc = state
    return _callback(_np_finalize, (acc.shape,), l, acc)[0]


def _finalize_exact_fwd(state):
    m, l, acc = state
    return _finalize_exact(state), (l, acc)


def _finalize_exact_bwd(res, ct):
    l, acc = res
    gl, gacc = finalize_bwd(ct, l, acc)
    return ((jnp.zeros_like(l), gl, gacc),)


_finalize_exact.defvjp(_finalize_exact_fwd, _finalize_exact_bwd)


def finalize_state(state: State, dtype, exact: bool = True) -> jnp.ndarray:
    """Normalize the folded state to the (B, Tq, H, Dv) attention output.

    Fully masked rows (``l == 0``) come out as zeros, matching the flash
    oracle's ``max(l, 1e-30)`` guard.
    """
    B, Tq, KH, G, Dv = state[2].shape
    out = (_finalize_exact if exact else _finalize_f32)(tuple(state))
    return out.reshape(B, Tq, KH * G, Dv).astype(dtype)


# --------------------------------------------------------------------------
# the hand-written VJP of a whole fold chain
# --------------------------------------------------------------------------
#
# Autodiff through the ring cannot meet the gradient bit contract: the
# transpose machinery accumulates each K/V shard's cotangent contributions
# in whatever association order the surrounding jaxpr dictates, and the
# emulation's ring transpose orders those f32 adds differently from the
# oracle's slice transpose.  So both executions install a custom VJP over
# the WHOLE schedule and build the backward from these pieces, summing
# contributions in one canonical order (own stripe, then clockwise
# deliveries by step, then counter-clockwise).  Elementwise f32 adds of
# the same values in the same order are bit-deterministic — XLA does not
# reassociate float adds — so the two programs agree bitwise.


def finalize_bwd(ct, l, acc):
    """Cotangents ``(gl, gacc)`` of :func:`finalize_state`'s exact
    normalize for output cotangent ``ct (B, Tq, KH, G, Dv)`` f32."""
    return _callback(_np_finalize_bwd, (l.shape, acc.shape), l, acc, ct)


def merge_bwd(m1, m2, gl, gacc):
    """Cotangents ``(gl1, gl2, gacc1, gacc2)`` of one exact merge, from
    the two sides' row maxes (the only residual the rescale needs)."""
    return _callback(_np_merge_bwd, (gl.shape, gl.shape, gacc.shape,
                                     gacc.shape), m1, m2, gl, gacc)


def stripe_bwd(qg, k_stripe, v_stripe, vis, gl, gacc):
    """Cotangents ``(gqg, gk, gv)`` (all f32) of one exact stripe pass."""
    k32 = k_stripe.astype(jnp.float32)
    v32 = v_stripe.astype(jnp.float32)
    mask = jnp.broadcast_to(vis, (qg.shape[0], qg.shape[1],
                                  k_stripe.shape[1])).astype(jnp.float32)
    return _callback(_np_stripe_bwd, (qg.shape, k32.shape, v32.shape),
                     qg, k32, v32, mask, gl, gacc)


def chain_grads(qg, stripes, ct):
    """Backward of ``finalize(fold(empty, stripes))`` for one rank.

    ``stripes``: the fold-order sequence of ``(k_stripe, v_stripe, vis)``;
    ``ct``: the f32 ``(B, Tq, KH, G, Dv)`` output cotangent.  Recomputes
    the exact forward chain (cheap at CI scale, and bit-reproducible by
    construction), walks the merges in reverse, and returns
    ``(gqg, [gk_i], [gv_i])`` — the query cotangent summed over stripes in
    fold order and the per-stripe K/V cotangents (f32, fold order), for
    the caller to route to the stripes' owners and accumulate canonically.
    """
    states, blocks = [], []
    state = empty_state(qg, stripes[0][1])
    for k_str, v_str, vis in stripes:
        blk = stripe_state(qg, k_str, v_str, vis=vis)
        states.append(state)
        blocks.append(blk)
        state = merge_states(state, blk)
    gl, gacc = finalize_bwd(ct, state[1], state[2])
    per_stripe = [None] * len(stripes)
    for i in reversed(range(len(stripes))):
        gl1, gl2, ga1, ga2 = merge_bwd(states[i][0], blocks[i][0], gl, gacc)
        per_stripe[i] = (gl2, ga2)
        gl, gacc = gl1, ga1                  # the empty state's dies at i=0
    gqg, gks, gvs = None, [], []
    for (k_str, v_str, vis), (gl_i, ga_i) in zip(stripes, per_stripe):
        gq_i, gk_i, gv_i = stripe_bwd(qg, k_str, v_str, vis, gl_i, ga_i)
        gqg = gq_i if gqg is None else gqg + gq_i
        gks.append(gk_i)
        gvs.append(gv_i)
    return gqg, gks, gvs
