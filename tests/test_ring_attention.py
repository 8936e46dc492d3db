"""Fused ring attention vs the host listing and the single-device oracle.

The bit contract (docs/ARCHITECTURE.md): the fused CPU emulation, the
serialized host listing, and :func:`ring_attention_ref` all fold the same
exact numpy stripe/merge ops in the same schedule order, so forward AND
gradients must agree ``==`` (not allclose) across ring sizes, GQA ratios,
bf16 inputs, non-divisible (padded) lengths, and traced chunked-prefill
offsets.  The put-side books must match :class:`AttentionRingPlan`
exactly.  ``RUN_SLOW=1`` widens the sweep to every mode x ring size.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import ompccl
from repro.core.compat import make_mesh, shard_map
from repro.core.context import DiompContext, use_default
from repro.core.groups import DiompGroup
from repro.core.rma import attention_window_names
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.plan import default_planner, resolve_seq_parallel
from repro.kernels.ring_attention import (resolve_attention_impl,
                                          ring_attention, ring_attention_ref)

GROUP = DiompGroup(("x",), name="x")


def _mesh(n):
    return make_mesh((n,), ("x",), axis_types="auto")


def _case(n, *, tq=4, H=4, KH=2, D=8, DV=8, B=2, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    T = n * tq
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32).astype(dtype)
    k = jnp.asarray(rng.randn(B, T, KH, D), jnp.float32).astype(dtype)
    v = jnp.asarray(rng.randn(B, T, KH, DV), jnp.float32).astype(dtype)
    return q, k, v


def _ring_fn(mesh, impl, **kw):
    def f(q, k, v):
        return ring_attention(q, k, v, GROUP, impl=impl, **kw)

    spec = P(None, "x")
    return jax.jit(shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec))


# ---------------------------------------------------------------------------
# the TPU kernel body, run by Pallas' TPU interpreter (remote copies and
# semaphores simulated across the CPU devices) against the oracle
# ---------------------------------------------------------------------------

TPU_CASES = [
    ("n4_causal_gqa_d80", 4, dict(), dict(tq=8, D=80, DV=80, B=1)),
    ("n4_bidi_mqa_dv_ne_d", 4, dict(causal=False), dict(tq=8, KH=1, DV=64)),
    ("n2_causal_valid_len", 2, dict(valid_len=13), dict(tq=8, KH=4)),
]


@pytest.mark.parametrize("name,n,kw,ckw", TPU_CASES,
                         ids=[c[0] for c in TPU_CASES])
def test_tpu_kernel_matches_oracle_in_tpu_interpreter(name, n, kw, ckw):
    from jax.experimental.pallas import tpu as pltpu

    q, k, v = _case(n, **ckw)
    causal = kw.get("causal", True)
    want = np.asarray(ring_attention_ref(q, k, v, n=n, causal=causal,
                                         valid_len=kw.get("valid_len")))
    spec = P(None, "x")
    # the interpreter's own ops carry no vma types: check_vma off here (the
    # compiled kernel runs vma-checked; tests/test_tpu_compile.py)
    fn = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, GROUP, impl="fused",
                                       interpret=False, **kw),
        mesh=_mesh(n), in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False))
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(fn(q, k, v))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# forward: fused == host == oracle, bitwise
# ---------------------------------------------------------------------------

CASES = [
    ("n2_causal", 2, dict(), dict(dtype=jnp.float32)),
    ("n4_bidi", 4, dict(causal=False), dict()),
    ("n4_bf16", 4, dict(), dict(dtype=jnp.bfloat16)),
    ("n4_mqa", 4, dict(), dict(KH=1)),
    ("n4_mha", 4, dict(), dict(KH=4)),
    ("n4_dv_ne_d", 4, dict(), dict(DV=4)),
    ("n1_group_of_one", 1, dict(), dict()),
    ("n8_causal", 8, dict(), dict(tq=2)),
]


@pytest.mark.parametrize("name,n,kw,ckw", CASES, ids=[c[0] for c in CASES])
def test_fused_host_oracle_bitwise(name, n, kw, ckw):
    q, k, v = _case(n, **ckw)
    causal = kw.get("causal", True)
    want = np.asarray(jax.jit(
        lambda q, k, v: ring_attention_ref(q, k, v, n=n, causal=causal)
    )(q, k, v))
    mesh = _mesh(n)
    for impl in ("host", "fused"):
        got = np.asarray(_ring_fn(mesh, impl, **kw)(q, k, v))
        np.testing.assert_array_equal(got, want, err_msg=impl)
    # and all of it tracks the plain flash oracle to float tolerance
    ref = np.asarray(flash_attention_ref(q, k, v, causal=causal))
    np.testing.assert_allclose(want.astype(np.float32),
                               ref.astype(np.float32), atol=3e-2 if
                               ckw.get("dtype") == jnp.bfloat16 else 3e-6,
                               rtol=3e-2 if ckw.get("dtype") == jnp.bfloat16
                               else 3e-6)


@pytest.mark.parametrize("impl", ["host", "fused"])
@pytest.mark.parametrize("causal", [True, False])
def test_grad_bitwise(impl, causal):
    n = 4
    q, k, v = _case(n, seed=3)
    ct = jnp.asarray(np.random.RandomState(9).randn(*q.shape[:2], q.shape[2],
                                                    v.shape[-1]), jnp.float32)
    mesh = _mesh(n)
    spec = P(None, "x")

    def g(q, k, v, ct):
        out, vjp = jax.vjp(
            lambda a, b, c: ring_attention(a, b, c, GROUP, causal=causal,
                                           impl=impl), q, k, v)
        return vjp(ct)

    got = jax.jit(shard_map(g, mesh=mesh, in_specs=(spec,) * 4,
                            out_specs=(spec,) * 3))(q, k, v, ct)

    def oracle(q, k, v):
        return ring_attention_ref(q, k, v, n=n, causal=causal)

    _, vjp = jax.vjp(oracle, q, k, v)
    want = vjp(ct)
    for name, a, b in zip("qkv", got, want):
        a = np.asarray(a)
        assert np.isfinite(a).all(), name
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=name)


def test_padded_ragged_length_bitwise():
    """T=20 padded to 24 over n=4 with valid_len=20: fwd + grad bitwise vs
    the oracle, real rows allclose vs unpadded flash."""
    n, T, T_pad = 4, 20, 24
    rng = np.random.RandomState(5)
    B, H, KH, D = 2, 4, 2, 8
    q = jnp.asarray(rng.randn(B, T_pad, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T_pad, KH, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T_pad, KH, D), jnp.float32)
    mesh = _mesh(n)
    spec = P(None, "x")
    kw = dict(causal=True, valid_len=T)

    outs = {}
    for impl in ("host", "fused"):
        outs[impl] = np.asarray(_ring_fn(mesh, impl, **kw)(q, k, v))
    want = np.asarray(ring_attention_ref(q, k, v, n=n, **kw))
    np.testing.assert_array_equal(outs["host"], want)
    np.testing.assert_array_equal(outs["fused"], want)
    ref = np.asarray(flash_attention_ref(q[:, :T], k[:, :T], v[:, :T],
                                         causal=True))
    np.testing.assert_allclose(want[:, :T], ref, atol=3e-6, rtol=3e-6)

    ct = jnp.asarray(rng.randn(*want.shape), jnp.float32)

    def g(q, k, v, ct):
        _, vjp = jax.vjp(
            lambda a, b, c: ring_attention(a, b, c, GROUP, impl="fused",
                                           **kw), q, k, v)
        return vjp(ct)

    got = jax.jit(shard_map(g, mesh=mesh, in_specs=(spec,) * 4,
                            out_specs=(spec,) * 3))(q, k, v, ct)
    _, vjp = jax.vjp(lambda a, b, c: ring_attention_ref(a, b, c, n=n, **kw),
                     q, k, v)
    for name, a, b in zip("qkv", got, vjp(ct)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("impl", ["host", "fused"])
def test_chunked_prefill_traced_offset_bitwise(impl):
    """q replicated (q_sharded=False), K/V striped, TRACED q_offset /
    valid_len — the dynamic chunked-prefill layout the serve step lowers."""
    n, tq, p0 = 4, 8, 8
    rng = np.random.RandomState(7)
    B, H, KH, D = 2, 4, 2, 8
    S = p0 + tq                    # 16 cached rows striped over 4 ranks
    q = jnp.asarray(rng.randn(B, tq, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KH, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KH, D), jnp.float32)
    mesh = _mesh(n)

    def f(q, k, v, off):
        return ring_attention(q, k, v, GROUP, causal=True, q_offset=off,
                              valid_len=off + tq, q_sharded=False, impl=impl)

    fn = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(), P(None, "x"), P(None, "x"), P()),
        out_specs=P(), check_vma=False))
    got = np.asarray(fn(q, k, v, jnp.asarray(p0, jnp.int32)))
    want = np.asarray(ring_attention_ref(q, k, v, n=n, causal=True,
                                         q_offset=p0, valid_len=p0 + tq,
                                         q_sharded=False))
    np.testing.assert_array_equal(got, want)
    ref = np.asarray(flash_attention_ref(q, k, v, causal=True, q_offset=p0))
    np.testing.assert_allclose(got, ref, atol=3e-6, rtol=3e-6)


# ---------------------------------------------------------------------------
# the put-side books
# ---------------------------------------------------------------------------


def test_fused_put_traffic_matches_plan():
    n = 4
    q, k, v = _case(n)
    B, T, H, D = q.shape
    plan = default_planner().plan_ring_attention(
        B, T // n, T // n, H, k.shape[2], D, v.shape[-1], jnp.float32, n,
        causal=True)
    dctx = DiompContext()
    with use_default(dctx):
        _ring_fn(_mesh(n), "fused").lower(q, k, v)
    desc = GROUP.descriptor()
    assert dctx.stats()[desc]["put"] == plan.puts_per_rank == 2 * (n - 1)
    put_bytes = dctx.byte_stats()[desc]["put"]
    cw_w, ccw_w = attention_window_names(GROUP, n)
    win_bytes = sum(dctx.rma.window_bytes[w] for w in cw_w + ccw_w)
    assert put_bytes == win_bytes == plan.wire_bytes == dctx.rma.put_bytes


def test_host_put_traffic_matches_plan():
    # the serialized listing moves the SAME bytes — overlap changes
    # scheduling, never traffic
    n = 4
    q, k, v = _case(n)
    plan = default_planner().plan_ring_attention(
        q.shape[0], q.shape[1] // n, q.shape[1] // n, q.shape[2], k.shape[2],
        q.shape[-1], v.shape[-1], jnp.float32, n, causal=True, overlap=False)
    dctx = DiompContext()
    with use_default(dctx):
        _ring_fn(_mesh(n), "host").lower(q, k, v)
    desc = GROUP.descriptor()
    assert dctx.stats()[desc]["put"] == plan.puts_per_rank
    assert dctx.byte_stats()[desc]["put"] == plan.wire_bytes


# ---------------------------------------------------------------------------
# API contracts
# ---------------------------------------------------------------------------


def test_resolvers():
    assert resolve_attention_impl(None) == "fused"
    assert resolve_attention_impl("auto") == "fused"
    assert resolve_attention_impl("host") == "host"
    with pytest.raises(ValueError, match="ring attention impl"):
        resolve_attention_impl("bogus")
    assert resolve_seq_parallel(None) == "allgather"
    assert resolve_seq_parallel("auto") == "allgather"
    assert resolve_seq_parallel("ring") == "ring"
    with pytest.raises(ValueError, match="seq_parallel"):
        resolve_seq_parallel("bogus")


def test_flash_attention_ring_impl_contract():
    q, k, v = _case(1)
    with pytest.raises(ValueError, match="DiompGroup"):
        flash_attention(q, k, v, impl="ring")
    with pytest.raises(ValueError, match="prefix_len"):
        flash_attention(q, k, v, impl="ring", group=GROUP, prefix_len=4)


def test_pallas_traced_offsets_raise():
    """Satellite regression: traced q_offset/valid_len into the pallas
    kernel must fail loudly at the API boundary, naming the contract."""
    q, k, v = _case(1)

    def f_off(off):
        return flash_attention(q, k, v, impl="pallas", q_offset=off)

    with pytest.raises(ValueError, match="static-offsets contract"):
        jax.jit(f_off)(jnp.asarray(3, jnp.int32))

    def f_vl(vl):
        return flash_attention(q, k, v, impl="pallas", valid_len=vl)

    with pytest.raises(ValueError, match="static-offsets contract"):
        jax.jit(f_vl)(jnp.asarray(3, jnp.int32))


# ---------------------------------------------------------------------------
# the model-layer knob (ctx.seq_parallel = "ring")
# ---------------------------------------------------------------------------


def test_attention_block_seq_parallel_ring_matches_allgather():
    """ctx.seq_parallel='ring' swaps the token-parallel flash for the ring
    without changing the block's numerics (bf16-quantized params)."""
    import dataclasses

    from repro.models import schema as sch
    from repro.models.config import ModelConfig, ParallelCtx
    from repro.models.layers import attention_block

    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                      num_heads=8, kv_heads=2, d_ff=128, vocab_size=32,
                      dtype="float32")
    mesh = make_mesh((4, 1), ("model", "data"), axis_types="auto")
    ctx = ParallelCtx.from_mesh(mesh)
    assert not sch.head_parallel(cfg)      # 8 heads -> token-parallel path
    params = sch.init_params(cfg, jax.random.PRNGKey(0))
    lp = {kk.split("/")[1]: vv[0] for kk, vv in params.items()
          if kk.startswith("layers/")}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model),
                          jnp.float32)

    def run(seq_parallel):
        c = dataclasses.replace(ctx, seq_parallel=seq_parallel)

        def f(x):
            out, _ = attention_block(x, lp, cfg, c)
            return out

        return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(), check_vma=False))(x)

    a, r = run("allgather"), run("ring")
    np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# RUN_SLOW=1: the full mode x ring-size sweep
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif(not os.environ.get("RUN_SLOW"),
                    reason="slow sweep; tier-1 runs the equivalence subset "
                           "(set RUN_SLOW=1)")
@pytest.mark.parametrize("impl", ["host", "fused"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("n", [2, 3, 4, 5, 6, 8])
def test_sweep_bitwise(n, causal, impl):
    q, k, v = _case(n, tq=3, seed=n)
    got = np.asarray(_ring_fn(_mesh(n), impl, causal=causal)(q, k, v))
    want = np.asarray(ring_attention_ref(q, k, v, n=n, causal=causal))
    np.testing.assert_array_equal(got, want)


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
