"""Production serving engine: chunked prefill, paged KV, preemption.

Covers the docs/SERVING.md contracts: chunked prefill output-equivalence
with the token-by-token baseline, the per-request engine-step bound,
O(1)-page ``extend`` (call-log asserted), free-list reuse (no arena growth
across request churn), OOM -> preempt -> resume round-trips, and migration
byte accounting against the OMPCCL/RMA call logs.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.compat import shard_map
from repro.core.context import DiompContext
from repro.core.groups import DiompGroup
from repro.core.pgas import GlobalMemory
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.models.layers import decode_attention
from repro.models import schema as sch
from repro.models.config import ParallelCtx
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import PagedKVAllocator

CFG = configs.get_reduced("stablelm-3b")


@pytest.fixture(scope="module")
def params():
    return sch.init_params(CFG, jax.random.PRNGKey(0))


def _engine(mesh8, params, **kw):
    ctx = ParallelCtx.from_mesh(mesh8, remat=False, inference=True)
    return ServeEngine(CFG, mesh8, ctx, params, **kw)


def _kv_bpt():
    return 2 * 2 * max(CFG.kv_heads, 1) * max(CFG.head_dim, 1) \
        * CFG.num_layers


def _serve(eng, lengths, max_new=4):
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, CFG.vocab_size, size=n).astype(np.int32)
               for n in lengths]
    reqs = [eng.submit(p, max_new=max_new) for p in prompts]
    eng.run()
    assert all(r.done and len(r.out) == max_new for r in reqs), \
        [(len(r.prompt), len(r.out), r.done) for r in reqs]
    return reqs


# -- chunked prefill -------------------------------------------------------

def test_chunked_equals_token_by_token(mesh8, params):
    """Mixed prompt lengths, continuous batching: the chunked engine's
    outputs match the token-by-token (prefill_chunk=1) baseline exactly."""
    lengths = (3, 9, 17, 5, 26)
    base = _serve(_engine(mesh8, params, slots=2, max_len=64,
                          prefill_chunk=1), lengths)
    fast = _serve(_engine(mesh8, params, slots=2, max_len=64,
                          prefill_chunk=8), lengths)
    for b, f in zip(base, fast):
        assert b.out == f.out, (len(b.prompt), b.out, f.out)
    # the chunked engine spends ceil(len/chunk) prefill device calls
    for f, n in zip(fast, lengths):
        assert f.prefill_steps == -(-n // 8)


def test_step_bound_mixed_batch(mesh8, params):
    """A mixed batch (prompt lengths 8..512) prefills in ceil(len/chunk)
    chunk calls and finishes within ceil(len/chunk) + max_new + O(1)
    engine steps per request."""
    chunk, max_new = 64, 4
    lengths = (8, 40, 230, 512)
    eng = _engine(mesh8, params, slots=len(lengths), max_len=544,
                  prefill_chunk=chunk)
    reqs = _serve(eng, lengths, max_new=max_new)
    for r, n in zip(reqs, lengths):
        assert r.prefill_steps == -(-n // chunk), (n, r.prefill_steps)
        assert r.decode_steps <= max_new
        resident = r.finish_step - r.admit_step
        assert resident <= -(-n // chunk) + max_new + 2, (n, resident)
    st = eng.kv_stats
    assert st["pages_allocated"] == st["pages_freed"] > 0
    assert st["oom_events"] == 0


def test_released_slot_keeps_no_stale_state(mesh8, params):
    """Seed-engine regression: a freed slot must not keep teacher-forcing
    its stale pending token / advancing the device position.  A request
    admitted into a previously used slot generates exactly what a fresh
    engine generates."""
    rng = np.random.RandomState(3)
    long_p = rng.randint(0, CFG.vocab_size, size=9).astype(np.int32)
    short_p = rng.randint(0, CFG.vocab_size, size=2).astype(np.int32)
    late_p = rng.randint(0, CFG.vocab_size, size=6).astype(np.int32)

    eng = _engine(mesh8, params, slots=2, max_len=64, prefill_chunk=4)
    eng.submit(short_p, max_new=2)           # finishes early, frees its slot
    eng.submit(long_p, max_new=12)           # keeps the engine running
    eng.run()
    late = eng.submit(late_p, max_new=4)     # reuses the churned slot
    eng.run()

    fresh = _engine(mesh8, params, slots=2, max_len=64, prefill_chunk=4)
    ref = fresh.submit(late_p, max_new=4)
    fresh.run()
    assert late.done and late.out == ref.out, (late.out, ref.out)


# -- decode attention over the cache as stored ----------------------------

@pytest.mark.parametrize("H,KH", [(4, 4), (8, 2)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_decode_attention_matches_blockwise_reference(H, KH, dtype):
    """One query per slot over the whole cache, each slot seeing its own
    count of rows (1 and S among them), against the blockwise reference."""
    B, S, D = 4, 24, 16
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(*shape), dtype) for shape in
               ((B, 1, H, D), (B, S, KH, D), (B, S, KH, D)))
    valid = jnp.asarray([1, 7, S - 1, S], jnp.int32)
    got = decode_attention(q, k, v, valid, scale=D ** -0.5)
    want = flash_attention_ref(q, k, v, causal=True, q_offset=valid - 1,
                               valid_len=valid, block=8)
    assert got.dtype == q.dtype
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_decode_attention_over_sharded_cache(ring8, dtype):
    """The cache's S axis split over eight members, each attending over
    its chunk and merging over the group, matches the whole-cache result
    (slots whose rows end inside the first, a middle and the last chunk)."""
    B, S, H, KH, D = 4, 32, 8, 2, 16
    rng = np.random.RandomState(9)
    q, k, v = (jnp.asarray(rng.randn(*shape), dtype) for shape in
               ((B, 1, H, D), (B, S, KH, D), (B, S, KH, D)))
    valid = jnp.asarray([1, 6, 17, S], jnp.int32)
    group = DiompGroup(("x",))
    sharded = jax.jit(shard_map(
        lambda q, k, v, n: decode_attention(q, k, v, n, scale=D ** -0.5,
                                            group=group),
        mesh=ring8, in_specs=(P(), P(None, "x"), P(None, "x"), P()),
        out_specs=P()))
    got = sharded(q, k, v, valid)
    want = decode_attention(q, k, v, valid, scale=D ** -0.5)
    assert got.dtype == q.dtype
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_greedy_tokens_unchanged_with_slot_on_last_row(mesh8, params):
    """Greedy output is token for token what the blockwise-reference decode
    produced.  One request fills the cache to its limit while the other
    slot, once finished, stays parked on the last row S-1: every decode
    step writes that row and attends over all S rows."""
    eng = _engine(mesh8, params, slots=2, max_len=32, prefill_chunk=8)
    rng = np.random.RandomState(11)
    long_p = rng.randint(0, CFG.vocab_size, size=7).astype(np.int32)
    short_p = rng.randint(0, CFG.vocab_size, size=3).astype(np.int32)
    long_r = eng.submit(long_p, max_new=eng.S - 1 - len(long_p))
    short_r = eng.submit(short_p, max_new=4)
    eng.run()
    assert long_r.out == [82, 126, 145, 38, 66, 83, 126, 145, 38, 66, 83,
                          126, 145, 38, 9, 139, 47, 30, 129, 127, 116, 134,
                          46, 85]
    assert short_r.out == [121, 126, 145, 38]


# -- paged allocator -------------------------------------------------------

def _alloc(page_tokens=16, nranks=4, segment=1 << 22):
    mem = GlobalMemory(nranks, segment, allocator="buddy")
    g = DiompGroup(("x",), name="x")
    return PagedKVAllocator(mem, g, page_tokens=page_tokens,
                            kv_bytes_per_token=64), mem


def test_extend_is_one_page_alloc():
    """Every ``extend`` that grows performs EXACTLY one page allocation
    (arena or free-list) — call-log asserted."""
    alloc, _ = _alloc()
    r = alloc.admit(10, 200)
    mark = len(alloc.call_log)
    grown = 0
    for _ in range(100):
        r.pos += 1
        before = len(alloc.call_log)
        assert alloc.extend(r)
        events = alloc.call_log[before:]
        allocs = [e for e in events if e[0] in ("arena_alloc", "page_reuse")]
        grows = [e for e in events if e[0] == "extend"]
        assert len(allocs) <= 1
        if grows:
            assert len(allocs) == 1 and grows[0][2] == 1
            grown += 1
    assert grown == len(r.page_table) - 2  # admit covered prompt + 1 page
    assert all(e[2] == 1 for e in alloc.call_log[mark:] if e[0] == "extend")
    alloc.release(r)


def test_free_list_reuse_no_arena_growth():
    """Steady-state request churn re-uses released pages: the arena sees no
    new allocations after the first request's working set exists."""
    alloc, mem = _alloc()
    def one_request():
        r = alloc.admit(20, 60)
        assert r is not None
        for _ in range(40):
            r.pos += 1
            assert alloc.extend(r)
        alloc.release(r)
    one_request()
    arena_after_first = alloc.stats["arena_page_allocs"]
    asym_after_first = mem.alloc_counts["asymmetric"]
    for _ in range(25):
        one_request()
    assert alloc.stats["arena_page_allocs"] == arena_after_first
    assert mem.alloc_counts["asymmetric"] == asym_after_first
    assert alloc.stats["page_reuses"] > 0
    assert alloc.stats["pages_allocated"] == alloc.stats["pages_freed"]
    # trim returns the pool to the arena cleanly
    alloc.trim()
    assert mem.bytes_in_use(0) == 0
    mem.check_invariants()


def test_lookup_resolves_through_page_table():
    alloc, mem = _alloc(page_tokens=16)
    r = alloc.admit(40, 80, home_rank=2)
    # token 20 lives on page 1 at within-page offset 4
    rank, off = alloc.lookup(r, 20)
    assert rank == 2
    p1_rank, p1_base = mem.translate(r.page_table[1], 2)
    assert (rank, off) == (p1_rank, p1_base + 4 * alloc.token_bytes)
    # repeated remote lookups hit the pointer cache after the first deref
    h0 = mem.ptr_cache.hits
    alloc.lookup(r, 21)
    alloc.lookup(r, 22)
    assert mem.ptr_cache.hits >= h0 + 2
    alloc.release(r)


def test_migrate_moves_pages_and_accounts_bytes():
    alloc, _ = _alloc(page_tokens=16)
    r = alloc.admit(30, 60, home_rank=0)
    npages = len(r.page_table)

    class _Rec:
        def __init__(self):
            self.calls, self.nbytes = {}, {}
        def record(self, op, payload=None):
            self.calls[op] = self.calls.get(op, 0) + 1
            if payload is not None:
                self.nbytes[op] = self.nbytes.get(op, 0) + payload.nbytes

    from repro.core.rma import RMATracker
    comm, tr = _Rec(), RMATracker()
    tr.register("w")
    moved = alloc.migrate(r, 3, comm=comm, tracker=tr, window="w")
    assert r.home_rank == 3 and len(r.page_table) == npages
    assert moved == npages * alloc.page_bytes
    assert comm.calls == {"get": npages, "put": npages}
    assert comm.nbytes["put"] == moved            # leaf-op byte convention
    assert tr.put_bytes == moved and tr.window_bytes["w"] == moved
    assert tr.fences == 1
    alloc.release(r)


# -- preemption / migration in the engine ----------------------------------

PAGE_TOKENS = 16
OOM_LENGTHS, OOM_MAX_NEW = (20, 21), 42   # both grow 3 -> 4 pages at pos 48


def _pressured_engine(mesh8, params):
    """2 slots, arena of exactly 8 pages minus 1 page of ballast: admits
    take 3 + 3 (+1 ballast), the first page-boundary extend fits (8/8),
    the second hard-OOMs.  Watermark preemption is disabled so the hard-OOM
    path itself is exercised (test_watermark_preemption covers the soft
    path)."""
    page_bytes = PAGE_TOKENS * _kv_bpt()
    ctx = DiompContext(mesh=mesh8, segment_bytes=8 * page_bytes,
                       allocator="buddy")
    eng = _engine(mesh8, params, slots=2, max_len=64, prefill_chunk=8,
                  page_tokens=PAGE_TOKENS, high_watermark=10.0, context=ctx)
    sizes = [page_bytes if r == 0 else 0 for r in range(eng.memory.nranks)]
    eng.memory.alloc_asymmetric("ballast", sizes, eng._group)
    return eng


def test_oom_preempt_resume_roundtrip(mesh8, params):
    """Decode growth past the arena forces preemption; the victim swaps its
    pages to a spill heap over RMA, resumes later, and ends with exactly
    the unpressured run's output."""
    ref = _serve(_engine(mesh8, params, slots=2, max_len=64,
                         prefill_chunk=8, page_tokens=PAGE_TOKENS),
                 OOM_LENGTHS, max_new=OOM_MAX_NEW)
    eng = _pressured_engine(mesh8, params)
    got = _serve(eng, OOM_LENGTHS, max_new=OOM_MAX_NEW)
    assert sum(r.preemptions for r in got) >= 1
    assert eng.alloc.stats["migrations"] >= 2      # swap out + swap home
    assert eng.alloc.stats["oom_events"] >= 1
    for a, b in zip(ref, got):
        assert a.out == b.out, (a.out, b.out)


def test_engine_migration_bytes_match_rma_log(mesh8, params):
    eng = _pressured_engine(mesh8, params)
    world = eng._group.descriptor()
    put0 = eng.dctx.byte_stats().get(world, {}).get("put", 0)
    _serve(eng, OOM_LENGTHS, max_new=OOM_MAX_NEW)
    moved = eng.alloc.stats["bytes_migrated"]
    assert moved > 0
    put1 = eng.dctx.byte_stats()[world]["put"]
    assert put1 - put0 == moved            # OMPCCL wire-volume log
    assert eng.dctx.rma.put_bytes == moved  # RMA tracker window accounting
    assert eng.dctx.stats()[world]["get"] == moved // eng.alloc.page_bytes


def test_watermark_preemption_still_correct(mesh8, params):
    """An aggressive high watermark serializes execution through preemption
    without changing any output (greedy sampling)."""
    lengths = (9, 14, 5)
    ref = _serve(_engine(mesh8, params, slots=3, max_len=64,
                         prefill_chunk=8), lengths, max_new=6)
    eng = _engine(mesh8, params, slots=3, max_len=64, prefill_chunk=8,
                  high_watermark=1e-4, low_watermark=5e-5)
    got = _serve(eng, lengths, max_new=6)
    assert sum(r.preemptions for r in got) >= 1
    for a, b in zip(ref, got):
        assert a.out == b.out


# -- sampling / scheduling --------------------------------------------------

def test_sampling_deterministic_and_nongreedy(mesh8, params):
    kw = dict(slots=2, max_len=64, prefill_chunk=8, temperature=0.9,
              top_k=8, seed=11)
    a = _serve(_engine(mesh8, params, **kw), (7, 12), max_new=6)
    b = _serve(_engine(mesh8, params, **kw), (7, 12), max_new=6)
    greedy = _serve(_engine(mesh8, params, slots=2, max_len=64,
                            prefill_chunk=8), (7, 12), max_new=6)
    for x, y in zip(a, b):
        assert x.out == y.out              # seeded sampling is reproducible
    assert any(x.out != g.out for x, g in zip(a, greedy))


def test_submit_rejects_unservable_chunk_span(mesh8, params):
    """The padded final chunk must fit the cache (a clamped device write
    would corrupt live rows): ceil(len/chunk)*chunk > max_len is rejected
    at submit, even when len + max_new fits."""
    eng = _engine(mesh8, params, slots=1, max_len=96, prefill_chunk=64)
    with pytest.raises(ValueError, match="chunked prefill"):
        eng.submit(np.ones(89, np.int32), max_new=5)   # 2*64 = 128 > 96
    eng.submit(np.ones(60, np.int32), max_new=4)       # 64 <= 96: fine
    eng.run()


def test_priority_admission(mesh8, params):
    eng = _engine(mesh8, params, slots=1, max_len=64, prefill_chunk=8)
    rng = np.random.RandomState(0)
    lo = eng.submit(rng.randint(0, CFG.vocab_size, 5), max_new=3, priority=0)
    hi = eng.submit(rng.randint(0, CFG.vocab_size, 5), max_new=3, priority=5)
    eng.run()
    assert lo.done and hi.done
    assert hi.admit_step < lo.admit_step   # higher priority admits first
    st = eng.latency_stats()
    assert st["requests_done"] == 2 and st["preemptions"] == 0
