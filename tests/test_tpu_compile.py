"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses here what the chip would
refuse (unaligned slices, more VMEM than a kernel may use, a program that
does not fit).  Nothing runs, so these tests say nothing about results or
times; ``chip_smoke.py`` is the run on the chip.

The topology is described inside a module fixture (never at import time):
only one process at a time may load the TPU library, so this file's tests
stay together in one file and compile in the test's own process.  The
persistent compilation cache is off around them — an entry written for a
described chip cannot be read back without one.

The fused kernels compile under ``shard_map``'s vma checking, as the
program runs them.
"""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import configs
from repro.core.compat import shard_map
from repro.core.context import DiompContext, use_default
from repro.core.groups import DiompGroup
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.moe_dispatch.fused import fused_moe_dispatch_tpu
from repro.kernels.plan import (OverlapPlanner, RingPlan, VMEM_LIMIT_BYTES,
                                default_planner)
from repro.kernels.ring_attention.fused import (
    fused_ring_attention_resident_bytes, fused_ring_attention_tpu)
from repro.kernels.ring_matmul.fused import (fused_ring_allgather_matmul_tpu,
                                             fused_ring_resident_bytes)
from repro.kernels.ring_matmul.kernel import matmul_pallas
from repro.kernels.stencil import fused as stencil_fused
from repro.kernels.stencil.ops import wave_step
from repro.models import api as model_api
from repro.models import schema
from repro.models.config import ParallelCtx
from repro.serve.step import build_decode_step

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("x",))


def _compile(f, *structs):
    compiled = jax.jit(f).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# -- one chip: the kernels at real widths -------------------------------------

def test_flash_attention_stablelm_widths(one_chip):
    """stablelm-3b: 32 heads x 80, 2048 tokens, bf16."""
    q = _struct((1, 32, 2048, 80), BF16, one_chip)
    _compile(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, block_q=512, block_k=512), q, q, q)


def test_matmul_stablelm_mlp(one_chip):
    """4096 tokens x d_model 2560 x d_ff 6912, bf16."""
    _compile(matmul_pallas, _struct((4096, 2560), BF16, one_chip),
             _struct((2560, 6912), BF16, one_chip))


def test_wave_step_full_grid(one_chip):
    """Minimod's one-chip grid: 512³ f32, the slab height from the planner
    against the kernel's VMEM limit."""
    g = _struct((512, 512, 512), F32, one_chip)
    compiled = _compile(lambda u, up: wave_step(
        u, up, 0.1, impl="pallas", interpret=False), g, g)
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def _minimod_program(devices, K=10):
    """Minimod's K-step program as the chip benchmark compiles it: the
    carried-halo schedule over a Z split of 256 × 1024² per chip (the
    whole field on one), ``fused_wave_step`` dispatching off the
    interpreter.  Returns the compiled module's text."""
    from jax import lax

    n = len(devices)
    Z, Y, X = 256 * n, 1024, 1024
    mesh = Mesh(np.array(devices).reshape(n, 1), ("z", "y"))
    zg = DiompGroup(("z",), name="z")
    plan = default_planner().plan_halo_slots(Z // n, Y, X, F32, n)
    spec = P("z", "y")

    def kprog(u, up, c2, *h):
        def body(carry, _):
            u, up, h = carry
            if plan.overlap:
                un, h = stencil_fused.fused_wave_step(
                    u, up, c2, zg, plan=plan, halos=stencil_fused.Halos(*h),
                    return_halos=True, interpret=False)
                h = (h.z_lo, h.z_hi)
            else:
                un = stencil_fused.fused_wave_step(u, up, c2, zg, plan=plan,
                                                   interpret=False)
            return (un, u, h), None

        (u, up, h), _ = lax.scan(body, (u, up, h), None, length=K)
        return (u, up) + tuple(h)

    sh = NamedSharding(mesh, spec)
    field = _struct((Z, Y, X), F32, sh)
    halos = (_struct((4 * n, Y, X), F32, sh),) * 2 if plan.overlap else ()
    with use_default(DiompContext(mesh=mesh)):
        step = jax.jit(shard_map(kprog, mesh=mesh,
                                 in_specs=(spec,) * (3 + len(halos)),
                                 out_specs=(spec,) * (2 + len(halos))))
        return step.lower(field, field, field, *halos).compile().as_text()


def _kernel_vmem(text, name):
    """The scoped VMEM the compiler gave the named kernel's custom call."""
    line = next(l for l in text.splitlines()
                if re.search(rf"%{name}(\.\d+)? = .*custom-call", l))
    cfg = re.search(r'"used_scoped_memory_configs":(\[[^\]]*\])', line)
    return max(int(c["size"]) for c in json.loads(cfg[1]))


def _pad_sizes(text):
    return [int(np.prod([int(d) for d in m.split(",")]))
            for m in re.findall(r"= f32\[([\d,]+)\]\S* pad\(", text)]


def test_minimod_one_chip_program(topo):
    """Minimod's one-chip cell (256 × 1024² f32): the step is the
    streamed kernel, with no pass that pads the whole field, inside the
    kernel's VMEM limit."""
    text = _minimod_program(topo.devices[:1])
    assert "tpu_custom_call" in text and "%stencil_step" in text
    assert not [n for n in _pad_sizes(text) if n >= 256 * 1024 * 1024]
    assert _kernel_vmem(text, "stencil_step") <= VMEM_LIMIT_BYTES


# -- four chips: the fused one-sided kernels ----------------------------------

def test_fused_ring_matmul_stablelm_mlp(ring4):
    """All-gather matmul at the stablelm MLP: 4096 tokens, K 2560, N 6912
    split over 4 — the size chip_smoke.py --chips 4 runs."""
    T, K, N = 4096, 2560, 6912
    plan = RingPlan(n=4)
    assert fused_ring_resident_bytes(T // 4, K, N // 4, BF16, plan) \
        <= VMEM_LIMIT_BYTES

    def f(x, w):
        return jax.shard_map(
            lambda a, b: fused_ring_allgather_matmul_tpu(a, b, axis="x",
                                                         plan=plan),
            mesh=ring4, in_specs=(P("x"), P(None, "x")),
            out_specs=P(None, "x"))(x, w)

    _compile(f, _struct((T, K), BF16, NamedSharding(ring4, P("x"))),
             _struct((K, N), BF16, NamedSharding(ring4, P(None, "x"))))


def test_fused_stencil_step(ring4):
    """The single-step split (no carried halos) over a Z ring of four,
    4·64 x 128 x 128 f32: the puts are collective-permutes started before
    the interior planes, which the streamed kernel computes."""
    plan = OverlapPlanner().plan_halo_slots(64, 128, 128, F32, 4)
    assert plan.overlap
    mesh = Mesh(np.array(ring4.devices).reshape(4, 1), ("z", "y"))
    spec = NamedSharding(mesh, P("z", "y"))
    zg = DiompGroup(("z",), name="z")

    def f(u, up):
        return jax.shard_map(
            lambda a, b: stencil_fused.fused_wave_step(
                a, b, 0.1, zg, plan=plan, interpret=False),
            mesh=mesh, in_specs=(P("z", "y"), P("z", "y")),
            out_specs=P("z", "y"))(u, up)

    g = _struct((256, 128, 128), F32, spec)
    with use_default(DiompContext(mesh=mesh)):
        text = _compile(f, g, g).as_text()
    assert "%stencil_interior" in text
    assert "collective-permute-start" in text


def test_minimod_four_chip_program(topo):
    """Minimod's whole 1024³ over four chips, carried halos: the interior
    planes are the streamed kernel's, and the halo puts are still the
    collective-permute pairs the exchange issues."""
    text = _minimod_program(topo.devices[:4])
    assert "tpu_custom_call" in text and "%stencil_interior" in text
    assert "collective-permute-start" in text
    assert "collective-permute-done" in text
    assert _kernel_vmem(text, "stencil_interior") <= VMEM_LIMIT_BYTES


def test_fused_moe_dispatch(ring4):
    """Dropless expert-parallel dispatch: 8 experts over 4 chips, top-2,
    128 tokens per chip, d 256, f 512."""
    t_loc, d, f, E, k = 128, 256, 512, 8, 2
    plan = OverlapPlanner().plan_alltoall(t_loc, d, k, E, 4, BF16)
    group = DiompGroup(("x",), name="ep")
    tok = NamedSharding(ring4, P("x"))

    def fn(toks, top_e, top_w, wg, wu, wd):
        def body(*a):
            out, _ = fused_moe_dispatch_tpu(*a, group, plan=plan)
            return out
        return jax.shard_map(body, mesh=ring4, in_specs=(P("x"),) * 6,
                             out_specs=P("x"))(
            toks, top_e, top_w, wg, wu, wd)

    _compile(fn, _struct((4 * t_loc, d), BF16, tok),
             _struct((4 * t_loc, k), jnp.int32, tok),
             _struct((4 * t_loc, k), F32, tok),
             _struct((E, d, f), BF16, tok), _struct((E, d, f), BF16, tok),
             _struct((E, f, d), BF16, tok))


def test_fused_ring_attention_stablelm_heads(ring4):
    """Causal sequence-parallel attention at stablelm-3b's heads (32 x 80,
    head dim padded to 128 lanes in the kernel), 4·256 tokens, bf16."""
    T, H, D = 4 * 256, 32, 80
    plan = OverlapPlanner().plan_ring_attention(1, T // 4, T // 4, H, H, D,
                                                D, BF16, 4, causal=True)
    assert fused_ring_attention_resident_bytes(plan, BF16) <= VMEM_LIMIT_BYTES

    def f(q, k, v):
        return jax.shard_map(
            lambda a, b, c: fused_ring_attention_tpu(a, b, c, axis="x",
                                                     plan=plan),
            mesh=ring4, in_specs=(P(None, "x"),) * 3,
            out_specs=P(None, "x"))(q, k, v)

    g = _struct((1, T, H, D), BF16, NamedSharding(ring4, P(None, "x")))
    _compile(f, g, g, g)


# -- one chip: the serving decode program ---------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def _hlo_instructions(text):
    """(computation, name, result type, opcode, operand names) of every
    instruction of a compiled module's text."""
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = ("ENTRY " if line.startswith("ENTRY") else "") + head[1]
            continue
        m = _INSTR.match(line)
        if m and comp:
            yield (comp, m[1], m[2], m[3],
                   re.findall(r"%[\w.\-]+", m[4].split("),")[0]))


def _bf16_sizes(result_type):
    return [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"bf16\[([\d,]*)\]", result_type)]


@pytest.mark.parametrize("B,S", [(8, 512), (4, 2048)])
def test_decode_keeps_cache_in_place(topo, B, S):
    """The serving engine's decode program (undonated cache, per-slot
    positions) at stablelm-3b widths reads each layer's K/V where it lies
    and writes only the new rows: no scheduled op inside the program's
    loops yields a whole layer of K or V (B·S·KH·D bf16), and no
    dynamic-update-slice writes a whole layer back into the stacked cache.
    The entry's one copy of the undonated input is allowed."""
    cfg = configs.get("stablelm-3b")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    ctx = ParallelCtx.from_mesh(mesh, remat=False, inference=True)
    step = build_decode_step(cfg, mesh, ctx, B=B, S=S, donate=False,
                             slot_pos=True)
    rep = NamedSharding(mesh, P())
    params = {k: _struct(v.shape, v.dtype, rep)
              for k, v in schema.param_structs(cfg).items()}
    structs, _ = model_api.cache_structs(cfg, mesh, ctx, B, S)
    cache = {k: _struct(v.shape, v.dtype, rep) for k, v in structs.items()}
    cache["pos"] = _struct((B,), jnp.int32, rep)
    text = step.lower(params, _struct((B, 1), jnp.int32, rep),
                      cache).compile().as_text()

    layer = B * S * cfg.kv_heads * cfg.head_dim
    instrs = list(_hlo_instructions(text))
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    fused |= set(re.findall(r"to_apply=(%[\w.\-]+)", text))
    whole_layer = [(c, n, op) for c, n, t, op, _ in instrs
                   if not c.startswith("ENTRY") and c not in fused
                   and layer in _bf16_sizes(t)]
    assert not whole_layer, whole_layer

    result = {n: t for _, n, t, _, _ in instrs}
    rewrites = [(c, n) for c, n, t, op, args in instrs
                if op == "dynamic-update-slice" and len(args) > 1
                and layer in _bf16_sizes(result.get(args[1], ""))]
    assert not rewrites, rewrites
    # the text was read: the entry takes the stacked K and V
    assert sum(c.startswith("ENTRY") and op == "parameter"
               and _bf16_sizes(t) == [cfg.num_layers * layer]
               for c, _, t, op, _ in instrs) == 2
