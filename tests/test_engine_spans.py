"""The serving engine's phase spans (repro.core.spans) and the names its
programs carry: spans nest inside ``engine.step``, count what the engine
did, sit on the perf_counter clock and in a profiler trace, stay bounded,
and cost nothing to turn off; the decode program is ``jit_serve_decode``
with its layers scoped and its compiled HLO otherwise the same."""

import contextlib
import glob
import os
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import spans as SP
from repro.core.compat import make_mesh
from repro.models import api as model_api
from repro.models import schema as sch
from repro.models.config import ParallelCtx
from repro.serve.engine import ServeEngine
from repro.serve.step import build_decode_step

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CFG = configs.get_reduced("stablelm-3b")
PHASES = ("engine.schedule", "engine.prefill.chunk", "engine.prefill.wait",
          "engine.decode.prepare", "engine.decode.call",
          "engine.decode.sample")
LENGTHS = (5, 13, 20, 9)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"), axis_types="auto")


@pytest.fixture(scope="module")
def params():
    return sch.init_params(CFG, jax.random.PRNGKey(0))


def _engine(mesh, params, rec, **kw):
    ctx = ParallelCtx.from_mesh(mesh, remat=False, inference=True)
    kw = {"slots": 2, "max_len": 64, "prefill_chunk": 8, **kw}
    return ServeEngine(CFG, mesh, ctx, params, spans=rec, **kw)


def _serve(eng, lengths=LENGTHS, max_new=4):
    rng = np.random.RandomState(3)
    reqs = [eng.submit(rng.randint(0, CFG.vocab_size, size=n)
                       .astype(np.int32), max_new=max_new) for n in lengths]
    t0 = time.perf_counter()
    eng.run()
    t1 = time.perf_counter()
    assert all(r.done for r in reqs)
    return reqs, t0, t1


def _named(rec, name):
    return [s for s in rec.items if s[0] == name]


def test_phase_spans_nest_count_and_clock(mesh, params):
    rec = SP.Recorder()
    eng = _engine(mesh, params, rec)
    reqs, t0, t1 = _serve(eng)
    steps = _named(rec, "engine.step")
    assert len(steps) == eng.steps
    engine = [s for s in rec.items if s[0].startswith("engine.")]
    for name, a, b in engine:
        assert t0 <= a <= b <= t1, (name, a, b)
        if name in PHASES:
            assert any(s <= a and b <= e for _, s, e in steps), name
    prefill = sum(r.prefill_steps for r in reqs)
    assert len(_named(rec, "engine.prefill.chunk")) == prefill
    assert len(_named(rec, "engine.prefill.wait")) == len(reqs)
    assert len(_named(rec, "engine.decode.call")) \
        == eng.device_calls - prefill
    assert len(_named(rec, "engine.decode.sample")) \
        == eng.device_calls - prefill
    assert rec.counters()["engine.pos_uploads"] >= 2 * (
        eng.device_calls - prefill)
    host = rec.exclusive("engine.step",
                         ("engine.decode.call", "engine.prefill.wait"),
                         t0, t1)
    assert len(host) == len(steps)
    assert all(0 < h <= b - a for h, (_, a, b) in zip(host, steps))


def test_compile_spans_in_the_first_step_only(mesh, params):
    rec = SP.Recorder()
    eng = _engine(mesh, params, rec, slots=3, max_len=48, prefill_chunk=4)
    _, t0, t1 = _serve(eng, lengths=(6, 11))
    compiles = [s for s in rec.between(t0, t1) if s[0] == SP.COMPILE_SPAN]
    _, a, b = _named(rec, "engine.step")[0]
    assert compiles and any(a <= c and d <= b for _, c, d in compiles)
    _, t0, t1 = _serve(eng, lengths=(6, 11))
    assert not [s for s in rec.between(t0, t1) if s[0] == SP.COMPILE_SPAN]


def test_ring_stays_at_maxlen(mesh, params):
    rec = SP.Recorder(maxlen=16)
    eng = _engine(mesh, params, rec)
    _serve(eng)
    assert len(rec.items) == 16
    assert rec.items[-1][0] == "engine.step"


def test_disabled_records_nothing_and_serves_the_same(mesh, params):
    on = SP.Recorder()
    served = [r.out for r in _serve(_engine(mesh, params, on))[0]]
    off = SP.Recorder()
    SP.set_enabled(False)
    try:
        again = [r.out for r in _serve(_engine(mesh, params, off))[0]]
    finally:
        SP.set_enabled(True)
    assert on.items and on.counters()
    assert not off.items and not off.counters()
    assert again == served


def test_spans_in_a_profiler_trace(mesh, params, tmp_path):
    """A CPU profiler capture around ``run()``, read as the benchmark reads
    a trace, with the CPU client's threads standing in for the device."""
    from chipbench import trace as TR

    rec = SP.Recorder()
    eng = _engine(mesh, params, rec)
    _serve(eng)                                  # compile outside the trace
    steps0 = eng.steps
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    got = TR.read_xplane(path, device_plane=re.compile(r"^/host:(CPU)$"),
                         op_line=re.compile(r"^tf_XLA"),
                         host_prefix="engine.")
    names = {s.name for s in got.host_spans}
    assert names == {"engine.step", *PHASES}
    assert sum(s.name == "engine.step" for s in got.host_spans) \
        == eng.steps - steps0
    ops = [e for evs in got.device_ops.values() for e in evs]
    calls = [s for s in got.host_spans if s.name == "engine.decode.call"]
    assert calls and ops
    for c in calls:
        assert any(e.start < c.end and c.start < e.end for e in ops), c


def _compiled_decode(mesh, params):
    ctx = ParallelCtx.from_mesh(mesh, remat=False, inference=True)
    structs, _ = model_api.cache_structs(CFG, mesh, ctx, 2, 32)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), structs)
    cache["pos"] = jnp.zeros((2,), jnp.int32)
    step = build_decode_step(CFG, mesh, ctx, B=2, S=32, donate=False,
                             slot_pos=True)
    lowered = step.lower(params, jnp.zeros((2, 1), jnp.int32), cache)
    return lowered, lowered.compile().as_text()


def _program(text):
    """A compiled module without its name, debug tables and metadata, and
    with its instructions renumbered in order of appearance."""
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    blocks = [b for b in text.split("\n\n")[1:]
              if b.strip() and b.strip().split("\n")[0] not in tables]
    body = re.sub(r", metadata=\{[^}]*\}", "", "\n\n".join(blocks))
    names = {}
    return re.sub(r"[A-Za-z_][\w\-]*(\.\d+)+",
                  lambda m: names.setdefault(m.group(0), f"n{len(names)}"),
                  body)


def test_decode_program_named_and_scoped(mesh, params, monkeypatch):
    lowered, compiled = _compiled_decode(mesh, params)
    assert lowered.as_text().startswith("module @jit_serve_decode")
    scopes = set(re.findall(r'op_name="([^"]*)"', compiled))
    for scope in ("/attention/", "/attention/kv_write/", "/mlp/",
                  "/lm_head/"):
        assert any(scope in s for s in scopes), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, plain = _compiled_decode(mesh, params)
    assert "/attention/" not in plain
    assert _program(compiled) == _program(plain)
