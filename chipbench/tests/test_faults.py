"""Whole runs on the CPU at a small size, past the look for a chip, with
the timed path broken underneath: each fault must make ``correct`` come
out false, and the sound run true.  The limits are the cells' own."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import run as R
import chipbench.runtime as runtime
from chipbench.tests.tiny import make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(runtime, "enable_compile_cache", lambda root: "off")


def _run(root, workload, seed=2**31 + 5):
    res = R.execute(workload, seed, 1.0, False, root=root, platform="cpu")
    assert res["attempted"] > 0
    return res


# -- serving -----------------------------------------------------------------

def _alter_token(mp):
    from repro.serve import engine

    orig = engine.ServeEngine._sample
    mp.setattr(engine.ServeEngine, "_sample",
               lambda self, req, row: (orig(self, req, row) + 1) % len(row))


def _decode_keeps_state(mp):
    from repro.serve import engine

    orig = engine.build_decode_step

    def build(*a, **kw):
        step = orig(*a, **kw)
        return lambda p, t, cache: (step(p, t, cache)[0], cache)

    mp.setattr(engine, "build_decode_step", build)


def _decode_drops_half_the_batch(mp):
    from repro.serve import engine

    orig = engine.build_decode_step

    def build(*a, **kw):
        step = orig(*a, **kw)

        def half(p, t, cache):
            logits, cache = step(p, t, cache)
            B = logits.shape[0]
            keep = logits[: B - B // 2]
            return jnp.concatenate([keep, keep[: B // 2]]), cache
        return half

    mp.setattr(engine, "build_decode_step", build)


def test_serving_sound_run_is_correct(root):
    res = _run(root, "serve-stablelm-3b-short")
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_alter_token, _decode_keeps_state,
                                   _decode_drops_half_the_batch])
def test_serving_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(root, "serve-stablelm-3b-short")
    assert not res["correct"], res["checks"]


# -- Minimod -----------------------------------------------------------------

def _step_keeps_state(mp):
    from repro.kernels.stencil import fused

    mp.setattr(fused, "fused_wave_step",
               lambda u, *a, return_halos=False, halos=None, **kw:
               (u, halos) if return_halos else u)


def _step_drops_half_the_field(mp):
    from repro.kernels.stencil import fused

    orig = fused.fused_wave_step

    def half(*a, **kw):
        out = orig(*a, **kw)
        u = out[0] if isinstance(out, tuple) else out
        u = u.at[: u.shape[0] // 2].set(0)
        return (u,) + tuple(out[1:]) if isinstance(out, tuple) else u

    mp.setattr(fused, "fused_wave_step", half)


def _answer_altered(mp):
    from repro.kernels.stencil import fused

    orig = fused.fused_wave_step

    def alter(*a, **kw):
        out = orig(*a, **kw)
        u = out[0] if isinstance(out, tuple) else out
        u = u.at[1, 2, 3].add(1.0)
        return (u,) + tuple(out[1:]) if isinstance(out, tuple) else u

    mp.setattr(fused, "fused_wave_step", alter)


def _exchange_left_out(mp):
    from repro.kernels.stencil import fused

    mp.setattr(fused, "ompx_put", lambda x, group, shift: jnp.zeros_like(x))


@pytest.mark.parametrize("cell", ["minimod-1024-1chip", "minimod-1024-4chip"])
def test_minimod_sound_run_is_correct(root, cell):
    if cell.endswith("4chip") and len(jax.devices()) < 4:
        pytest.skip("needs 4 CPU devices")
    res = _run(root, cell)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("cell,fault", [
    ("minimod-1024-1chip", _step_keeps_state),
    ("minimod-1024-1chip", _step_drops_half_the_field),
    ("minimod-1024-1chip", _answer_altered),
    ("minimod-1024-4chip", _step_keeps_state),
    ("minimod-1024-4chip", _exchange_left_out),
])
def test_minimod_fault_is_not_correct(root, monkeypatch, cell, fault):
    if cell.endswith("4chip") and len(jax.devices()) < 4:
        pytest.skip("needs 4 CPU devices")
    fault(monkeypatch)
    res = _run(root, cell)
    assert not res["correct"], res["checks"]
