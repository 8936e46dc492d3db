"""The readers of the engine's own spans, on a hand-made recorder and
window: each reads its spans inside the window, and nothing outside it."""

import pytest

from chipbench import spec as S
from chipbench.runtime import Window
from repro.core import spans as SP


@pytest.fixture
def rec(monkeypatch):
    """Three engine steps at 10, 20 and 30 s, 4, 6 and 8 ms long; each
    waits 1 ms on a prefill and spends k ms in its decode call and
    k / 2 ms sampling."""
    r = SP.Recorder()
    for t, k in ((10.0, 1.0), (20.0, 2.0), (30.0, 3.0)):
        ms = 1e-3
        r.items.append(("engine.prefill.wait", t + 0.5 * ms, t + 1.5 * ms))
        r.items.append(("engine.decode.call", t + 2 * ms, t + (2 + k) * ms))
        r.items.append(("engine.decode.sample", t + (2 + k) * ms,
                        t + (2 + 1.5 * k) * ms))
        r.items.append(("engine.step", t, t + (2 + 2 * k) * ms))
    r.items.append((SP.COMPILE_SPAN, 29.0, 30.0))
    monkeypatch.setattr(SP, "recorder", lambda: r)
    return r


def _read(name, t0, t1):
    return S.reader(S.load_spec(S.ROOT), name).read(
        Window(setup_s=0.0, t0=t0, t1=t1))


@pytest.mark.parametrize("name", ["serve.host_ms_per_step.short",
                                  "serve.host_ms_per_step.long"])
def test_host_ms_per_step(rec, name):
    # step less decode call and prefill wait: 4-1-1, 6-2-1, 8-3-1 ms
    assert _read(name, 0.0, 40.0) == pytest.approx(3.0)
    assert _read(name, 15.0, 40.0) == pytest.approx(3.5)
    assert _read(name, 40.0, 50.0) is None


def test_decode_program_ms(rec):
    assert _read("serve.decode_program_ms.short", 0.0, 40.0) \
        == pytest.approx(2.0)
    assert _read("serve.decode_program_ms.short", 25.0, 40.0) \
        == pytest.approx(3.0)
    assert _read("serve.decode_program_ms.short", 40.0, 50.0) is None


def test_sample_ms_per_step(rec):
    assert _read("serve.sample_ms_per_step.short", 0.0, 40.0) \
        == pytest.approx(1.0)
    assert _read("serve.sample_ms_per_step.short", 0.0, 15.0) \
        == pytest.approx(0.5)
    assert _read("serve.sample_ms_per_step.short", 40.0, 50.0) is None
