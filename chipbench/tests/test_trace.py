"""The trace reduction: busy union, op classes, exposed collective time
and gap attribution, on hand-made events and on a recorded CPU trace."""

import re
import time

import jax
import jax.numpy as jnp

from chipbench import trace as TR


def _ev(name, a, b):
    return TR.Event(name, a, b)


def test_interval_arithmetic():
    assert TR.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert TR.length(TR.clip([(0, 2), (3, 4)], 1, 3.5)) == 1.5
    assert TR.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                         (7, 10)]
    assert TR.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


def test_reduce_splits_busy_collective_and_idle():
    rec = TR.Recorded(
        device_ops={"0": [_ev("fusion.1", 1.0, 3.0),
                          _ev("collective-permute-done.2", 2.5, 4.0),
                          _ev("fusion.3", 6.0, 7.0)],
                    "1": [_ev("fusion.1", 1.0, 5.0)]},
        host_spans=[_ev(TR.WINDOW_SPAN, 0.0, 8.0),
                    _ev("bench.step", 0.0, 8.0),
                    _ev("bench.decode_call", 4.5, 6.4)])
    s = TR.reduce(rec)
    assert s.chips == 2 and s.window_s == 8.0
    assert s.busy_s == (4.0 + 4.0) / 2            # chip 0: [1,4]+[6,7]
    assert s.collective_s == 1.5 / 2
    assert s.exposed_collective_s == 1.0 / 2     # [3,4] on chip 0
    assert s.compute_s == (3.0 + 4.0) / 2
    gaps = dict(s.idle_gaps)
    # chip 0 idle [0,1] [4,6] [7,8]; chip 1 idle [0,1] [5,8]
    assert abs(gaps["bench.decode_call"] - 2.0 / 2) < 1e-9   # [4,6]
    assert abs(gaps["bench.step"] - 6.0 / 2) < 1e-9
    assert dict(s.device_ops)["fusion.1"] == (2.0 + 4.0) / 2


def test_reduce_without_window_or_ops_reads_nothing():
    assert TR.reduce(TR.Recorded({}, [_ev(TR.WINDOW_SPAN, 0, 1)])) is None
    assert TR.reduce(TR.Recorded({"0": [_ev("f", 0, 1)]}, [])) is None


def test_recorded_cpu_trace(tmp_path):
    """A real profiler trace of a jitted call on the CPU, read with the
    CPU client's threads standing in for the device."""
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    cap = TR.Capture(True, 0.0, 0.05)
    t0 = time.perf_counter()
    cap.poll(t0, t0)
    assert cap.active
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.call"):
            f(x).block_until_ready()
        time.sleep(0.02)
    cap.stop()
    s = cap.summary(device_plane=re.compile(r"^/host:(CPU)$"),
                    op_line=re.compile(r"^tf_XLA"))
    assert s is not None and s.chips == 1
    assert 0 < s.busy_s < s.window_s
    assert cap.overhead_s > 0
    names = [n for n, _ in s.idle_gaps]
    assert "bench.call" in names or "no_host_span" in names
    assert cap.dir is not None and not __import__("os").path.exists(cap.dir)


def test_op_labels_and_leaves():
    text = ("%fusion.39 = f32[256,1024]{1,0:T(8,128)} fusion(f32[256,1024]"
            "{1,0} %collective-permute-done.1), kind=kLoop")
    assert TR.op_label(text) == "%fusion.39 fusion"
    assert not TR.is_collective(TR.op_label(text))
    assert TR.is_collective(TR.op_label(
        "%collective-permute-done.1 = f32[4,8]{1,0} collective-permute-done("
        "(f32[4,8]{1,0}) %collective-permute-start.1)"))
    loop = _ev("%while.2 while", 0.0, 10.0)
    body = [_ev("%fusion.1 fusion", 1.0, 4.0),
            _ev("%collective-permute-done.3 collective-permute-done", 4.0,
                6.0), _ev("%copy.2 copy", 7.0, 9.0)]
    assert TR.leaves([loop] + body) == body
    s = TR.reduce(TR.Recorded({"0": [loop] + body},
                              [_ev(TR.WINDOW_SPAN, 0.0, 10.0)]))
    assert s.busy_s == 10.0 and s.exposed_collective_s == 2.0
    assert "%while.2 while" not in dict(s.device_ops)
