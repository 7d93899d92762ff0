"""The trace reduction: busy and idle shares, executables, named gaps."""

from __future__ import annotations

import pytest

from bench import trace_reduce


def test_busy_idle_and_modules_from_events():
    # one device: ops cover [0,2] and [3,4] (overlapping ops inside) of a
    # window [0,5] spanned by host spans "bench.a" [0,3.5] and "bench.b"
    # [3.5,5]
    ops = [[(0.0, 1.5, "fusion"), (1.0, 2.0, "fusion"), (3.0, 4.0, "dot")]]
    mods = [[(0.0, 2.0, "jit_core(12)"), (3.0, 4.0, "jit_core(12)")]]
    spans = [(0.0, 3.5, "bench.a"), (3.5, 5.0, "bench.b")]
    red = trace_reduce.reduce_events(ops, mods, spans)
    assert red.window_s == pytest.approx(5.0)
    assert red.busy_s == pytest.approx(3.0)
    assert red.idle_share == pytest.approx(0.4)
    assert red.module_time(r"(^|_)core$") == (pytest.approx(3.0), 2)
    assert red.ops == {"fusion": pytest.approx(2.5), "dot": pytest.approx(1.0)}
    # gaps [2,3] inside bench.a and [4,5] inside bench.b
    assert [(round(s, 9), n) for s, n in red.gaps] == [
        (1.0, "bench.b"), (1.0, "bench.a")]
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0] == ["fusion", pytest.approx(2.5)]
    assert len(bd["idle_gaps"]) == 2


def test_busy_is_averaged_over_devices():
    ops = [[(0.0, 1.0, "x")], [(0.0, 3.0, "x")]]
    red = trace_reduce.reduce_events(ops, ops, [(0.0, 4.0, "bench.w")])
    assert red.busy_s == pytest.approx(2.0)
    assert red.idle_share == pytest.approx(0.5)


def test_spans_from_a_profile_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    red = trace_reduce.reduce_xplane(str(path))
    assert len(red.spans["bench.step"]) == 3
    assert red.devices == 0  # the CPU has no device plane
    assert red.window_s >= sum(red.spans["bench.step"]) > 0
