"""The reduction from a profiler trace to busy time, program time and the
breakdown, on a small synthetic trace and on one recorded on the CPU."""
from types import SimpleNamespace as NS

from pytest import approx

import devtrace


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_decode_step(7)", 0, 100),
                                       _ev("jit_decode_step(7)", 300, 100),
                                       _ev("jit__route_wave(3)", 600, 50)]),
        NS(name="XLA Ops", events=[_ev("%fusion.1 = bf16[8] fusion(..)", 0, 60),
                                   _ev("%fusion.2 = f32[] fusion(..)", 40, 60),
                                   _ev("%fusion.1 = bf16[8] fusion(..)", 300,
                                       100),
                                   _ev("%while.3 = (u32[]) while(..)", 600,
                                       50)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.engine_step", 0, 420),
        _ev("bench.hooks", 420, 170),
        _ev("PjitFunction(x)", 10, 5)])])
    return [NS(name="/host:metadata", lines=[]), dev, host]


def test_reduce_planes():
    s = devtrace.reduce_planes(_planes())
    assert s["chips"] == 1
    assert abs(s["busy_s"] - 250e-9) < 1e-15         # 0-100, 300-400, 600-650
    assert devtrace.module_seconds(s, "decode_step") == (approx(200e-9), 2)
    assert devtrace.module_seconds(s, "route_wave") == (approx(50e-9), 1)
    assert s["ops"][0] == ["jit_decode_step/fusion.1", approx(160e-9)]
    assert s["ops"][-1] == ["jit__route_wave/while.3", approx(50e-9)]
    # gaps 100-300 (inside engine_step) and 400-600 (midpoint in hooks)
    assert s["gaps"] == [["engine_step", approx(200e-9)],
                         ["hooks", approx(200e-9)]]


def test_no_device_plane_reads_zero_busy():
    s = devtrace.reduce_planes(_planes()[2:])
    assert s["busy_s"] == 0.0 and s["chips"] == 0 and s["gaps"] == []


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    w = devtrace.Window(str(tmp_path / "t"), 60.0)
    w.start()
    for _ in range(3):
        with devtrace.annotate(True, "step"):
            f(x).block_until_ready()
    w.stop()
    s = devtrace.reduce(w.log_dir, w.window_s)
    assert s["window_s"] == w.window_s > 0
    assert not (tmp_path / "t").exists()             # the trace is removed
    assert s["chips"] == 0                           # the CPU is no device
