"""The reduction from a profiler trace to busy time, idle share, top
operations and labelled idle gaps, and the compile counter."""

import os

import jax
import jax.numpy as jnp
import pytest

import bench_paths  # noqa: F401  (puts bench/ on the path)
import traces

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_synthetic_events(monkeypatch):
    devices = {"/device:TPU:0": [(0, 10, "a"), (5, 20, "b"), (30, 40, "a"),
                                 (150, 160, "a")]}
    spans = [(0, 100, "window"), (20, 30, "poll"), (25, 28, "submit"),
             (45, 100, "build_index")]
    monkeypatch.setattr(traces, "read_events", lambda path: (devices, spans))
    r = traces.reduce_trace("unused")
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["device_ops"] == [["a", pytest.approx(20e-9)],
                               ["b", pytest.approx(15e-9)]]
    assert r["idle_gaps"] == [["build_index", pytest.approx(60e-9)],
                              ["submit", pytest.approx(10e-9)]]


def test_busy_time_averages_over_devices(monkeypatch):
    devices = {"/device:TPU:0": [(0, 50, "x")],
               "/device:TPU:1": [(0, 10, "x"), (5, 30, "y")]}
    monkeypatch.setattr(traces, "read_events",
                        lambda path: (devices, [(0, 100, "window")]))
    r = traces.reduce_trace("unused")
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["device_ops"][0] == ["x", pytest.approx(60e-9)]


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a TPU v5 lite: three sorts inside ``bench.poll``
    spans, inside ``bench.window``."""
    path = os.path.join(DATA, "tpu_probe.xplane.pb")
    assert os.path.getsize(path) < 1 << 20
    devices, spans = traces.read_events(path)
    assert list(devices) == ["/device:TPU:0"]
    assert sum(s[2] == "poll" for s in spans) == 3
    r = traces.reduce_trace(path)
    window = [s for s in spans if s[2] == "window"][0]
    assert r["window_s"] == pytest.approx((window[1] - window[0]) / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    assert r["busy_s"] == pytest.approx(
        sum(d for _, d in r["device_ops"]), rel=1e-6)
    assert {g[0] for g in r["idle_gaps"]} <= {"poll", "idle"}
    # the reduction of this file, pinned
    assert r["busy_s"] == pytest.approx(0.000131268, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.033623985, rel=1e-9)
    assert r["device_ops"][0][0].startswith("%sort.6 sort (f32[512,512]")


def test_compile_counter_counts_only_new_programs():
    counter = traces.CompileCounter()
    f = jax.jit(lambda x: x * 3 + 1)
    x5, x7, x9 = jnp.ones(5), jnp.ones(7), jnp.ones(9)
    f(x5).block_until_ready()
    with counter.counting():
        f(x5).block_until_ready()
    assert counter.count == 0
    with counter.counting():
        f(x7).block_until_ready()
    assert counter.count == 1
    f(x9).block_until_ready()
    assert counter.count == 1
