"""The trace reduction: busy/idle union, device time per step by phase, and
idle time by what the host was doing — on plain data, and on a small trace
recorded on a TPU v5e and committed with the benchmark
(``tests/bench/data/small.xplane.pb``, made by ``record_trace.py``)."""
from __future__ import annotations

import os

import pytest

from bench.harness import trace
from bench.harness.trace import Span

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def test_bench_union_and_gaps():
    iv = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (0.0, 0.5)]
    assert trace.union_length(iv, 1.0, 10.0) == pytest.approx(3.0)
    assert trace.union_length(iv, 0.0, 5.5) == pytest.approx(3.0)
    assert trace.idle_gaps(iv, 0.0, 7.0) == [
        (0.5, 1.0), (3.0, 5.0), (6.0, 7.0)]
    assert trace.union_length([], 0.0, 1.0) == 0.0


def test_bench_reduce_events_attributes_programs_to_steps():
    spans = [Span("bench.window", 10.0, 20.0),
             Span("bench.step.prefill", 9.0, 10.5),     # starts before
             Span("bench.step.decode", 11.0, 13.0),
             Span("bench.kv.materialize", 11.0, 11.8),
             Span("bench.step.prefill", 14.0, 17.0),
             Span("bench.kv.harvest", 16.0, 17.0)]
    dev = {
        trace.MODULES_LINE: [("jit_serve_step", 9.5, 10.2),
                             ("jit_serve_step", 12.0, 12.5),
                             ("jit_serve_step", 14.5, 15.5),
                             ("jit_other", 18.0, 18.5)],
        trace.OPS_LINE: [("fusion.1", 12.0, 12.3), ("fusion.2", 12.3, 12.5),
                         ("fusion.1", 14.5, 15.5), ("copy", 18.0, 18.5)],
    }
    out = trace.reduce_events(spans, [dev])
    assert out["window_s"] == pytest.approx(10.0)
    assert out["busy_s"] == pytest.approx(2.0)
    assert out["phase_steps"] == {"decode": 1, "prefill": 1}
    assert out["phase_device_s"] == pytest.approx({"decode": 0.5,
                                                   "prefill": 1.0})
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(1.3)]
    idle = dict(out["idle_gaps"])
    # 10-12: materialize 11-11.8 holds the middle of the 10-12 gap
    assert idle["kv.materialize"] == pytest.approx(2.0)
    assert idle["kv.harvest"] == pytest.approx(2.5)     # 15.5-18 gap
    # 12.5-14.5 falls between the decode and prefill spans, 18.5-20 after
    assert idle["between steps"] == pytest.approx(3.5)
    assert sum(idle.values()) == pytest.approx(8.0)


def test_bench_op_names_drop_shapes():
    text = ("%fusion.155 = (f32[4,2,2]{2,1,0:T(2,128)S(1)}, bf16[4]{0}) "
            "fusion(bf16[4,2,2,16]{3,2,1,0:T(2,128)(2,1)S(1)} %b), "
            "kind=kOutput")
    assert trace.op_name(text) == "%fusion.155 fusion"
    assert trace.op_name("%copy-start.9 = s32[4]{0} copy-start(s32[4] %p)") \
        == "%copy-start.9 copy-start"
    assert trace.op_name("jit_serve_step(91)") == "jit_serve_step(91)"


def test_bench_reduce_needs_one_window():
    with pytest.raises(ValueError):
        trace.reduce_events([], [{}])


def test_bench_recorded_tpu_trace():
    out = trace.reduce_file(DATA)
    assert out["window_s"] > 0
    assert 0 < out["busy_s"] <= out["window_s"]
    steps = out["phase_steps"]
    assert sum(steps.values()) > 0
    for phase, n in steps.items():
        # every step span holds one program execution of the step
        assert 0 < out["phase_device_s"][phase] < out["window_s"]
    assert out["device_ops"] and out["idle_gaps"]
    idle = sum(s for _, s in out["idle_gaps"])
    assert idle <= out["window_s"] - out["busy_s"] + 1e-9
