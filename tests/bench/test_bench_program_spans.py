"""The program's spans in the benchmark: the per-step sums the seven readers
take, on plain data, on the small trace recorded on a TPU v5e with the
program's spans (``small_spans.xplane.pb``, made by ``record_trace.py``)
and on a run of the harness at the reduced preset on the CPU."""
from __future__ import annotations

import os
import time
import types

import pytest
from jax.profiler import ProfileData

from bench.harness import program_spans, serve, spec, trace
from conftest import reduced_config, small_traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("kv_wait_ms_per_step", "kv_transfer_ms_per_step",
           "kv_copy_ms_per_step", "kv_mb_per_step", "dispatch_ms_per_step",
           "sample_ms_per_step", "control_ms_per_step")


def test_bench_per_step_sums_spans_held_by_window_steps():
    spans = [("serve.step", 0.0, 1.0, {"step": 0}),
             ("kv.upload", 0.1, 0.2, {"bytes": 1000}),    # before the window
             ("serve.step", 1.0, 2.0, {"step": 1}),
             ("kv.upload", 1.1, 1.2, {"bytes": 100}),
             ("kv.wait", 1.5, 2.5, None),                 # not held whole
             ("serve.step", 2.0, 3.0, {"step": 2}),
             ("kv.upload", 2.1, 2.3, {"bytes": 300}),
             ("kv.download", 2.4, 2.5, {"bytes": 50}),
             ("serve.step", 3.0, 4.0, {"step": 3})]       # after the window
    w = program_spans.per_step(spans, 0.5, 3.0)
    assert w.steps == 2
    assert w.ms_per_step("kv.upload") == pytest.approx(150.0)
    assert w.ms_per_step("kv.upload", "kv.download") == pytest.approx(200.0)
    assert w.mb_per_step("kv.upload", "kv.download") == pytest.approx(
        450 / 2 / 1e6)
    assert "kv.wait" not in w.seconds
    assert w.ms_per_step("kv.gather") is None
    assert program_spans.per_step(spans, 4.0, 5.0) is None


def _fake_run(monkeypatch, spans, t0=0.5, t1=3.0, ring=1 << 16):
    from repro.core import telemetry

    monkeypatch.setattr(telemetry, "recent_spans", lambda: list(spans))
    monkeypatch.setattr(telemetry, "SPAN_RING_SIZE", ring)
    return types.SimpleNamespace(t0=t0, t1=t1)


def test_bench_readers_read_the_span_ring(monkeypatch):
    # as the ring holds them: a span is kept when it ends
    spans = [("kv.gather", 1.0, 1.1, None),
             ("kv.upload", 1.1, 1.3, {"bytes": 8}),
             ("serve.dispatch", 1.3, 1.34, None),
             ("kv.wait", 1.34, 1.74, None),
             ("kv.download", 1.74, 1.84, {"bytes": 2_000_000}),
             ("kv.scatter", 1.84, 1.9, None),
             ("serve.sample", 1.9, 1.92, None),
             ("serve.control", 1.92, 1.93, None),
             ("serve.step", 1.0, 2.0, None)]
    run = _fake_run(monkeypatch, spans)
    got = {m: spec.load_metric(m).read(run) for m in READERS}
    assert got == pytest.approx({
        "kv_wait_ms_per_step": 400.0, "kv_transfer_ms_per_step": 300.0,
        "kv_copy_ms_per_step": 160.0, "kv_mb_per_step": 2.000008,
        "dispatch_ms_per_step": 40.0, "sample_ms_per_step": 20.0,
        "control_ms_per_step": 10.0})


def test_bench_readers_fall_silent_without_program_spans(monkeypatch):
    from repro.core import telemetry

    run = _fake_run(monkeypatch, [])
    assert all(spec.load_metric(m).read(run) is None for m in READERS)
    # a program that keeps no span ring at all
    monkeypatch.delattr(telemetry, "recent_spans")
    assert all(spec.load_metric(m).read(run) is None for m in READERS)


def test_bench_ring_that_dropped_the_window_start_counts_whole_steps(
        monkeypatch):
    # a full ring lost part of the first step: only the steps that start
    # after its oldest kept span ended count
    spans = [("kv.wait", 1.5, 1.9, None), ("serve.step", 1.0, 2.0, None),
             ("kv.wait", 2.1, 2.3, None), ("serve.step", 2.0, 2.9, None)]
    read = spec.load_metric("kv_wait_ms_per_step").read
    run = _fake_run(monkeypatch, spans, t0=0.0, t1=5.0, ring=4)
    assert read(run) == pytest.approx(200.0)
    run = _fake_run(monkeypatch, spans, t0=0.0, t1=5.0, ring=5)
    assert read(run) == pytest.approx(300.0)


def _traced_program_spans(path):
    """The ``iri.`` spans of the thread that holds ``bench.window``, as
    ``(name, start_s, end_s, None)`` with the prefix taken off."""
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(ev.name == trace.WINDOW_SPAN for ev in events):
                return [(ev.name[4:], ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, None)
                        for ev in events if ev.name.startswith("iri.")]
    return []


def test_bench_recorded_trace_with_program_spans():
    path = os.path.join(DATA, "small_spans.xplane.pb")
    host, _ = trace.read_xspace(path)
    program = _traced_program_spans(path)
    (win,) = [sp for sp in host if sp.name == trace.WINDOW_SPAN]
    w = program_spans.per_step(program, win.start, win.end)
    steps = [(s, e) for n, s, e, _ in program
             if n == program_spans.STEP and win.start <= s < win.end]
    assert steps and w.steps == len(steps)
    kv_names = ("kv.gather", "kv.upload", "kv.wait", "kv.download",
                "kv.scatter")
    for s, e in steps:
        held = {n for n, a, b, _ in program if s <= a and b <= e}
        assert set(kv_names) <= held
    # the inside of the KV calls against the harness's spans around them
    inner = sum(w.seconds[n] for n in kv_names)
    outer = sum(sp.end - sp.start for sp in host
                if sp.name.startswith("bench.kv.")
                and any(s <= sp.start and sp.end <= e for s, e in steps))
    assert inner <= outer
    assert inner == pytest.approx(outer, rel=0.05)


@pytest.mark.parametrize("name", ["qwen3-0.6b", "rwkv6-1.6b"])
def test_bench_readers_on_a_served_window(name, state_dir):
    cfg, model = reduced_config(name)
    run = serve.Run(cell=None, cfg=cfg, model=model,
                    traffic=small_traffic("backlog"), seed=2 ** 31 + 5,
                    seconds=2.0, t_process=time.perf_counter())
    serve.run_cell(run, state_dir=state_dir, reduced=True)
    got = {m: spec.load_metric(m).read(run) for m in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    w = program_spans.window(run)
    assert w.steps == len(run.window_steps())
    # the KV spans sit inside the harness's spans around the KV calls
    inner = (got["kv_wait_ms_per_step"] + got["kv_transfer_ms_per_step"]
             + got["kv_copy_ms_per_step"])
    assert inner <= spec.load_metric("kv_host_ms_per_step").read(run)
