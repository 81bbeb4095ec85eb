"""The program's spans: one API on the profiler's clock, the tree a serve
step opens, and the bytes the paged KV manager's upload spans carry (its
index tables and shared leaves; nothing comes down).

A small engine runs one prefill and one decode step under
``jax.profiler`` on the CPU; the trace's ``iri.`` annotations and the
in-process span ring must hold the same tree.
"""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import EventBus, IridescentRuntime, telemetry
from repro.serve import (AdmissionQueue, ContinuousBatcher, FCFS, PagedKV,
                         PhasedExecutor, Request, ServeEngine, ServeMetrics)
from repro.training import phase_context_fn

MAX_LEN = 16
VOCAB = 5


def _template():
    return {"k": jnp.zeros((1, MAX_LEN, 3), jnp.float32),
            "state": jnp.zeros((1, 2), jnp.float32),
            "tick": jnp.zeros((), jnp.int32)}


AXES = {"k": ("batch", "seq_kv", "model"), "state": ("batch", "model"),
        "tick": ()}


def _builder(spec):
    def f(params, cache, tokens, pos, n_new):
        logits = jnp.zeros((tokens.shape[0], VOCAB), jnp.float32)
        return logits, {"k": cache["k"] + 1.0, "state": cache["state"] + 1.0,
                        "tick": cache["tick"]}
    return f


def _engine():
    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("spans", _builder, context_fn=phase_context_fn)
    kv = PagedKV(_template(), AXES, max_len=MAX_LEN,
                 capacity_tokens=4 * MAX_LEN, page_size=4)
    executor = PhasedExecutor(handler, None, kv, prefill_chunk=4,
                              vocab_size=VOCAB)
    engine = ServeEngine(handler, None, ContinuousBatcher(2, scheme="single"),
                         FCFS(), executor=executor, queue=AdmissionQueue(),
                         metrics=ServeMetrics())
    return rt, engine, kv


def _serving_thread_spans(path):
    """``(name, start, end)`` of the ``iri.`` spans on the line (thread)
    that opened ``iri.serve.step``."""
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(ev.name[4:], ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith("iri.")]
            if any(n == "serve.step" for n, _, _ in evs):
                return evs
    return []


def _since(t0):
    """The ring's spans that started at or after ``t0`` (the ring is
    bounded, so an index into it is not stable)."""
    return [sp for sp in telemetry.recent_spans() if sp[1] >= t0]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_serve_step_span_tree_on_the_profiler_trace(tmp_path):
    t0 = time.perf_counter()
    rt, engine, kv = _engine()
    try:
        assert engine.submit(Request(prompt_tokens=3, max_new_tokens=8))
        engine.step()                       # prefill: compiles its program
        engine.step()                       # decode: compiles its program
        with jax.profiler.trace(str(tmp_path)):
            assert engine.submit(Request(prompt_tokens=2, max_new_tokens=4))
            for _ in range(4):              # the phases take turns
                engine.step()
    finally:
        rt.shutdown()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _serving_thread_spans(path)
    names = {n for n, _, _ in spans}
    assert names >= {"serve.step", "serve.exec.decode", "serve.exec.prefill",
                     "kv.gather", "kv.upload", "serve.dispatch", "kv.wait",
                     "kv.scatter", "serve.sample", "serve.control"}
    assert "kv.download" not in names
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == 4
    execs = [s for s in spans if s[0].startswith("serve.exec.")]
    assert len(execs) == 4
    assert all(any(_inside(ex, st) for st in steps) for ex in execs)
    for ex in (s for s in execs if s[0] == "serve.exec.decode"):
        for name in ("kv.gather", "kv.upload", "serve.dispatch", "kv.wait",
                     "kv.scatter", "serve.sample"):
            assert any(_inside(s, ex) for s in spans if s[0] == name), name
    for c in (s for s in spans if s[0] == "serve.control"):
        assert any(_inside(c, st) for st in steps)
        assert not any(_inside(c, ex) for ex in execs)
    # the ring kept the same steps
    ring = [n for n, *_ in _since(t0)]
    assert ring.count("serve.step") == 6


def test_decode_step_spans_in_the_order_of_the_work():
    rt, engine, kv = _engine()
    try:
        assert engine.submit(Request(prompt_tokens=2, max_new_tokens=3))
        engine.step()
        t0 = time.perf_counter()
        engine.step()                       # one decode step
    finally:
        rt.shutdown()
    # the ring is appended as spans end: children before their parents
    names = [n for n, *_ in _since(t0) if n != "compile.build"]      # the decode program's compile
    # materialize: the index table, its upload and the shared leaf's, the
    # gather; harvest: the wait, the scatter's table, its upload, the
    # scatter
    leaves = ["kv.gather", "kv.upload", "kv.upload", "kv.gather",
              "serve.dispatch", "kv.wait", "kv.scatter", "kv.upload",
              "kv.scatter", "serve.sample"]
    assert names[:len(leaves)] == leaves
    assert names[len(leaves):] == ["serve.exec.decode", "serve.control",
                                   "serve.step"]


def test_paged_kv_byte_counters_match_the_leaves():
    # the byte counts ride on the KV transfer spans as their ``bytes`` arg:
    # the pools stay on the device, so only the index tables and the
    # shared leaf go up, and nothing comes down
    kv = PagedKV(_template(), AXES, max_len=MAX_LEN,
                 capacity_tokens=4 * MAX_LEN, page_size=4)
    kv.join("a")
    kv.join("b")

    def moved(t0):
        spans = _since(t0)
        return {name: sum(a["bytes"] for n, _, _, a in spans if n == name)
                for name in ("kv.upload", "kv.download")}

    t0 = time.perf_counter()
    cache, _ = kv.materialize(["a", "b"], 4)
    leaves = jax.tree_util.tree_leaves(cache)
    dense = sum(int(np.asarray(x).nbytes) for x in leaves)
    # k (4, 16, 3) + state (4, 2) in float32, tick int32
    assert dense == 4 * 16 * 3 * 4 + 4 * 2 * 4 + 4
    # an int32 table of each row's 16 slots and row-state slot, and the
    # shared tick
    assert moved(t0) == {"kv.upload": 4 * (16 + 1) * 4 + 4, "kv.download": 0}
    # the table's build and the gather's dispatch
    assert [n for n, *_ in _since(t0)].count("kv.gather") == 2
    t0 = time.perf_counter()
    kv.harvest(["a", "b"], cache, [1, 1])
    # the scatter's table: each row's written slot, length and row-state
    # slot
    assert moved(t0) == {"kv.upload": 4 * (1 + 2) * 4, "kv.download": 0}
    # a harvest with no token written writes the row state alone
    cache, _ = kv.materialize(["a"], 1)
    t0 = time.perf_counter()
    kv.harvest(["a"], cache, [0])
    assert moved(t0) == {"kv.upload": 1 * 2 * 4, "kv.download": 0}


def test_bus_span_is_the_span_bound_to_that_bus(tmp_path):
    b = EventBus()
    t0 = time.perf_counter()
    with jax.profiler.trace(str(tmp_path)):
        with b.span("t.bound", track="x", k=1) as p:
            p["status"] = "done"
    (ev,) = b.events()
    assert ev["kind"] == "span" and ev["status"] == "done" and ev["k"] == 1
    (entry,) = _since(t0)
    assert entry[0] == "t.bound" and entry[3]["status"] == "done"
    assert ev["dur"] == pytest.approx((entry[2] - entry[1]) * 1e6)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for pl in ProfileData.from_file(path).planes
             for line in pl.lines for e in line.events}
    assert "iri.t.bound" in names


def test_module_span_emits_on_the_process_bus_only_when_on():
    t0 = time.perf_counter()
    with telemetry.span("t.off"):
        pass
    b = telemetry.enable()
    try:
        with telemetry.span("t.on", track=("decode", 8), rows=3):
            pass
        evs = [e for e in b.events() if e["name"].startswith("t.")]
    finally:
        telemetry.disable()
    assert [e["name"] for e in evs] == ["t.on"]
    assert evs[0]["rows"] == 3 and evs[0]["track"] == repr(("decode", 8))
    assert [n for n, *_ in _since(t0)] == ["t.off", "t.on"]


def test_span_closes_when_its_block_raises():
    b = EventBus()
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with b.span("t.raises", rows=2):
            raise ValueError("step failed")
    (ev,) = b.events()
    assert ev["name"] == "t.raises" and ev["rows"] == 2
    assert [n for n, *_ in _since(t0)] == ["t.raises"]
    with telemetry.span("t.after"):         # the annotation stack is whole
        pass
    assert [n for n, *_ in _since(t0)] == ["t.raises", "t.after"]


def test_compile_build_span_in_a_worker_thread(tmp_path):
    rt = IridescentRuntime(async_compile=True)
    b = telemetry.enable()
    try:
        def builder(spec):
            k = spec.enum("k", 1, (1, 2))
            return lambda x: x * k

        h = rt.register("spans_build", builder)
        h(jnp.float32(2.0))
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("t.caller"):
                h.specialize({"k": 2}, wait=True)
        builds = [e for e in b.events() if e["name"] == "compile.build"]
    finally:
        telemetry.disable()
        rt.shutdown()
    assert builds and builds[-1]["status"] == "done"
    assert {"handler", "config", "cache_hit", "speculative", "wait_s",
            "compile_s", "build_s"} <= set(builds[-1])
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [{e.name for e in line.events}
             for pl in ProfileData.from_file(path).planes
             for line in pl.lines]
    (built,) = [ln for ln in lines if "iri.compile.build" in ln]
    assert "t.caller" not in built          # a compile worker's thread
