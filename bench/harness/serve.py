"""Drive the serving engine through set-up, warm-up and a measured window.

The engine is the one users run: ``launch/serve.py``'s ``build_engine``,
with its Controller (the SafetyController with shadow evaluation), bucket
tuner and KV tuner at that file's defaults.  The benchmark supplies only
its inputs and its own stamps:

* weights made from ``--seed`` in one jitted call, in the serving dtype,
  handed to ``build_engine`` in place of its fixed-key initializer (their
  layout is checked against the program's);
* prompts made from the seed, handed to the executor's ``prompt_fn``;
* requests submitted by the traffic generator; the loop calls
  ``ServeEngine.step`` and stamps, after each step, every output token
  that became visible;
* host-clock spans, written as profiler annotations too, around the calls
  into the model step and into the paged-KV host copies, set on the
  engine's instances from here.

No spec state is carried from run to run: the state directory keeps the
runtime's persistent variant cache (compiled executables) and nothing
else.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import os
import time
from typing import Any, Callable

import numpy as np

from bench.harness import traffic as traffic_mod

clock = time.perf_counter


@dataclasses.dataclass
class Req:
    """One submitted request and the stamps the benchmark takes of it."""

    item: traffic_mod.Item
    request: Any                       # repro.serve.Request
    prompt: np.ndarray
    due: float
    submit_t: float
    accepted: bool
    tokens: list = dataclasses.field(default_factory=list)

    @property
    def first_token(self) -> float | None:
        return self.tokens[0] if self.tokens else None

    @property
    def service_t(self) -> float | None:
        return self.request.service_t


@dataclasses.dataclass
class Step:
    """One call into the model step."""

    phase: str
    size: int                          # padded batch (the bucket)
    rows: int                          # real rows
    lengths: list                      # each real row's cached tokens before
    t0: float
    t1: float = 0.0
    kv_s: float = 0.0                  # host seconds in materialize+harvest


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: Any
    cfg: dict
    model: Any
    traffic: dict
    seed: int
    seconds: float
    t_process: float
    t_built: float = 0.0
    t0: float = 0.0
    t1: float = 0.0
    reqs: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)
    counters0: dict = dataclasses.field(default_factory=dict)
    counters1: dict = dataclasses.field(default_factory=dict)
    compile_events: list = dataclasses.field(default_factory=list)
    trace: dict | None = None
    peaks: dict | None = None
    memory_peak_bytes: int | None = None
    served: list = dataclasses.field(default_factory=list)
    params: Any = None

    def summary(self) -> dict:
        """What the run did, for the log: set-up split, steps by phase,
        host milliseconds per step, engine counters over the window."""
        out = {"build_s": self.t_built - self.t_process,
               "warmup_s": self.t0 - self.t_built, "phases": {}}
        for phase in ("prefill", "decode"):
            steps = self.window_steps(phase)
            if steps:
                out["phases"][phase] = {
                    "steps": len(steps),
                    "rows_mean": sum(s.rows for s in steps) / len(steps),
                    "host_ms_mean": 1e3 * sum(s.t1 - s.t0 for s in steps)
                    / len(steps),
                    "kv_ms_mean": 1e3 * sum(s.kv_s for s in steps)
                    / len(steps)}
        c0, c1 = self.counters0, self.counters1
        out["engine_steps"] = c1.get("steps", 0) - c0.get("steps", 0)
        out["bucket_steps"] = {b: n - c0["bucket_steps"].get(b, 0)
                               for b, n in c1.get("bucket_steps", {}).items()
                               if n - c0["bucket_steps"].get(b, 0)}
        out["shadow_pairs"] = c1.get("shadow_pairs", 0) - \
            c0.get("shadow_pairs", 0)
        out["compile_service"] = {
            k: c1["compile"][k] - c0["compile"][k]
            for k in ("xla_compiles", "cache_hits")} if c1 else {}
        out["memory_peak_bytes"] = self.memory_peak_bytes
        return out

    def window_steps(self, phase: str | None = None) -> list:
        return [s for s in self.steps if self.t0 <= s.t0 < self.t1
                and (phase is None or s.phase == phase)]

    def compiles_between(self, t0: float, t1: float) -> int:
        """XLA compilations that ended in ``[t0, t1)``: backend compile
        requests less those the persistent cache served."""
        kinds = [kind for t, kind in self.compile_events if t0 <= t < t1]
        return kinds.count("compile") - kinds.count("hit")


class _CompileCounter:
    """Counts XLA compilations (a backend compile that the persistent
    cache did not serve) from JAX's own monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, events: list):
        import jax

        self.events = events
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == self.COMPILE:
            # the event closes when the compile (or cache read) ends
            self.events.append((clock(), "compile"))

    def _event(self, name, **kw):
        if name == self.HIT:
            self.events.append((clock(), "hit"))

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def engine_args(cfg: dict, traffic: dict, state_dir: str,
                reduced: bool = False) -> argparse.Namespace:
    """``launch/serve.py``'s engine flags at their defaults, with the
    cell's model, batch cap, cache length and state directory."""
    from repro.launch.serve import add_engine_args

    ap = argparse.ArgumentParser()
    add_engine_args(ap)
    eng = traffic["engine"]
    flags = ["--arch", cfg["program_arch"], "--batch", str(eng["batch"]),
             "--max-len", str(eng["max_len"]), "--cache-dir", state_dir]
    return ap.parse_args(flags + (["--reduced"] if reduced else []))


def seeded_params(model, cfg: dict, seed: int, dtype, sharding):
    """The configuration's weights from ``seed``, made on the device in one
    jitted call, in the serving dtype."""
    import jax

    state = np.random.SeedSequence(abs(seed)).generate_state(2)
    key = jax.random.wrap_key_data(np.asarray(state, np.uint32),
                                   impl="threefry2x32")

    def make(k):
        return jax.tree.map(lambda a: a.astype(dtype),
                            model.make_params(cfg, k))

    return jax.jit(make, out_shardings=sharding)(key)


def _check_layout(mine, want) -> None:
    import jax

    a = jax.tree_util.tree_structure(mine)
    b = jax.tree_util.tree_structure(want)
    if a != b:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"  benchmark: {a}\n  program:   {b}")
    for x, y in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(want)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"weight leaf {x.shape} {x.dtype} differs from "
                             f"the program's {y.shape} {y.dtype}")


def build(cfg: dict, model, traffic: dict, seed: int, state_dir: str,
          reduced: bool = False):
    """``build_engine`` with the benchmark's weights; returns its namespace
    and the weights."""
    import jax

    import repro.launch.serve as serve_mod

    spec_state = os.path.join(state_dir, "spec_state.json")
    if os.path.exists(spec_state):
        os.remove(spec_state)
    made = {}
    program_init = serve_mod.init_serving_params

    def init_from_seed(pcfg, sharding):
        params = seeded_params(model, cfg, seed, pcfg.compute_dtype, sharding)
        _check_layout(params, jax.eval_shape(
            lambda: program_init(pcfg, sharding)))
        made["params"] = params
        return params

    serve_mod.init_serving_params = init_from_seed
    try:
        built = serve_mod.build_engine(
            engine_args(cfg, traffic, state_dir, reduced))
    finally:
        serve_mod.init_serving_params = program_init
    return built, made["params"]


def _instrument(built, run: Run) -> None:
    """Host-clock spans around the model-step and paged-KV calls, set on
    the engine's own instances."""
    import jax

    ex, kv = built.executor, built.kv
    current: list[Step | None] = [None]

    def timed_kv(name: str, fn: Callable) -> Callable:
        def call(*a, **k):
            t = clock()
            with jax.profiler.TraceAnnotation(f"bench.kv.{name}"):
                out = fn(*a, **k)
            if current[0] is not None:
                current[0].kv_s += clock() - t
            return out
        return call

    def timed_step(phase: str, fn: Callable) -> Callable:
        def call(batch):
            lengths = [r.prompt_consumed if phase == "prefill"
                       else kv.length(r.rid) for r in batch.requests]
            step = Step(phase, batch.size, len(batch.requests), lengths,
                        clock())
            run.steps.append(step)
            current[0] = step
            try:
                with jax.profiler.TraceAnnotation(f"bench.step.{phase}"):
                    return fn(batch)
            finally:
                step.t1 = clock()
                current[0] = None
        return call

    kv.materialize = timed_kv("materialize", kv.materialize)
    kv.harvest = timed_kv("harvest", kv.harvest)
    ex.prefill.execute = timed_step("prefill", ex.prefill.execute)
    ex.decode.execute = timed_step("decode", ex.decode.execute)


def counters(built) -> dict:
    e = built.engine
    return {"steps": e.steps, "padded_rows": e.padded_rows,
            "bucket_steps": dict(e.bucket_steps),
            "compile": built.rt.compile_stats(),
            "shadow_pairs": e.shadow_pairs}


def run_cell(run: Run, *, state_dir: str, trace_dir: str | None = None,
             reduced: bool = False,
             on_built: Callable | None = None) -> Run:
    """Set up, warm up, measure ``run.seconds`` and free the engine.

    ``on_built(built)`` runs once the engine exists (tests use it to break
    the timed path underneath)."""
    import jax

    cfg, traffic, seed = run.cfg, run.traffic, run.seed
    events: list = []
    counter = _CompileCounter(events)
    built, params = build(cfg, run.model, traffic, seed, state_dir, reduced)
    run.params = params
    run.t_built = clock()
    vocab = built.cfg.vocab_size
    engine = built.engine
    _instrument(built, run)
    if on_built is not None:
        on_built(built)

    from repro.serve import Request

    prompts: dict = {}
    built.executor.prompt_fn = lambda req: prompts[req.rid]
    live: dict = {}

    def submit(item: traffic_mod.Item, due: float) -> bool:
        req = Request(prompt_tokens=item.prompt_len,
                      max_new_tokens=item.output_len)
        prompts[req.rid] = traffic_mod.prompt_ids(
            seed, item.index, item.prompt_len, vocab)
        t = clock()
        ok = engine.submit(req)
        r = Req(item, req, prompts[req.rid], due, t, ok)
        run.reqs.append(r)
        if ok:
            live[req.rid] = r
        return ok

    items = traffic_mod.make_items(
        traffic, seed, traffic_mod.item_count(traffic, run.seconds))
    gen = traffic_mod.Generator(traffic, items, submit,
                                lambda: len(engine.queue))

    def stamp(t: float) -> None:
        for rid, r in list(live.items()):
            new = r.request.generated - len(r.tokens)
            if new > 0:
                r.tokens.extend([t] * new)
            if r.request.finish_t is not None or r.request.shed:
                del live[rid]

    def serve_until(done: Callable[[float], bool]) -> None:
        while True:
            now = clock()
            if done(now):
                return
            gen.pump(now)
            produced = engine.step()
            t = clock()
            stamp(t)
            if produced == 0 and not engine.active:
                wait = gen.seconds_to_next(t)
                time.sleep(0.0005 if wait is None else min(wait, 0.005))

    cap = int(traffic["engine"]["batch"])
    warm_limit = float(traffic.get("warmup_max_s", 300.0))
    t_warm = clock()
    gen.start(t_warm)

    def warmed(now: float) -> bool:
        if now - t_warm > warm_limit:
            raise RuntimeError(f"warm-up did not finish in {warm_limit} s")
        if traffic["kind"] == "backlog":
            return (len(engine.active) == cap
                    and not any(r.prefilling for r in engine.active))
        return now >= t_warm + float(traffic["warmup_s"]) - 2.0

    serve_until(warmed)
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    if traffic["kind"] == "backlog":
        run.t0 = clock()
    else:
        run.t0 = t_warm + float(traffic["warmup_s"])
        serve_until(lambda now: now >= run.t0)
    run.t1 = run.t0 + run.seconds
    run.counters0 = counters(built)
    with jax.profiler.TraceAnnotation("bench.window"):
        serve_until(lambda now: now >= run.t1)
    run.counters1 = counters(built)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    counter.close()
    run.compile_events = events
    stats = built.device.memory_stats() or {}
    run.memory_peak_bytes = stats.get("peak_bytes_in_use")

    # what was served: each request with at least one token
    state = built.executor.state
    for r in run.reqs:
        out = (state[r.request.rid].out if r.request.rid in state
               else r.request.payload)
        if out:
            run.served.append((r.prompt, list(out)))
    # free the engine's state before the reference runs
    if built.shadow is not None:
        built.shadow.close()
    built.rt.shutdown()
    del built, engine, gen, live, state
    gc.collect()
    return run
