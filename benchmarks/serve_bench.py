"""Reduced serve benchmark with machine-readable output (BENCH_serve.json).

Runs the launch/serve decode loop in-process on a reduced model, then
emits one JSON document with the numbers this repo's perf trajectory is
tracked by:

* ``tok_per_s``            — end-to-end decode throughput,
* ``compile``              — CompileService totals (XLA compiles, cache
                             hits, cancelled stale builds, total compile
                             seconds) plus variant-cache stats,
* ``dispatch_overhead_us`` — trampoline cost over calling the AOT
                             executable directly (measured on a trivial
                             handler so the number isolates the dispatch
                             machinery, not the model), including the
                             per-request context-routing path,
* ``mixed``                — a mixed-batch-size serve scenario: one
                             handler, ``context_fn`` = batch size, one
                             Controller; each batch-shape class settles on
                             its own specialization (the contexts converge
                             to *different* configs),
* ``open_loop``            — the continuous-batching ServeEngine under
                             open-loop load (deterministic pseudo-Poisson
                             arrivals, mixed decode budgets, a rate ramp):
                             the same arrival schedule is served twice —
                             once with Controller-tuned bucket boundaries,
                             once with a fixed single bucket — recording
                             tok/s, goodput (in-SLO tok/s), p50/p95/p99
                             latency, shed counts, and the bucket scheme
                             the tuner settles on.  The SLO and arrival
                             rate are calibrated from measured step costs,
                             so the comparison is meaningful on hosts of
                             very different speeds,
* ``disagg``               — prefill/decode disaggregation over the paged
                             per-request KV runtime: the same prompt-heavy
                             schedule served twice through the phased
                             executor — once with ``(phase, bucket)``
                             contexts + paged KV, once phase-blind with
                             contiguous per-request slabs — recording the
                             per-phase settled configs (they differ: the
                             acceptance criterion), goodput vs the
                             baseline, TTFT, and page-pool stats,
* ``fleet``                — fleet serving over subprocess replicas: one
                             cold replica explores and publishes its
                             settled winners to a shared SpecPlane (plus
                             a shared portable variant cache), then N
                             fresh replicas warm-start off the plane
                             behind a ReplicaRouter — recording goodput
                             scaling vs the single replica, recompiles
                             on the warm replicas (must be zero), and
                             the cold-vs-warm time-to-settled speedup,
* ``tenants``              — multi-tenant multi-model serving: a
                             tight-SLO qwen3 tenant and a loose-SLO
                             rwkv6 tenant share one engine, one
                             CompileService and one variant cache,
                             each dispatching through its own
                             ``(tenant, phase, bucket)`` contexts.  The
                             tight tenant's burst is served three ways —
                             alone, against a loose-tenant flood under
                             weighted-fair DRR, and against the same
                             flood under plain FCFS — recording that the
                             two tenants settle on structurally distinct
                             per-context configs and that DRR preserves
                             the tight tenant's in-SLO tokens (>= 0.8x
                             its solo run) while FCFS loses them to the
                             flood,
* ``safety``               — safe online exploration: the same open-loop
                             schedule served three times with a
                             deliberately-broken candidate and an
                             adoption-correlated fault injected mid-run —
                             a no-injection baseline, an unsafe run
                             (live sweep serves the broken config and
                             silently absorbs the fault), and a safe run
                             (shadow evaluation rejects the broken
                             config off-path, the winner canaries and
                             promotes, auto-rollback reverts the fault
                             and quarantines the config) — recording
                             goodput ratios, rollback/quarantine
                             counters, and per-call dispatch-slot
                             samples proving the broken config never
                             served live and no quarantined config was
                             ever reactivated.

CLI:
    PYTHONPATH=src:. python -m benchmarks.serve_bench \
        --steps 120 --out BENCH_serve.json
    PYTHONPATH=src:. python -m benchmarks.serve_bench --scenario open_loop

Also runs under ``benchmarks/run.py`` (module name ``serve``), where it
writes ``BENCH_serve.json`` to the CWD (override with $BENCH_SERVE_JSON).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import Row, measure_dispatch_overhead
from repro import configs
from repro.core import (ChangeDetector, Controller, EWMA, ExhaustiveSweep,
                        IridescentRuntime, SafetyController, guards)
from repro.models import transformer as model
from repro.models.transformer import RunOptions
from repro.training import SERVE_SEARCH_POINTS, make_decode_builder


def run_serve(steps: int = 120, arch: str = "qwen3-0.6b", batch: int = 4,
              max_len: int = 64, dwell: int = 10, compile_workers: int = 2,
              prefetch: int = 2, cache_dir: str | None = None) -> dict:
    # Measure dispatch overhead first: after the serve loop the process is
    # full of jit caches / GC debt and the µs-scale timings drift.
    dispatch_us = measure_dispatch_overhead()
    cfg = configs.get_reduced(arch).replace(compute_dtype="float32")
    variant_cache = (os.path.join(cache_dir, "variants")
                     if cache_dir else None)
    rt = IridescentRuntime(async_compile=True,
                           max_compile_workers=compile_workers,
                           variant_cache=variant_cache)
    handler = rt.register(
        "serve_step", make_decode_builder(cfg, kernel_impl="xla"),
        donate_argnums=1)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    cache = model.init_cache(cfg, batch, max_len,
                             RunOptions(decode_cache_dtype="float32"))
    tokens = jnp.zeros((batch,), jnp.int32)

    space = handler.spec_space()
    controller = Controller(
        handler, lambda: ExhaustiveSweep.from_space(space,
                                                    SERVE_SEARCH_POINTS),
        dwell=dwell, change_detector=lambda: ChangeDetector(0.3),
        wait_compiles=False, prefetch=prefetch)

    t0 = time.perf_counter()
    for step in range(steps):
        pos = jnp.int32(step % max_len)
        logits, cache = handler(params, cache, tokens, pos)
        controller.step()
    jax.block_until_ready(logits)
    wall_s = time.perf_counter() - t0
    rt.compile_service.drain(timeout=120)   # settle in-flight builds
    best, best_metric = controller.best()
    compile_stats = rt.compile_stats()
    n_variants = len(handler.variants())
    rt.shutdown()

    return {
        "bench": "serve",
        "arch": arch,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "steps": steps,
        "batch": batch,
        "wall_s": round(wall_s, 3),
        "tok_per_s": round(steps * batch / wall_s, 2),
        "best_config": {k: repr(v) for k, v in (best or {}).items()},
        "variants": n_variants,
        "guard_misses": handler.guard_misses,
        "compile": compile_stats,
        "dispatch_overhead_us": dispatch_us,
    }


def _mixed_decode_builder(spec):
    """A decode-like handler whose best specialization depends on the batch
    size: the generic path must stay batch-agnostic (row-by-row scan, the
    safe fallback any batch can take), while a variant specialized to an
    assumed batch size may use the vectorized fused matmul.  A variant
    whose assumption does not match the incoming batch guard-misses to the
    generic path — so each batch-shape context converges to *its own*
    assumption, never a rival context's."""
    n = spec.generic("batch", None, guard=guards.shape_equals(0, 0))

    def f(x, w):
        if n is None:
            # generic: handles any batch, one row at a time
            return jax.lax.map(lambda r: r @ w, x)
        # specialized: the batch==n assumption licenses one fused matmul
        return x @ w

    return f


def run_mixed(steps: int = 360, batches=(1, 64), d: int = 128,
              dwell: int = 20) -> dict:
    """Mixed-batch-size serve: per-request context routing + one Controller
    searching each batch-shape class independently.

    The policy metric is each class's *specialized-service* rate: guard-hit
    fraction over the dwell window divided by the class's per-call latency
    (EWMA).  Guard-missed calls were served by the generic fallback — a
    specialization whose assumption never matches its class delivers zero
    specialized service, however fast the fallback is.  Per-class numbers
    (not wall-clock rate) keep the measurement unconfounded by whatever the
    *other* context is dwelling on in the interleaved loop.
    """
    import numpy as np

    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("mixed_decode", _mixed_decode_builder,
                          context_fn=lambda a, k: int(a[0].shape[0]))
    w = jnp.asarray(np.random.RandomState(0).randn(d, d).astype(np.float32))
    xs = {b: jnp.ones((b, d), jnp.float32) for b in batches}
    candidates = [{"batch": b} for b in batches]
    latency = {b: EWMA(0.3) for b in batches}   # per-class seconds/call
    marks = {b: (0, 0) for b in batches}    # (guard_misses, calls) at last read

    def specialized_rate(view):
        gm, calls = view.guard_misses, view.calls()
        prev_gm, prev_calls = marks[view.key]
        marks[view.key] = (gm, calls)
        dcalls = max(1, calls - prev_calls)
        hit = 1.0 - (gm - prev_gm) / dcalls
        return hit / max(latency[view.key].value or 1e-9, 1e-9)

    controller = Controller(
        handler, lambda: ExhaustiveSweep(candidates),
        metric=specialized_rate,
        # The scenario under test is per-context *settling*; µs-scale
        # latencies on a shared 2-core CI host jitter far past any sane
        # change threshold, so re-exploration is disabled here (change
        # adaptation has its own benchmarks: fig7/fig8).
        change_detector=lambda: ChangeDetector(float("inf")),
        dwell=dwell, wait_compiles=True, prefetch=0)

    t0 = time.perf_counter()
    for step in range(steps):
        for b in batches:                   # interleave workload classes
            t1 = time.perf_counter()
            out = handler(xs[b], w)
            jax.block_until_ready(out)
            latency[b].update(time.perf_counter() - t1)
        controller.step()
    wall_s = time.perf_counter() - t0

    status = controller.status()
    contexts = {}
    for b in batches:
        st = status.get(b, {})
        contexts[str(b)] = {
            "config": {k: repr(v) for k, v in (st.get("active") or {}).items()},
            "phase": st.get("phase"),
            "calls": st.get("calls"),
            "guard_misses": handler.context(b).guard_misses,
            "tok_per_s": round(st.get("calls", 0) * b / wall_s, 2),
        }
    settled = controller.settled()
    distinct = len({json.dumps(c["config"], sort_keys=True)
                    for c in contexts.values()}) == len(contexts)
    rt.shutdown()
    return {
        "steps": steps,
        "batches": list(batches),
        "wall_s": round(wall_s, 3),
        "contexts": contexts,
        "settled": settled,
        "distinct_configs": distinct,
    }


def _open_loop_builder(spec):
    """Bench handler: fused matmul vs a generic split-and-concat form.

    The per-bucket Controller sweep settles each bucket context on the
    faster form by measured rate — the "specialization pays" half of the
    scenario; the batcher's bucket tuning is the other half.  The generic
    form is deliberately only *mildly* slower (an extra concat + worse
    blocking), so exploration dwells perturb latency instead of wrecking
    it."""
    fused = spec.enum("fused", False, (False, True), guarded=False)

    def f(x, w):
        if fused:
            return x @ w
        h = w.shape[1] // 2
        return jnp.concatenate([x @ w[:, :h], x @ w[:, h:]], axis=-1)

    return f


def _calibrate_step_cost(d: int, batches, reps: int = 7) -> dict:
    """Median seconds per *effective* decode step at each batch size.

    Measured through a registered contextual handler plus a bucket-plan
    tick — i.e. the same per-step work the engine's executor does (array
    build, contextual trampoline dispatch, tuner tick), not a bare jit
    call; on hosts where dispatch overhead rivals the matmul this is the
    number that decides whether an SLO is meetable."""
    from repro.serve.batcher import bucket_plan_builder as _plan_builder

    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("calib_step", _open_loop_builder,
                          context_fn=lambda a, k: int(a[0].shape[0]))
    plan = rt.register("calib_plan", _plan_builder(["a", "b"], "a"))
    w = jnp.zeros((d, d), jnp.float32)
    tick = jnp.int32(0)
    out = {}
    for b in batches:
        jax.block_until_ready(handler(jnp.zeros((b, d), jnp.float32), w))
        handler.specialize({"fused": True}, context=b, wait=True)
        jax.block_until_ready(handler(jnp.zeros((b, d), jnp.float32), w))
        plan(tick)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            x = jnp.zeros((b, d), jnp.float32)
            y = handler(x, w)
            plan(tick)
            jax.block_until_ready(y)
            ts.append(time.perf_counter() - t0)
        out[b] = sorted(ts)[len(ts) // 2]
    rt.shutdown()
    return out


def _calibrate_engine_overhead(steps: int = 60) -> float:
    """Median per-step cost of the serve machinery itself (queue, pack,
    scheduler, tuner tick, controller scan — everything but the model):
    one request decoding through a no-op executor with the full tuned-run
    engine attached.  Folded into the SLO calibration so the scenario is
    meaningful on hosts where dispatch overhead rivals the model cost."""
    from repro.core.metrics import ChangeDetector as _CD
    from repro.serve import (AdmissionQueue, BucketTuner, ContinuousBatcher,
                             Request, ServeEngine, ServeMetrics,
                             ShortestJobFirst)

    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("overhead_probe", _open_loop_builder,
                          context_fn=lambda a, k: int(a[0].shape[0]))

    class NoopExec:
        def execute(self, batch):
            pass

    metrics = ServeMetrics()
    batcher = ContinuousBatcher(8)
    tuner = BucketTuner(batcher, rt, metric=metrics.interval_goodput,
                        dwell=10000, wait_compiles=True,
                        change_detector=lambda: _CD(float("inf")))
    controller = Controller(handler, lambda: ExhaustiveSweep([{}]),
                            dwell=10000, wait_compiles=True, prefetch=0)
    engine = ServeEngine(handler, controller, batcher, ShortestJobFirst(),
                         executor=NoopExec(), queue=AdmissionQueue(),
                         tuner=tuner, metrics=metrics)
    engine.submit(Request(max_new_tokens=steps))
    ts = []
    engine.step()                                  # warm the probe path
    for _ in range(steps - 1):
        t0 = time.perf_counter()
        engine.step()
        ts.append(time.perf_counter() - t0)
    rt.shutdown()
    return sorted(ts)[len(ts) // 2] if ts else 0.0


def run_open_loop(max_batch: int = 64, d: int = 1536, seed: int = 7,
                  phase_s: float = 1.5, ramp=(0.3, 0.6, 1.0),
                  burst: float = 3.0, utilization: float = 0.4,
                  slo_slack: float = 1.4,
                  target_inflight: int = 6, budgets=(4, 8, 16),
                  prompts=(16, 128, 512), queue_depth: int = 64,
                  dwell: int = 6, bucket_dwell: int = 40,
                  max_wall_s: float = 90.0) -> dict:
    """Open-loop continuous-batching scenario (see module docstring).

    Both runs replay the *same* pseudo-Poisson schedule; the only
    difference is the bucketing: Controller-tuned scheme search vs the
    fixed single bucket (every batch pads to ``max_batch``).
    ``bucket_dwell`` must comfortably exceed a request lifetime in steps
    (the largest token budget), or every scheme's goodput window is
    dominated by the previous scheme's stragglers and the search ties at
    zero.  Strictly
    higher goodput for the tuned run is the acceptance bar, and the
    mechanism is latency: at the calibrated load (``utilization`` of the
    small-bucket capacity at ``target_inflight`` concurrent requests) a
    tuned batcher runs ~``target_inflight``-row buckets, so each request's
    per-token service time is the small-bucket step cost; the single
    bucket pads every step to ``max_batch`` rows and its per-token service
    time is the full-batch step cost.  Each request's deadline is set at
    its token budget times the *geometric mean* of the two measured step
    costs — comfortably met by the tuned run, comfortably missed by the
    padded one, on any host speed, because both sides are measured on this
    host.  The final schedule phase is a short burst far above capacity:
    both engines shed it at the bounded queue (backpressure), which is
    what the shed counters in the output exercise.
    """
    import random as _random

    from repro.core.metrics import ChangeDetector as _CD
    from repro.serve import (AdmissionQueue, BucketTuner, ContinuousBatcher,
                             OpenLoopSource, Request, ServeEngine,
                             ServeMetrics, ShortestJobFirst,
                             pseudo_poisson_times)

    small = max(1, 2 ** (target_inflight - 1).bit_length())  # bucket(inflight)
    costs = _calibrate_step_cost(d, (small, max_batch))
    overhead = _calibrate_engine_overhead()
    c_small = costs[small] + overhead          # effective per-step costs
    c_big = costs[max_batch] + overhead
    budget_mean = sum(budgets) / len(budgets)
    # Per-request deadline: budget x geometric mean of the two effective
    # step costs (a request's per-token latency IS its batch's step time),
    # times a slack factor absorbing host-speed drift between calibration
    # and run.  Tuned margin ~= slack x sqrt(c_big/c_small); single-bucket
    # shortfall ~= sqrt(c_big/c_small) / slack — both > 1 while
    # 1 < slack < sqrt(c_big/c_small).
    slo_per_token = slo_slack * (c_small * c_big) ** 0.5
    # Peak arrival rate targeting `utilization` of the small-bucket
    # capacity (the ramp approaches it from below, so in-flight stays near
    # target_inflight and the tuned batcher actually runs small buckets).
    rate0 = utilization * (target_inflight / c_small) / budget_mean
    phases = [(phase_s, rate0 * m) for m in ramp]
    # Terminal burst sized to overflow the bounded queue (~2x depth past
    # what full-batch service absorbs): the backpressure/shed path under
    # test, identical for both engines.
    cap_req_s = (max_batch / c_big) / budget_mean
    burst_rate = max(burst * cap_req_s, rate0)
    burst_dur = min(0.5 * phase_s,
                    2.0 * queue_depth / max(burst_rate - cap_req_s, 1e-9))
    phases.append((burst_dur, burst_rate))

    def schedule():
        rng = _random.Random(seed)
        out = []
        for t in pseudo_poisson_times(phases, seed=seed):
            budget = rng.choice(budgets)
            out.append((t, Request(prompt_tokens=rng.choice(prompts),
                                   max_new_tokens=budget,
                                   deadline_s=budget * slo_per_token)))
        return out

    w = jnp.zeros((d, d), jnp.float32)

    def run_once(tune_buckets: bool) -> dict:
        # Async compile pipeline + wait_compiles=False: variant builds stay
        # off the serving path (the paper's critical-path rule) — a
        # synchronous compile inside the loop would stall every in-flight
        # request past its deadline.
        rt = IridescentRuntime(async_compile=True, max_compile_workers=2)
        handler = rt.register("open_loop_step", _open_loop_builder,
                              context_fn=lambda a, k: int(a[0].shape[0]))

        class Exec:
            def execute(self, batch):
                x = jnp.zeros((batch.size, d), jnp.float32)
                jax.block_until_ready(handler(x, w))

        candidates = [{"fused": True}, {"fused": False}]
        controller = Controller(
            handler, lambda: ExhaustiveSweep(candidates), dwell=dwell,
            change_detector=lambda: ChangeDetector(float("inf")),
            wait_compiles=False, prefetch=0)
        metrics = ServeMetrics()
        if tune_buckets:
            batcher = ContinuousBatcher(max_batch)
            # The scenario under test is *settling* on a scheme; goodput on
            # a shared CI host jitters past any sane change threshold, so
            # re-exploration is disabled here (as in run_mixed).
            tuner = BucketTuner(batcher, rt,
                                metric=metrics.interval_goodput,
                                dwell=bucket_dwell, wait_compiles=False,
                                change_detector=lambda: _CD(float("inf")))
        else:
            batcher = ContinuousBatcher(max_batch, scheme="single")
            tuner = None
        engine = ServeEngine(
            handler, controller, batcher, ShortestJobFirst(),
            executor=Exec(),
            queue=AdmissionQueue(depth=queue_depth, policy="shed-oldest"),
            tuner=tuner, metrics=metrics)
        source = OpenLoopSource(engine.queue, schedule())
        t0 = time.perf_counter()
        engine.run(source=source, duration_s=max_wall_s)
        engine.drain(timeout_s=max_wall_s / 2)
        wall = time.perf_counter() - t0
        stats = engine.stats()
        serve = stats["serve"]
        row = {
            "bucketing": "tuned" if tune_buckets else "single",
            "wall_s": round(wall, 3),
            "offered": stats["queue"]["submitted"],
            "completed": serve["completed"],
            "completed_tokens": serve["completed_tokens"],
            "tok_per_s": round(serve["completed_tokens"] / wall, 2),
            "goodput_tok_per_s": round(serve["goodput_tokens"] / wall, 2),
            "slo_met": serve["slo_met"],
            "slo_missed": serve["slo_missed"],
            "shed": stats["queue"]["shed"] + serve["shed"],
            "rejected": stats["queue"]["rejected"],
            "shed_errors": stats["queue"]["shed_errors"],
            "latency_p50_ms": serve["latency_p50_ms"],
            "latency_p95_ms": serve["latency_p95_ms"],
            "latency_p99_ms": serve["latency_p99_ms"],
            "bucket_steps": {str(k): v
                             for k, v in stats["bucket_steps"].items()},
            "padded_rows": stats["padded_rows"],
        }
        if tuner is not None:
            row["scheme"] = tuner.active_scheme()
            row["boundaries"] = list(
                batcher.schemes[tuner.active_scheme()])
            row["scheme_settled"] = tuner.settled()
        else:
            row["scheme"] = "single"
            row["boundaries"] = list(batcher.schemes["single"])
        rt.shutdown()
        return row

    tuned = run_once(tune_buckets=True)
    single = run_once(tune_buckets=False)
    return {
        "seed": seed,
        "d": d,
        "max_batch": max_batch,
        "slo_per_token_ms": round(slo_per_token * 1e3, 4),
        "calibration_ms": {**{str(b): round(c * 1e3, 3)
                              for b, c in costs.items()},
                           "engine_overhead": round(overhead * 1e3, 3)},
        "arrival_phases": [[round(s, 3), round(r, 2)] for s, r in phases],
        "tuned": tuned,
        "single_bucket": single,
        "tuned_gt_single": (tuned["goodput_tok_per_s"]
                            > single["goodput_tok_per_s"]),
    }


def _disagg_builder(d: int, vocab: int, rounds: int = 2):
    """Serve-contract handler (``(params, cache, tokens, pos, n_new) ->
    (logits, new_cache)``) whose best specialization depends on the
    *phase*: a ``tile`` spec point sets the sequence block the step is
    padded to and processed in.

    Each tile-block pays a fixed setup cost (a serial ``w_run = tanh(w_run
    @ w)`` chain — the data dependency defeats both CSE and inter-op
    parallelism) plus compute proportional to the padded block.  A decode
    step (S=1) with ``tile=64`` burns 64x the block FLOPs it needs; a
    64-token prefill chunk with ``tile=8`` pays the per-block setup 8
    times over.  So the prefill context wants ``tile=64``, the decode
    context wants ``tile=8``, and a phase-blind context must compromise —
    the cost asymmetry the disagg scenario measures.
    """

    def build(spec):
        tile = spec.enum("tile", 8, (8, 64), guarded=False)

        def f(params, cache, tokens, pos, n_new):
            toks = tokens if tokens.ndim == 2 else tokens[:, None]
            b, s = toks.shape
            n_blocks = -(-s // tile)
            x = jnp.pad(toks, ((0, 0), (0, n_blocks * tile - s)))
            x = x.astype(jnp.float32)[:, :, None] * jnp.ones(
                (d,), jnp.float32)                       # (B, S_pad, d)
            w = params
            w_run = w
            ys = []
            for i in range(n_blocks):
                w_run = jnp.tanh(w_run @ w)              # serial setup
                y = x[:, i * tile:(i + 1) * tile, :]
                for _ in range(rounds):
                    y = jnp.tanh(y @ w_run)              # block compute
                ys.append(y)
            y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
            return y[:, -1, :vocab], cache

        return f

    return build


def _calibrate_disagg(handler, w, cache, bucket: int, chunk: int,
                      tiles=(8, 64), reps: int = 5) -> dict:
    """Median seconds per (phase, tile) serve step on this host."""
    from repro.training import phase_context_fn

    out = {}
    for phase in ("prefill", "decode"):
        if phase == "prefill":
            tokens = jnp.zeros((bucket, chunk), jnp.int32)
            n_new = jnp.full((bucket,), chunk, jnp.int32)
        else:
            tokens = jnp.zeros((bucket,), jnp.int32)
            n_new = jnp.ones((bucket,), jnp.int32)
        pos = jnp.zeros((bucket,), jnp.int32)
        key = phase_context_fn((w, cache, tokens, pos, n_new), {})
        for tile in tiles:
            handler.specialize({"tile": tile}, context=key, wait=True)
            jax.block_until_ready(handler(w, cache, tokens, pos, n_new)[0])
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    handler(w, cache, tokens, pos, n_new)[0])
                ts.append(time.perf_counter() - t0)
            out[(phase, tile)] = sorted(ts)[len(ts) // 2]
    return out


def _calibrate_kv_cycle(template, axes, max_len: int, bucket: int,
                        chunk: int, page_size: int,
                        reps: int = 5) -> dict:
    """Median seconds per materialize+harvest cycle per phase — the
    engine-side per-step cost the handler calibration cannot see."""
    from repro.serve import PagedKV

    out = {}
    for phase, n in (("prefill", chunk), ("decode", 1)):
        kv = PagedKV(template, axes, max_len=max_len,
                     capacity_tokens=2 * bucket * max_len,
                     page_size=page_size)
        rids = [f"calib-{i}" for i in range(bucket)]
        ts = []
        for _ in range(reps):                  # rejoin: stay under max_len
            for rid in rids:
                kv.join(rid)
            t0 = time.perf_counter()
            cache, _ = kv.materialize(rids, bucket)
            kv.harvest(rids, cache, [n] * bucket)
            ts.append(time.perf_counter() - t0)
            for rid in rids:
                kv.retire(rid)
        out[phase] = sorted(ts)[len(ts) // 2]
    return out


def _calibrate_serve_overhead(template, axes, max_len: int, bucket: int,
                              chunk: int, prompt: int, vocab: int,
                              n: int = 16, warm_steps: int = 6) -> float:
    """Per-engine-step cost of the full phased serve path minus the
    model: a near-zero handler through the real PhasedExecutor + PagedKV
    + engine on a small burst.  Captures everything the noop-executor
    probe (:func:`_calibrate_engine_overhead`) cannot — token-array
    builds, materialize/harvest page copies, logits transfer, sampling."""
    from repro.serve import (AdmissionQueue, ContinuousBatcher, PagedKV,
                             PhasedExecutor, Request, ServeEngine,
                             ServeMetrics, ShortestJobFirst)
    from repro.training import phase_context_fn

    def trivial_builder(spec):
        def f(params, cache, tokens, pos, n_new):
            toks = tokens if tokens.ndim == 2 else tokens[:, None]
            logits = toks[:, -1:].astype(jnp.float32) * jnp.ones(
                (vocab,), jnp.float32)
            return logits, cache
        return f

    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("serve_ov_probe", trivial_builder,
                          context_fn=phase_context_fn)
    kv = PagedKV(template, axes, max_len=max_len,
                 capacity_tokens=2 * bucket * max_len, page_size=8)
    executor = PhasedExecutor(handler, None, kv, prefill_chunk=chunk,
                              vocab_size=vocab)
    metrics = ServeMetrics()
    controller = Controller(handler, lambda: ExhaustiveSweep([{}]),
                            dwell=10000, wait_compiles=True, prefetch=0)
    engine = ServeEngine(handler, controller,
                         ContinuousBatcher(bucket, scheme="single"),
                         ShortestJobFirst(), executor=executor,
                         queue=AdmissionQueue(depth=n + bucket),
                         metrics=metrics)
    for _ in range(n):
        engine.submit(Request(prompt_tokens=prompt, max_new_tokens=8))
    steps = 0
    t_mark, s_mark = None, 0
    while metrics.completed < n and steps < 10_000:
        engine.step()
        steps += 1
        if steps == warm_steps:                 # past both phase compiles
            t_mark, s_mark = time.perf_counter(), steps
    ov = ((time.perf_counter() - t_mark) / max(1, steps - s_mark)
          if t_mark is not None else 0.0)
    rt.shutdown()
    return ov


def run_disagg(d: int = 512, vocab: int = 32, bucket: int = 8,
               chunk: int = 64, prompt: int = 192, budgets=(4, 8),
               n_requests: int = 128, slo_slack: float = 1.0,
               dwell: int = 6, seed: int = 11, page_size: int = 8,
               max_wall_s: float = 120.0) -> dict:
    """Prefill/decode disaggregation over the paged KV runtime vs a
    phase-blind baseline.

    Both runs replay the same prompt-heavy open-loop schedule through the
    *same* machinery — :class:`~repro.serve.executor.PhasedExecutor`
    (chunked prefill interleaved with decode) over a
    :class:`~repro.serve.kv.PagedKV` manager at the same page size — and
    differ only in **context keying**: the disagg run dispatches through
    ``(phase, bucket)`` contexts (``phase_context_fn``), so the
    Controller settles prefill and decode on *different* ``tile``
    configs; the baseline keys by bucket alone, so one config must serve
    both phases and compromises one of them (:func:`_disagg_builder`
    makes both compromises measurably bad).

    The Controller metric is each context's own per-call latency (EWMA,
    as in :func:`run_mixed`) — interleaving makes wall-clock rate
    confounded by whatever the *other* phase is dwelling on.  The load is
    a **saturating burst** (all requests arrive at once), so the engine
    stays batch-full and wall time is the service *makespan* — a
    deterministic function of the settled configs, not of arrival-process
    jitter.  Every request shares one deadline: the geometric mean of the
    two *predicted makespans* (from the measured per-phase step costs,
    plus an exploration allowance both runs pay).  The disagg run drains
    the whole burst before the deadline; the phase-blind run's makespan
    overshoots it by ``sqrt(blind/opt)``, so its stragglers miss — and
    its wall is longer — which compound into the goodput gap.
    Acceptance: distinct settled per-phase configs and disagg goodput >=
    the phase-blind baseline.
    """
    import random as _random

    from repro.serve import (AdmissionQueue, ContinuousBatcher,
                             OpenLoopSource, PagedKV, PhasedExecutor,
                             Request, ServeEngine, ServeMetrics,
                             ShortestJobFirst)
    from repro.training import phase_context_fn

    max_len = prompt + max(budgets) + page_size     # headroom: one page
    rng_w = __import__("numpy").random.RandomState(0)
    w = jnp.asarray(0.05 * rng_w.randn(d, d).astype("float32"))
    # The paged state is deliberately thin (the synthetic handler's cost
    # lives in ``w``-sized compute, not cache traffic): the scenario under
    # test is phase-context settling, so per-step KV traffic should not
    # drown the phase asymmetry.  Page mechanics are still fully
    # exercised — ~26 pages per request through join/harvest/retire.
    template = {"k": jnp.zeros((1, max_len, 8), jnp.float32)}
    axes = {"k": ("batch", "seq_kv", "model")}

    # -- calibration (measured on this host, through a real handler) -----------
    rt = IridescentRuntime(async_compile=False)
    calib = rt.register("disagg_calib", _disagg_builder(d, vocab),
                        context_fn=phase_context_fn)
    cache0 = {"k": jnp.zeros((bucket, max_len, 8), jnp.float32)}
    costs = _calibrate_disagg(calib, w, cache0, bucket, chunk)
    rt.shutdown()
    kv_cycle = _calibrate_kv_cycle(template, axes, max_len, bucket,
                                   chunk, page_size)
    overhead = _calibrate_serve_overhead(template, axes, max_len, bucket,
                                         chunk, prompt, vocab)
    steps_pre = -(-prompt // chunk)
    g_mean = sum(budgets) / len(budgets)

    def service_s(c_pre: float, c_dec: float, g: float) -> float:
        return (steps_pre * (c_pre + overhead)
                + g * (c_dec + overhead))

    def opt_s(g):                    # best per-phase configs
        return service_s(costs[("prefill", 64)], costs[("decode", 8)], g)

    def blind_s(g):                  # best phase-blind compromise
        return min(service_s(costs[("prefill", t)], costs[("decode", t)], g)
                   for t in (8, 64))

    # Predicted burst makespans: every step serves ``bucket`` rows, so the
    # backlog is n/bucket request-equivalents of service, plus an
    # exploration allowance (each context dwells on both tiles; both runs
    # pay it).  The shared deadline is the geometric mean of the two
    # predictions: the disagg run drains before it (margin
    # sqrt(blind/opt)/slack), the phase-blind run overshoots it by the
    # same factor — a makespan comparison, immune to arrival jitter.
    explore_pad = dwell * sum(
        costs[(p, t)] + overhead
        for p in ("prefill", "decode") for t in (8, 64))

    def makespan_s(per_req: float) -> float:
        return n_requests / bucket * per_req + explore_pad

    deadline = slo_slack * (makespan_s(opt_s(g_mean))
                            * makespan_s(blind_s(g_mean))) ** 0.5

    def schedule():
        rng = _random.Random(seed)
        return [(i * 1e-4, Request(prompt_tokens=prompt,
                                   max_new_tokens=rng.choice(budgets),
                                   deadline_s=deadline))
                for i in range(n_requests)]

    def run_once(disagg: bool) -> dict:
        # Synchronous compiles + wait_compiles=True: with 4 tiny variants
        # per run, clean dwell attribution matters more than compile
        # pipelining here — a dwell measured on the fallback variant
        # (compile still in flight) would credit one tile with the
        # other's latency (pipelining has its own scenarios above).
        rt = IridescentRuntime(async_compile=False)
        context_fn = (phase_context_fn if disagg
                      else lambda a, k: int(a[2].shape[0]))
        handler = rt.register("disagg_step", _disagg_builder(d, vocab),
                              context_fn=context_fn)
        latency = {}                 # context key -> per-call seconds EWMA

        def timed_handler(params, cache, tokens, pos, n_new):
            key = context_fn((params, cache, tokens, pos, n_new), {})
            t0 = time.perf_counter()
            logits, new_cache = handler(params, cache, tokens, pos, n_new)
            jax.block_until_ready(logits)
            latency.setdefault(key, EWMA(0.5)).update(
                time.perf_counter() - t0)
            return logits, new_cache

        def context_latency_rate(view):
            v = latency[view.key].value if view.key in latency else None
            return 1.0 / max(v, 1e-9) if v else 0.0

        controller = Controller(
            handler, lambda: ExhaustiveSweep([{"tile": 8}, {"tile": 64}]),
            metric=context_latency_rate, dwell=dwell,
            change_detector=lambda: ChangeDetector(float("inf")),
            wait_compiles=True, prefetch=0)
        kv = PagedKV(template, axes, max_len=max_len,
                     capacity_tokens=2 * bucket * max_len,
                     page_size=page_size)
        executor = PhasedExecutor(timed_handler, w, kv,
                                  prefill_chunk=chunk, vocab_size=vocab)
        metrics = ServeMetrics()
        batcher = ContinuousBatcher(bucket, scheme="single")
        engine = ServeEngine(
            handler, controller, batcher, ShortestJobFirst(),
            executor=executor,
            queue=AdmissionQueue(depth=n_requests + bucket,
                                 policy="shed-oldest"),
            metrics=metrics)
        source = OpenLoopSource(engine.queue, schedule())
        t0 = time.perf_counter()
        engine.run(source=source, duration_s=max_wall_s)
        engine.drain(timeout_s=max_wall_s / 2)
        wall = time.perf_counter() - t0
        stats = engine.stats()
        serve = stats["serve"]
        best = controller.best_configs()
        status = controller.status()
        contexts = {
            str(key): {
                "config": {kk: repr(vv) for kk, vv in (cfg or {}).items()},
                "phase": status.get(key, {}).get("phase"),
                "calls": status.get(key, {}).get("calls"),
            }
            for key, cfg in best.items()}
        row = {
            "mode": "disagg" if disagg else "phase_blind",
            "wall_s": round(wall, 3),
            "offered": stats["queue"]["submitted"],
            "completed": serve["completed"],
            "completed_tokens": serve["completed_tokens"],
            "goodput_tok_per_s": round(serve["goodput_tokens"] / wall, 2),
            "tok_per_s": round(serve["completed_tokens"] / wall, 2),
            "slo_met": serve["slo_met"],
            "slo_missed": serve["slo_missed"],
            "shed": stats["queue"]["shed"] + serve["shed"],
            "shed_errors": stats["queue"]["shed_errors"],
            "latency_p50_ms": serve["latency_p50_ms"],
            "latency_p95_ms": serve["latency_p95_ms"],
            "ttft_p50_ms": serve["ttft_p50_ms"],
            "phase_steps": dict(stats.get("phase_steps", {})),
            "contexts": contexts,
            "kv_pool": kv.stats(),
        }
        if disagg:
            pre = best.get(("prefill", bucket)) or {}
            dec = best.get(("decode", bucket)) or {}
            row["prefill_tile"] = pre.get("tile")
            row["decode_tile"] = dec.get("tile")
        rt.shutdown()
        return row

    disagg = run_once(True)
    baseline = run_once(False)
    return {
        "seed": seed,
        "d": d,
        "bucket": bucket,
        "prefill_chunk": chunk,
        "prompt_tokens": prompt,
        "budgets": list(budgets),
        "calibration_ms": {
            **{f"{p}_tile{t}": round(c * 1e3, 3)
               for (p, t), c in costs.items()},
            **{f"kv_cycle_{p}": round(c * 1e3, 3)
               for p, c in kv_cycle.items()},
            "serve_overhead": round(overhead * 1e3, 3)},
        "service_ms": {"disagg": round(opt_s(g_mean) * 1e3, 3),
                       "phase_blind": round(blind_s(g_mean) * 1e3, 3)},
        "makespan_est_ms": {
            "disagg": round(makespan_s(opt_s(g_mean)) * 1e3, 3),
            "phase_blind": round(makespan_s(blind_s(g_mean)) * 1e3, 3)},
        "deadline_ms": round(deadline * 1e3, 3),
        "disagg": disagg,
        "baseline": baseline,
        "distinct_phase_configs": (
            disagg["prefill_tile"] is not None
            and disagg["decode_tile"] is not None
            and disagg["prefill_tile"] != disagg["decode_tile"]),
        "disagg_ge_baseline": (disagg["goodput_tok_per_s"]
                               >= baseline["goodput_tok_per_s"]),
    }


def _fleet_schedule(n_requests: int, rate: float, seed: int,
                    ) -> list[tuple[float, "Request"]]:
    """Per-replica open-loop schedule: seeded exponential interarrivals at
    ``rate`` with mixed decode budgets.  Callers derive ``seed`` via
    ``substream_seed(root, replica_id)`` so every replica gets an
    independent-looking but reproducible substream."""
    import random as _random

    from repro.serve import Request
    rng = _random.Random(seed)
    t = 0.0
    out = []
    for _ in range(n_requests):
        t += rng.expovariate(rate)
        out.append((t, Request(prompt_tokens=rng.randrange(8, 33),
                               max_new_tokens=rng.randrange(2, 9))))
    return out


def run_fleet(replicas: int = 2, n_requests: int = 48, rate: float = 40.0,
              seed: int = 0, router: str = "jsq", d: int = 256,
              dwell: int = 12, slo_ms: float = 5000.0) -> dict:
    """Fleet serving: router + shared spec plane, cross-replica warm starts.

    Two phases over one shared plane directory and one shared *portable*
    variant cache:

    1. **cold** — replica ``0`` alone serves its substream of the arrival
       schedule, pays the exploration (full sweep per context) and the
       compiles, and publishes its settled winners to the plane.
    2. **warm fleet** — ``replicas`` fresh workers (ids ``1..N``) poll the
       plane before traffic, so every context is seeded and admits in
       EXPLOIT; the shared portable cache turns activation into cache
       hits.  A :class:`~repro.serve.fleet.ReplicaRouter` spreads the
       union of per-replica substreams across them.

    Acceptance: warm replicas recompile **nothing** (``xla_compiles == 0``
    on every warm replica), fleet goodput beats the single cold replica,
    and warm time-to-settled is >= 2x faster than cold.
    """
    import shutil
    import tempfile

    from repro.serve import OpenLoopSource, ServeMetrics, substream_seed
    from repro.serve.fleet import ReplicaRouter
    from repro.serve.fleet.worker import SubprocessReplica, worker_command

    root = tempfile.mkdtemp(prefix="fleet_bench_")
    plane_dir = os.path.join(root, "plane")
    cache_dir = os.path.join(root, "cache")

    def spawn(replica_id: str) -> SubprocessReplica:
        from repro.core import telemetry
        cmd = worker_command(
            "--replica-id", replica_id,
            "--plane-dir", plane_dir, "--plane-poll-s", "0.2",
            "--cache-dir", cache_dir, "--d", str(d), "--dwell", str(dwell),
            "--slo-ms", str(slo_ms), "--max-wall-s", "120",
            # with the front's flight recorder on, workers forward their
            # event streams for one merged per-replica trace
            *(("--telemetry",) if telemetry.bus() is not None else ()))
        return SubprocessReplica(cmd, name=replica_id)

    def drive(sink, schedule) -> float:
        """Pump one open-loop schedule to exhaustion; returns the wall
        seconds of the traffic window (arrivals are exogenous — the pump
        loop sleeps to the next due offset, never on service)."""
        src = OpenLoopSource(sink, schedule)
        t0 = time.perf_counter()
        while not src.exhausted:
            now = time.perf_counter()
            src.pump(now)
            due = src.next_due(time.perf_counter())
            if due:
                time.sleep(min(due, 0.02))
        return time.perf_counter() - t0

    def replica_section(stats: dict | None) -> dict:
        if stats is None:
            return {"alive": False}
        comp = stats.get("compile", {})
        return {
            "alive": True,
            "replica": stats.get("replica"),
            "xla_compiles": comp.get("xla_compiles"),
            "cache_hits": comp.get("cache_hits"),
            "time_to_settled_s": stats.get("time_to_settled_s"),
            "completed": stats.get("metrics", {}).get("completed"),
            "settled": stats.get("settled"),
        }

    try:
        # -- phase 1: one cold replica explores and publishes ----------------
        cold = spawn("0")
        if not cold.wait_ready(300.0):
            cold.join(10.0)
            raise RuntimeError("cold fleet replica failed to start")
        t0 = time.perf_counter()
        drive(cold, _fleet_schedule(n_requests, rate,
                                    substream_seed(seed, "0")))
        cold.close()
        cold_stats = cold.join(300.0)
        cold_wall = time.perf_counter() - t0
        if cold_stats is None:
            raise RuntimeError("cold fleet replica died without stats")

        # -- phase 2: N fresh replicas warm-start off the plane --------------
        warm = [spawn(str(i + 1)) for i in range(replicas)]
        for r in warm:
            if not r.wait_ready(300.0):
                for w in warm:
                    w.close()
                    w.join(10.0)
                raise RuntimeError(f"warm replica {r.name} failed to start")
        front = ReplicaRouter(warm, policy=router)
        union = []
        for r in warm:
            union.extend(_fleet_schedule(n_requests, rate,
                                         substream_seed(seed, r.name)))
        t0 = time.perf_counter()
        drive(front, union)
        for r in warm:
            r.close()
        warm_stats = [r.join(300.0) for r in warm]
        fleet_wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(root, ignore_errors=True)

    live = [s for s in warm_stats if s is not None]
    merged = ServeMetrics.merge(*(s["metrics"] for s in live)) if live \
        else ServeMetrics()

    def goodput(metrics_state: dict, wall: float) -> float:
        return metrics_state.get("goodput_tokens", 0) / max(wall, 1e-9)

    single_good = goodput(cold_stats["metrics"], cold_wall)
    fleet_good = goodput(merged.state(), fleet_wall)
    cold_tts = cold_stats.get("time_to_settled_s")
    warm_tts = [s.get("time_to_settled_s") for s in live]
    worst_warm_tts = (max(t for t in warm_tts)
                      if warm_tts and all(t is not None for t in warm_tts)
                      else None)
    speedup = (cold_tts / max(worst_warm_tts, 1e-9)
               if cold_tts is not None and worst_warm_tts is not None
               else None)
    warm_recompiles = sum(int(s["compile"].get("xla_compiles", 0) or 0)
                          for s in live)
    return {
        "replicas": replicas,
        "router": router,
        "requests_per_replica": n_requests,
        "rate_per_replica": rate,
        "single": {
            "goodput_tok_per_s": round(single_good, 2),
            "wall_s": round(cold_wall, 3),
            "time_to_settled_s": cold_tts,
            **replica_section(cold_stats),
        },
        "fleet": {
            "goodput_tok_per_s": round(fleet_good, 2),
            "wall_s": round(fleet_wall, 3),
            "completed": merged.completed,
            "goodput_tokens": merged.goodput_tokens,
            "latency_p95_ms": round(merged.percentile(95) * 1e3, 3)
            if merged.completed else None,
            "per_replica": [replica_section(s) for s in warm_stats],
        },
        "goodput_scaling_x": (round(fleet_good / single_good, 3)
                              if single_good > 0 else None),
        "warm_recompiles": warm_recompiles,
        "warm_recompiles_zero": (len(live) == len(warm_stats)
                                 and warm_recompiles == 0),
        "fleet_goodput_gt_single": fleet_good > single_good,
        "time_to_settled_speedup_x": (round(speedup, 2)
                                      if speedup is not None else None),
        "warm_start_2x_faster": speedup is not None and speedup >= 2.0,
    }


def _calibrate_tenant_step(arch: str, batch: int, max_len: int,
                           chunk: int, reps: int = 5) -> dict:
    """Median seconds per (phase,) serve step of one reduced model at the
    serving bucket, through the real phase-disaggregated handler on its
    default config — the per-step costs the tenant scenario's deadline
    prediction is built from."""
    from repro.training import make_serve_builder, phase_context_fn

    cfg = configs.get_reduced(arch).replace(compute_dtype="float32")
    rt = IridescentRuntime(async_compile=False)
    handler = rt.register(f"tenant_calib[{arch}]",
                          make_serve_builder(cfg, kernel_impl="xla"),
                          context_fn=phase_context_fn, donate_argnums=1)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    run_opts = RunOptions(decode_cache_dtype="float32")
    out = {}
    for phase in ("prefill", "decode"):
        if phase == "prefill":
            tokens = jnp.zeros((batch, chunk), jnp.int32)
            n_new = jnp.full((batch,), chunk, jnp.int32)
        else:
            tokens = jnp.zeros((batch,), jnp.int32)
            n_new = jnp.ones((batch,), jnp.int32)
        pos = jnp.zeros((batch,), jnp.int32)
        cache = model.init_cache(cfg, batch, max_len, run_opts)
        logits, cache = handler(params, cache, tokens, pos, n_new)
        jax.block_until_ready(logits)          # warm the variant
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            logits, cache = handler(params, cache, tokens, pos, n_new)
            jax.block_until_ready(logits)
            ts.append(time.perf_counter() - t0)
        out[phase] = sorted(ts)[len(ts) // 2]
    rt.shutdown()
    return out


def run_tenants(tight_arch: str = "qwen3-0.6b",
                loose_arch: str = "rwkv6-1.6b", batch: int = 4,
                max_len: int = 160, chunk: int = 32, dwell: int = 3,
                n_tight: int = 20, tight_prompt: int = 128,
                tight_budget: int = 6, loose_prompt: int = 64,
                loose_budget: int = 16, loose_mult: float = 8.0,
                tight_weight: float = 2.0, loose_weight: float = 1.0,
                max_wall_s: float = 240.0) -> dict:
    """Multi-tenant serving: per-tenant specialization + DRR isolation.

    Two real reduced models share one engine through the multi-tenant
    plane (:mod:`repro.serve.tenancy`): a **tight** qwen3 tenant whose
    burst carries a calibrated deadline, and a **loose** rwkv6 tenant
    flooding the queue with long-budget work under an effectively
    infinite deadline.  Each tenant's traffic dispatches through its own
    ``(tenant, phase, bucket)`` contexts, so the shared runtime runs two
    independent Controller searches, each over ``SERVE_SEARCH_POINTS`` of
    its own handler.  The settled configs are structurally distinct, the
    first acceptance criterion: each handler declares its mixer's points
    (``attention_impl`` for the attention tenant, ``chunk_len`` and
    ``linear_attention_impl`` for the rwkv tenant), so the two tenants'
    configs differ in their keys whichever ``rmsnorm_impl`` each settles
    on.

    Isolation is a three-run makespan comparison on identical tight
    bursts (the loose flood arrives *before* the tight burst in both
    mixed runs):

    * **solo** — the tight tenant alone: the reference in-SLO tokens.
    * **drr**  — both tenants under :class:`DeficitRoundRobin`: the
      flood cannot displace the tight tenant's weighted share, so its
      burst drains within ~``1 + (w_l/w_t)`` of the solo makespan.
    * **fcfs** — both tenants under plain FCFS: the earlier-arrived
      flood is served to exhaustion first, pushing the tight burst past
      ``loose_mult`` solo makespans.

    Every run is **two passes over the same engine**: a warmup pass
    (huge deadlines) pays all compiles and settles every Controller,
    then the measured pass replays the schedule against the real
    deadline with the engine in steady-state exploit — so the measured
    numbers reflect scheduling, not compile noise.  The shared deadline
    is the geometric mean of the predicted DRR and FCFS tight-burst
    makespans (from per-phase step costs measured on this host), met by
    DRR and missed by FCFS with the same multiplicative margin.
    Acceptance: ``distinct_tenant_configs`` and ``drr_isolation`` (DRR
    in-SLO tight tokens >= 0.8x solo while FCFS falls below 0.8x).
    """
    from repro.serve import (AdmissionQueue, ContinuousBatcher,
                             ControllerGroup, DeficitRoundRobin,
                             MultiTenantExecutor, OpenLoopSource, PagedKV,
                             PhasedExecutor, Request, ServeEngine,
                             ServeMetrics, make_scheduler,
                             make_tenant_context_fn)
    from repro.training import make_serve_builder, phase_context_fn

    import shutil
    import tempfile

    # -- calibration: per-phase step costs of each model on this host ------
    costs = {"tight": _calibrate_tenant_step(tight_arch, batch, max_len,
                                             chunk),
             "loose": _calibrate_tenant_step(loose_arch, batch, max_len,
                                             chunk)}
    overhead = _calibrate_engine_overhead()

    def s_req(who: str, prompt: int, budget: int) -> float:
        steps_pre = -(-prompt // chunk)
        return (steps_pre * (costs[who]["prefill"] + overhead)
                + budget * (costs[who]["decode"] + overhead))

    s_tight = s_req("tight", tight_prompt, tight_budget)
    s_loose = s_req("loose", loose_prompt, loose_budget)
    m_tight = n_tight / batch * s_tight        # solo tight makespan
    # Flood sized to bury the tight burst `loose_mult` deep under FCFS.
    n_loose = max(24, min(120, batch * round(
        loose_mult * m_tight / max(s_loose, 1e-9))))
    n_loose -= n_loose % batch
    m_loose = n_loose / batch * s_loose
    # DRR prediction: the tight burst's own service plus the loose tokens
    # DRR interleaves during contention (w_l/w_t per tight token) at the
    # loose model's per-token cost.
    ptc_loose = s_loose / (loose_prompt + loose_budget)
    drr_pred = m_tight + (loose_weight / tight_weight) * n_tight * \
        (tight_prompt + tight_budget) * ptc_loose
    fcfs_pred = m_loose + m_tight
    deadline = (drr_pred * fcfs_pred) ** 0.5

    def tight_schedule(deadline_s: float):
        return [(0.05 + i * 1e-4,
                 Request(tenant="tight", prompt_tokens=tight_prompt,
                         max_new_tokens=tight_budget,
                         deadline_s=deadline_s))
                for i in range(n_tight)]

    def loose_schedule():
        return [(i * 1e-4,
                 Request(tenant="loose", prompt_tokens=loose_prompt,
                         max_new_tokens=loose_budget, deadline_s=1e6))
                for i in range(n_loose)]

    cache_root = tempfile.mkdtemp(prefix="tenant_bench_")

    def run_once(kind: str) -> dict:
        tenants = [("tight", tight_arch)] + (
            [("loose", loose_arch)] if kind != "solo" else [])
        # One runtime, one CompileService, one variant cache for every
        # tenant — shared across the three runs so repeat activations of
        # the same (model, config) variant are cache hits, as in a fleet.
        rt = IridescentRuntime(async_compile=False,
                               variant_cache=os.path.join(cache_root,
                                                          "variants"))
        latency = {}                # full context key -> seconds EWMA

        def context_latency_rate(view):
            v = latency[view.key].value if view.key in latency else None
            return 1.0 / max(v, 1e-9) if v else 0.0

        pairs, executors = [], {}
        for name, arch in tenants:
            cfg = configs.get_reduced(arch).replace(
                compute_dtype="float32")
            ctx_fn = make_tenant_context_fn(name, phase_context_fn)
            handler = rt.register(f"serve_step[{name}]",
                                  make_serve_builder(cfg,
                                                     kernel_impl="xla"),
                                  context_fn=ctx_fn, donate_argnums=1)
            params = model.init_params(jax.random.PRNGKey(0), cfg)
            run_opts = RunOptions(decode_cache_dtype="float32")
            kv = PagedKV(model.init_cache(cfg, 1, max_len, run_opts),
                         model.cache_axes(cfg), max_len=max_len,
                         capacity_tokens=batch * max_len, page_size=16)

            def timed(params, cache, tokens, pos, n_new,
                      _h=handler, _ctx=ctx_fn):
                key = _ctx((params, cache, tokens, pos, n_new), {})
                t0 = time.perf_counter()
                logits, new_cache = _h(params, cache, tokens, pos, n_new)
                jax.block_until_ready(logits)
                latency.setdefault(key, EWMA(0.5)).update(
                    time.perf_counter() - t0)
                return logits, new_cache

            executors[name] = PhasedExecutor(timed, params, kv,
                                             prefill_chunk=chunk,
                                             vocab_size=cfg.vocab_size)
            space = handler.spec_space()
            controller = Controller(
                handler,
                (lambda space=space:
                 ExhaustiveSweep.from_space(space, SERVE_SEARCH_POINTS)),
                metric=context_latency_rate, dwell=dwell,
                change_detector=lambda: ChangeDetector(float("inf")),
                wait_compiles=True, prefetch=0)
            pairs.append((handler, controller))

        group = ControllerGroup(pairs)
        if kind == "fcfs":
            scheduler = make_scheduler("fcfs")
        else:
            scheduler = DeficitRoundRobin({"tight": tight_weight,
                                           "loose": loose_weight})
        metrics = ServeMetrics(slo_s=deadline)
        engine = ServeEngine(
            pairs[0][0], group, ContinuousBatcher(batch, scheme="single"),
            scheduler, executor=MultiTenantExecutor(executors),
            queue=AdmissionQueue(depth=n_tight + n_loose + batch),
            metrics=metrics, slo_s=deadline)

        def serve_pass(deadline_s: float) -> float:
            # No drain between passes: ``run`` serves the schedule to
            # exhaustion on its own, and ``drain`` would close admission
            # for the next pass.
            schedule = ([] if kind == "solo" else loose_schedule()) \
                + tight_schedule(deadline_s)
            source = OpenLoopSource(engine.queue, schedule)
            t0 = time.perf_counter()
            engine.run(source=source, duration_s=max_wall_s)
            return time.perf_counter() - t0

        # Warmup pass(es): pay every compile, settle every Controller.
        warm_wall = serve_pass(1e6)
        warm_tries = 1
        while not group.settled() and warm_tries < 3:
            warm_wall += serve_pass(1e6)
            warm_tries += 1

        def tenant_counts():
            return {t: (ch.goodput_tokens, ch.completed, ch.slo_missed)
                    for t, ch in metrics.tenants().items()}

        before = tenant_counts()
        wall = serve_pass(deadline)            # the measured pass
        after = tenant_counts()
        per_tenant = {
            t: {"goodput_tokens": after[t][0] - before.get(t, (0,) * 3)[0],
                "completed": after[t][1] - before.get(t, (0,) * 3)[1],
                "slo_missed": after[t][2] - before.get(t, (0,) * 3)[2]}
            for t in after}
        configs_by_tenant = {
            h.name.split("[", 1)[1].rstrip("]"): {
                str(k): {kk: repr(vv) for kk, vv in (cfg_ or {}).items()}
                for k, cfg_ in ctl.best_configs().items()}
            for h, ctl in group.pairs}
        stats = engine.stats()
        row = {
            "kind": kind,
            "warmup_wall_s": round(warm_wall, 3),
            "warmup_passes": warm_tries,
            "wall_s": round(wall, 3),
            "settled": group.settled(),
            "tenants": per_tenant,
            "configs": configs_by_tenant,
            "tenant_steps": dict(stats.get("tenant_steps", {})),
            "compile": rt.compile_stats(),
        }
        if "scheduler" in stats:
            row["scheduler"] = stats["scheduler"]
        engine.shutdown()
        return row

    try:
        solo = run_once("solo")
        drr = run_once("drr")
        fcfs = run_once("fcfs")
    finally:
        shutil.rmtree(cache_root, ignore_errors=True)

    solo_good = solo["tenants"].get("tight", {}).get("goodput_tokens", 0)
    drr_good = drr["tenants"].get("tight", {}).get("goodput_tokens", 0)
    fcfs_good = fcfs["tenants"].get("tight", {}).get("goodput_tokens", 0)
    tight_cfgs = {json.dumps(c, sort_keys=True)
                  for c in drr["configs"].get("tight", {}).values()}
    loose_cfgs = {json.dumps(c, sort_keys=True)
                  for c in drr["configs"].get("loose", {}).values()}
    distinct = (drr["settled"] and bool(tight_cfgs) and bool(loose_cfgs)
                and tight_cfgs.isdisjoint(loose_cfgs))
    return {
        "tight": {"arch": tight_arch, "n": n_tight,
                  "prompt": tight_prompt, "budget": tight_budget,
                  "weight": tight_weight},
        "loose": {"arch": loose_arch, "n": n_loose,
                  "prompt": loose_prompt, "budget": loose_budget,
                  "weight": loose_weight},
        "batch": batch,
        "prefill_chunk": chunk,
        "calibration_ms": {
            **{f"{who}_{p}": round(c * 1e3, 3)
               for who, by_phase in costs.items()
               for p, c in by_phase.items()},
            "engine_overhead": round(overhead * 1e3, 3)},
        "predicted_ms": {"solo": round(m_tight * 1e3, 3),
                         "drr": round(drr_pred * 1e3, 3),
                         "fcfs": round(fcfs_pred * 1e3, 3)},
        "deadline_ms": round(deadline * 1e3, 3),
        "solo": solo,
        "drr": drr,
        "fcfs": fcfs,
        "tight_goodput_tokens": {"solo": solo_good, "drr": drr_good,
                                 "fcfs": fcfs_good},
        "drr_x_solo": (round(drr_good / solo_good, 3)
                       if solo_good else None),
        "fcfs_x_solo": (round(fcfs_good / solo_good, 3)
                        if solo_good else None),
        "distinct_tenant_configs": distinct,
        "drr_isolation": (solo_good > 0
                          and drr_good >= 0.8 * solo_good
                          and fcfs_good < 0.8 * solo_good),
    }


def _safety_builder(state):
    """Bench handler whose per-mode cost is a host-side sleep.

    ``mode`` is the spec point under search; the sleep magnitudes live in
    the mutable ``state`` dict read *at call time* through
    ``jax.pure_callback``, so the bench driver can degrade a mode
    mid-run (the injected fault) without recompiling anything:

    * ``split``  — the dependable incumbent (moderate, stable sleep),
    * ``fused``  — the attractive candidate (fast… until
      ``state["degraded"]`` flips, then it costs ``degrade_s``),
    * ``bad``    — the deliberately-broken candidate (always slow).

    Every mode routes through the same callback (sleep 0 where not
    penalised) so the host-roundtrip overhead is symmetric, and the
    callback's result is folded into the output so XLA cannot elide it.
    """
    _np = __import__("numpy")

    def build(spec):
        mode = spec.enum("mode", "split", ("split", "fused", "bad"),
                         guarded=False)

        def cb(_):
            s = (state["degrade_s"]
                 if (mode == "fused" and state["degraded"])
                 else state["sleep"][mode])
            if s > 0:
                time.sleep(s)
            return _np.float32(0.0)

        def f(x, w):
            if mode == "split":
                h = w.shape[1] // 2
                y = jnp.concatenate([x @ w[:, :h], x @ w[:, h:]], axis=-1)
            else:
                y = x @ w
            pen = jax.pure_callback(
                cb, jax.ShapeDtypeStruct((), jnp.float32), x[0, 0])
            return y + pen

        return f

    return build


def _calibrate_safety_step(d: int, batch: int, reps: int = 7) -> float:
    """Median seconds per call of the safety handler with all sleeps at
    zero — the base cost (matmul + dispatch + pure_callback roundtrip)
    the synthetic mode latencies sit on top of."""
    state = {"degraded": False, "degrade_s": 0.0,
             "sleep": {"split": 0.0, "fused": 0.0, "bad": 0.0}}
    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("safety_calib", _safety_builder(state),
                          context_fn=lambda a, k: int(a[0].shape[0]))
    w = jnp.zeros((d, d), jnp.float32)
    x = jnp.zeros((batch, d), jnp.float32)
    jax.block_until_ready(handler(x, w))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(handler(x, w))
        ts.append(time.perf_counter() - t0)
    rt.shutdown()
    return sorted(ts)[len(ts) // 2]


def run_safety(d: int = 256, batch: int = 8, n_requests: int = 160,
               rate: float = 8.0, budgets=(4, 8), seed: int = 13,
               dwell: int = 6, slo_slack: float = 2.0,
               grace_s: float = 0.25, split_ms: float = 5.0,
               fused_ms: float = 2.0, degrade_ms: float = 100.0,
               bad_ms: float = 120.0, max_wall_s: float = 90.0) -> dict:
    """Safe online exploration: shadow evaluation, canary activation and
    auto-rollback under a deliberately-broken candidate plus a
    post-promotion fault.

    The same open-loop schedule (exponential interarrivals at ``rate``)
    is served three times through the same engine/handler; per-mode cost
    is a host sleep (:func:`_safety_builder`), so the margins are
    deterministic on any host:

    * **baseline** — plain Controller, candidate set {split, fused}, no
      fault: the no-injection reference goodput.
    * **unsafe**   — plain Controller with the broken ``bad`` candidate
      in the sweep; the moment the search settles on ``fused``, that
      config degrades (``degrade_ms`` per call, an adoption-correlated
      fault).  The live sweep serves ``bad`` to real requests for a full
      dwell, and the degradation lands inside the fresh ChangeDetector's
      warmup window, so it is silently absorbed as the new baseline —
      the context serves degraded ``fused`` for the rest of the run.
    * **safe**     — SafetyController + ShadowEvaluator (idle-tick
      mirrored pairs): ``bad`` is rejected in shadow without a single
      live call; ``fused`` passes shadow, canaries, and promotes; the
      same degradation then fires the seeded detector in one dwell and
      auto-rollback reverts to the last-known-good incumbent and
      quarantines ``fused``.

    Per-request deadlines are ``slack x budget x`` the *incumbent* step
    cost plus a fixed ``grace_s`` — sized so a shadow-pair stall
    (``<= bad_ms``) never blows a deadline while a degraded live token
    stream (``budget x degrade_ms``) always does.

    Every live call samples both dispatch slots (active + canary), so
    the output *proves* the two safety claims rather than asserting
    them: ``bad`` never occupies a slot with safety on (it does in the
    unsafe run), and after the rollback no sampled slot config was in
    quarantine at sample time.  Acceptance: ``rollbacks >= 1``, safe
    goodput >= 0.9x the no-injection baseline while the unsafe run
    falls below it, and zero quarantine violations.
    """
    import random as _random

    from repro.serve import (AdmissionQueue, ContinuousBatcher,
                             OpenLoopSource, Request, ServeEngine,
                             ServeMetrics, ShadowEvaluator,
                             ShortestJobFirst)

    split_s, fused_s = split_ms * 1e-3, fused_ms * 1e-3
    degrade_s, bad_s = degrade_ms * 1e-3, bad_ms * 1e-3
    c0 = _calibrate_safety_step(d, batch)
    overhead = _calibrate_engine_overhead()
    # Deadline: slack x the incumbent (split) per-token cost, plus a
    # fixed grace absorbing bounded stalls (a shadow pair holds the loop
    # for <= bad_s + split_s, under the grace by construction).
    slo_per_token = slo_slack * (split_s + c0 + overhead)

    def schedule():
        rng = _random.Random(seed)
        out, t = [], 0.0
        for _ in range(n_requests):
            t += rng.expovariate(rate)
            g = rng.choice(budgets)
            out.append((t, Request(prompt_tokens=16, max_new_tokens=g,
                                   deadline_s=g * slo_per_token + grace_s)))
        return out

    w = jnp.zeros((d, d), jnp.float32)

    def run_once(kind: str) -> dict:
        state = {"degraded": False, "degrade_s": degrade_s,
                 "sleep": {"split": split_s, "fused": fused_s,
                           "bad": bad_s}}
        rt = IridescentRuntime(async_compile=False)
        handler = rt.register("safety_step", _safety_builder(state),
                              context_fn=lambda a, k: int(a[0].shape[0]))
        candidates = [{"mode": "split"}, {"mode": "fused"}]
        if kind != "baseline":
            candidates.append({"mode": "bad"})     # the injected fault
        latency = {}

        def context_latency_rate(view):
            v = latency[view.key].value if view.key in latency else None
            return 1.0 / max(v, 1e-9) if v else 0.0

        # Sync compiles + wait_compiles=True as in run_disagg: dwell
        # attribution over compile pipelining (covered elsewhere).
        kwargs = dict(metric=context_latency_rate, dwell=dwell,
                      change_detector=lambda: ChangeDetector(0.3),
                      wait_compiles=True, prefetch=0)
        shadow = None
        if kind == "safe":
            shadow = ShadowEvaluator(handler, sample_frac=0.25, k=3,
                                     tolerance=1.5)
            controller = SafetyController(
                handler, lambda: ExhaustiveSweep(candidates),
                shadow=shadow, canary_frac=0.25, promote_after=2,
                **kwargs)
        else:
            controller = Controller(
                handler, lambda: ExhaustiveSweep(candidates), **kwargs)

        slots = {"modes": {}, "bad_live": 0, "quarantine_violations": 0}
        flip = {"t": None}
        t_start = 0.0

        def maybe_flip():
            # The adoption-correlated fault: fused degrades the moment
            # the system adopts it for live traffic — at promotion with
            # safety on, at settling without.
            if kind == "baseline" or flip["t"] is not None:
                return
            if (controller.promotions >= 1 if kind == "safe"
                    else controller.settled()):
                state["degraded"] = True
                flip["t"] = time.perf_counter() - t_start

        def timed_handler(x, w):
            key = int(x.shape[0])
            view = handler.context(key)
            for cfg in (view.active_config(), view.canary_config()):
                if not cfg:
                    continue             # empty = generic incumbent
                m = cfg.get("mode", "split")
                slots["modes"][m] = slots["modes"].get(m, 0) + 1
                if m == "bad":
                    slots["bad_live"] += 1
                if (controller.quarantine is not None
                        and controller.quarantine.blocked(
                            handler.name, key, cfg)):
                    slots["quarantine_violations"] += 1
            maybe_flip()
            t0 = time.perf_counter()
            y = handler(x, w)
            jax.block_until_ready(y)
            latency.setdefault(key, EWMA(0.5)).update(
                time.perf_counter() - t0)
            return y

        class Exec:
            def execute(self, batch):
                timed_handler(jnp.zeros((batch.size, d), jnp.float32), w)

        metrics = ServeMetrics()
        engine = ServeEngine(
            handler, controller, ContinuousBatcher(batch, scheme="single"),
            ShortestJobFirst(), executor=Exec(),
            queue=AdmissionQueue(depth=n_requests + batch,
                                 policy="shed-oldest"),
            metrics=metrics, shadow=shadow)
        source = OpenLoopSource(engine.queue, schedule())
        t_start = time.perf_counter()
        engine.run(source=source, duration_s=max_wall_s)
        engine.drain(timeout_s=max_wall_s / 2)
        wall = time.perf_counter() - t_start
        stats = engine.stats()
        serve = stats["serve"]
        best = controller.best_configs().get(batch) or {}
        row = {
            "kind": kind,
            "wall_s": round(wall, 3),
            "offered": stats["queue"]["submitted"],
            "completed": serve["completed"],
            "completed_tokens": serve["completed_tokens"],
            "goodput_tok_per_s": round(serve["goodput_tokens"] / wall, 2),
            "tok_per_s": round(serve["completed_tokens"] / wall, 2),
            "slo_met": serve["slo_met"],
            "slo_missed": serve["slo_missed"],
            "shed": stats["queue"]["shed"] + serve["shed"],
            "latency_p50_ms": serve["latency_p50_ms"],
            "latency_p95_ms": serve["latency_p95_ms"],
            "settled_mode": best.get("mode"),
            "fault_injected_at_s": (round(flip["t"], 3)
                                    if flip["t"] is not None else None),
            "live_slot_modes": dict(slots["modes"]),
            "bad_live_slot_samples": slots["bad_live"],
            "quarantine_violations": slots["quarantine_violations"],
        }
        if "safety" in stats:
            row["safety"] = stats["safety"]
        if "shadow" in stats:
            row["shadow"] = stats["shadow"]
        if shadow is not None:
            shadow.close()
        rt.shutdown()
        return row

    baseline = run_once("baseline")
    unsafe = run_once("unsafe")
    safe = run_once("safe")
    base_good = baseline["goodput_tok_per_s"]
    safety = safe.get("safety", {})
    violations = safe["quarantine_violations"]
    return {
        "seed": seed,
        "d": d,
        "batch": batch,
        "rate_per_s": rate,
        "n_requests": n_requests,
        "mode_latency_ms": {"split": split_ms, "fused": fused_ms,
                            "fused_degraded": degrade_ms, "bad": bad_ms},
        "calibration_ms": {"base_step": round(c0 * 1e3, 3),
                           "engine_overhead": round(overhead * 1e3, 3)},
        "slo_per_token_ms": round(slo_per_token * 1e3, 3),
        "grace_ms": round(grace_s * 1e3, 1),
        "baseline": baseline,
        "unsafe": unsafe,
        "safe": safe,
        "goodput_safe_x_baseline": (round(safe["goodput_tok_per_s"]
                                          / base_good, 3)
                                    if base_good > 0 else None),
        "goodput_unsafe_x_baseline": (round(unsafe["goodput_tok_per_s"]
                                            / base_good, 3)
                                      if base_good > 0 else None),
        "rollback_triggered": safety.get("rollbacks", 0) >= 1,
        "promoted_before_rollback": safety.get("promotions", 0) >= 1,
        "shadow_rejected_bad": safety.get("shadow_rejections", 0) >= 1,
        "bad_never_live_with_safety": safe["bad_live_slot_samples"] == 0,
        "bad_served_live_without_safety":
            unsafe["bad_live_slot_samples"] > 0,
        "quarantine_violations": violations,
        "quarantined_never_reactivated": violations == 0,
        "goodput_with_safety_ge_0.9x_baseline":
            safe["goodput_tok_per_s"] >= 0.9 * base_good,
        "unsafe_craters":
            unsafe["goodput_tok_per_s"] < 0.9 * base_good,
    }


def write_json(path: str, result: dict) -> None:
    with open(path, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
        f.write("\n")


def run() -> list[Row]:
    """benchmarks/run.py entry: CSV rows + BENCH_serve.json side artifact."""
    result = run_serve()
    result["mixed"] = run_mixed()
    result["open_loop"] = run_open_loop()
    result["disagg"] = run_disagg()
    result["fleet"] = run_fleet()
    result["tenants"] = run_tenants()
    result["safety"] = run_safety()
    write_json(os.environ.get("BENCH_SERVE_JSON", "BENCH_serve.json"), result)
    d = result["dispatch_overhead_us"]
    mixed = result["mixed"]
    ol = result["open_loop"]
    dg = result["disagg"]
    fl = result["fleet"]
    tn = result["tenants"]
    sf = result["safety"]
    return [
        Row("serve/tok_per_s", result["tok_per_s"],
            f"wall={result['wall_s']}s"),
        Row("serve/compile_total_s",
            result["compile"]["total_compile_s"] * 1e6,
            f"xla_compiles={result['compile']['xla_compiles']} "
            f"cache_hits={result['compile']['cache_hits']} "
            f"cancelled={result['compile']['cancelled']}"),
        Row("serve/dispatch_fast", d["trampoline_fast"],
            f"+{d['overhead']}us vs direct"),
        Row("serve/dispatch_contextual", d["trampoline_contextual"],
            f"+{d['contextual_overhead']}us vs fast path"),
        Row("serve/mixed_distinct_configs",
            float(mixed["distinct_configs"]),
            f"contexts={list(mixed['contexts'])}"),
        Row("serve/open_loop_goodput", ol["tuned"]["goodput_tok_per_s"],
            f"single={ol['single_bucket']['goodput_tok_per_s']} "
            f"scheme={ol['tuned']['scheme']}"),
        Row("serve/open_loop_p95_ms", ol["tuned"]["latency_p95_ms"],
            f"single={ol['single_bucket']['latency_p95_ms']}"),
        Row("serve/disagg_goodput", dg["disagg"]["goodput_tok_per_s"],
            f"baseline={dg['baseline']['goodput_tok_per_s']} "
            f"tiles=pre:{dg['disagg']['prefill_tile']}"
            f"/dec:{dg['disagg']['decode_tile']}"),
        Row("serve/disagg_distinct_configs",
            float(dg["distinct_phase_configs"]),
            f"ttft_p50={dg['disagg']['ttft_p50_ms']}ms"),
        Row("serve/fleet_goodput_scaling",
            fl["goodput_scaling_x"] or 0.0,
            f"fleet={fl['fleet']['goodput_tok_per_s']} "
            f"single={fl['single']['goodput_tok_per_s']} "
            f"router={fl['router']}"),
        Row("serve/fleet_warm_recompiles", float(fl["warm_recompiles"]),
            f"settle_speedup={fl['time_to_settled_speedup_x']}x"),
        Row("serve/tenants_drr_x_solo", tn["drr_x_solo"] or 0.0,
            f"fcfs={tn['fcfs_x_solo']} "
            f"distinct_configs={tn['distinct_tenant_configs']}"),
        Row("serve/tenants_drr_isolation", float(tn["drr_isolation"]),
            f"tight_tokens={tn['tight_goodput_tokens']}"),
        Row("serve/safety_goodput_x_baseline",
            sf["goodput_safe_x_baseline"] or 0.0,
            f"unsafe={sf['goodput_unsafe_x_baseline']} "
            f"rollbacks={sf['safe'].get('safety', {}).get('rollbacks')}"),
        Row("serve/safety_quarantine_violations",
            float(sf["quarantine_violations"]),
            f"bad_live_with_safety={sf['safe']['bad_live_slot_samples']} "
            f"without={sf['unsafe']['bad_live_slot_samples']}"),
    ]


_SCENARIOS = ("all", "serve", "mixed", "open_loop", "disagg", "fleet",
              "tenants", "safety")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--dwell", type=int, default=10)
    ap.add_argument("--compile-workers", type=int, default=2)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--cache-dir", default=None)
    ap.add_argument("--scenario", default="all", choices=_SCENARIOS,
                    help="which section(s) to run; non-'all' runs merge "
                         "into an existing --out file when present")
    ap.add_argument("--open-loop-phase-s", type=float, default=1.5,
                    help="seconds per rate-ramp phase of the open-loop "
                         "scenario (3 phases)")
    ap.add_argument("--fleet-replicas", type=int, default=2,
                    help="warm replica count for the fleet scenario")
    ap.add_argument("--fleet-router", default="jsq",
                    help="routing policy for the fleet scenario "
                         "(round-robin | jsq | spill)")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--trace-out", default=None,
                    help="enable the flight-recorder bus for the run and "
                         "write its stream as Chrome-trace JSON here")
    args = ap.parse_args()
    if args.trace_out:
        from repro.core import telemetry
        telemetry.enable()
    result: dict = {}
    if args.scenario != "all" and os.path.exists(args.out):
        try:
            with open(args.out) as f:
                result = json.load(f)
        except ValueError:
            result = {}
    if args.scenario in ("all", "serve"):
        result.update(run_serve(
            steps=args.steps, arch=args.arch, batch=args.batch,
            max_len=args.max_len, dwell=args.dwell,
            compile_workers=args.compile_workers,
            prefetch=args.prefetch, cache_dir=args.cache_dir))
    if args.scenario in ("all", "mixed"):
        result["mixed"] = run_mixed()
    if args.scenario in ("all", "open_loop"):
        result["open_loop"] = run_open_loop(
            phase_s=args.open_loop_phase_s)
    if args.scenario in ("all", "disagg"):
        result["disagg"] = run_disagg()
    if args.scenario in ("all", "fleet"):
        result["fleet"] = run_fleet(replicas=args.fleet_replicas,
                                    router=args.fleet_router)
    if args.scenario in ("all", "tenants"):
        result["tenants"] = run_tenants()
    if args.scenario in ("all", "safety"):
        result["safety"] = run_safety()
    write_json(args.out, result)
    if args.trace_out:
        from repro.core import telemetry
        _tb = telemetry.bus()
        if _tb is not None:
            doc = telemetry.export_chrome_trace(_tb.events(), args.trace_out)
            print(f"trace: wrote {len(doc['traceEvents'])} events to "
                  f"{args.trace_out} ({json.dumps(_tb.stats())})")
    print(json.dumps(result, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
