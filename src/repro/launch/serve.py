"""Serving driver: continuous-batching LM decode with online specialization.

Run:
    PYTHONPATH=src python -m repro.launch.serve --steps 300
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve --reduced

The driver is built on the :mod:`repro.serve` engine: requests arrive
open-loop (deterministic pseudo-Poisson at ``--rate``), pass through a
bounded admission queue with backpressure, are ordered by a pluggable
scheduler (``--scheduler fcfs|sjf|deadline``), and are packed each
iteration into bucketed batch shapes by the continuous batcher.

Execution is **phase-disaggregated** over a **paged per-request KV
runtime**: every request's decode state lives in block-paged device pools
(:class:`~repro.serve.kv.PagedKV` — fixed-size pages, per-request page
tables, free-list reuse on retire), and each engine step runs either a
chunked-prefill or a decode batch through one registered serve handler
whose context key is ``(phase, bucket)``
(:func:`~repro.training.steps.phase_context_fn`).  The Iridescent
``Controller`` therefore tunes prefill and decode *separately* per
bucket — they are free to settle on different configs.  One more spec
point rides the same machinery: the bucket-boundary scheme
(``BucketTuner``), searched online against measured goodput (in-SLO
tokens/s).  The KV page size is a constant (``--kv-page-size``).

The model is served at its published widths in its own compute dtype
(``configs.get_config``); ``--reduced`` asks for the reduced same-family
preset in float32, which is what CPU runs and tests use.

**Fleet mode** (``--replicas N`` with N > 1): N engine replicas in this
process, one per device (``jax.devices()[i]``; a chip belongs to one
process, so one process drives them all), each served by its own thread
behind a :class:`~repro.serve.fleet.ReplicaRouter` that spreads the
open-loop load with the ``--router`` policy (round-robin /
join-shortest-queue / deadline-aware spill); it reports fleet-merged
metrics.  With ``--plane-dir`` the replicas share a specialization plane
(:class:`~repro.serve.fleet.SpecPlane`): each publishes its settled
per-context winners and seeds remotely-settled ones, so one replica's
exploration warm-starts the rest.
``--plane-dir`` also works at ``--replicas 1``: the single engine polls
the plane before serving and publishes its winners after draining
(cross-*run* warm start through the plane instead of spec_state.json).

Migration note: the old in-file ``DecodeExecutor`` (one shared ring
cache per bucket — a load harness, not a sampling-correctness harness)
moved to :mod:`repro.serve.executor` as the paged
``PrefillExecutor``/``DecodeExecutor`` pair behind a
:class:`~repro.serve.executor.PhasedExecutor`; decode is now real
(per-request isolated state, greedy sampling over synthetic prompts).
Every pre-engine flag (``--arch --batch --max-len --steps --dwell
--compile-workers --prefetch --budget --cache-dir``) is preserved;
``--batch`` caps the largest batch bucket and ``--steps`` caps engine
iterations.  With ``--cache-dir`` the runtime persists AOT executables
and the tuned per-context configurations — a drained and restarted
server resumes every context's tuned config with zero recompiles.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
from types import SimpleNamespace

from repro.core import telemetry
from repro.launch.jax_cache import enable_compile_cache
from repro.serve import Request, pseudo_poisson_times


def synthetic_workload(n: int, rate: float, seed: int = 0,
                       budgets=(4, 8, 16, 32),
                       prompts=(16, 64, 128), tenant: str | None = None,
                       deadline_s: float | None = None
                       ) -> list[tuple[float, Request]]:
    """Deterministic open-loop schedule: pseudo-Poisson arrivals at
    ``rate`` req/s with mixed prompt/decode lengths.  ``tenant`` and
    ``deadline_s`` stamp every request (multi-tenant runs give each
    tenant its own schedule off its own seed substream)."""
    rng = random.Random(seed)
    times = pseudo_poisson_times([(n / max(rate, 1e-9) * 4, rate)], seed=seed)
    return [(t, Request(prompt_tokens=rng.choice(prompts),
                        max_new_tokens=rng.choice(budgets),
                        tenant=tenant, deadline_s=deadline_s))
            for t in times[:n]]


def add_engine_args(ap: argparse.ArgumentParser) -> None:
    """The single-engine flag set (every replica of a fleet shares it)."""
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced same-family preset in float32 "
                         "(CPU runs and tests) instead of the published "
                         "config")
    ap.add_argument("--batch", type=int, default=8,
                    help="batch cap = largest batch-shape bucket")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--steps", type=int, default=240,
                    help="cap on engine iterations")
    ap.add_argument("--dwell", type=int, default=20)
    ap.add_argument("--compile-workers", type=int, default=2,
                    help="CompileService worker threads")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="speculative compiles ahead of the policy")
    ap.add_argument("--budget", type=float, default=None,
                    help="skip candidates whose expected compile cost "
                         "exceeds BUDGET x the expected dwell time "
                         "(CompileService telemetry; default: no gating)")
    ap.add_argument("--cache-dir", default=None,
                    help="persist AOT executables + tuned config here; a "
                         "warm restart then performs zero recompiles")
    ap.add_argument("--portable-cache", action="store_true",
                    help="drop the device count from the variant-cache "
                         "fingerprint so AOT artifacts are shareable "
                         "across fleet replicas (same platform/device "
                         "kind required)")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="KV page size (tokens per page)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens consumed per chunked-prefill step "
                         "(long prompts interleave with decode steps)")
    ap.add_argument("--requests", type=int, default=64,
                    help="open-loop workload size (per replica in fleet "
                         "mode: each replica's substream offers this many)")
    ap.add_argument("--rate", type=float, default=40.0,
                    help="mean arrival rate (req/s) of the open-loop load")
    ap.add_argument("--slo-ms", type=float, default=2000.0,
                    help="per-request arrival-to-finish SLO")
    ap.add_argument("--queue-depth", type=int, default=256,
                    help="admission queue bound (backpressure)")
    ap.add_argument("--shed-policy", default="reject",
                    choices=("reject", "shed-oldest"))
    ap.add_argument("--scheduler", default="fcfs",
                    choices=("fcfs", "sjf", "deadline", "drr"))
    ap.add_argument("--bucket-dwell", type=int, default=25,
                    help="engine steps per bucket-scheme candidate")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shadow-frac", type=float, default=0.25,
                    help="fraction of live calls mirrored for shadow "
                         "evaluation (0 disables shadowing; candidates "
                         "then go straight to canary)")
    ap.add_argument("--canary-frac", type=float, default=0.1,
                    help="slice of a context's live traffic a "
                         "shadow-passed candidate serves during canary "
                         "probation")
    ap.add_argument("--promote-after", type=int, default=2,
                    help="consecutive in-SLO canary dwells required "
                         "before a candidate is promoted")
    ap.add_argument("--no-safety", action="store_true",
                    help="disable shadow/canary/rollback and run the "
                         "plain Controller (pre-safety behavior)")


def build_engine(args, device=None) -> SimpleNamespace:
    """Build the full single-replica serving stack from parsed engine
    args; returns the runtime, engine, and every tuned part (a fleet runs
    exactly this stack per replica).  ``device`` holds the replica's
    weights and step inputs (``None``: the default device)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro import configs
    from repro.checkpoint import load_safety_state, restore_spec_state
    from repro.core import (ChangeDetector, Controller, ExhaustiveSweep,
                            IridescentRuntime, Quarantine, SafetyController,
                            VariantCache)
    from repro.core.runtime import decode_context_key
    from repro.models import transformer as model
    from repro.models.transformer import RunOptions
    from repro.serve import (AdmissionQueue, BucketTuner, ContinuousBatcher,
                             PagedKV, PhasedExecutor, ServeEngine,
                             ServeMetrics, ShadowEvaluator,
                             bucket_plan_builder, make_scheduler)
    from repro.serve.batcher import BUCKET_POINT
    from repro.training import (SERVE_SEARCH_POINTS, make_serve_builder,
                                phase_context_fn)

    cfg = configs.select(args.arch, args.reduced)
    device = device if device is not None else jax.devices()[0]
    variant_cache = None
    if args.cache_dir:
        variant_cache = VariantCache(
            os.path.join(args.cache_dir, "variants"),
            portable=getattr(args, "portable_cache", False))
    rt = IridescentRuntime(async_compile=True,
                           max_compile_workers=args.compile_workers,
                           variant_cache=variant_cache)
    handler = rt.register(
        "serve_step", make_serve_builder(cfg),
        context_fn=phase_context_fn,          # (phase, bucket) contexts
        donate_argnums=1)
    batcher = ContinuousBatcher(args.batch)
    plan_handler = rt.register(
        "bucket_plan",
        bucket_plan_builder(list(batcher.schemes), batcher.default_scheme))

    # Restore *before* building the controllers: per-(phase,bucket) configs
    # are seeded onto the handler (the Controller warm-starts each context
    # as its traffic materializes), and the tuned bucket scheme lands on
    # its plan handler's active config.
    spec_state_path = (os.path.join(args.cache_dir, "spec_state.json")
                       if args.cache_dir else None)
    initial_scheme = None
    restored = False
    if spec_state_path and restore_spec_state(spec_state_path, rt, wait=True):
        restored = True
        initial_scheme = plan_handler.active_config().get(BUCKET_POINT)

    params = init_serving_params(cfg, SingleDeviceSharding(device))
    run_opts = RunOptions(decode_cache_dtype=cfg.compute_dtype)
    kv = PagedKV(model.init_cache(cfg, 1, args.max_len, run_opts),
                 model.cache_axes(cfg), max_len=args.max_len,
                 capacity_tokens=args.batch * args.max_len,
                 page_size=args.kv_page_size, device=device)
    executor = PhasedExecutor(handler, params, kv,
                              prefill_chunk=args.prefill_chunk,
                              vocab_size=cfg.vocab_size)

    space = handler.spec_space()
    policy_factory = lambda: ExhaustiveSweep.from_space(
        space, SERVE_SEARCH_POINTS)
    controller_kwargs = dict(
        dwell=args.dwell, change_detector=lambda: ChangeDetector(0.3),
        wait_compiles=False, prefetch=args.prefetch, budget=args.budget)
    shadow = None
    if getattr(args, "no_safety", False):
        # Pre-safety behavior: candidates serve live traffic directly and
        # a detected change restarts exploration without rollback.
        controller = Controller(handler, policy_factory, **controller_kwargs)
    else:
        shadow_frac = getattr(args, "shadow_frac", 0.25)
        if shadow_frac and shadow_frac > 0:
            shadow = ShadowEvaluator(handler, sample_frac=shadow_frac)
        # Warm-start the safety plane from the previous run's v3 state:
        # last-known-good configs seed rollback targets; quarantined
        # configs are blocked before the first proposal.
        safety_init = (load_safety_state(spec_state_path).get(
            "serve_step", {}) if spec_state_path else {})
        quarantine = Quarantine()
        for enc, cfgs in (safety_init.get("quarantined") or {}).items():
            for q in cfgs:
                quarantine.add("serve_step", decode_context_key(enc), q)
        controller = SafetyController(
            handler, policy_factory, shadow=shadow,
            canary_frac=getattr(args, "canary_frac", 0.1),
            promote_after=getattr(args, "promote_after", 2),
            quarantine=quarantine,
            initial_last_known_good=safety_init.get("last_known_good"),
            **controller_kwargs)

    slo_s = args.slo_ms / 1e3
    metrics = ServeMetrics(slo_s=slo_s)
    tuner = BucketTuner(batcher, metric=metrics.interval_goodput,
                        dwell=args.bucket_dwell, plan_handler=plan_handler,
                        initial_scheme=initial_scheme, device=device)
    engine = ServeEngine(
        handler, controller, batcher, make_scheduler(args.scheduler),
        executor=executor,
        queue=AdmissionQueue(depth=args.queue_depth, policy=args.shed_policy),
        tuner=tuner, metrics=metrics, slo_s=slo_s,
        shadow=shadow)
    return SimpleNamespace(
        rt=rt, engine=engine, handler=handler, controller=controller,
        batcher=batcher, tuner=tuner, kv=kv,
        metrics=metrics, restored=restored, initial_scheme=initial_scheme,
        shadow=shadow, cfg=cfg, params=params,
        executor=executor, device=device, policy_factory=policy_factory)


def init_serving_params(cfg, sharding):
    """Seeded random weights (``PRNGKey(0)``) in the config's compute
    dtype, placed by ``sharding``: serving keeps no float32 masters."""
    import jax

    from repro.models import transformer as model

    def init(key):
        return jax.tree.map(lambda a: a.astype(cfg.compute_dtype),
                            model.init_params(key, cfg))

    return jax.jit(init, out_shardings=sharding)(jax.random.PRNGKey(0))


def build_tenant_engine(args, tenants) -> SimpleNamespace:
    """Build one multi-tenant engine: N models, one runtime, one
    CompileService, one variant cache.

    Each :class:`~repro.serve.tenancy.TenantSpec` gets its own registered
    handler ``serve_step[name]`` whose context key is ``(tenant, phase,
    bucket)``, its own params/paged-KV/executor, and its own Controller —
    aggregated behind a :class:`~repro.serve.tenancy.ControllerGroup` and
    a :class:`~repro.serve.tenancy.MultiTenantExecutor`.  Scheduling
    between tenants defaults to weighted-fair DRR (``--scheduler drr``)
    using each tenant's declared weight.  The bucket tuner and the
    safety plane are single-model machinery and stay off here
    (tenant engines run plain Controllers with a fixed bucket scheme).
    """
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro import configs
    from repro.checkpoint import restore_spec_state
    from repro.core import (ChangeDetector, Controller, ExhaustiveSweep,
                            IridescentRuntime, VariantCache)
    from repro.models import transformer as model
    from repro.models.transformer import RunOptions
    from repro.serve import (AdmissionQueue, ContinuousBatcher,
                             ControllerGroup, DeficitRoundRobin,
                             MultiTenantExecutor, PagedKV, PhasedExecutor,
                             ServeEngine, ServeMetrics,
                             make_scheduler, make_tenant_context_fn)
    from repro.training import (SERVE_SEARCH_POINTS, make_serve_builder,
                                phase_context_fn)

    names = [t.name for t in tenants]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate tenant names: {names}")
    variant_cache = None
    if args.cache_dir:
        variant_cache = VariantCache(
            os.path.join(args.cache_dir, "variants"),
            portable=getattr(args, "portable_cache", False))
    rt = IridescentRuntime(async_compile=True,
                           max_compile_workers=args.compile_workers,
                           variant_cache=variant_cache)

    stacks = {}
    for spec in tenants:
        cfg = configs.select(spec.arch, args.reduced)
        handler = rt.register(
            f"serve_step[{spec.name}]",
            make_serve_builder(cfg),
            context_fn=make_tenant_context_fn(spec.name, phase_context_fn),
            donate_argnums=1)
        stacks[spec.name] = SimpleNamespace(spec=spec, cfg=cfg,
                                            handler=handler)

    # Restore before building controllers (same ordering contract as the
    # single-model path): every tenant's settled (tenant, phase, bucket)
    # contexts seed onto its handler, keyed losslessly by the tuple codec.
    spec_state_path = (os.path.join(args.cache_dir, "spec_state.json")
                       if args.cache_dir else None)
    restored = bool(spec_state_path
                    and restore_spec_state(spec_state_path, rt, wait=True))

    pairs = []
    executors = {}
    for spec in tenants:
        st = stacks[spec.name]
        cfg = st.cfg
        params = init_serving_params(
            cfg, SingleDeviceSharding(jax.devices()[0]))
        run_opts = RunOptions(decode_cache_dtype=cfg.compute_dtype)
        kv = PagedKV(model.init_cache(cfg, 1, args.max_len, run_opts),
                     model.cache_axes(cfg), max_len=args.max_len,
                     capacity_tokens=args.batch * args.max_len,
                     page_size=args.kv_page_size)
        st.kv = kv
        executors[spec.name] = PhasedExecutor(
            st.handler, params, kv, prefill_chunk=args.prefill_chunk,
            vocab_size=cfg.vocab_size)
        space = st.handler.spec_space()
        st.controller = Controller(
            st.handler,
            (lambda space=space:
             ExhaustiveSweep.from_space(space, SERVE_SEARCH_POINTS)),
            dwell=args.dwell, change_detector=lambda: ChangeDetector(0.3),
            wait_compiles=False, prefetch=args.prefetch, budget=args.budget)
        pairs.append((st.handler, st.controller))

    group = ControllerGroup(pairs)
    tenant_slos = {t.name: t.slo_s for t in tenants if t.slo_s is not None}
    if args.scheduler == "drr":
        scheduler = DeficitRoundRobin({t.name: t.weight for t in tenants})
    else:
        scheduler = make_scheduler(args.scheduler)
    slo_s = args.slo_ms / 1e3
    metrics = ServeMetrics(slo_s=slo_s, tenant_slos=tenant_slos)
    first = stacks[tenants[0].name]
    engine = ServeEngine(
        first.handler, group,
        ContinuousBatcher(args.batch), scheduler,
        executor=MultiTenantExecutor(executors),
        queue=AdmissionQueue(depth=args.queue_depth, policy=args.shed_policy),
        metrics=metrics, slo_s=slo_s, tenant_slos=tenant_slos)
    return SimpleNamespace(rt=rt, engine=engine, group=group,
                           stacks=stacks, tenants=list(tenants),
                           metrics=metrics, restored=restored)


def _run_tenants(args) -> None:
    """Multi-tenant single-process serving (``--tenant`` given)."""
    from repro.serve import OpenLoopSource, parse_tenant_arg, substream_seed

    tenants = [parse_tenant_arg(t, default_slo_ms=args.slo_ms)
               for t in args.tenant]
    built = build_tenant_engine(args, tenants)
    rt, engine = built.rt, built.engine
    if built.restored:
        seeded = {name: list(st.handler._seeded)
                  for name, st in built.stacks.items()}
        print(f"restored spec state: seeded contexts={seeded}")
    schedule: list = []
    for spec in tenants:
        schedule += synthetic_workload(
            args.requests, args.rate, seed=substream_seed(args.seed,
                                                          spec.name),
            tenant=spec.name, deadline_s=spec.slo_s)
    source = OpenLoopSource(engine.queue, schedule)

    t0 = time.perf_counter()
    engine.run(source=source, max_steps=args.steps)
    engine.drain(timeout_s=60.0)
    wall = time.perf_counter() - t0
    stats = engine.stats()
    served = stats["serve"]
    print(f"served {served['completed']} requests / "
          f"{served['completed_tokens']} tokens in {wall:.2f}s across "
          f"{len(tenants)} tenants "
          f"(met={served['slo_met']} missed={served['slo_missed']})")
    for name, sub in (served.get("tenants") or {}).items():
        print(f"tenant {name}: completed={sub['completed']} "
              f"goodput_tokens={sub['goodput_tokens']} "
              f"slo_ms={(sub['slo_s'] or 0) * 1e3:.0f} "
              f"met={sub['slo_met']} missed={sub['slo_missed']} "
              f"p95_ms={sub['latency_p95_ms']}")
    print(f"tenant steps: {stats.get('tenant_steps')}  "
          f"scheduler: {json.dumps(stats.get('scheduler', {}))}")
    for name, st in built.stacks.items():
        cfgs = {str(k): ({kk: repr(vv) for kk, vv in cfg.items()}
                         if cfg is not None else None)
                for k, cfg in st.controller.best_configs().items()}
        print(f"tenant {name} per-context configs: {json.dumps(cfgs)}")
    print(f"compile stats: {json.dumps(rt.compile_stats())}")
    _export_trace(args)
    engine.shutdown(state_dir=args.cache_dir)


def _status_provider(built, rt, args):
    """Assemble the live snapshot ``launch/status.py`` renders: per-context
    lifecycle, safety stage, goodput window, compile queue, bus health."""
    def provider() -> dict:
        controller, engine = built.controller, built.engine
        contexts = {}
        for key, st in controller.status().items():
            contexts[repr(key)] = {
                "phase": st["phase"],
                "active": st["active"],
                "pending": st["pending"],
                "best_metric": st["best_metric"],
                "calls": st["calls"],
                "explorations": st["explorations"],
                "tput_window": st["tput_window"],
            }
        doc = {
            "mode": "single",
            "replica": args.replica_id,
            "handler": built.handler.name,
            "slo_ms": args.slo_ms,
            "contexts": contexts,
            "serve": built.metrics.summary(),
            "queue": {"waiting": len(engine.queue),
                      "in_flight": len(engine.active)},
            "compile": rt.compile_stats(),
        }
        status_fn = getattr(controller, "safety_status", None)
        if callable(status_fn):
            doc["safety"] = status_fn()
        _tb = telemetry.bus()
        if _tb is not None:
            doc["bus"] = _tb.stats()
        return doc
    return provider


def _run_single(args) -> None:
    from repro.serve import OpenLoopSource
    from repro.serve.fleet import SpecPlane

    built = build_engine(args)
    rt, engine = built.rt, built.engine
    snap = (telemetry.SnapshotWriter(args.telemetry_snapshot,
                                     _status_provider(built, rt, args),
                                     interval_s=args.snapshot_interval_s)
            if args.telemetry_snapshot else None)
    if built.restored:
        print(f"restored spec state: bucket scheme={built.initial_scheme}, "
              f"seeded contexts={list(built.handler._seeded)}")
    plane = (SpecPlane(args.plane_dir, replica=args.replica_id,
                       quarantine=getattr(built.controller, "quarantine",
                                          None))
             if args.plane_dir else None)
    if plane is not None and plane.poll(rt):
        # Warm start off the fleet plane: remotely settled (phase, bucket)
        # contexts begin in EXPLOIT when their traffic materializes.
        print(f"plane: seeded contexts={list(built.handler._seeded)}")

    schedule = synthetic_workload(args.requests, args.rate, seed=args.seed)
    source = OpenLoopSource(engine.queue, schedule)

    t0 = time.perf_counter()
    engine.run(source=source, max_steps=args.steps)
    engine.drain(timeout_s=60.0)
    wall = time.perf_counter() - t0
    stats = engine.stats()
    served = stats["serve"]
    print(f"served {served['completed']} requests / "
          f"{served['completed_tokens']} tokens in {wall:.2f}s "
          f"(goodput basis: slo={args.slo_ms:.0f}ms, "
          f"met={served['slo_met']} missed={served['slo_missed']})")
    print(f"p50/p95/p99 latency ms: {served['latency_p50_ms']} / "
          f"{served['latency_p95_ms']} / {served['latency_p99_ms']}")
    print(f"bucket steps: {stats['bucket_steps']}  "
          f"phase steps: {stats['phase_steps']}  "
          f"scheme: {built.tuner.active_scheme()} "
          f"(boundaries {built.batcher.schemes[built.tuner.active_scheme()]})")
    print(f"kv: {json.dumps(built.kv.stats())}")
    best_cfgs = {str(k): ({kk: repr(vv) for kk, vv in cfg.items()}
                          if cfg is not None else None)
                 for k, cfg in built.controller.best_configs().items()}
    print(f"per-context configs: {json.dumps(best_cfgs)}")
    print(f"compile stats: {json.dumps(rt.compile_stats())}")
    status_fn = getattr(built.controller, "safety_status", None)
    if callable(status_fn):
        st = status_fn()
        print(f"safety: promotions={st['promotions']} "
              f"rollbacks={st['rollbacks']} "
              f"shadow_rejections={st['shadow_rejections']} "
              f"canary_rejections={st['canary_rejections']} "
              f"quarantined={st['quarantined']}")
    if plane is not None:
        n = plane.publish_controller("serve_step", built.controller)
        print(f"plane: published {n} settled winners")
    if snap is not None:
        snap.close()                      # one final snapshot at rest
    _export_trace(args)
    # shutdown drains (already drained), persists spec state once settled,
    # and stops the compile workers.
    engine.shutdown(state_dir=args.cache_dir)


def _export_trace(args) -> None:
    if not args.trace_out:
        return
    _tb = telemetry.bus()
    if _tb is None:
        return
    doc = telemetry.export_chrome_trace(_tb.events(), args.trace_out)
    print(f"trace: wrote {len(doc['traceEvents'])} events to "
          f"{args.trace_out} ({json.dumps(_tb.stats())})")


def fleet_router(builts, policy: str = "jsq"):
    """A :class:`~repro.serve.fleet.ReplicaRouter` over in-process
    replicas (``builts``: :func:`build_engine` results, each on its own
    device)."""
    from repro.serve.fleet import LocalReplica, ReplicaRouter

    devices = [b.device for b in builts]
    if len(set(devices)) != len(devices):
        raise ValueError(f"replicas share a device: {devices}")
    return ReplicaRouter([LocalReplica(b.engine, name=str(i))
                          for i, b in enumerate(builts)], policy=policy)


def serve_fleet(builts, front, schedule, *, plane_dir: str | None = None,
                plane_poll_s: float = 0.5) -> float:
    """Serve ``schedule`` open-loop through ``front`` (:func:`fleet_router`
    over ``builts``), one serving thread per replica; returns the wall
    seconds.

    Each replica drains its own queue until the schedule is exhausted and
    it is idle.  With ``plane_dir`` every replica polls the shared
    :class:`~repro.serve.fleet.SpecPlane` before serving and on
    ``plane_poll_s`` while serving, publishing its settled winners."""
    from repro.serve import OpenLoopSource
    from repro.serve.fleet import SpecPlane

    planes = [SpecPlane(plane_dir, replica=str(i),
                        quarantine=getattr(b.controller, "quarantine", None))
              if plane_dir else None for i, b in enumerate(builts)]
    for plane, b in zip(planes, builts):
        if plane is not None:
            plane.poll(b.rt)
    source = OpenLoopSource(front, schedule)
    closed = threading.Event()
    errors: list[BaseException] = []

    def serve(b, plane) -> None:
        engine = b.engine
        last_plane = time.perf_counter()
        try:
            while not (closed.is_set() and not engine.active
                       and not len(engine.queue)):
                if engine.step() == 0 and not engine.active:
                    time.sleep(0.001)
                now = time.perf_counter()
                if plane is not None and now - last_plane >= plane_poll_s:
                    plane.poll(b.rt)
                    plane.publish_controller("serve_step", b.controller)
                    last_plane = now
            engine.drain(timeout_s=60.0)
            if plane is not None:
                plane.publish_controller("serve_step", b.controller)
        except BaseException as e:            # surfaced by the front below
            errors.append(e)

    threads = [threading.Thread(target=serve, args=(b, plane),
                                name=f"replica-{i}", daemon=True)
               for i, (b, plane) in enumerate(zip(builts, planes))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    while not source.exhausted and not errors:
        source.pump(time.perf_counter())
        delay = source.next_due(time.perf_counter())
        if delay:
            time.sleep(min(delay, 0.02))
    closed.set()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return time.perf_counter() - t0


def _run_fleet(args) -> None:
    """Router front over N in-process replicas, one per device."""
    import jax

    from repro.serve import ServeMetrics, substream_seed

    devices = jax.devices()
    if args.replicas > len(devices):
        raise SystemExit(f"--replicas {args.replicas} needs one device per "
                         f"replica; this host has {len(devices)}")
    builts = [build_engine(args, device=devices[i])
              for i in range(args.replicas)]
    for i, b in enumerate(builts):
        print(f"replica {i}: device {b.device.id} ({b.device.device_kind})")
    print(f"fleet: {args.replicas} in-process replicas "
          f"(router={args.router}, plane={args.plane_dir or 'off'})")
    # Per-replica substreams of the root seed: N times the single-replica
    # offered load without N byte-identical arrival processes.
    schedule: list = []
    for i in range(args.replicas):
        schedule += synthetic_workload(args.requests, args.rate,
                                       seed=substream_seed(args.seed, i))
    front = fleet_router(builts, args.router)

    def fleet_provider() -> dict:
        doc = {"mode": "fleet", "router": front.stats(),
               "replicas": {r.name: {"depth": r.depth()}
                            for r in front.replicas}}
        _tb = telemetry.bus()
        if _tb is not None:
            doc["bus"] = _tb.stats()
        return doc

    snap = (telemetry.SnapshotWriter(args.telemetry_snapshot, fleet_provider,
                                     interval_s=args.snapshot_interval_s)
            if args.telemetry_snapshot else None)
    wall = serve_fleet(builts, front, schedule, plane_dir=args.plane_dir,
                       plane_poll_s=args.plane_poll_s)
    print(f"router: {json.dumps(front.stats())}")
    merged = ServeMetrics.merge(*(b.metrics for b in builts)).summary()
    print(f"fleet served {merged['completed']} requests / "
          f"{merged['completed_tokens']} tokens across {len(builts)} "
          f"replicas in {wall:.2f}s "
          f"({merged['goodput_tokens'] / wall:.1f} goodput tok/s; "
          f"met={merged['slo_met']} missed={merged['slo_missed']})")
    print(f"fleet p50/p95/p99 latency ms: {merged['latency_p50_ms']} / "
          f"{merged['latency_p95_ms']} / {merged['latency_p99_ms']}")
    for i, b in enumerate(builts):
        print(f"replica {i}: steps={b.engine.steps} "
              f"compile={json.dumps(b.rt.compile_stats())}")
    if snap is not None:
        snap.close()
    _export_trace(args)
    for b in builts:
        b.engine.shutdown(state_dir=None)


def main() -> None:
    ap = argparse.ArgumentParser()
    add_engine_args(ap)
    ap.add_argument("--tenant", action="append", default=None,
                    metavar="NAME=ARCH[:SLO_MS[:WEIGHT]]",
                    help="repeatable: serve several models as tenants of "
                         "one engine (own SLO class and DRR fair-share "
                         "weight per tenant); implies single-process mode "
                         "and defaults --scheduler to drr")
    ap.add_argument("--replicas", type=int, default=1,
                    help="N > 1 serves N in-process engine replicas, one "
                         "per device, behind a router")
    ap.add_argument("--router", default="jsq",
                    choices=("round-robin", "jsq", "spill"),
                    help="fleet routing policy")
    ap.add_argument("--plane-dir", default=None,
                    help="shared SpecPlane directory: publish settled "
                         "winners, seed remotely-settled ones")
    ap.add_argument("--plane-poll-s", type=float, default=0.5,
                    help="plane subscribe/publish interval")
    ap.add_argument("--replica-id", default="0",
                    help="this replica's plane identity (single mode)")
    ap.add_argument("--trace-out", default=None,
                    help="write the flight-recorder stream as Chrome-trace "
                         "JSON here on exit (enables the event bus)")
    ap.add_argument("--telemetry-snapshot", default=None,
                    help="periodically write an atomic live-status JSON "
                         "snapshot here (read it with repro.launch.status)")
    ap.add_argument("--snapshot-interval-s", type=float, default=1.0,
                    help="telemetry snapshot period")
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace_out or args.telemetry_snapshot:
        telemetry.enable()
    if args.tenant:
        if args.replicas > 1:
            ap.error("--tenant is single-process; drop --replicas")
        if "--scheduler" not in sys.argv and args.scheduler == "fcfs":
            args.scheduler = "drr"    # tenants default to weighted-fair
        _run_tenants(args)
    elif args.replicas > 1:
        _run_fleet(args)
    else:
        _run_single(args)


if __name__ == "__main__":
    main()
