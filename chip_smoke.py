"""Smoke check: the serving main path runs on a TPU, at published width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip replicas behind the
                                      # router, against one replica
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced [--chips 4]
                                      # CPU rehearsal of the same paths at
                                      # the reduced float32 preset (for four
                                      # replicas add XLA_FLAGS=
                                      # --xla_force_host_platform_device_count=4)

With no option it serves qwen3-0.6b (28 layers, d_model 1024, vocab
151,936, bf16) through ``launch/serve.py``'s ``build_engine`` with the
Controller, shadow evaluation and canaries on, and checks, each phase
failing the run:

1. kernels — each Pallas TPU kernel of the main path (rmsnorm; attention
   at decode and prefill widths) against its ``xla_ref``, with no
   registry fallback;
2. serve — bursts of 16 open-loop requests all complete, none shed;
   between bursts the engine idles until the search is quiet (builds
   done, shadow verdicts in), and within ``SEARCH_ROUNDS`` rounds one
   context compiled and activated at least two configs, the Pallas
   rmsnorm among them, with no registry fallback while serving; prints
   programs compiled, compile seconds, peak device memory;
3. logits — the served logits of two requests, after prefill and after
   each cached decode step, against ``transformer.apply`` over the whole
   sequence in float32 at ``highest`` matmul precision, same weights;
4. warm restart — a new engine on the same cache directory replays
   requests with zero XLA compiles (the persistent variant cache).

The last line of standard output is one JSON object naming the device as
JAX reports it.  The script exits non-zero, printing no such line, when
the device is not a TPU (unless ``--reduced`` asks for the CPU
rehearsal, which ends with a plain-text line instead).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen3-0.6b"
#: engine-state directory of the serve phase (listed in .gitignore); its
#: fixed path is what the warm restart reopens.
STATE_DIR = os.path.join(ROOT, ".smoke_state")

#: Tolerances, and why.
#: A Pallas kernel and its xla_ref read the same bf16 inputs and accumulate
#: in float32; they differ by the order of float32 sums plus one rounding
#: of the bf16 output (2^-8 relative).  Outputs here are O(1) (rmsnorm) or
#: below 4 in magnitude (attention: a convex mix of N(0, 1) values), so one
#: bf16 ulp is at most 2^-8 * 4 = 0.016.
KERNEL_TOL = 0.03
#: The server runs bf16 weights and activations and keeps its KV cache in
#: bf16; the reference runs the same (bf16-valued) weights in float32 at
#: highest precision.  Each of 28 layers rounds its residual-stream update
#: to bf16 (2^-9 relative); these independent errors add like a random
#: walk, and the final rmsnorm maps them onto logits whose spread is set
#: by the tied embedding (std about 0.6 at these random weights).  The
#: bound is on the max |served - reference| over the vocabulary, relative
#: to the reference's own max |logit| in that row.
LOGITS_TOL = 0.05

#: Search bounds of the serve phase: at most this many (settle, burst)
#: rounds, each settle at most SETTLE_S seconds (a cold run's builds of
#: four candidates in six contexts fit in the first).
SEARCH_ROUNDS = 3
SETTLE_S = 240.0

#: engine flags of the four-replica path and its one-replica comparison
FLEET_FLAGS = dict(requests=16, rate=8, dwell=20, prefetch=0)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def device_line(devs) -> str:
    return json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}})


def engine_args(reduced: bool, cache_dir: str, **over):
    """Parsed ``launch/serve.py`` engine flags for the smoke's engines.

    Dwells are short so a 16-request run gets through the search: each
    candidate is shadow-evaluated on every captured call, a canary serves
    half the calls and is promoted after one in-SLO dwell."""
    from repro.launch.serve import add_engine_args

    ap = argparse.ArgumentParser()
    add_engine_args(ap)
    flags = ["--arch", ARCH, "--batch", "4", "--max-len", "256",
             "--requests", "16", "--rate", "4", "--dwell", "2",
             "--shadow-frac", "1.0", "--canary-frac", "0.5",
             "--promote-after", "1", "--bucket-dwell", "4",
             "--cache-dir", cache_dir]
    for k, v in over.items():
        flags += [f"--{k.replace('_', '-')}", str(v)]
    return ap.parse_args(flags + (["--reduced"] if reduced else []))


def fallbacks_since(before: dict) -> dict:
    """(family, impl) -> registry fallbacks counted since ``before``."""
    from repro.kernels import registry

    return {k: n - before.get(k, 0)
            for k, n in registry.default_registry.fallback_counts.items()
            if n != before.get(k, 0)}


# -- phase 1: kernels -------------------------------------------------------------

def check_kernels(impl: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import attention, registry, rmsnorm

    before = dict(registry.default_registry.fallback_counts)
    key = jax.random.PRNGKey(1)
    worst = 0.0

    def compare(name, fn, *args, **kw):
        nonlocal worst
        out = np.asarray(fn(*args, impl=impl, **kw), np.float32)
        ref = np.asarray(fn(*args, impl="xla_ref", **kw), np.float32)
        err = float(np.max(np.abs(out - ref)))
        print(f"kernel {name} {impl}: max |err| vs xla_ref = {err:.3g}")
        if not np.all(np.isfinite(out)) or err > KERNEL_TOL:
            fail(f"kernel {name}: error {err} above {KERNEL_TOL}")
        worst = max(worst, err)

    for rows in (1, 8, 128):
        x = jax.random.normal(key, (rows, 1024), jnp.bfloat16)
        w = 1.0 + 0.1 * jax.random.normal(key, (1024,), jnp.float32)
        compare(f"rmsnorm[{rows}x1024]", rmsnorm.rmsnorm, x, w,
                block_rows=256)
    ks = jax.random.split(key, 3)
    for phase, b, sq, skv, blk in (("decode", 8, 1, 256, 256),
                                   ("prefill", 1, 2048, 2048, 512)):
        q = jax.random.normal(ks[0], (b, 16, sq, 128), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, 8, skv, 128), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, 8, skv, 128), jnp.bfloat16)
        compare(f"attention[{phase} b={b} q={sq} kv={skv}]",
                attention.attention, q, k, v, block_q=blk, block_kv=blk)
    grew = fallbacks_since(before)
    if grew:
        fail(f"registry fell back to xla_ref: {grew}")
    print(f"kernels: ok (max err {worst:.3g}, no fallback)")


# -- phase 2: serve ---------------------------------------------------------------

def activated_configs(events) -> dict:
    """context -> set of config reprs that served live calls (published
    as active, or serving as a canary)."""
    out: dict = {}
    for ev in events:
        if ev.get("handler") == "serve_step" and ev["name"] in (
                "dispatch.activate", "dispatch.canary_call"):
            out.setdefault(ev["track"], set()).add(ev["config"])
    return out


def rmsnorm_impl(config_repr: str) -> str:
    """The rmsnorm entry a config runs (no ``rmsnorm_impl`` = auto)."""
    from repro.kernels import registry

    m = re.search(r"'rmsnorm_impl': '(\w+)'", config_repr)
    return registry.resolve("rmsnorm", m.group(1) if m else None).name


def serve(built, schedule) -> dict:
    """Serve one open-loop burst; fail unless every request of it
    completed and none was shed or refused."""
    from repro.serve import OpenLoopSource

    done0 = built.metrics.summary()
    queue0 = built.engine.queue.stats()
    t0 = time.perf_counter()
    built.engine.run(source=OpenLoopSource(built.engine.queue, schedule),
                     max_steps=100_000)
    summary = built.metrics.summary()
    queue = built.engine.queue.stats()
    completed = summary["completed"] - done0["completed"]
    tokens = summary["completed_tokens"] - done0["completed_tokens"]
    dropped = {k: queue[k] - queue0[k] for k in ("rejected", "shed")}
    dropped["shed_in_flight"] = summary["shed"] - done0["shed"]
    print(f"served {completed}/{len(schedule)} requests, {tokens} tokens in "
      f"{time.perf_counter() - t0:.1f}s (host clock, not a benchmark); "
      f"dropped={json.dumps(dropped)}")
    if completed != len(schedule):
        fail(f"{len(schedule) - completed} requests not served")
    if any(dropped.values()):
        fail(f"requests were dropped: {dropped}")
    return {"completed": completed, "completed_tokens": tokens}


def settle(built, timeout_s: float) -> None:
    """Idle engine iterations until the search is quiet: no build in
    flight and no candidate awaiting its shadow verdict.  Idle ticks run
    the shadow pairs and advance the shadow-stage contexts."""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout_s:
        built.engine.step()
        if not (built.rt.compile_service.busy() or built.shadow.pending()):
            break
        time.sleep(0.002)
    print(f"search settled for {time.perf_counter() - t0:.1f}s; shadow "
      f"{json.dumps(built.shadow.stats())}")


def short(config_repr: str) -> str:
    """A config repr without its disabled points."""
    return ",".join(f"{k}={v}" for k, v in
                    re.findall(r"'(\w+)': '([^']*)'", config_repr)) or "generic"


def searched_context(built, rmsnorm_entry: str):
    """The context that activated two or more configs with the
    ``rmsnorm_entry`` rmsnorm among them, else None."""
    from repro.core import telemetry

    for ctx, cfgs in sorted(activated_configs(telemetry.bus().events()).items()):
        if len(cfgs) >= 2 and rmsnorm_entry in {rmsnorm_impl(c) for c in cfgs}:
            return ctx
    return None


def report_search(built) -> None:
    from repro.core import telemetry

    events = telemetry.bus().events()
    for ev in events:
        if ev["name"] == "safety.shadow_verdict":
            print(f"  shadow verdict {ev['track']} {short(ev['config'])}: "
              f"in_slo={ev['in_slo']} candidate_s={ev['candidate_s']} "
              f"incumbent_s={ev['incumbent_s']}")
    for ctx, cfgs in sorted(activated_configs(events).items()):
        print(f"context {ctx}: activated {len(cfgs)} configs: "
          f"{sorted(short(c) for c in cfgs)}")
    status = built.controller.safety_status()
    print(f"safety: promotions={status['promotions']} "
      f"shadow_rejections={status['shadow_rejections']} "
      f"canary_rejections={status['canary_rejections']}")


# -- phase 3: logits against the float32 reference -----------------------------

def check_logits(built, requests) -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import KernelOptions
    from repro.models import transformer as model
    from repro.models.transformer import RunOptions

    cfg32 = built.cfg.replace(compute_dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), built.params)
    opts = RunOptions(kernels=KernelOptions(impl="xla_ref"))
    length = 256                       # one padded shape; causal, so the
                                       # padding never reaches real rows

    @jax.jit
    def reference(params, tokens):
        with jax.default_matmul_precision("highest"):
            return model.apply(params, cfg32, opts, tokens=tokens)[0][0]

    worst = 0.0
    for req in requests:
        rows = built.executor.logits_log[req.rid]
        prompt = built.executor.prompt_fn(req)
        seq = np.concatenate([prompt, np.asarray(req.payload[:-1],
                                                 np.int32)])
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :len(seq)] = seq
        ref = np.asarray(reference(params32, jax.device_put(
            tokens, built.device)))[:, :built.cfg.vocab_size]
        errs = []
        for j, row in enumerate(rows):
            r = ref[len(prompt) - 1 + j]
            errs.append(float(np.max(np.abs(row - r)) / np.max(np.abs(r))))
        worst = max(worst, max(errs))
        print(f"logits rid={req.rid} prompt={len(prompt)} "
          f"steps={len(rows)} (prefill + {len(rows) - 1} decode): "
          f"max |err|/max|ref| = {max(errs):.4g} "
          f"(after prefill {errs[0]:.4g})")
    if worst > LOGITS_TOL:
        fail(f"served logits off the reference by {worst} > {LOGITS_TOL}")
    return worst


# -- phase 4: warm restart --------------------------------------------------------

def replay(built, requests) -> None:
    """Serve ``requests`` one at a time (closed loop): every step runs in
    the (phase, bucket) contexts of a lone request."""
    from repro.serve import Request

    for r in requests:
        built.engine.submit(Request(prompt_tokens=r.prompt_tokens,
                                    max_new_tokens=r.max_new_tokens))
        built.engine.run(max_steps=100_000)


def build_all_candidates(built) -> int:
    """Compile every candidate of every serve context seen, so whatever a
    warm restart explores is in the variant cache."""
    n = 0
    for key in built.handler.contexts():
        for cfg in built.policy_factory().candidates:
            built.handler.build(cfg, context=key)
            n += 1
    built.rt.compile_service.drain()
    return n


def warm_restart(args, tail) -> None:
    from repro.launch.serve import build_engine

    built = build_engine(args)
    if not built.restored:
        fail("restart found no saved spec state")
    replay(built, tail)
    built.rt.compile_service.drain()
    stats = built.rt.compile_stats()
    print(f"warm restart: replayed {len(tail)} requests; "
      f"xla_compiles={stats['xla_compiles']} "
      f"cache_hits={stats['cache_hits']}")
    for rec in built.rt.compile_service.telemetry():
        if rec.get("compile_s") is not None and not rec.get("cache_hit"):
            print(f"  compiled: {rec['handler']} {short(repr(rec['config']))}")
    if stats["xla_compiles"] or not stats["cache_hits"]:
        fail("warm restart compiled instead of loading the variant cache")
    built.engine.shutdown(state_dir=None)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return ("not reported" if peak is None
            else f"{peak} ({peak / 2**30:.2f} GiB)")


def one_chip(reduced: bool, kernel_impl: str, serve_impl: str) -> None:
    import jax

    from repro.core import telemetry
    from repro.kernels import registry
    from repro.launch.serve import build_engine, synthetic_workload

    dev = jax.devices()[0]
    check_kernels(kernel_impl)

    shutil.rmtree(STATE_DIR, ignore_errors=True)
    args = engine_args(reduced, STATE_DIR)
    telemetry.enable()
    built = build_engine(args)
    print(f"engine: {built.cfg.name} layers={built.cfg.n_layers} "
      f"d_model={built.cfg.d_model} vocab={built.cfg.vocab_size} "
      f"dtype={built.cfg.compute_dtype} params="
      f"{built.cfg.param_count() / 1e6:.0f}M on device {dev.id}")
    schedule = synthetic_workload(args.requests, args.rate, seed=args.seed)
    first = schedule[0][1]
    probes = [first, max((r for _, r in schedule[1:]),
                         key=lambda r: r.max_new_tokens)]
    built.executor.logits_log = {r.rid: [] for r in probes}
    before = dict(registry.default_registry.fallback_counts)
    serve(built, schedule)
    # Rounds of (search, burst): the idle search shadow-evaluates each
    # context's candidates, the next burst serves the canaries.
    ctx = None
    for rnd in range(1, SEARCH_ROUNDS + 1):
        settle(built, SETTLE_S)
        serve(built, synthetic_workload(args.requests, args.rate,
                                        seed=args.seed + rnd))
        ctx = searched_context(built, serve_impl)
        if ctx is not None:
            break
    tail = [r for _, r in schedule[:3]]
    replay(built, tail)                # the warm restart's traffic, cold
    report_search(built)
    if ctx is None:
        fail(f"no context activated two configs with rmsnorm {serve_impl} "
             f"among them")
    print(f"search: context {ctx} activated two or more configs, rmsnorm "
      f"{serve_impl} among them")
    grew = fallbacks_since(before)
    print(f"registry fallbacks while serving: {grew or 'none'}")
    if ("rmsnorm", serve_impl) in grew:
        fail(f"rmsnorm {serve_impl} fell back to xla_ref while serving")
    print(f"peak_bytes_in_use after serving: {peak_bytes(dev)}")
    err = check_logits(built, probes)
    print(f"logits: ok (max relative err {err:.4g} <= {LOGITS_TOL})")
    n = build_all_candidates(built)
    stats = built.rt.compile_stats()
    print(f"compiled programs: xla_compiles={stats['xla_compiles']} "
      f"compile_s={stats['total_compile_s']:.1f} (host clock, not a "
      f"benchmark); candidates ensured={n}")
    built.engine.shutdown(state_dir=STATE_DIR)
    del built
    telemetry.disable()
    warm_restart(args, tail)


# -- four chips -------------------------------------------------------------------

def four_chips(reduced: bool) -> None:
    import jax

    from repro.launch.serve import (build_engine, fleet_router, serve_fleet,
                                    synthetic_workload)
    from repro.serve import ServeMetrics

    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--chips 4 needs four devices, found {len(devs)}")
    # The search is left at launch/serve.py's default dwell with no
    # speculative builds: this path checks placement and routing, not the
    # search.
    args = engine_args(reduced, os.path.join(STATE_DIR, "fleet"),
                       **FLEET_FLAGS)
    shutil.rmtree(STATE_DIR, ignore_errors=True)
    schedule = synthetic_workload(args.requests, args.rate, seed=args.seed)
    rids = {r.rid for _, r in schedule}

    builts = [build_engine(args, device=devs[i]) for i in range(4)]
    ids = [b.device.id for b in builts]
    for i, b in enumerate(builts):
        print(f"replica {i}: device id {b.device.id} ({b.device.device_kind})")
        b.executor.logits_log = {rid: [] for rid in rids}
    if len(set(ids)) != 4:
        fail(f"replicas share a device: {ids}")
    front = fleet_router(builts, "jsq")
    wall = serve_fleet(builts, front, schedule)
    merged = ServeMetrics.merge(*(b.metrics for b in builts)).summary()
    print(f"fleet: served {merged['completed']}/{len(schedule)} requests, "
      f"{merged['completed_tokens']} tokens in {wall:.1f}s (host clock, "
      f"not a benchmark); routed={front.stats()['routed']} "
      f"shed={merged['shed']}")
    if merged["completed"] != len(schedule) or merged["shed"]:
        fail("fleet did not serve every request")
    by_rid = {r.rid: r for _, r in schedule}
    worst = 0.0
    for i, b in enumerate(builts):
        served = [by_rid[rid] for rid, rows in b.executor.logits_log.items()
                  if rows]
        if not served:
            fail(f"replica {i} served nothing")
        print(f"replica {i} (device {b.device.id}): "
          f"{len(served)} requests, compile="
          f"{json.dumps(b.rt.compile_stats()['xla_compiles'])} programs")
        worst = max(worst, check_logits(b, served[:1]))
        b.engine.shutdown(state_dir=None)
    del builts

    # what it is compared with: one replica, the same seeded schedule
    single_args = engine_args(reduced, os.path.join(STATE_DIR, "single"),
                              **FLEET_FLAGS)
    single = build_engine(single_args, device=devs[0])
    again = synthetic_workload(args.requests, args.rate, seed=args.seed)
    single.executor.logits_log = {again[0][1].rid: []}
    summary = serve(single, again)
    print(f"single replica (device {single.device.id}): same schedule, "
      f"{summary['completed_tokens']} tokens")
    if summary["completed_tokens"] != merged["completed_tokens"]:
        fail("fleet and single replica served different token counts")
    worst = max(worst, check_logits(single, [again[0][1]]))
    single.engine.shutdown(state_dir=None)
    print(f"four replicas on devices {ids}: ok (max relative logits err "
      f"{worst:.4g})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at the reduced float32 preset")
    opts = ap.parse_args()
    if not opts.reduced:
        os.environ.setdefault("JAX_PLATFORMS", "tpu")
    import jax

    from repro.launch.jax_cache import enable_compile_cache

    devs = jax.devices()
    print(f"devices: {len(devs)} x {devs[0].platform} ({devs[0].device_kind}); "
      f"jax {jax.__version__}")
    if devs[0].platform != "tpu" and not opts.reduced:
        fail(f"no TPU: JAX reports {devs[0].platform}")
    print(f"compile cache: {enable_compile_cache()}")
    # On the chip the Pallas rmsnorm is the registry's auto choice and so
    # the generic config's; the CPU rehearsal runs the interpreter in the
    # kernel phase and expects xla_ref (the CPU auto choice) in serving.
    on_tpu = devs[0].platform == "tpu"
    kernel_impl = "pallas_tpu" if on_tpu else "pallas_interpret"
    serve_impl = "pallas_tpu" if on_tpu else "xla_ref"
    t0 = time.perf_counter()
    if opts.chips == 4:
        four_chips(opts.reduced)
        devs = devs[:4]
    else:
        one_chip(opts.reduced, kernel_impl, serve_impl)
        devs = devs[:1]
    print(f"all phases passed in {time.perf_counter() - t0:.0f}s")
    if devs[0].platform == "tpu":
        print(device_line(devs))
    else:
        print(f"rehearsal passed on {devs[0].platform} (not a chip run)")


if __name__ == "__main__":
    main()
