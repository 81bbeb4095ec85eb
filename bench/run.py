"""Run one cell of the benchmark on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name in
``BENCHMARK.json`` (see ``bench/harness/spec.py``).  The run serves the
cell's traffic through ``launch/serve.py``'s engine, measures ``--seconds``
seconds after set-up and warm-up, then checks what was served against the
configuration's plain float32 reference.  With ``--trace 0`` it reports the
cell's end-to-end metrics; with ``--trace 1`` it traces the window with
the JAX profiler and reports the per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``busy_s`` and ``window_s`` in it, and ``breakdown``), and last
``check``, each number compared beside its limit.  The same numbers close
standard error.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, "bench", ".cache")


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(CACHE, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def read_metrics(run, metrics: list[dict]) -> dict:
    """Each metric's reader, found by name; a reader that finds nothing to
    read returns None and the metric is left out."""
    from bench.harness import spec

    out = {}
    for m in metrics:
        value = spec.load_metric(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args()

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from bench.harness import check, peaks, serve, spec
        bench = spec.load_benchmark()
        cell = spec.find_cell(bench, opts.workload)
        cfg, model = spec.load_config(cell.config_name)
        traffic = spec.load_traffic(cell.traffic_name)
        limits = spec.load_json(os.path.join(spec.config_dir(cell.config_name),
                                             "check.json"))
        import repro.launch.serve  # noqa: F401  (the system under test)
    except (ImportError, OSError, KeyError, ValueError) as e:
        fail(f"cannot set up {opts.workload!r}: {type(e).__name__}: {e}")

    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX reports {devices[0].platform}")
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, JAX reports "
             f"{len(devices)}")
    dev = devices[0]
    chip_peaks = peaks.peaks(dev.device_kind)
    compile_cache()

    trace_dir = None
    if opts.trace:
        trace_dir = os.path.join(CACHE, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = serve.Run(cell=cell, cfg=cfg, model=model, traffic=traffic,
                    seed=opts.seed, seconds=opts.seconds,
                    t_process=T_PROCESS)
    run.peaks = chip_peaks
    serve.run_cell(run, state_dir=os.path.join(CACHE, "state",
                                                cell.config_name),
                   trace_dir=trace_dir)
    if trace_dir is not None:
        from bench.harness import trace
        run.trace = trace.reduce_file(trace.newest_xspace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    print(f"bench: {json.dumps(run.summary())}", file=sys.stderr, flush=True)
    metrics = read_metrics(run, cell.per_layer if opts.trace
                           else cell.end_to_end)
    numbers = check.compare(run, limits)
    live = [r for r in run.reqs
            if r.due < run.t1 and (r.request.finish_t is None
                                   or r.request.finish_t >= run.t0)]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": check.is_correct(numbers), "attempted": len(live),
              "failed": sum(1 for r in live
                            if not r.accepted or r.request.shed),
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["check"] = numbers
    for name, n in numbers.items():
        print(f"check {name} = {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
