"""Find an open-loop cell's knee once, by a sweep of fixed rates on the chip.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> --rates 1 2 4

Each rate runs the cell's mix at that rate (set-up, warm-up, a window of
``--seconds``) in this one process and prints one JSON line: the rate
offered and completed, the requests still waiting when the window closed,
and the time-to-first-token tail of the window's first and second halves.
The knee is the highest rate whose backlog does not grow: nothing is left
waiting and the second half's tail is no longer than the first's.  The
mix's file then takes 0.8 of it as a number.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import time

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    opts = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), os.path.join(ROOT,
                                                                  "bench")]
    import jax

    import run as bench_run
    from bench.harness import serve, spec, stats

    if jax.devices()[0].platform != "tpu":
        bench_run.fail("no TPU")
    bench_run.compile_cache()
    cell = spec.find_cell(spec.load_benchmark(), opts.workload)
    cfg, model = spec.load_config(cell.config_name)
    base = spec.load_traffic(cell.traffic_name)
    if base["kind"] != "poisson":
        bench_run.fail(f"{cell.name} is not an open-loop mix")
    for rate in opts.rates:
        run = serve.Run(cell=cell, cfg=cfg, model=model,
                        traffic=dict(base, rate=rate), seed=opts.seed,
                        seconds=opts.seconds, t_process=time.perf_counter())
        serve.run_cell(run, state_dir=os.path.join(bench_run.CACHE, "state",
                                                    cell.config_name))
        mid = (run.t0 + run.t1) / 2
        due = stats.due_in(run.reqs, run.t0, run.t1)
        done = [r for r in due if r.request.finish_t is not None
                and r.request.finish_t <= run.t1]
        waiting = [r for r in due if r.service_t is None
                   or r.service_t > run.t1]
        print(json.dumps({
            "workload": cell.name, "rate": rate,
            "offered_per_s": len(due) / opts.seconds,
            "completed_per_s": len(done) / opts.seconds,
            "waiting_at_close": len(waiting),
            "ttft_p90_ms_first_half": _p90(run, run.t0, mid),
            "ttft_p90_ms_second_half": _p90(run, mid, run.t1),
            "itl_p95_ms": 1e3 * (stats.percentile(
                stats.gaps_in(run.reqs, run.t0, run.t1), 95) or 0.0),
            "tokens_per_s": stats.tokens_in(run.reqs, run.t0, run.t1)
            / opts.seconds,
            "steps": len(run.window_steps())}), flush=True)
        del run


def _p90(run, t0, t1):
    """TTFT p90 of the requests due in ``[t0, t1)``, censored at the
    window's close."""
    from bench.harness import stats

    p = stats.percentile(stats.censored_waits(run.reqs, t0, t1,
                                              "first_token", run.t1), 90)
    return None if p is None else p * 1e3


if __name__ == "__main__":
    main()
