"""Readings that set a configuration's limit: the program's and the
control's, on the chip, at a cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed the cell runs as ``bench/run.py`` runs it (set-up, warm-up,
a window of ``--seconds``), in one process.  On the requests the check
samples it reads the program's widest gap (its served tokens against the
float32 reference) and the control's: the reference computed with every
matrix product's operands in float8 e4m3 (the step below the configured
bfloat16) put in the program's place, its top-ranked token's gap read in
the float32 reference.  One JSON line per seed.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    opts = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), os.path.join(ROOT,
                                                                  "bench")]
    import jax
    import numpy as np

    import run as bench_run
    from bench.harness import check, serve, spec

    if jax.devices()[0].platform != "tpu":
        bench_run.fail("no TPU")
    bench_run.compile_cache()
    cell = spec.find_cell(spec.load_benchmark(), opts.workload)
    cfg, model = spec.load_config(cell.config_name)
    traffic = spec.load_traffic(cell.traffic_name)
    limits = spec.load_json(os.path.join(spec.config_dir(cell.config_name),
                                         "check.json"))
    length = int(traffic["engine"]["max_len"])
    for seed in opts.seeds:
        t = time.perf_counter()
        run = serve.Run(cell=cell, cfg=cfg, model=model, traffic=traffic,
                        seed=seed, seconds=opts.seconds, t_process=t)
        serve.run_cell(run, state_dir=os.path.join(bench_run.CACHE, "state",
                                                    cell.config_name))
        picked = check.sample(run.served, seed, int(limits["requests"]))
        program = check.gaps(model, cfg, run.params, picked, length)
        control = check.gaps(model, cfg, run.params, picked, length,
                             mm=check.mm_fp8, compare="ranked")
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "tokens": int(len(program)),
            "program_gap_max": float(np.max(program)),
            "program_gap_p99": float(np.percentile(program, 99)),
            "control_gap_max": float(np.max(control)),
            "control_gap_p99": float(np.percentile(control, 99)),
            "control_disagree": float(np.mean(control > 0)),
            "program_disagree": float(np.mean(program > 0)),
            "run_s": time.perf_counter() - t}), flush=True)
        del run


if __name__ == "__main__":
    main()
