"""Record the small profiler trace the trace-reduction test reads.

    python3 tests/bench/record_trace.py tests/bench/data/small.xplane.pb

Run on a TPU: it serves the qwen3 configuration at the program's reduced
preset under a small backlog through the benchmark's harness for a fraction
of a second with the profiler on, copies the trace to the path given, and
prints its planes and lines and the reduction's result.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> None:
    out = sys.argv[1]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]
    import jax
    from jax.profiler import ProfileData

    from bench.harness import serve, trace
    from conftest import reduced_config, small_traffic

    if jax.devices()[0].platform != "tpu":
        sys.exit("record the trace on a TPU")
    cfg, model = reduced_config("qwen3-0.6b")
    run = serve.Run(cell=None, cfg=cfg, model=model,
                    traffic=small_traffic("backlog"), seed=1, seconds=0.2,
                    t_process=time.perf_counter())
    with tempfile.TemporaryDirectory() as tmp:
        serve.run_cell(run, state_dir=os.path.join(tmp, "state"),
                       trace_dir=os.path.join(tmp, "trace"), reduced=True)
        path = trace.newest_xspace(os.path.join(tmp, "trace"))
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copyfile(path, out)
    pd = ProfileData.from_file(out)
    for plane in pd.planes:
        lines = {line.name: sum(1 for _ in line.events)
                 for line in plane.lines}
        print(f"plane {plane.name}: {json.dumps(lines)[:600]}")
        if plane.name.startswith(trace.DEVICE_PREFIX):
            for line in plane.lines:
                names = [ev.name for _, ev in zip(range(5), line.events)]
                print(f"  line {line.name}: {names}")
    print(json.dumps(trace.reduce_file(out)))
    print(f"{out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main()
