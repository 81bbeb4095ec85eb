"""Real rows over padded rows of the window's engine steps, in percent,
from the engine's own counters (steps per bucket, padded rows)."""


def read(run):
    c0, c1 = run.counters0, run.counters1
    rows = sum(b * (n - c0["bucket_steps"].get(b, 0))
               for b, n in c1["bucket_steps"].items())
    if rows == 0:
        return None
    pad = c1["padded_rows"] - c0["padded_rows"]
    return 100.0 * (rows - pad) / rows
