"""95th percentile of every gap between consecutive output tokens of one
request, both inside the window, over all requests."""
from bench.harness import stats


def read(run):
    p = stats.percentile(stats.gaps_in(run.reqs, run.t0, run.t1), 95)
    return None if p is None else p * 1e3
