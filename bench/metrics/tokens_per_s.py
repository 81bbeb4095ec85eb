"""Output tokens that became visible in the window, over its seconds."""
from bench.harness import stats


def read(run):
    return stats.tokens_in(run.reqs, run.t0, run.t1) / (run.t1 - run.t0)
