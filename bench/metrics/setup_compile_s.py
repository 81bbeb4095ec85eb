"""Seconds the runtime's compile service spent in XLA compiles before the
window (zero when every program came from the variant cache)."""


def read(run):
    return float(run.counters0["compile"]["total_compile_s"])
