"""Process start to the start of the window (the first timed request):
imports, weights, engine, compile or cache loads, and warm-up."""


def read(run):
    return run.t0 - run.t_process
