"""XLA compilations inside the window, from JAX's monitoring events: backend
compile requests less those the persistent compilation cache served.  It
should read 0."""


def read(run):
    return run.compiles_between(run.t0, run.t1)
