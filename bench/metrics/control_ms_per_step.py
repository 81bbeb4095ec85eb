"""Host milliseconds per step in the controllers and tuners that run after
each step (the program's ``serve.control`` spans), over the window's
``serve.step`` spans."""
from bench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return None if w is None else w.ms_per_step("serve.control")
