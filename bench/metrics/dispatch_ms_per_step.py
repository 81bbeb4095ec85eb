"""Host milliseconds per step in the call into the step handler: the
runtime's trampoline, guard, context routing and enqueue, with the step
inputs' upload (the program's ``serve.dispatch`` spans), over the window's
``serve.step`` spans.  It does not wait for the KV cache's upload, but it
runs beside the runtime's host-side layout transposes of that upload,
which share the host's cores."""
from bench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return None if w is None else w.ms_per_step("serve.dispatch")
