"""Host milliseconds per step waiting in the paged KV manager's ``harvest``
for the step program and the step's uploads (the program's ``kv.wait``
spans), over the window's ``serve.step`` spans.  The part of the
host-to-device copy that has not landed by then is counted here."""
from bench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return None if w is None else w.ms_per_step("kv.wait")
