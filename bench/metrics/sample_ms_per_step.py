"""Host milliseconds per step bringing the logits to the host and taking
each row's next token (the program's ``serve.sample`` spans), over the
window's ``serve.step`` spans."""
from bench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return None if w is None else w.ms_per_step("serve.sample")
