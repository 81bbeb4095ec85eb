"""Host milliseconds per step in the paged KV cache's numpy copies: staging
each leaf and gathering pages into it in ``materialize``, writing pages
and row state back in ``harvest`` (the program's ``kv.gather`` and
``kv.scatter`` spans), over the window's ``serve.step`` spans."""
from bench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return None if w is None else w.ms_per_step("kv.gather", "kv.scatter")
