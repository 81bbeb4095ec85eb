"""Megabytes (1e6 bytes) the paged KV cache moves between host and device
per step, both ways: the ``bytes`` of the program's ``kv.upload`` and
``kv.download`` spans, over the window's ``serve.step`` spans."""
from bench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return None if w is None else w.mb_per_step("kv.upload", "kv.download")
