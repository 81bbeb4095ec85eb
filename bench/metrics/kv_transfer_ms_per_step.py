"""Host milliseconds per step in the paged KV cache's transfer calls: each
leaf's ``jax.device_put`` in ``materialize`` and its copy back to the host
in ``harvest`` (the program's ``kv.upload`` and ``kv.download`` spans),
over the window's ``serve.step`` spans.

``device_put`` returns before its copy lands, so ``kv.upload`` times the
enqueue alone.  The rest of the host-to-device copy (the runtime's layout
transpose on its worker threads, then the transfer) runs during
``serve.dispatch`` and ``kv.wait``, and the part not done by then is
counted in ``kv_wait_ms_per_step``, not here.  The device-to-host
direction is timed whole."""
from bench.harness import program_spans


def read(run):
    w = program_spans.window(run)
    return None if w is None else w.ms_per_step("kv.upload", "kv.download")
