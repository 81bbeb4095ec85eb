"""Host milliseconds per model step spent in the paged KV manager's
``materialize`` and ``harvest`` (host clock around each call), over the
window's steps."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 1e3 * sum(s.kv_s for s in steps) / len(steps)
