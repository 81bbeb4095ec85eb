"""Device milliseconds per decode step: the program executions the trace
shows inside the window's decode-step spans, over the number of spans."""


def read(run):
    t = run.trace
    if t is None or not t["phase_steps"].get("decode"):
        return None
    return 1e3 * t["phase_device_s"].get("decode", 0.0) / \
        t["phase_steps"]["decode"]
