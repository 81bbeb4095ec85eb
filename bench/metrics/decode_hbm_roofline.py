"""Decode steps' share of their memory roofline, in percent: the least time
the chip needs for the bytes a decode step must move (weights once, the
real rows' cache or state, logits; ``model.decode_cost``) at the chip's
HBM bandwidth, summed over the window's decode steps, over their measured
device time."""


def read(run):
    t = run.trace
    device_s = t and t["phase_device_s"].get("decode")
    if not device_s:
        return None
    least = sum(run.model.decode_cost(run.cfg, s.lengths)[1]
                for s in run.window_steps("decode")) / \
        run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / device_s
