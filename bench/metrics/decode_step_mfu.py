"""Decode steps' model FLOP utilization, in percent: the operations the
real rows need (``model.decode_cost``), summed over the window's decode
steps, over their measured device time times the chip's bf16 peak."""


def read(run):
    t = run.trace
    device_s = t and t["phase_device_s"].get("decode")
    if not device_s:
        return None
    flops = sum(run.model.decode_cost(run.cfg, s.lengths)[0]
                for s in run.window_steps("decode"))
    return 100.0 * flops / (device_s * run.peaks["bf16_flops_per_s"])
