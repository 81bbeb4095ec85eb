"""The share of the rows the held experts computed that a token routed to
them, in percent: the ``expert_rows_routed`` over the
``expert_rows_computed`` args of the program's ``serve.sample`` spans
(counters its MoE serve step returns), over the window's ``serve.step``
spans, prefill and decode steps alike.  A program that keeps no such
counters gives nothing."""
import bisect

from bench.harness import program_spans


def read(run):
    try:
        from repro.core.telemetry import SPAN_RING_SIZE, recent_spans
    except ImportError:
        return None
    spans = recent_spans()
    lo = run.t0
    if len(spans) >= SPAN_RING_SIZE:
        lo = max(lo, spans[0][2])
    steps = sorted((s, e) for name, s, e, _ in spans
                   if name == program_spans.STEP and lo <= s < run.t1)
    starts = [s for s, _ in steps]
    routed = computed = 0
    for name, s, e, args in spans:
        if name != "serve.sample" or not args \
                or "expert_rows_computed" not in args:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= steps[i][1]:          # inside a window step
            routed += args["expert_rows_routed"]
            computed += args["expert_rows_computed"]
    return 100.0 * routed / computed if computed else None
