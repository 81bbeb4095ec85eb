"""The program's own spans, summed over a run's window steps.

The program opens its spans with ``repro.core.telemetry.span``.  Each one
is a profiler annotation named ``iri.<name>`` and an entry of an
in-process ring on the ``time.perf_counter`` clock
(``telemetry.recent_spans()``).  ``serve.step`` wraps each engine step
that runs a batch.  Inside it, in the order the work happens:

* ``serve.exec.<phase>``: the executor's step;
* ``kv.gather`` and ``kv.upload``: staging each cache leaf in numpy, then
  its ``jax.device_put``, which may return before the copy lands;
* ``serve.dispatch``: the call into the step handler;
* ``kv.wait``: the host waiting for the step program and the uploads;
* ``kv.download`` and ``kv.scatter``: each leaf to the host, then the
  page and row-state writes;
* ``serve.sample``: logits to the host and the next tokens;
* ``serve.control``: the controllers and tuners.

The per-layer readers sum the ring with :func:`window`: the harness's
trace reduction keeps only ``bench.`` spans, and the trace is gone by the
time they run.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

STEP = "serve.step"


@dataclasses.dataclass
class Steps:
    """Per-name totals over the window's ``serve.step`` spans."""

    steps: int
    seconds: dict            # name -> seconds of spans inside window steps
    bytes: dict              # name -> the ``bytes`` args of those spans

    def ms_per_step(self, *names: str) -> float | None:
        if not any(n in self.seconds for n in names):
            return None
        return 1e3 * sum(self.seconds.get(n, 0.0) for n in names) / \
            self.steps

    def mb_per_step(self, *names: str) -> float | None:
        if not any(n in self.bytes for n in names):
            return None
        return sum(self.bytes.get(n, 0) for n in names) / self.steps / 1e6


def per_step(spans: list[tuple], lo: float, hi: float) -> Steps | None:
    """Sum ``(name, start, end, args)`` spans by name over the
    ``serve.step`` spans that start in ``[lo, hi)``; a span counts toward
    the step that holds it whole.  None when no step starts there."""
    steps = sorted((s, e) for name, s, e, _ in spans
                   if name == STEP and lo <= s < hi)
    if not steps:
        return None
    starts = [s for s, _ in steps]
    seconds: dict = collections.defaultdict(float)
    nbytes: dict = collections.defaultdict(int)
    for name, s, e, args in spans:
        i = bisect.bisect_right(starts, s) - 1
        if name == STEP or i < 0 or e > steps[i][1]:
            continue
        seconds[name] += e - s
        if args and "bytes" in args:
            nbytes[name] += int(args["bytes"])
    return Steps(len(steps), dict(seconds), dict(nbytes))


def window(run) -> Steps | None:
    """The run's window from the program's span ring; None for a program
    that keeps no such ring, or kept no step of the window."""
    try:
        from repro.core.telemetry import SPAN_RING_SIZE, recent_spans
    except ImportError:
        return None
    spans = recent_spans()
    lo = run.t0
    if len(spans) >= SPAN_RING_SIZE:
        # a full ring has dropped the spans that ended first: a step that
        # starts after the oldest kept span ended has all of its spans
        lo = max(lo, spans[0][2])
    return per_step(spans, lo, run.t1)

