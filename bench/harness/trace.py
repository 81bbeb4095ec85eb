"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The harness marks its own host spans with ``jax.profiler.TraceAnnotation``
names that start with ``bench.``: ``bench.window`` around the measured
window, ``bench.step.<phase>`` around each call into the model step, and
``bench.kv.<call>`` around the paged-KV host copies.  The device planes
(``/device:TPU:<n>``) carry one event per program execution on their
``XLA Modules`` line and one per operation on their ``XLA Ops`` line, on
the same timeline as the host spans.

From those the reduction takes, inside the window:

* ``busy_s`` — the union of the operation intervals, averaged over the
  device planes (a device is busy while any operation runs on it);
* per phase, the device seconds of the program executions that start
  inside a ``bench.step.<phase>`` span, and the number of such spans;
* the operations that took most device time, and the idle time by what
  the host was doing (the innermost ``bench.`` span over the gap, or
  ``between steps`` when none).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
STEP_PREFIX = "bench.step."


_OP = re.compile(r"^(%[^ ]+) = .*?\b([a-z][a-z0-9_-]*)\(")


def op_name(text: str) -> str:
    """``%fusion.12 fusion`` for an operation event named by its whole HLO
    instruction text (name and opcode, without the shapes)."""
    m = _OP.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float          # seconds on the trace's timeline
    end: float


def union_length(intervals: list[tuple[float, float]], lo: float,
                 hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: list[tuple[float, float]], lo: float,
              hi: float) -> list[tuple[float, float]]:
    """The gaps inside ``[lo, hi]`` not covered by ``intervals``."""
    gaps = []
    t = lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


class SpanIndex:
    """Which ``bench.`` span covers a time: the shortest one among those
    that do.  Spans of one name never overlap (the harness annotates from
    one thread), so each name is searched by bisection."""

    def __init__(self, spans: list[Span]):
        by_name: dict[str, list[Span]] = collections.defaultdict(list)
        for sp in spans:
            if sp.name != WINDOW_SPAN:
                by_name[sp.name].append(sp)
        self._lists = []
        for name, sps in by_name.items():
            sps.sort(key=lambda sp: sp.start)
            self._lists.append(([sp.start for sp in sps], sps))

    def label(self, t: float) -> str:
        best = None
        for starts, sps in self._lists:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and sps[i].end >= t and (
                    best is None
                    or sps[i].end - sps[i].start < best.end - best.start):
                best = sps[i]
        return best.name[len(SPAN_PREFIX):] if best is not None \
            else "between steps"


def reduce_events(host_spans: list[Span],
                  devices: list[dict[str, list[tuple[str, float, float]]]],
                  top: int = 10) -> dict:
    """The reduction, on plain data.

    ``host_spans`` are the ``bench.`` spans; ``devices`` one dict per
    device plane, mapping a line name to its ``(name, start_s, end_s)``
    events.  Returns ``busy_s``, ``window_s``, ``phase_device_s``,
    ``phase_steps``, ``device_ops`` and ``idle_gaps``.
    """
    windows = [sp for sp in host_spans if sp.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    steps = [sp for sp in host_spans if sp.name.startswith(STEP_PREFIX)
             and lo <= sp.start < hi]
    phase_steps = collections.Counter(sp.name[len(STEP_PREFIX):]
                                      for sp in steps)
    phase_device_s: dict[str, float] = collections.defaultdict(float)
    op_s: dict[str, float] = collections.defaultdict(float)
    idle_by: dict[str, float] = collections.defaultdict(float)
    busy = []
    index = SpanIndex(host_spans)
    steps.sort(key=lambda sp: sp.start)
    starts = [sp.start for sp in steps]
    for dev in devices:
        ops = [(s, e) for _, s, e in dev.get(OPS_LINE, ())]
        busy.append(union_length(ops, lo, hi))
        for name, s, e in dev.get(OPS_LINE, ()):
            if lo <= s < hi:
                op_s[name] += e - s
        for name, s, e in dev.get(MODULES_LINE, ()):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s <= steps[i].end:
                phase_device_s[steps[i].name[len(STEP_PREFIX):]] += e - s
        for s, e in idle_gaps(ops, lo, hi):
            idle_by[index.label((s + e) / 2)] += (e - s) / len(devices)
    ranked_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    ranked_idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": hi - lo,
        "phase_device_s": dict(phase_device_s),
        "phase_steps": dict(phase_steps),
        "device_ops": [[n, s] for n, s in ranked_ops],
        "idle_gaps": [[n, s] for n, s in ranked_idle],
    }


def read_xspace(path: str) -> tuple[list[Span], list[dict]]:
    """The ``bench.`` host spans and the device planes' lines of one
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: list[Span] = []
    devices: list[dict] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES_LINE, OPS_LINE):
                    lines[line.name] = [
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
            devices.append(lines)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return spans, devices


def newest_xspace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_file(path: str) -> dict:
    spans, devices = read_xspace(path)
    if not devices:
        raise ValueError(f"{path} holds no {DEVICE_PREFIX}* plane")
    return reduce_events(spans, devices)
