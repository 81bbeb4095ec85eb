"""The one traffic generator: it reads a mix's parameter file and turns a
seed into requests.

Two kinds of mix exist, named by the file's ``"kind"``:

* ``"backlog"`` — offline batch generation: before every engine step the
  generator tops the admission queue up to the batch cap, so the queue never runs dry and every step is as full
  as the engine makes it.  A request is due when it is submitted.
* ``"poisson"`` — independent users: open-loop arrivals at ``rate``
  requests/s, due at fixed offsets from the start of the run whether or
  not the engine kept up.  The first ``warmup_s`` seconds are set-up; the
  window follows.

Every seed gets the same work in another order.  Lengths are drawn block
by block: each block of ``block`` requests holds the ``block`` stratified
quantiles of the length distribution (a log-normal clipped to
``[min, max]``), permuted by the seed, prompt and output lengths each on
their own.  Inter-arrival gaps are the stratified quantiles of an
exponential, permuted the same way.  Prompt token ids are drawn from the
seed and the request's index.
"""
from __future__ import annotations

import dataclasses
import math
import random
import statistics
from typing import Callable

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """The ``n`` stratified quantiles ``(i + 0.5) / n`` of a log-normal
    with the given median and sigma, rounded and clipped to
    ``[min, max]``."""
    if dist.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {dist!r}")
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def quantile_gaps(rate: float, n: int) -> list[float]:
    """The ``n`` stratified quantiles of an exponential of mean
    ``1 / rate``."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


@dataclasses.dataclass
class Item:
    """One request of the mix, before it is submitted."""

    index: int
    prompt_len: int
    output_len: int
    due_offset: float | None = None      # poisson: seconds after the origin


def _blocks(values: list, rng: random.Random, count: int) -> list:
    out: list = []
    while len(out) < count:
        block = list(values)
        rng.shuffle(block)
        out.extend(block)
    return out[:count]


def make_items(traffic: dict, seed: int, count: int) -> list[Item]:
    """The mix's first ``count`` requests for ``seed``."""
    block = int(traffic.get("block", 16))
    rng = random.Random(seed)
    prompts = _blocks(quantile_lengths(traffic["prompt"], block), rng, count)
    outputs = _blocks(quantile_lengths(traffic["output"], block), rng, count)
    items = [Item(i, p, o) for i, (p, o) in enumerate(zip(prompts, outputs))]
    if traffic["kind"] == "poisson":
        gaps = _blocks(quantile_gaps(traffic["rate"],
                                     int(traffic.get("gap_block", 64))),
                       rng, count)
        t = 0.0
        for item, gap in zip(items, gaps):
            t += gap
            item.due_offset = t
    elif traffic["kind"] != "backlog":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    return items


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``index``'s prompt: uniform over the vocabulary,
    from the seed and the index alone."""
    rng = np.random.default_rng([abs(seed), int(seed < 0), index])
    return rng.integers(0, vocab, size=length, dtype=np.int32)


def item_count(traffic: dict, seconds: float) -> int:
    """How many requests a run may draw: a poisson mix's schedule covers
    warm-up and window with room to spare; a backlog is bounded by what a
    run can consume."""
    if traffic["kind"] == "poisson":
        horizon = float(traffic["warmup_s"]) + seconds
        return int(traffic["rate"] * horizon * 1.5) + 64
    return int(traffic.get("max_requests", 4096))


class Generator:
    """Submits a mix's requests to ``submit`` as they fall due.

    ``submit(item, due_t)`` hands one request to the engine and returns
    True when the queue took it.  ``waiting()`` returns the queue's depth
    (the backlog tops it up).  ``pump(now)`` is called before every engine
    step; it returns the number submitted.
    """

    def __init__(self, traffic: dict, items: list[Item],
                 submit: Callable[[Item, float], bool],
                 waiting: Callable[[], int]):
        self.traffic = traffic
        self.kind = traffic["kind"]
        self.items = items
        self.submit = submit
        self.waiting = waiting
        self.next = 0
        self.origin: float | None = None
        #: a backlog keeps this many requests waiting: the batch cap
        self.top_up = int(traffic["engine"]["batch"])

    def start(self, now: float) -> None:
        self.origin = now

    def due(self, item: Item) -> float:
        return self.origin + item.due_offset

    def pump(self, now: float) -> int:
        n = 0
        if self.kind == "backlog":
            while self.waiting() < self.top_up:
                if self.next >= len(self.items):
                    raise RuntimeError("backlog ran out of requests; raise "
                                       "max_requests in the traffic file")
                self.submit(self.items[self.next], now)
                self.next += 1
                n += 1
            return n
        while self.next < len(self.items) and \
                self.due(self.items[self.next]) <= now:
            item = self.items[self.next]
            self.submit(item, self.due(item))
            self.next += 1
            n += 1
        return n

    def seconds_to_next(self, now: float) -> float | None:
        if self.kind == "backlog" or self.next >= len(self.items):
            return None
        return max(0.0, self.due(self.items[self.next]) - now)
