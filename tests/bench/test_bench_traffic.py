"""The traffic generator: seeds, clips, and the backlog's guarantee."""
from __future__ import annotations

import collections

import pytest

from bench.harness import spec, traffic
from conftest import small_traffic

MIXES = ("backlog", "short_chat")


@pytest.mark.parametrize("mix", MIXES)
def test_bench_same_seed_same_schedule(mix):
    tr = spec.load_traffic(mix)
    a = traffic.make_items(tr, 2 ** 31 + 7, 300)
    b = traffic.make_items(tr, 2 ** 31 + 7, 300)
    c = traffic.make_items(tr, 3, 300)
    assert a == b
    assert a != c
    for item in a[:5]:
        ids = traffic.prompt_ids(2 ** 31 + 7, item.index, item.prompt_len,
                                 1000)
        again = traffic.prompt_ids(2 ** 31 + 7, item.index, item.prompt_len,
                                   1000)
        assert (ids == again).all() and len(ids) == item.prompt_len
        assert ids.min() >= 0 and ids.max() < 1000


@pytest.mark.parametrize("mix", MIXES)
def test_bench_lengths_inside_clips_and_same_work_every_seed(mix):
    tr = spec.load_traffic(mix)
    block = tr["block"]
    seen = []
    for seed in (0, 1, 2 ** 33 + 1):
        items = traffic.make_items(tr, seed, block * 8)
        for item in items:
            assert tr["prompt"]["min"] <= item.prompt_len <= tr["prompt"]["max"]
            assert tr["output"]["min"] <= item.output_len <= tr["output"]["max"]
        # every block holds the same lengths, in an order of the seed's
        for k in range(8):
            blk = items[k * block:(k + 1) * block]
            seen.append((collections.Counter(i.prompt_len for i in blk),
                         collections.Counter(i.output_len for i in blk)))
    assert all(s == seen[0] for s in seen)


def test_bench_poisson_offsets_rise_at_the_rate():
    tr = spec.load_traffic("short_chat")
    items = traffic.make_items(tr, 5, 64 * 20)
    offs = [i.due_offset for i in items]
    assert all(b > a for a, b in zip(offs, offs[1:]))
    rate = len(offs) / offs[-1]
    assert abs(rate - tr["rate"]) / tr["rate"] < 0.1


def test_bench_backlog_keeps_the_queue_at_the_cap():
    """Before every step the queue holds at least the batch cap, whatever
    the engine took in the step before."""
    tr = small_traffic("backlog")
    cap = tr["engine"]["batch"]
    queue: list = []
    gen = traffic.Generator(tr, traffic.make_items(tr, 9, 400),
                            lambda item, due: queue.append(item) or True,
                            lambda: len(queue))
    gen.start(0.0)
    takes = [cap, 0, 1, 3, cap, 2, 0, cap, 1] * 10
    for step, k in enumerate(takes):
        gen.pump(float(step))
        assert len(queue) >= cap
        del queue[:k]                     # the batcher joins k requests
    assert gen.next <= 400


def test_bench_poisson_submits_only_what_is_due():
    tr = small_traffic("poisson")
    items = traffic.make_items(tr, 4, 50)
    got = []
    gen = traffic.Generator(tr, items, lambda item, due: got.append(
        (item, due)) or True, lambda: 0)
    gen.start(100.0)
    horizon = items[10].due_offset
    gen.pump(100.0 + horizon)
    assert [i for i, _ in got] == items[:11]
    assert all(due == 100.0 + i.due_offset for i, due in got)
    wait = gen.seconds_to_next(100.0 + horizon)
    assert wait == pytest.approx(items[11].due_offset - horizon)
