"""The check catches a broken timed path.

Each case drives the rest of a run — set-up, warm-up, window, sampling and
the comparison with the reference — at the program's reduced preset on
the CPU, past the harness's look for a chip, with one fault planted under
the engine, and sees ``correct`` come out false by the configuration's
own gap limit.  The faults a serving cell on one chip can have:

* ``state_unchanged`` — the step hands back its cache or recurrent state
  unchanged (the paged-KV harvest writes nothing back);
* ``half_batch`` — half of the batch is left out: its rows are served the
  other half's logits;
* ``token_altered`` — a token is altered where it is produced (every
  second sampled token is the next id instead of the arg-max).

There is no exchange between chips on a one-chip cell, so that fault has
no case.  The unbroken run is the control: it must come out correct.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench.harness import check, serve, spec
from conftest import reduced_config, small_traffic


def state_unchanged(built):
    built.kv.harvest = lambda rids, new_cache, n_new: None


def half_batch(built):
    handler = built.executor.handler

    def call(params, cache, tokens, pos, n_new):
        logits, new_cache = handler(params, cache, tokens, pos, n_new)
        h = logits.shape[0] // 2
        return logits.at[h:2 * h].set(logits[:h]), new_cache

    built.executor.handler = call


def token_altered(built):
    vocab = built.cfg.vocab_size
    calls = [0]

    def sample(row):
        calls[0] += 1
        best = int(np.argmax(row))
        return (best + 1) % vocab if calls[0] % 2 == 0 else best

    built.executor.sample = sample


CASES = [("qwen3-0.6b", None), ("rwkv6-1.6b", None),
         ("qwen3-0.6b", state_unchanged), ("rwkv6-1.6b", state_unchanged),
         ("qwen3-0.6b", half_batch), ("rwkv6-1.6b", token_altered)]


@pytest.mark.parametrize("name,fault", CASES,
                         ids=[f"{n}-{f.__name__ if f else 'sound'}"
                              for n, f in CASES])
def test_bench_fault_fails_the_check(name, fault, state_dir):
    cfg, model = reduced_config(name)
    tr = small_traffic("backlog")
    run = serve.Run(cell=None, cfg=cfg, model=model, traffic=tr,
                    seed=2 ** 31 + 11, seconds=5.0,
                    t_process=time.perf_counter())
    serve.run_cell(run, state_dir=state_dir, reduced=True, on_built=fault)
    limits = spec.load_json(f"{spec.config_dir(name)}/check.json")
    # a loaded CPU serves fewer tokens than a chip window: compare every
    # request it served
    numbers = check.compare(run, dict(limits, requests=len(run.served),
                                            min_tokens=10))
    assert numbers["tokens_compared"]["value"] >= 10
    gaps = [numbers[k] for k in check.NUMBERS if k in numbers]
    assert gaps
    if fault is None:
        assert check.is_correct(numbers), numbers
    else:
        assert any(g["value"] > g["limit"] for g in gaps), numbers
        assert not check.is_correct(numbers)
