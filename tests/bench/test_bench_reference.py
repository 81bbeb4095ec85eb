"""Each configuration's plain float32 reference against the served path, at
the program's reduced preset on the CPU: what the engine served after
chunked prefill and after each cached decode step matches the reference's
logits over the whole sequence."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench.harness import check, serve, traffic
from conftest import CONFIGS, reduced_config, small_traffic


@pytest.mark.parametrize("name", CONFIGS)
def test_bench_reference_matches_served_logits(name, state_dir):
    from repro.serve import Request

    cfg, model = reduced_config(name)
    tr = small_traffic()
    built, params = serve.build(cfg, model, tr, seed=2 ** 31 + 3,
                                state_dir=state_dir, reduced=True)
    try:
        vocab = built.cfg.vocab_size
        prompts = {}
        reqs = []
        for i, (p, o) in enumerate(((21, 6), (5, 9), (17, 4))):
            req = Request(prompt_tokens=p, max_new_tokens=o)
            prompts[req.rid] = traffic.prompt_ids(7, i, p, vocab)
            reqs.append(req)
        built.executor.prompt_fn = lambda r: prompts[r.rid]
        built.executor.logits_log = {r.rid: [] for r in reqs}
        for r in reqs:
            built.engine.submit(r)
        built.engine.run(max_steps=500)
        length = tr["engine"]["max_len"]
        worst = 0.0
        for r in reqs:
            served = list(r.payload)
            assert len(served) == r.max_new_tokens
            rows = np.stack(built.executor.logits_log[r.rid])
            seq = np.concatenate([prompts[r.rid], served[:-1]])
            tokens = np.zeros((1, length), np.int32)
            tokens[0, :len(seq)] = seq
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(model.logits(params, cfg, tokens,
                                              check.mm_f32))[0]
            pos = r.prompt_tokens - 1 + np.arange(len(served))
            # float32 both sides; logits are of order one
            worst = max(worst, float(np.max(np.abs(rows - ref[pos]))))
            gaps = check.gaps(model, cfg, params,
                              [(prompts[r.rid], served)], length)
            assert float(np.max(gaps)) <= 1e-4
        assert worst < 2e-3, worst
    finally:
        built.rt.shutdown()
