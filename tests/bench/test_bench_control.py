"""The control comes out not correct.

The control is the reference computed one precision step below the
configured bfloat16 — every matrix product's operands rounded to float8
e4m3 — put in the program's place: at each position it serves the token
its own logits rank first, and that token's gap is read in the float32
reference.  Here it runs on the CPU with each configuration as published,
over a seeded prompt and continuation of 64 tokens (short, so that a test
run holds it), and one of the numbers the configuration compares must
exceed its limit.  (On the chip, at the cells' own sizes, the control
is read by ``bench/control.py``; its readings are in ``PERF.md``.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import check, serve, spec
from conftest import CONFIGS


@pytest.mark.parametrize("name", CONFIGS)
def test_bench_fp8_control_fails_the_limit(name):
    cfg, model = spec.load_config(name)
    limits = spec.load_json(f"{spec.config_dir(name)}/check.json")
    params = serve.seeded_params(model, cfg, 2 ** 31 + 1, jnp.bfloat16,
                                 jax.sharding.SingleDeviceSharding(
                                     jax.devices()[0]))
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg["vocab_size"], size=64).astype(np.int32)
    requests = [(ids[:16], ids[16:])]
    control = check.gaps(model, cfg, params, requests, 64, mm=check.mm_fp8,
                         compare="ranked")
    exact = check.gaps(model, cfg, params, requests, 64, mm=check.mm_f32,
                       compare="ranked")
    assert float(np.max(exact)) == 0.0      # the reference ranks itself first
    # the control fails one of the configuration's numbers
    read = {k: fn(control) for k, fn in check.NUMBERS.items() if k in limits}
    assert read and any(v > limits[k] for k, v in read.items()), (read,
                                                                  limits)
