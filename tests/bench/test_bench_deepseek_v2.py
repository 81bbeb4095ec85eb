"""The deepseek-v2-236b-ep16 configuration's plain float32 reference
against the served path, at the program's reduced preset on the CPU (8
experts in 4 groups, 4 of them held, top-2 in the best 2 groups, YaRN on).

* What the engine served after the chunked-prefill scan and after each
  cached decode step matches the reference's logits over the whole
  sequence.
* The reference computed in float8 (the check's control) fails the
  configuration's ``check.json``.
* Planted faults in the program's gate fail it too: the top-k weights
  renormalized (the published gate scales them by
  ``routed_scaling_factor``), and the softmax and top-k taken over the
  held experts only (the published gate scores all of them).
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import check, serve, spec, traffic
from conftest import small_traffic

NAME = "deepseek-v2-236b-ep16"

#: the program's ModelConfig fields -> the configuration file's keys
KEYS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
        "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
        "d_head": "v_head_dim", "d_ff": "intermediate_size",
        "vocab_size": "vocab_size", "q_lora_rank": "q_lora_rank",
        "kv_lora_rank": "kv_lora_rank", "rope_head_dim": "qk_rope_head_dim",
        "nope_head_dim": "qk_nope_head_dim", "n_experts": "router_experts",
        "n_experts_held": "n_routed_experts", "first_expert": "first_expert",
        "n_group": "n_group", "topk_group": "topk_group",
        "top_k": "num_experts_per_tok", "moe_d_ff": "moe_intermediate_size"}

#: (prompt, output) lengths of the served requests: prompts that take one
#: to two prefill chunks, decodes to near the cache's end, 134 tokens to
#: compare
REQUESTS = ((21, 40), (5, 50), (17, 44))


def reduced_config():
    """``(cfg, model)`` at the sizes of the program's reduced preset."""
    from repro import configs

    cfg, model = spec.load_config(NAME)
    small = configs.get_reduced(cfg["program_arch"])
    cfg = copy.deepcopy(cfg)
    for field, key in KEYS.items():
        cfg[key] = getattr(small, field)
    return cfg, model


def serve_requests(state_dir):
    """Serve ``REQUESTS`` through ``build_engine`` at the reduced preset;
    returns the weights, ``(prompt, served)`` pairs and each request's
    logits rows."""
    from repro.serve import Request

    cfg, model = reduced_config()
    tr = small_traffic()
    built, params = serve.build(cfg, model, tr, seed=2 ** 31 + 5,
                                state_dir=state_dir, reduced=True)
    try:
        vocab = built.cfg.vocab_size
        prompts, reqs = {}, []
        for i, (p, o) in enumerate(REQUESTS):
            req = Request(prompt_tokens=p, max_new_tokens=o)
            prompts[req.rid] = traffic.prompt_ids(11, i, p, vocab)
            reqs.append(req)
        built.executor.prompt_fn = lambda r: prompts[r.rid]
        built.executor.logits_log = {r.rid: [] for r in reqs}
        for r in reqs:
            built.engine.submit(r)
        built.engine.run(max_steps=500)
        served = [(prompts[r.rid], list(r.payload)) for r in reqs]
        rows = [np.stack(built.executor.logits_log[r.rid]) for r in reqs]
    finally:
        built.rt.shutdown()
    return cfg, model, params, served, rows


def verdict(cfg, model, params, served, **kw) -> bool:
    """Whether ``served`` passes ``check.json``, every request compared."""
    limits = spec.load_json(f"{spec.config_dir(NAME)}/check.json")
    g = check.gaps(model, cfg, params, served,
                   small_traffic()["engine"]["max_len"], **kw)
    numbers = {name: {"value": fn(g), "limit": float(limits[name])}
               for name, fn in check.NUMBERS.items() if name in limits}
    numbers["tokens_compared"] = {"value": len(g), "limit": 1}
    return check.is_correct(numbers)


def test_served_logits_match_reference(state_dir):
    cfg, model, params, served, rows = serve_requests(state_dir)
    length = small_traffic()["engine"]["max_len"]
    worst = 0.0
    for (prompt, out), got, (p, o) in zip(served, rows, REQUESTS):
        assert len(out) == o
        seq = np.concatenate([prompt, out[:-1]])
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(model.logits(params, cfg, tokens,
                                          check.mm_f32))[0]
        # float32 on both sides with logits of order one: the program's
        # absorbed decode and chunked scan sum in another order than the
        # reference's materialized attention, so rounding differs in the
        # last bits, far below 2e-3
        worst = max(worst, float(np.max(np.abs(got - ref[p - 1 + np.arange(
            len(out))]))))
    assert worst < 2e-3, worst
    # every served token is the reference's first choice, up to rounding
    assert verdict(cfg, model, params, served)
    assert float(np.max(check.gaps(model, cfg, params, served,
                                   length))) <= 1e-4


def test_serve_steps_count_held_expert_rows(state_dir):
    """Every serve step records the MoE counters in its ``serve.sample``
    span: the rows computed are the step's rows times the held experts
    of each MoE layer, and the picks are those of real tokens only, so at
    most ``top_k`` a token a layer."""
    from repro import configs
    from repro.core import telemetry

    small = configs.get_reduced(spec.load_config(NAME)[0]["program_arch"])
    per_row = small.held_experts * small.n_moe_layers
    before = len(telemetry.recent_spans())
    serve_requests(state_dir)
    args = [a for name, _, _, a in telemetry.recent_spans()[before:]
            if name == "serve.sample"]
    assert args and all("expert_rows_computed" in a for a in args)
    routed = sum(a["expert_rows_routed"] for a in args)
    assert all(a["expert_rows_computed"] % per_row == 0
               and 0 <= a["expert_rows_routed"] <= a["expert_rows_computed"]
               for a in args)
    tokens = sum(p + o - 1 for p, o in REQUESTS)
    assert 0 < routed <= tokens * small.top_k * small.n_moe_layers


def test_fp8_control_fails_the_check(state_dir):
    cfg, model, params, served, _ = serve_requests(state_dir)
    assert not verdict(cfg, model, params, served, mm=check.mm_fp8,
                       compare="ranked")


def renormalized(monkeypatch):
    from repro import configs

    select = configs.select
    monkeypatch.setattr(configs, "select", lambda name, reduced=False:
                        select(name, reduced).replace(norm_topk_prob=True))


def held_only(monkeypatch):
    from repro.models import moe

    gate = moe._gate

    def gate_over_held(logits, cfg):
        ids = jnp.arange(logits.shape[-1])
        mine = (ids >= cfg.first_expert) & \
            (ids < cfg.first_expert + cfg.held_experts)
        return gate(jnp.where(mine, logits, -jnp.inf), cfg)

    monkeypatch.setattr(moe, "_gate", gate_over_held)


@pytest.mark.parametrize("fault", [renormalized, held_only],
                         ids=lambda f: f.__name__)
def test_gate_fault_fails_the_check(fault, state_dir, monkeypatch):
    fault(monkeypatch)
    cfg, model, params, served, _ = serve_requests(state_dir)
    assert not verdict(cfg, model, params, served)
