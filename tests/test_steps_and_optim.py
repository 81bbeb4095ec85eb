"""Step-builder invariants + optimizer behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.specializer import discover_space, specialize_builder
from repro.models import transformer as model
from repro.optim import OptConfig, apply_updates, cosine_lr, init_opt_state
from repro.models.transformer import RunOptions
from repro.training import (SERVE_SEARCH_POINTS, cross_entropy,
                            make_serve_builder, make_train_builder)

CFG = configs.get_reduced("yi-6b").replace(compute_dtype="float32")
OPT = OptConfig(lr=1e-2, warmup_steps=1, total_steps=100)


def _state_and_batch(cfg=CFG, b=4, s=16):
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    state = {"params": params, "opt": init_opt_state(params, OPT)}
    toks = jax.random.randint(jax.random.PRNGKey(7), (b, s + 1), 0,
                              cfg.vocab_size)
    return state, {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_spec_space_discovered():
    space = discover_space(make_train_builder(CFG, OPT, kernel_impl="xla"))
    labels = set(space.labels())
    assert {"remat", "microbatch", "block_q", "block_kv", "logits_layout",
            "sharding_profile", "logits_dtype"} <= labels


def test_microbatch_equivalence():
    """Grad accumulation (microbatch spec point) must not change the math."""
    state, batch = _state_and_batch()
    builder = make_train_builder(CFG, OPT, kernel_impl="xla")
    outs = {}
    for m in (1, 2, 4):
        step = jax.jit(specialize_builder(builder, {"microbatch": m}).fn)
        s2, metrics = step(jax.tree_util.tree_map(jnp.copy, state), batch)
        outs[m] = (float(metrics["loss"]),
                   np.asarray(jax.tree_util.tree_leaves(s2["params"])[0]))
    for m in (2, 4):
        assert abs(outs[m][0] - outs[1][0]) < 1e-4
        np.testing.assert_allclose(outs[m][1], outs[1][1], rtol=2e-4,
                                   atol=2e-4)


def test_remat_equivalence():
    """Remat policies change memory, never the result."""
    state, batch = _state_and_batch()
    builder = make_train_builder(CFG, OPT, kernel_impl="xla")
    ref = None
    for remat in ("none", "dots", "full"):
        step = jax.jit(specialize_builder(builder, {"remat": remat}).fn)
        _, metrics = step(jax.tree_util.tree_map(jnp.copy, state), batch)
        if ref is None:
            ref = float(metrics["loss"])
        else:
            assert abs(float(metrics["loss"]) - ref) < 1e-4


def test_logits_layout_equivalence():
    state, batch = _state_and_batch()
    builder = make_train_builder(CFG, OPT, kernel_impl="xla")
    losses = []
    for layout in ("sharded", "gathered"):
        step = jax.jit(specialize_builder(
            builder, {"logits_layout": layout}).fn)
        _, m = step(jax.tree_util.tree_map(jnp.copy, state), batch)
        losses.append(float(m["loss"]))
    assert abs(losses[0] - losses[1]) < 1e-5


def test_cross_entropy_masking():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.array([[1, 2, -1, -1]])
    loss = cross_entropy(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(8), rtol=1e-5)


def test_cosine_schedule_monotone_warmup():
    lrs = [float(cosine_lr(OPT, jnp.float32(s))) for s in range(0, 5)]
    assert lrs[0] <= lrs[1]
    assert abs(lrs[1] - OPT.lr) < 1e-6   # warmup_steps=1
    late = float(cosine_lr(OPT, jnp.float32(OPT.total_steps)))
    assert late < 1e-4


def test_clip_norm_bounds_update():
    cfg = OptConfig(lr=1.0, warmup_steps=0, total_steps=10, clip_norm=1e-3,
                    weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    st = init_opt_state(params, cfg)
    g = {"w": jnp.full(4, 1e6)}
    p2, _ = apply_updates(params, g, st, cfg)
    # clipped: first Adam step is bounded by lr regardless of raw grad
    assert float(jnp.abs(p2["w"]).max()) <= 1.1 * cfg.lr


def test_int8_ef_error_feedback_accumulates():
    cfg = OptConfig(compress="int8_ef")
    params = {"w": jnp.zeros(3)}
    st = init_opt_state(params, cfg)
    assert "ef" in st
    g = {"w": jnp.array([1e-9, 1.0, -1.0])}   # tiny grad lost to quant
    _, st2 = apply_updates(params, g, st, cfg)
    assert float(jnp.abs(st2["ef"]["w"][0])) > 0  # error retained for later


def test_chunked_ce_equals_full():
    """loss_chunk spec point: identical loss & params (never materializes
    the (B,S,V) fp32 logits)."""
    state, batch = _state_and_batch()
    builder = make_train_builder(CFG, OPT, kernel_impl="xla")
    outs = {}
    for lc in (0, 16):
        step = jax.jit(specialize_builder(
            builder, {"loss_chunk": lc} if lc else {}).fn)
        s2, m = step(jax.tree_util.tree_map(jnp.copy, state), batch)
        outs[lc] = (float(m["loss"]),
                    np.asarray(jax.tree_util.tree_leaves(s2["params"])[0]))
    assert abs(outs[0][0] - outs[16][0]) < 1e-5
    np.testing.assert_allclose(outs[0][1], outs[16][1], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "rwkv6-1.6b",
                                  "deepseek-v2-236b-ep16"])
def test_every_searched_serve_point_changes_the_program(arch):
    """Each point the serve step's search sweeps lowers two of its choices
    available here to different programs, in both phases: a point that
    changes nothing would only spend the search's dwells."""
    cfg = configs.select(arch, reduced=True)
    b, max_len = 2, 32
    params = jax.eval_shape(lambda k: model.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(
        cfg, b, max_len, RunOptions(decode_cache_dtype=cfg.compute_dtype)))
    rows = jax.ShapeDtypeStruct((b,), jnp.int32)
    builder = make_serve_builder(cfg)
    space = discover_space(builder)
    for label in SERVE_SEARCH_POINTS:
        choices = space[label].choices[:2]      # xla_ref, pallas_interpret
        assert len(choices) == 2, (label, choices)
        for shape in ((b,), (b, 16)):
            tokens = jax.ShapeDtypeStruct(shape, jnp.int32)
            texts = [jax.jit(specialize_builder(builder, {label: c}).fn)
                     .lower(params, cache, tokens, rows, rows).as_text()
                     for c in choices]
            assert texts[0] != texts[1], (label, choices, shape)
