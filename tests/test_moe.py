"""MoE dispatch implementations: agreement, capacity semantics, rankings;
the published gate and one chip's share of the experts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.config import ModelConfig
from repro.models.moe import (MoEOptions, _capacity, apply_moe,
                              assign_experts, init_moe, route)

CFG = ModelConfig(name="t", family="moe", n_layers=2, d_model=32, n_heads=4,
                  n_kv_heads=2, d_ff=64, vocab_size=100, n_experts=8,
                  top_k=2, moe_d_ff=48, n_shared_experts=2)
P = init_moe(jax.random.PRNGKey(0), CFG)
X = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32), jnp.float32)


def test_impls_agree_with_dense_oracle_when_unbounded():
    o_dense, aux_d = apply_moe(P, X, CFG, MoEOptions(impl="dense"))
    for impl in ("gather", "einsum"):
        for ranking in ("cumsum", "sort"):
            o, aux = apply_moe(P, X, CFG, MoEOptions(
                impl=impl, capacity_factor=100.0, ranking=ranking))
            np.testing.assert_allclose(o, o_dense, rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(aux, aux_d, rtol=1e-5)


@pytest.mark.parametrize("group_size", [0, 16])
@pytest.mark.parametrize("cf", [1.0, 2.0])
def test_gather_equals_einsum_under_drops(group_size, cf):
    o_g, _ = apply_moe(P, X, CFG, MoEOptions(
        impl="gather", capacity_factor=cf, group_size=group_size))
    o_e, _ = apply_moe(P, X, CFG, MoEOptions(
        impl="einsum", capacity_factor=cf, group_size=group_size))
    np.testing.assert_allclose(o_g, o_e, rtol=2e-5, atol=2e-5)


def test_sort_ranking_equals_cumsum():
    for gs in (0, 16):
        a = assign_experts(jax.random.normal(jax.random.PRNGKey(2), (64, 8)),
                           2, 8, 16, gs, "cumsum")
        b = assign_experts(jax.random.normal(jax.random.PRNGKey(2), (64, 8)),
                           2, 8, 16, gs, "sort")
        np.testing.assert_array_equal(a["pos"], b["pos"])
        np.testing.assert_array_equal(a["keep"], b["keep"])


def test_capacity_drops_tokens():
    logits = jnp.zeros((64, 8))                     # all route to expert 0/1
    a = assign_experts(logits, 2, 8, capacity=16)
    assert int(a["keep"].sum()) <= 2 * 16 * 8       # bounded by capacity*E
    assert not bool(a["keep"].all())                # some dropped


def test_positions_are_dense_rank():
    logits = jax.random.normal(jax.random.PRNGKey(3), (32, 8))
    a = assign_experts(logits, 2, 8, capacity=1000)
    # for each expert, the set of positions is exactly {0..count-1}
    idx = np.asarray(a["idx"]).reshape(-1)
    pos = np.asarray(a["pos"]).reshape(-1)
    for e in range(8):
        ps = np.sort(pos[idx == e])
        np.testing.assert_array_equal(ps, np.arange(len(ps)))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([1.0, 1.25, 2.0]))
def test_property_moe_output_finite(seed, cf):
    x = jax.random.normal(jax.random.PRNGKey(seed % 2**30), (1, 16, 32))
    o, aux = apply_moe(P, x, CFG, MoEOptions(impl="gather",
                                             capacity_factor=cf,
                                             ranking="sort"))
    assert bool(jnp.isfinite(o).all())
    assert bool(jnp.isfinite(aux))


def test_capacity_rounding_shardable():
    assert _capacity(1_000_000, 8, 384, 1.25) % 512 == 0
    assert _capacity(128, 8, 384, 1.25) % 16 == 0
    assert _capacity(1, 1, 1, 1.0) >= 1


# -- the published gate and one chip's share of the experts ------------------

DS = ModelConfig(name="ds", family="moe", n_layers=2, d_model=32, n_heads=4,
                 n_kv_heads=4, d_ff=64, vocab_size=100, n_experts=8,
                 top_k=2, moe_d_ff=24, n_shared_experts=1, n_group=4,
                 topk_group=2, norm_topk_prob=False,
                 routed_scaling_factor=2.5)
P_DS = init_moe(jax.random.PRNGKey(4), DS)


def _published_gate(logits, cfg):
    """hf ``MoEGate`` (softmax, group_limited_greedy) token by token in
    numpy: the reference the program's gate is held to."""
    logits = np.asarray(logits, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    e, g = cfg.n_experts, cfg.n_group
    w, idx = [], []
    for p in probs:
        groups = np.argsort(-p.reshape(g, e // g).max(-1))[: cfg.topk_group]
        eligible = np.where(np.repeat(np.isin(np.arange(g), groups), e // g),
                            p, 0.0)
        top = np.argsort(-eligible)[: cfg.top_k]
        wt = eligible[top]
        wt = (wt / wt.sum() if cfg.norm_topk_prob
              else wt * cfg.routed_scaling_factor)
        w.append(wt)
        idx.append(top)
    return np.array(w), np.array(idx)


def _swiglu_np(x, wg, wu, wd):
    h = x @ wg
    return (h / (1 + np.exp(-h)) * (x @ wu)) @ wd


def _uncut_layer(p, x, cfg):
    """The whole layer token by token: each pick's expert times its weight,
    plus the shared expert."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    xf = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    w, idx = _published_gate(xf @ p["router"], cfg)
    out = _swiglu_np(xf, p["shared"]["wg"], p["shared"]["wu"],
                     p["shared"]["wd"])
    for t in range(len(xf)):
        for wt, e in zip(w[t], idx[t]):
            out[t] += wt * _swiglu_np(xf[t], p["wg"][e], p["wu"][e],
                                      p["wd"][e])
    return out.reshape(x.shape)


@pytest.mark.parametrize("impl", ["dense", "gather", "einsum"])
@pytest.mark.parametrize("shares", [2, 4])
def test_held_shares_sum_to_the_uncut_layer(shares, impl):
    """Experts partitioned over ``shares`` chips: the parts every share
    computes, with the shared expert counted once (share 0 holds it), add
    up to the uncut layer; no capacity drops anything here."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 32), jnp.float32)
    held = DS.n_experts // shares
    total = 0.0
    for i in range(shares):
        cfg = DS.replace(n_experts_held=held, first_expert=i * held)
        p = {k: (v[i * held:(i + 1) * held] if k in ("wg", "wu", "wd")
                 else v) for k, v in P_DS.items()
             if k != "shared" or i == 0}
        out, _ = apply_moe(p, x, cfg, MoEOptions(impl=impl,
                                                 capacity_factor=100.0))
        total = total + np.asarray(out)
    np.testing.assert_allclose(total, _uncut_layer(P_DS, x, DS),
                               rtol=1e-4, atol=1e-5)


def test_dropless_token_output_ignores_batch_mates():
    """On the serving path a token's output is the same alone as among any
    batch-mates (held experts, no capacity); under a capacity of one the
    generic path is not."""
    cfg = DS.replace(n_experts_held=4, first_expert=2)
    p = {k: (v[2:6] if k in ("wg", "wu", "wd") else v)
         for k, v in P_DS.items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 256, 32), jnp.float32)
    whole, _ = apply_moe(p, x, cfg, MoEOptions(impl="dense"))
    for t in (0, 100, 255):
        alone, _ = apply_moe(p, x[:, t:t + 1], cfg, MoEOptions(impl="dense"))
        np.testing.assert_allclose(alone[0, 0], whole[0, t], rtol=1e-5,
                                   atol=1e-6)
    # alone, a token fits any capacity; among 255 others some are dropped
    capped, _ = apply_moe(p, x, cfg, MoEOptions(impl="gather",
                                                capacity_factor=1.0))
    assert not np.allclose(capped, whole, rtol=1e-5, atol=1e-6)


def test_group_limited_gate_picks_from_top_groups():
    logits = jax.random.normal(jax.random.PRNGKey(7), (256, 8)) * 3.0
    probs, w, idx = route(logits, 2, n_group=4, topk_group=2,
                          norm_topk_prob=False, scale=2.5)
    best = np.asarray(probs).reshape(256, 4, 2).max(-1)
    top_groups = np.argsort(-best, -1)[:, :2]
    picked_groups = np.asarray(idx) // 2
    for t in range(256):
        assert set(picked_groups[t]) <= set(top_groups[t])
    want_w, want_idx = _published_gate(logits, DS)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)


@pytest.mark.parametrize("norm,scale", [(False, 16.0), (False, 1.0),
                                        (True, 16.0)])
def test_routed_scaling_and_norm_topk_prob(norm, scale):
    """norm_topk_prob off: the picked softmax scores times
    routed_scaling_factor; on: renormalized to sum to one, the factor
    unused (hf ``MoEGate``)."""
    logits = jax.random.normal(jax.random.PRNGKey(8), (64, 8))
    probs, w, idx = route(logits, 2, n_group=4, topk_group=2,
                          norm_topk_prob=norm, scale=scale)
    picked = np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
    if norm:
        np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
        np.testing.assert_allclose(
            w, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    else:
        np.testing.assert_allclose(w, picked * scale, rtol=1e-6)


@pytest.mark.parametrize("impl", ["dense", "assign"])
def test_default_config_matches_previous_outputs_bitwise(impl):
    """A config that sets none of the gate or held-expert fields routes as
    before them: softmax, top-k, renormalized, all experts held."""
    logits = (X.reshape(-1, 32) @ P["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, 2)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    if impl == "assign":
        for cfg in (None, CFG):
            a = assign_experts(logits, 2, 8, 16, 0, "cumsum", cfg)
            np.testing.assert_array_equal(a["idx"], idx)
            np.testing.assert_array_equal(a["w"], w)
            b = assign_experts(logits, 2, 8, 16)
            np.testing.assert_array_equal(a["pos"], b["pos"])
            np.testing.assert_array_equal(a["keep"], b["keep"])
        return
    t = logits.shape[0]
    full = jnp.zeros((t, 8), jnp.float32).at[
        jnp.arange(t)[:, None], idx].set(w)
    xf = X.reshape(-1, 32)
    h = jax.nn.silu(jnp.einsum("etd,edf->etf", jnp.broadcast_to(
        xf[None], (8, t, 32)), P["wg"])) * jnp.einsum(
        "etd,edf->etf", jnp.broadcast_to(xf[None], (8, t, 32)), P["wu"])
    out = jnp.einsum("te,etd->td", full, jnp.einsum("etf,efd->etd", h,
                                                    P["wd"]))
    sh = P["shared"]
    out = out + (jax.nn.silu(xf @ sh["wg"]) * (xf @ sh["wu"])) @ sh["wd"]
    got, _ = apply_moe(P, X, CFG, MoEOptions(impl="dense"))
    np.testing.assert_array_equal(got.reshape(-1, 32), out)


@pytest.mark.parametrize("first,held", [(0, 8), (2, 4)])
def test_dense_path_counts_held_picks_of_counted_rows(first, held):
    """The serving path's counters: the held experts that the counted
    tokens picked, and every row the held experts computed; counting
    leaves the output as it is."""
    cfg = DS.replace(n_experts_held=held, first_expert=first)
    p = {k: (v[first:first + held] if k in ("wg", "wu", "wd") else v)
         for k, v in P_DS.items()}
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 8, 32), jnp.float32)
    rows = jnp.arange(16) % 3 != 0
    out, _, counts = apply_moe(p, x, cfg, MoEOptions(impl="dense"),
                               count_rows=rows)
    _, idx = _published_gate(
        np.asarray(x).reshape(-1, 32) @ np.asarray(P_DS["router"]), DS)
    mine = (idx >= first) & (idx < first + held)
    assert np.asarray(counts).tolist() == [
        int(mine[np.asarray(rows)].sum()), 16 * held]
    plain, _ = apply_moe(p, x, cfg, MoEOptions(impl="dense"))
    np.testing.assert_array_equal(out, plain)
