"""Layer-major chunked prefill against the token-major scan it replaces.

``prefill_chunk`` pushes a (B, C) chunk through each layer once for GQA,
MLA and RWKV6 (MoE on the dropless ``dense`` path), and keeps the scan of
C single-token decode steps for everything else.  Both are run here in
float32 on the reduced presets, from a cache that already holds context,
over ragged rows: a full chunk, a short one, none, and one whose chunk
runs past the end of the cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import KernelOptions, MoEOptions, RunOptions
from repro.models import transformer as model

B, C, MAX_LEN = 4, 8, 32
POS = jnp.array([0, 5, 28, 10], jnp.int32)        # row 2 runs past MAX_LEN
N_NEW = jnp.array([8, 3, 8, 0], jnp.int32)


def _opts(window=None, moe_impl="dense", cache_dtype="float32"):
    return RunOptions(kernels=KernelOptions(impl="xla", chunk_len=8),
                      moe=MoEOptions(impl=moe_impl), window=window,
                      decode_cache_dtype=cache_dtype)


def _filled_cache(cfg, opts, key):
    """A cache with context in every row: each leaf with a batch axis
    drawn at random (recurrent state small, so it stays a state)."""
    cache = model.init_cache(cfg, B, MAX_LEN, opts)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    keys = jax.random.split(key, len(leaves))
    out = [a if a.dtype == jnp.int32
           else (0.3 * jax.random.normal(k, a.shape)).astype(a.dtype)
           for a, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _both(arch, opts):
    cfg = configs.get_reduced(arch).replace(compute_dtype="float32")
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    cache = _filled_cache(cfg, opts, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, C), 0,
                                cfg.vocab_size)
    run = lambda fn: jax.jit(
        lambda p, c, t: fn(p, c, t, POS, N_NEW, cfg, opts, cfg.is_moe)
    )(params, cache, tokens)
    return cfg, run(model.prefill_chunk), run(model._prefill_scan)


CASES = {
    "gqa_qk_norm": ("qwen3-0.6b", _opts()),
    "gqa_window": ("qwen3-0.6b", _opts(window=6)),
    "mla_yarn_moe_held": ("deepseek-v2-236b-ep16", _opts()),
    "rwkv6": ("rwkv6-1.6b", _opts()),
    # the serve path's cache dtype: both orders round what they keep alike
    "gqa_bf16_cache": ("qwen3-0.6b", _opts(cache_dtype="bfloat16")),
    "rwkv6_bf16_cache": ("rwkv6-1.6b", _opts(cache_dtype="bfloat16")),
}


@pytest.mark.parametrize("case", list(CASES))
def test_layer_major_prefill_matches_token_scan(case):
    arch, opts = CASES[case]
    cfg, chunk, scan = _both(arch, opts)
    assert len(chunk) == len(scan) == (3 if cfg.is_moe else 2)
    logits, cache = chunk[:2]
    np.testing.assert_allclose(logits, scan[0], rtol=1e-4, atol=1e-4)
    assert not np.any(np.asarray(logits)[np.asarray(N_NEW) == 0])
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    for (path, leaf), ref in zip(flat, jax.tree_util.tree_leaves(scan[1])):
        # a bfloat16 leaf may round a value that differs in float32's last
        # bits to the neighbouring bfloat16: one unit in its last place
        tol = 1e-2 if leaf.dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol,
                                   err_msg=jax.tree_util.keystr(path))
    if cfg.is_moe:
        # the held experts' picks by the chunk's real tokens, and the
        # rows they computed: every row of the chunk, in both orders
        np.testing.assert_array_equal(chunk[2], scan[2])
        assert int(chunk[2][1]) == (cfg.n_moe_layers * B * C
                                    * cfg.held_experts)


PATHS = {
    "qwen3-0.6b": True, "deepseek-v2-236b-ep16": True, "rwkv6-1.6b": True,
    "hymba-1.5b": False,
}


def _chunk_scans(arch, opts):
    """Lengths of the top-level scans of ``prefill_chunk``'s program."""
    cfg = configs.get_reduced(arch).replace(compute_dtype="float32")
    params = jax.eval_shape(lambda k: model.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(cfg, B, MAX_LEN, opts))
    tokens = jax.ShapeDtypeStruct((B, C), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, c, t: model.prefill_chunk(p, c, t, POS, N_NEW, cfg, opts)
    )(params, cache, tokens)
    return cfg, [e.params["length"] for e in jaxpr.eqns
                 if e.primitive.name == "scan"]


@pytest.mark.parametrize("arch", list(PATHS))
def test_prefill_path_follows_the_layer_type(arch):
    cfg, lengths = _chunk_scans(arch, _opts())
    assert model._layer_major(cfg, _opts()) is PATHS[arch]
    if PATHS[arch]:
        # one scan per layer stack, none over the chunk's tokens
        assert lengths and C not in lengths, lengths
        assert sum(lengths) == cfg.n_layers
    else:
        assert lengths == [C]


def test_capacity_moe_keeps_the_token_scan():
    cfg = configs.get_reduced("deepseek-v2-236b-ep16")
    assert not model._layer_major(cfg, _opts(moe_impl="gather"))
    _, lengths = _chunk_scans("deepseek-v2-236b-ep16",
                              _opts(moe_impl="gather"))
    assert lengths == [C]


def test_hymba_prefill_is_the_token_scan():
    cfg, chunk, scan = _both("hymba-1.5b", _opts())
    np.testing.assert_array_equal(chunk[0], scan[0])
    for a, b in zip(jax.tree_util.tree_leaves(chunk[1]),
                    jax.tree_util.tree_leaves(scan[1])):
        np.testing.assert_array_equal(a, b)
