"""The Pallas kernels compiled for a described TPU v5e chip, at real widths.

Nothing runs: the TPU compiler installed with jax compiles each kernel for
a v5e chip that is described, not attached, and refuses what the chip's
compiler would refuse (tiling rules, VMEM limits) — which interpret-mode
tests cannot see.  The topology is described inside a module fixture, so
collecting this file never loads the TPU library; where it cannot be
described the tests skip.

Widths: qwen3-0.6b attention (16 q heads / 8 kv heads, head dim 128) at
decode and prefill, the d_model-1024 rmsnorm, 128-tiles for the matmul,
rwkv6-1.6b's head size 64 for the linear-attention recurrence; and the
whole serve step at published width, decode and prefill: qwen3-0.6b, and
deepseek-v2-236b-ep16 (one chip's share of DeepSeek-V2 under expert
parallelism) at the batch and cache length its benchmark cell serves.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile can be written to the persistent cache but
    # never read back here; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def test_rmsnorm_compiles(one_chip):
    from repro.kernels.rmsnorm.kernel import rmsnorm_pallas

    _compile(functools.partial(rmsnorm_pallas, block_rows=8), one_chip,
             ((8, 1024), BF16), ((1024,), F32))


@pytest.mark.parametrize("phase,b,s,block_q,block_kv", [
    ("decode", 8, 1, 1, 256),        # one new token against 256 cached
    ("prefill", 1, 2048, 512, 512),
])
def test_flash_attention_compiles(one_chip, phase, b, s, block_q, block_kv):
    from repro.kernels.attention.kernel import flash_attention_pallas

    skv = 256 if phase == "decode" else s
    fn = functools.partial(flash_attention_pallas, block_q=block_q,
                           block_kv=block_kv, group=2)
    _compile(fn, one_chip, ((b * 16, s, 128), BF16),
             ((b * 8, skv, 128), BF16), ((b * 8, skv, 128), BF16))


def test_matmul_compiles(one_chip):
    from repro.kernels.matmul.kernel import matmul_pallas

    fn = functools.partial(matmul_pallas, bm=128, bn=128, bk=128)
    _compile(fn, one_chip, ((256, 512), BF16), ((512, 256), BF16))


def test_linear_attention_compiles(one_chip):
    from repro.kernels.linear_attention.kernel import linear_attention_pallas

    bh, t, hs = 2 * 32, 128, 64          # 2 rows x 32 heads of size 64
    fn = functools.partial(linear_attention_pallas, chunk=64)
    _compile(fn, one_chip, *[((bh, t, hs), F32)] * 4, ((bh, hs), F32))


def test_fastpath_lookup_compiles(one_chip):
    from repro.kernels.fastpath.kernel import fastpath_lookup_pallas

    fn = functools.partial(fastpath_lookup_pallas, block_b=256)
    _compile(fn, one_chip, ((512, 4), I32), ((16, 4), I32), ((16, 128), F32))


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_serve_step_compiles_at_published_width(one_chip, monkeypatch, phase):
    """The qwen3-0.6b serve step (28 layers, bf16, vocab 151,936) with the
    Pallas rmsnorm, as one chip would run it at batch 4, max_len 256."""
    from repro import compat, configs
    from repro.core.specializer import specialize_builder
    from repro.kernels import registry
    from repro.models import transformer as model
    from repro.models.transformer import RunOptions
    from repro.training import make_serve_builder

    # trace as on the chip: the Pallas TPU entries are the available ones
    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    monkeypatch.setattr(compat, "on_cpu", lambda: False)
    cfg = configs.get_config("qwen3-0.6b")
    b, max_len = 4, 256

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: jax.tree.map(lambda a: a.astype(cfg.compute_dtype),
                               model.init_params(k, cfg)),
        jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(
        cfg, b, max_len, RunOptions(decode_cache_dtype="bfloat16"))))
    tokens = on_chip(jax.ShapeDtypeStruct(
        (b, 16) if phase == "prefill" else (b,), I32))
    rows = on_chip(jax.ShapeDtypeStruct((b,), I32))
    before = dict(registry.default_registry.fallback_counts)
    fn = specialize_builder(make_serve_builder(cfg),
                            {"rmsnorm_impl": "pallas_tpu"}).fn
    compiled = jax.jit(fn, donate_argnums=1).lower(
        params, cache, tokens, rows, rows).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert registry.default_registry.fallback_counts == before


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_deepseek_v2_serve_step_fits_one_chip(one_chip, monkeypatch, phase):
    """The deepseek-v2-236b-ep16 serve step (the dense layer and 5 MoE
    layers with 10 of 160 experts each, 128-head MLA, bf16, vocab 102,400)
    at batch 64 and max_len 1024: it compiles for one v5e, its weights,
    cache and temporaries fit the chip's 16 GB, and the step carries the
    ``iri.`` scopes a device trace can attribute its time by."""
    from repro import compat, configs
    from repro.core.specializer import specialize_builder
    from repro.models import transformer as model
    from repro.models.transformer import RunOptions
    from repro.training import make_serve_builder

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    monkeypatch.setattr(compat, "on_cpu", lambda: False)
    cfg = configs.get_config("deepseek-v2-236b-ep16")
    b, max_len = 64, 1024

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: jax.tree.map(lambda a: a.astype(cfg.compute_dtype),
                               model.init_params(k, cfg)),
        jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(
        cfg, b, max_len, RunOptions(decode_cache_dtype="bfloat16"))))
    tokens = on_chip(jax.ShapeDtypeStruct(
        (b, 16) if phase == "prefill" else (b,), I32))
    rows = on_chip(jax.ShapeDtypeStruct((b,), I32))
    fn = specialize_builder(make_serve_builder(cfg),
                            {"rmsnorm_impl": "pallas_tpu"}).fn
    lowered = jax.jit(fn, donate_argnums=1).lower(params, cache, tokens,
                                                  rows, rows)
    text = lowered.as_text(debug_info=True)
    for scope in ("iri.mla", "iri.moe.route", "iri.moe.experts",
                  "iri.moe.shared"):
        assert scope in text, scope
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("program", ["gather", "scatter"])
def test_paged_kv_programs_compile_in_place(one_chip, program):
    """The paged KV gather and scatter at published widths: qwen3-0.6b's
    k and v at batch 8, max_len 1024 over 8192 pool tokens, rwkv6-1.6b's
    row state at batch 16.  The scatter updates the donated pools in
    place, and the gather keeps no copy of a pool on the side (a pool in
    the leaf's own axis order is copied whole into the layout the TPU
    indexes, every step)."""
    from repro.serve import kv

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, max_len, tokens = 8, 1024, 8192
    dense = on_chip((28, b, 8, max_len, 128), BF16)
    pools = [on_chip((tokens + 1, 28, 8, 128), BF16)] * 2
    rows = [on_chip((24, 16 + 2, 32, 64, 64), F32),
            on_chip((24, 16 + 2, 2048), BF16),
            on_chip((24, 16 + 2, 2048), BF16)]
    state = [on_chip((24, 16, 32, 64, 64), F32),
             on_chip((24, 16, 2048), BF16), on_chip((24, 16, 2048), BF16)]
    paged, row = ((1, 3), (1, 3)), (1, 1, 1)
    mib = 1 << 20
    if program == "gather":
        compiled = kv._gather.lower(pools, rows, on_chip((b, max_len + 1), I32),
                                    paged=paged, row=row).compile()
        leaf = 28 * b * 8 * max_len * 128 * 2
        assert compiled.memory_analysis().temp_size_in_bytes < leaf + 16 * mib
    else:
        for width in (1, 16):
            table = on_chip((b, width + 2), I32)
            compiled = kv._scatter.lower(pools, [], [dense] * 2, [], table,
                                         paged=paged, row=()).compile()
            assert compiled.memory_analysis().temp_size_in_bytes < 16 * mib
        compiled = kv._scatter.lower([], rows, [], state,
                                     on_chip((16, 2), I32),
                                     paged=(), row=row).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 16 * mib
