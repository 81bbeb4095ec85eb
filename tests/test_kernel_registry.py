"""Backend-portable kernel registry: listing, availability filtering on a
CPU-only host, the ``{family}_impl`` spec point round-tripping through
``Handler.specialize``, and guard-miss / unavailability fallback to
``xla_ref``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import ExhaustiveSweep, Explorer, IridescentRuntime, Phase
from repro.kernels import matmul, registry, rmsnorm
from repro.kernels.registry import (FALLBACK_IMPL, KernelRegistry,
                                    canonical_name, impl_point)

FAMILIES = ("matmul", "attention", "rmsnorm", "linear_attention", "fastpath")


# -- listing & availability -------------------------------------------------------

def test_all_families_registered_with_fallback():
    fams = registry.families()
    for family in FAMILIES:
        assert family in fams
        impls = registry.implementations(family)
        assert FALLBACK_IMPL in impls, family
        assert "pallas_tpu" in impls, family


def test_cpu_availability_filtering():
    # this suite pins JAX_PLATFORMS=cpu: TPU/GPU-only entries must be
    # filtered out of the candidate set, xla_ref must always survive.
    for family in FAMILIES:
        names = registry.choices(family)
        assert FALLBACK_IMPL in names, family
        assert "pallas_tpu" not in names, family
        assert "pallas_gpu" not in names, family
    assert registry.get("matmul", "pallas_tpu").is_available() is False


def test_interpreter_is_never_a_candidate_off_the_cpu(monkeypatch):
    # on a chip the interpreter would be explored (and could win) in place
    # of the kernel it emulates
    assert "pallas_interpret" in registry.choices("rmsnorm")
    monkeypatch.setattr(compat, "on_cpu", lambda: False)
    for family in FAMILIES:
        assert "pallas_interpret" not in registry.choices(family), family


def test_raising_availability_probe_is_an_error():
    reg = KernelRegistry()

    @reg.register("toy", "xla_ref")
    def _ref(x):
        return x

    def probe():
        raise ImportError("platform module failed to import")

    @reg.register("toy", "fancy", priority=10, available=probe)
    def _fancy(x):
        return x

    # not an absence: resolving must not quietly run xla_ref instead
    with pytest.raises(ImportError):
        reg.resolve("toy", None)
    with pytest.raises(ImportError):
        reg.resolve("toy", "fancy")
    assert not reg.fallback_counts


def test_pallas_tpu_import_failure_raises_only_on_tpu(monkeypatch):
    monkeypatch.setattr(compat, "pallas_tpu", None)
    assert compat.has_pallas_tpu() is False         # a CPU host: absent
    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="failed to import"):
        compat.has_pallas_tpu()


def test_auto_resolution_prefers_xla_ref_on_cpu():
    # xla_ref (priority 0) outranks pallas_interpret (negative priority)
    for family in FAMILIES:
        assert registry.resolve(family, None).name == FALLBACK_IMPL
        assert registry.resolve(family, "auto").name == FALLBACK_IMPL


def test_legacy_alias_names_accepted():
    assert canonical_name("xla") == "xla_ref"
    assert canonical_name("interpret") == "pallas_interpret"
    assert canonical_name("pallas") == "pallas_tpu"
    assert registry.get("rmsnorm", "xla").name == "xla_ref"
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16,), jnp.float32)
    np.testing.assert_allclose(rmsnorm.rmsnorm(x, w, impl="xla"),
                               rmsnorm.rmsnorm(x, w, impl="xla_ref"))


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        registry.get("matmul", "no_such_impl")
    with pytest.raises(KeyError):
        registry.resolve("no_such_family", None)


# -- fallback semantics -----------------------------------------------------------

def test_unavailable_named_impl_falls_back_to_xla_ref():
    # pallas_tpu cannot run on this host; dispatch must produce the
    # reference result instead of crashing.
    x = jnp.asarray(np.random.RandomState(0).randn(16, 8), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randn(8, 12), jnp.float32)
    out = matmul.matmul(x, y, impl="pallas_tpu")
    np.testing.assert_allclose(out, matmul.matmul(x, y, impl="xla_ref"),
                               rtol=1e-6, atol=1e-6)


def test_guard_miss_falls_back_to_xla_ref():
    reg = KernelRegistry()

    @reg.register("toy", "xla_ref")
    def _ref(x):
        return x + 1

    @reg.register("toy", "fancy", priority=10,
                  guard=lambda x: x.shape[0] % 2 == 0)
    def _fancy(x):
        return x * 0 - 999          # wrong on purpose: must not run on odd

    even = jnp.ones((4,))
    odd = jnp.ones((3,))
    assert float(reg.dispatch("toy", "fancy", even)[0]) == -999.0
    # guard miss: odd batch re-routes this call to xla_ref
    np.testing.assert_allclose(reg.dispatch("toy", "fancy", odd), odd + 1)
    assert reg.fallback_counts[("toy", "fancy")] == 1
    # auto selection also respects the guard at dispatch time
    np.testing.assert_allclose(reg.dispatch("toy", None, odd), odd + 1)


def test_real_guard_linear_attention_chunk_divisibility():
    from repro.kernels import linear_attention as la

    rs = np.random.RandomState(2)
    q = jnp.asarray(rs.randn(2, 20, 4), jnp.float32)    # T=20 % 16 != 0
    k = jnp.asarray(rs.randn(2, 20, 4), jnp.float32)
    v = jnp.asarray(rs.randn(2, 20, 4), jnp.float32)
    lw = jnp.full((2, 20, 4), -0.5, jnp.float32)
    before = dict(registry.default_registry.fallback_counts)
    out = la.linear_attention(q, k, v, lw, chunk=16, impl="pallas_interpret")
    ref = la.linear_attention(q, k, v, lw, chunk=4, impl="xla_ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    after = registry.default_registry.fallback_counts
    key = ("linear_attention", "pallas_interpret")
    assert after.get(key, 0) == before.get(key, 0) + 1


# -- spec-point integration -------------------------------------------------------

def _matmul_builder(spec):
    impl = impl_point(spec, "matmul", default="xla")

    def handler(x, y):
        return matmul.matmul(x, y, bm=16, bn=16, bk=16, impl=impl)

    return handler


def test_impl_point_roundtrip_through_handler_specialize():
    rt = IridescentRuntime(async_compile=False)
    h = rt.register("mm", _matmul_builder)

    space = h.spec_space()
    assert "matmul_impl" in space
    assert set(space["matmul_impl"].choices) == set(registry.choices("matmul"))

    x = jnp.asarray(np.random.RandomState(3).randn(32, 32), jnp.float32)
    y = jnp.asarray(np.random.RandomState(4).randn(32, 32), jnp.float32)
    ref = np.asarray(h(x, y))                           # generic (default)

    for name in registry.choices("matmul"):
        h.specialize({"matmul_impl": name}, wait=True)
        assert h.active_config() == {"matmul_impl": name}
        np.testing.assert_allclose(np.asarray(h(x, y)), ref,
                                   rtol=1e-4, atol=1e-4)

    h.despecialize()
    assert h.active_config() == {}


def test_explorer_selects_xla_ref_on_cpu():
    """The acceptance scenario: sweeping the impl point online on a CPU-only
    host must converge on xla_ref (the interpreter entry is orders of
    magnitude slower), purely from the measured throughput."""
    from repro.core import ChangeDetector

    rt = IridescentRuntime(async_compile=False)
    h = rt.register("mm_explore", _matmul_builder)

    # 128x128 over 16-tiles: the interpreter emulates a 512-step grid, a
    # ~50x measured gap vs xla_ref — far beyond scheduler noise.
    x = jnp.asarray(np.random.RandomState(5).randn(128, 128), jnp.float32)
    y = jnp.asarray(np.random.RandomState(6).randn(128, 128), jnp.float32)
    h(x, y)
    # warm up every candidate once so one-time process costs (tracing,
    # executable load) don't pollute the first measured dwell window
    for name in registry.choices("matmul"):
        h.specialize({"matmul_impl": name}, wait=True)
        jax.block_until_ready(h(x, y))
    h.despecialize()

    # loose change threshold: python-overhead jitter in the tiny exploit
    # windows must not re-trigger exploration mid-test
    ex = Explorer(h, ExhaustiveSweep.from_space(h.spec_space(),
                                                ["matmul_impl"]),
                  dwell=5, change_detector=ChangeDetector(threshold=5.0))
    for _ in range(10 * len(registry.choices("matmul")) + 10):
        jax.block_until_ready(h(x, y))
        ex.step()
    assert ex.phase is Phase.EXPLOIT
    assert h.active_config()["matmul_impl"] == FALLBACK_IMPL


def test_tpu_tuned_config_replays_on_cpu():
    """A config naming an impl that is unavailable on this host (e.g. tuned
    on a TPU pod, replayed on CPU CI) must specialize and degrade to
    xla_ref at dispatch — not be rejected by spec validation."""
    rt = IridescentRuntime(async_compile=False)
    h = rt.register("mm_replay", _matmul_builder)
    x = jnp.asarray(np.random.RandomState(7).randn(32, 32), jnp.float32)
    y = jnp.asarray(np.random.RandomState(8).randn(32, 32), jnp.float32)
    ref = np.asarray(h(x, y))

    h.specialize({"matmul_impl": "pallas_tpu"}, wait=True)   # unavailable
    np.testing.assert_allclose(np.asarray(h(x, y)), ref, rtol=1e-5,
                               atol=1e-5)
    h.specialize({"matmul_impl": "interpret"}, wait=True)    # legacy alias
    np.testing.assert_allclose(np.asarray(h(x, y)), ref, rtol=1e-4,
                               atol=1e-4)
    with pytest.raises(ValueError):
        h.specialize({"matmul_impl": "not_an_impl"}, wait=True)


def test_attention_guard_covers_block_divisibility():
    from repro.kernels import attention as attn

    rs = np.random.RandomState(9)
    q = jnp.asarray(rs.randn(1, 2, 192, 16), jnp.float32)   # 192 % 128 != 0
    k = jnp.asarray(rs.randn(1, 2, 192, 16), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 192, 16), jnp.float32)
    before = registry.default_registry.fallback_counts.get(
        ("attention", "pallas_interpret"), 0)
    out = attn.attention(q, k, v, block_q=128, block_kv=128,
                         impl="pallas_interpret")
    ref = attn.attention(q, k, v, impl="xla_ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    after = registry.default_registry.fallback_counts[
        ("attention", "pallas_interpret")]
    assert after == before + 1


def test_require_grad_pins_concrete_grad_safe_impl():
    """Differentiated builders must never leave the impl on auto: dispatch
    cannot know a call sits under jax.grad, so impl_point(require_grad=True)
    returns a concrete grad-safe name even when the point is disabled or
    the default is a non-differentiable kernel."""
    from repro.core.specializer import SpecCtx

    for default in (None, "xla", "pallas_tpu", "pallas_interpret"):
        spec = SpecCtx({})                       # point disabled -> default
        value = impl_point(spec, "matmul", default=default,
                           require_grad=True)
        assert value is not None
        assert registry.get("matmul", value).supports_grad, (default, value)
    # grad actually flows through the pinned choice
    spec = SpecCtx({})
    impl = impl_point(spec, "rmsnorm", default="pallas_interpret",
                      require_grad=True)
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8,), jnp.float32)
    g = jax.grad(lambda a: rmsnorm.rmsnorm(a, w, impl=impl).sum())(x)
    assert bool(jnp.isfinite(g).all())


# -- compat layer -----------------------------------------------------------------

def test_compat_surface():
    # the shim must resolve on this host: shard_map callable, tree utils,
    # and the TPU compiler-params builder passing its fields through — an
    # unknown field raises instead of being dropped.
    assert callable(compat.shard_map)
    assert compat.tree_map(lambda a: a + 1, {"x": 1}) == {"x": 2}
    params = compat.tpu_compiler_params(dimension_semantics=("parallel",))
    if compat.has_pallas_tpu():
        assert params.dimension_semantics == ("parallel",)
        with pytest.raises(TypeError):
            compat.tpu_compiler_params(not_a_real_field=1)
    assert compat.backend() == "cpu"


def test_no_direct_experimental_imports_outside_compat():
    """Repo-wide drift firewall: jax.experimental.shard_map and
    jax.experimental.pallas.* are imported only through repro.compat."""
    import pathlib
    import re

    src_root = pathlib.Path(registry.__file__).resolve().parents[2]
    offenders = []
    for path in src_root.rglob("*.py"):
        if path.name == "compat.py":
            continue
        text = path.read_text()
        if re.search(r"jax\.experimental\.shard_map|"
                     r"from jax\.experimental import shard_map|"
                     r"from jax\.experimental\.pallas import|"
                     r"from jax\.experimental import pallas", text):
            offenders.append(str(path))
    assert not offenders, offenders
