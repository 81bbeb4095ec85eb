"""Multi-device tests (8 fake CPU devices) in a subprocess, since the main
test process must keep the real single-device view.

Covers: small-mesh dry-run lower+compile for a reduced arch of each family
(the miniature of launch/dryrun.py), sharded train-step numerics vs
single-device, and the int8 compressed-psum collective.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # never probe TPU/GPU runtimes here
import json
import jax, jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.dryrun import run_cell, build_lowerable
from repro.launch import dryrun as dr
from repro.optim import OptConfig
from repro.configs import Shape

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
out = {}

# 1) miniature dry-run: one reduced arch per family, train + decode
for arch in ["yi-6b", "deepseek-v2-236b", "rwkv6-1.6b", "hymba-1.5b"]:
    cfg = configs.get_reduced(arch)
    shape_t = Shape("t", "train", 64, 8)
    shape_d = Shape("d", "decode", 64, 8)
    for shape in (shape_t, shape_d):
        step, args, kw = build_lowerable(cfg, shape, mesh, {}, OptConfig(),
                                         scan_layers=True)
        compiled = jax.jit(step, **kw).lower(*args).compile()
        from repro import compat
        ca = compat.cost_analysis(compiled)
        out[f"{arch}:{shape.kind}"] = float(ca.get("flops", 0))

# 1b) shard_map expert-parallel MoE == dense oracle (ample capacity)
from repro.models.moe import init_moe, apply_moe, MoEOptions
from repro.models.config import ModelConfig
from repro.distributed.sharding import mesh_context, DEFAULT_RULES

cfg_m = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                    n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                    n_experts=8, top_k=2, moe_d_ff=48, n_shared_experts=1)
pm = init_moe(jax.random.PRNGKey(0), cfg_m)
xm = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)


def run_moe(impl):
    def f(p_, x_):
        with mesh_context(mesh, DEFAULT_RULES):
            o, aux = apply_moe(p_, x_, cfg_m,
                               MoEOptions(impl=impl, capacity_factor=8.0))
            return o
    return jax.jit(f)(pm, xm)


o_dense = run_moe("dense")
o_shard = run_moe("shard")
out["shard_moe_err"] = float(jnp.abs(o_shard - o_dense).max())

# shard impl must be differentiable (training path)
def loss_fn(p_):
    with mesh_context(mesh, DEFAULT_RULES):
        o, aux = apply_moe(p_, xm, cfg_m,
                           MoEOptions(impl="shard", capacity_factor=8.0))
        return jnp.sum(o ** 2) + aux
g = jax.jit(jax.grad(loss_fn))(pm)
out["shard_moe_grad_finite"] = bool(
    all(bool(jnp.isfinite(x).all()) for x in jax.tree_util.tree_leaves(g)))

# 2) compressed psum: int8 all-gather appears in HLO, result ~= plain psum
from repro.distributed.compression import compressed_psum
x = jnp.asarray(np.random.RandomState(0).randn(64, 64).astype(np.float32))
compiled = jax.jit(lambda v: compressed_psum(v, "data", mesh)).lower(
    jax.ShapeDtypeStruct(x.shape, x.dtype)).compile()
hlo = compiled.as_text()
out["int8_allgather_in_hlo"] = ("s8" in hlo and "all-gather" in hlo)
got = jax.jit(lambda v: compressed_psum(v, "data", mesh))(x)
# replicated input: psum over axis of size 2 = 2*x, quantized
err = float(jnp.abs(got - 2 * x).max() / jnp.abs(x).max())
out["compressed_psum_rel_err"] = err

print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def test_small_mesh_dryrun_compiles(results):
    for key in ["yi-6b:train", "yi-6b:decode", "deepseek-v2-236b:train",
                "rwkv6-1.6b:train", "hymba-1.5b:decode"]:
        assert results[key] > 0, key


def test_compressed_psum(results):
    assert results["int8_allgather_in_hlo"]
    assert results["compressed_psum_rel_err"] < 0.02   # int8 quant error


def test_shard_map_moe(results):
    assert results["shard_moe_err"] < 2e-5
    assert results["shard_moe_grad_finite"]
