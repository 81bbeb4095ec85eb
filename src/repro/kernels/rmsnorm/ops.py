"""Public RMSNorm op (any leading batch dims), registry-dispatched.

The Pallas kernel body is platform-neutral (no scratch, no TPU-only
compiler params), so this family also registers a ``pallas_gpu`` entry that
lowers through Triton when a GPU backend is active.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import compat
from repro.kernels import registry
from repro.kernels.common import pad_to_multiple
from repro.kernels.rmsnorm import ref

__all__ = ["rmsnorm"]


def _pallas_rmsnorm(x, weight, *, eps, block_rows, interpret):
    from repro.kernels.rmsnorm.kernel import rmsnorm_pallas

    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    br = min(block_rows, x2.shape[0])
    xp, rows = pad_to_multiple(x2, br, 0)
    out = rmsnorm_pallas(xp, weight, eps=eps, block_rows=br,
                         interpret=interpret)
    return out[:rows].reshape(shape)


def _guard(x, weight, **_kw):
    return (x.ndim >= 1 and weight.ndim == 1
            and x.shape[-1] == weight.shape[0]
            and jnp.issubdtype(x.dtype, jnp.floating))


@registry.register("rmsnorm", "xla_ref", priority=0,
                   description="pure-jnp rmsnorm (the numerical oracle)")
def _rmsnorm_xla_ref(x, weight, *, eps=1e-6, block_rows=256):
    del block_rows
    return ref.rmsnorm(x, weight, eps)


@registry.register("rmsnorm", "pallas_tpu", priority=20,
                   supports_grad=False, guard=_guard,
                   available=lambda: compat.has_pallas_tpu()
                   and compat.on_tpu(),
                   description="single-VMEM-pass fused rmsnorm")
def _rmsnorm_pallas_tpu(x, weight, *, eps=1e-6, block_rows=256):
    return _pallas_rmsnorm(x, weight, eps=eps, block_rows=block_rows,
                           interpret=False)


@registry.register("rmsnorm", "pallas_gpu", priority=10,
                   supports_grad=False, guard=_guard,
                   available=lambda: compat.has_pallas_triton()
                   and compat.on_gpu(),
                   description="same kernel body lowered through Triton")
def _rmsnorm_pallas_gpu(x, weight, *, eps=1e-6, block_rows=256):
    return _pallas_rmsnorm(x, weight, eps=eps, block_rows=block_rows,
                           interpret=False)


@registry.register("rmsnorm", "pallas_interpret", priority=-10,
                   supports_grad=False, guard=_guard,
                   available=lambda: compat.on_cpu()
                   and compat.has_pallas(),
                   description="Pallas kernel under the interpreter")
def _rmsnorm_pallas_interpret(x, weight, *, eps=1e-6, block_rows=256):
    return _pallas_rmsnorm(x, weight, eps=eps, block_rows=block_rows,
                           interpret=True)


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, *, eps: float = 1e-6,
            block_rows: int = 256, impl: str | None = None) -> jnp.ndarray:
    return registry.dispatch("rmsnorm", impl, x, weight, eps=eps,
                             block_rows=block_rows)
