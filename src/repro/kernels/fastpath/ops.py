"""Public fast-path lookup op, registry-dispatched.

The matcher kernel body is platform-neutral (no scratch), so a Triton-
lowered ``pallas_gpu`` entry is registered alongside the TPU one.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import compat
from repro.kernels import registry
from repro.kernels.common import pad_to_multiple
from repro.kernels.fastpath import ref

__all__ = ["lookup"]


def _pallas_lookup(x, keys, values, *, block_b, interpret):
    from repro.kernels.fastpath.kernel import fastpath_lookup_pallas

    b = x.shape[0]
    bb = min(block_b, b)
    xp, _ = pad_to_multiple(x, bb, 0)
    out, hit = fastpath_lookup_pallas(xp, keys, values, block_b=bb,
                                      interpret=interpret)
    return out[:b], hit[:b]


def _guard(x, keys, values, **_kw):
    return (x.ndim == 2 and keys.ndim == 2 and values.ndim == 2
            and x.shape[1] == keys.shape[1]
            and keys.shape[0] == values.shape[0]
            and jnp.issubdtype(x.dtype, jnp.integer))


@registry.register("fastpath", "xla_ref", priority=0,
                   description="vectorized compare/select reference")
def _lookup_xla_ref(x, keys, values, *, block_b=256):
    del block_b
    return ref.lookup(x, keys, values)


@registry.register("fastpath", "pallas_tpu", priority=20,
                   supports_grad=False, guard=_guard,
                   available=lambda: compat.has_pallas_tpu()
                   and compat.on_tpu(),
                   description="dense hot-key matcher (VPU compare + "
                               "MXU onehot gather)")
def _lookup_pallas_tpu(x, keys, values, *, block_b=256):
    return _pallas_lookup(x, keys, values, block_b=block_b, interpret=False)


@registry.register("fastpath", "pallas_gpu", priority=10,
                   supports_grad=False, guard=_guard,
                   available=lambda: compat.has_pallas_triton()
                   and compat.on_gpu(),
                   description="same matcher body lowered through Triton")
def _lookup_pallas_gpu(x, keys, values, *, block_b=256):
    return _pallas_lookup(x, keys, values, block_b=block_b, interpret=False)


@registry.register("fastpath", "pallas_interpret", priority=-10,
                   supports_grad=False,
                   guard=_guard, available=lambda: compat.on_cpu()
                   and compat.has_pallas(),
                   description="matcher kernel under the interpreter")
def _lookup_pallas_interpret(x, keys, values, *, block_b=256):
    return _pallas_lookup(x, keys, values, block_b=block_b, interpret=True)


def lookup(x: jnp.ndarray, keys: jnp.ndarray, values: jnp.ndarray, *,
           block_b: int = 256, impl: str | None = None
           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    return registry.dispatch("fastpath", impl, x, keys, values,
                             block_b=block_b)
