"""Public blocked-matmul op, dispatched through the kernel registry.

``assume_divisible=True`` is the kernel-level effect of the paper's
``spec_assume("N % B == 0")``: the padding/cropping code is removed entirely
from the compiled program (dead-code elimination by construction); the host
guard at the handler level ensures the assumption actually holds.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import compat
from repro.kernels import registry
from repro.kernels.common import pad_to_multiple
from repro.kernels.matmul import ref

__all__ = ["matmul"]


def _pallas_matmul(x, y, *, bm, bn, bk, out_dtype, assume_divisible,
                   interpret):
    from repro.kernels.matmul.kernel import matmul_pallas

    if assume_divisible:
        return matmul_pallas(x, y, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                             interpret=interpret)
    m, n = x.shape[0], y.shape[1]
    xp, _ = pad_to_multiple(x, bm, 0)
    xp, _ = pad_to_multiple(xp, bk, 1)
    yp, _ = pad_to_multiple(y, bk, 0)
    yp, _ = pad_to_multiple(yp, bn, 1)
    out = matmul_pallas(xp, yp, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                        interpret=interpret)
    return out[:m, :n]


def _guard(x, y, **kw):
    """Pallas path precondition: 2-D float operands with matching inner dim
    (padding handles non-divisible shapes, so divisibility is NOT guarded
    here — only when the caller bakes the assume_divisible assumption)."""
    if x.ndim != 2 or y.ndim != 2 or x.shape[1] != y.shape[0]:
        return False
    if not (jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(y.dtype, jnp.floating)):
        return False
    if kw.get("assume_divisible"):
        bm, bn, bk = kw.get("bm", 128), kw.get("bn", 128), kw.get("bk", 128)
        m, k = x.shape
        n = y.shape[1]
        return m % bm == 0 and n % bn == 0 and k % bk == 0
    return True


@registry.register("matmul", "xla_ref", priority=0,
                   description="jnp.dot reference (the numerical oracle)")
def _matmul_xla_ref(x, y, *, bm=128, bn=128, bk=128, out_dtype=None,
                    assume_divisible=False):
    del bm, bn, bk, assume_divisible          # no tiling in the generic path
    return ref.matmul(x, y, out_dtype=out_dtype or x.dtype)


@registry.register("matmul", "pallas_tpu", priority=20,
                   supports_grad=False, guard=_guard,
                   available=lambda: compat.has_pallas_tpu()
                   and compat.on_tpu(),
                   description="BlockSpec-tiled Pallas TPU kernel")
def _matmul_pallas_tpu(x, y, *, bm=128, bn=128, bk=128, out_dtype=None,
                       assume_divisible=False):
    return _pallas_matmul(x, y, bm=bm, bn=bn, bk=bk,
                          out_dtype=out_dtype or x.dtype,
                          assume_divisible=assume_divisible, interpret=False)


@registry.register("matmul", "pallas_interpret", priority=-10,
                   supports_grad=False, guard=_guard,
                   available=lambda: compat.on_cpu()
                   and compat.has_pallas_tpu(),
                   description="Pallas kernel under the interpreter "
                               "(kernel-logic validation on any host)")
def _matmul_pallas_interpret(x, y, *, bm=128, bn=128, bk=128, out_dtype=None,
                             assume_divisible=False):
    return _pallas_matmul(x, y, bm=bm, bn=bn, bk=bk,
                          out_dtype=out_dtype or x.dtype,
                          assume_divisible=assume_divisible, interpret=True)


def matmul(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    impl: str | None = None,
    assume_divisible: bool = False,
) -> jnp.ndarray:
    return registry.dispatch(
        "matmul", impl, x, y, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
        assume_divisible=assume_divisible)
