"""jax API surface used by this repo — the one place ``jax.experimental``
platform modules are imported.

The repo targets exactly the jax pinned in ``pyproject.toml`` (0.9.0); there
are no branches for other versions.  What stays here:

* ``shard_map`` and the tree utilities, under the names callers use;
* the Pallas platform modules (``jax.experimental.pallas`` and its ``tpu`` /
  ``triton`` submodules), imported guarded so a host without one can still
  import the kernels.  On a TPU backend a Pallas TPU module that failed to
  import is an error (:func:`has_pallas_tpu` raises), never a silent
  absence;
* backend probes and the TPU compiler-params builder, which passes its
  fields straight through (an unknown field raises).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax

__all__ = [
    "shard_map",
    "tree_map", "tree_leaves", "tree_flatten", "tree_unflatten",
    "tree_structure",
    "pallas", "pallas_tpu", "pallas_triton",
    "has_pallas", "has_pallas_tpu", "has_pallas_triton",
    "require_pallas", "require_pallas_tpu",
    "backend", "on_cpu", "on_gpu", "on_tpu",
    "tpu_compiler_params", "vmem",
    "abstract_mesh", "cost_analysis",
]

shard_map = jax.shard_map

tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten
tree_structure = jax.tree.structure


# -- meshes ----------------------------------------------------------------------

def abstract_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str]) -> Any:
    """``jax.sharding.AbstractMesh`` (its axes default to ``Auto``)."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def cost_analysis(compiled: Any) -> dict:
    """``Compiled.cost_analysis()`` as a (possibly empty) dict."""
    return dict(compiled.cost_analysis() or {})


# -- pallas platform modules -----------------------------------------------------

try:
    from jax.experimental import pallas as pallas  # noqa: PLC0414
except Exception:                                   # pragma: no cover
    pallas = None

_PALLAS_TPU_ERROR: Exception | None = None
try:
    from jax.experimental.pallas import tpu as pallas_tpu
except Exception as e:                              # pragma: no cover
    pallas_tpu = None
    _PALLAS_TPU_ERROR = e

try:
    from jax.experimental.pallas import triton as pallas_triton
except Exception:                                   # pragma: no cover
    pallas_triton = None


def has_pallas() -> bool:
    """Pallas core is importable (interpret mode works on any backend)."""
    return pallas is not None


def has_pallas_tpu() -> bool:
    """The Pallas TPU platform module is importable (needed for VMEM scratch
    and TPU compiler params, including in interpret mode).

    On a TPU backend a failed import raises: the kernels would otherwise
    quietly run as their ``xla_ref`` fallback on the chip."""
    if pallas_tpu is None and on_tpu():
        raise RuntimeError(
            "jax.experimental.pallas.tpu failed to import on a TPU backend"
        ) from _PALLAS_TPU_ERROR
    return pallas_tpu is not None


def has_pallas_triton() -> bool:
    return pallas_triton is not None


def require_pallas(feature: str = "this kernel"):
    if pallas is None:
        raise RuntimeError(
            f"{feature} needs jax.experimental.pallas, which is not "
            f"importable in this jax install; use the xla_ref implementation")
    return pallas


def require_pallas_tpu(feature: str = "this kernel"):
    if pallas_tpu is None:
        raise RuntimeError(
            f"{feature} needs jax.experimental.pallas.tpu, which is not "
            f"importable in this jax install; use the xla_ref implementation")
    return pallas_tpu


# -- backend probes --------------------------------------------------------------

def backend() -> str:
    """The default jax backend platform name ('cpu' | 'gpu' | 'tpu')."""
    return jax.default_backend()


def on_cpu() -> bool:
    return backend() == "cpu"


def on_gpu() -> bool:
    return backend() == "gpu"


def on_tpu() -> bool:
    return backend() == "tpu"


# -- TPU compiler params / scratch -----------------------------------------------

def tpu_compiler_params(**kwargs: Any) -> Any:
    """``pltpu.CompilerParams(**kwargs)``; an unknown field raises.
    Returns ``None`` when the TPU platform module is unavailable
    (``pallas_call`` accepts that)."""
    if pallas_tpu is None:
        return None
    return pallas_tpu.CompilerParams(**kwargs)


def vmem(shape: Sequence[int], dtype: Any) -> Any:
    """A VMEM scratch allocation spec (TPU platform module required)."""
    return require_pallas_tpu("VMEM scratch").VMEM(tuple(shape), dtype)
