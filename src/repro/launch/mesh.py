"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the 512-device dry-run
must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def _auto(n: int) -> tuple:
    """``Auto`` axis types: the sharding rules place arrays through
    ``with_sharding_constraint``, which only refers to ``Auto`` axes
    (``jax.make_mesh`` defaults to ``Explicit``)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh.

    Single pod: 256 chips as (data=16, model=16).
    Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the
    ``pod`` axis is the slow (DCN) tier; batch shards across it, and the
    ``fsdp_pods`` sharding profile optionally spreads ZeRO-3 across it too.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(shape)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests / CPU runs)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))
