"""Shared model components: norms, rope, swiglu, initializers."""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels import rmsnorm as rmsnorm_kernel

__all__ = ["KernelOptions", "rms_norm", "rope", "apply_rope", "swiglu",
           "dense_init", "embed_init", "yarn_mscale", "chunk_slots",
           "chunk_visible"]


@dataclasses.dataclass(frozen=True)
class KernelOptions:
    """Per-step kernel configuration — populated from Iridescent spec points.

    These are the constants the specializer bakes into each variant: the
    kernel implementation choices and the VMEM tile shapes (the paper's
    block size ``B``, TPU edition).

    ``impl`` is the step-wide implementation choice (a registry entry name —
    ``xla_ref`` | ``pallas_tpu`` | ``pallas_interpret`` | ... — legacy
    ``xla``/``pallas``/``interpret`` spellings still accepted; ``None`` =
    registry auto).  The per-family ``*_impl`` fields override it for one
    kernel family — each is its own spec point, so the policy can e.g. keep
    attention on the Pallas kernel while pinning rmsnorm to ``xla_ref``.
    """

    impl: str | None = None          # step-wide default (None = auto)
    attention_impl: str | None = None
    rmsnorm_impl: str | None = None
    linear_attention_impl: str | None = None
    block_q: int = 512
    block_kv: int = 512
    norm_block_rows: int = 256
    matmul_bm: int = 256
    matmul_bn: int = 256
    matmul_bk: int = 256
    chunk_len: int = 64              # linear-attention chunk size (rwkv/ssm)
    swa_impl: str = "full"           # full | banded (sliding-window band only)

    def impl_for(self, family: str) -> str | None:
        """The effective impl choice for one kernel family (families the
        model step does not route per-family fall through to ``impl``)."""
        return getattr(self, f"{family}_impl", None) or self.impl


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6,
             opts: KernelOptions | None = None) -> jnp.ndarray:
    opts = opts or KernelOptions()
    return rmsnorm_kernel.rmsnorm(x, weight, eps=eps,
                                  block_rows=opts.norm_block_rows,
                                  impl=opts.impl_for("rmsnorm"))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor``
    times (hf ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_freqs(dim: int, theta: float, cfg) -> tuple[jnp.ndarray, float]:
    """YaRN's inverse frequencies and cos/sin scale (hf
    ``DeepseekV2YarnRotaryEmbedding``): dimensions that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn fewer than ``beta_slow`` times are divided by the
    factor, and a linear ramp blends the ones between."""
    half = dim // 2
    extra = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    inter = extra / cfg.yarn_factor

    def at(rotations):
        return dim * math.log(cfg.yarn_original_max_len
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(at(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(at(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                          / (high - low), 0.0, 1.0)
    scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return inter * (1.0 - keep) + extra * keep, scale


def rope(positions: jnp.ndarray, dim: int, theta: float = 1e4,
         dtype=jnp.float32, cfg=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rotary embedding tables. positions (...,) -> cos/sin (..., dim/2).
    A ``cfg`` (ModelConfig) with ``yarn_factor`` set gives YaRN's."""
    assert dim % 2 == 0, dim
    if cfg is not None and cfg.yarn_factor:
        freqs, scale = _yarn_freqs(dim, theta, cfg)
    else:
        freqs = theta ** (-jnp.arange(0, dim // 2, dtype=jnp.float32)
                          / (dim // 2))
        scale = 1.0
    angles = positions.astype(jnp.float32)[..., None] * freqs
    if scale == 1.0:
        return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)
    return ((jnp.cos(angles) * scale).astype(dtype),
            (jnp.sin(angles) * scale).astype(dtype))


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """x (..., S, D) with cos/sin (S, D/2) (or broadcastable)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos.astype(x1.dtype)
    sin = sin.astype(x1.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def chunk_slots(positions: jnp.ndarray, n_new: jnp.ndarray,
                w: int) -> jnp.ndarray:
    """Cache slots a prompt chunk writes: ``positions (B,C)`` where row b's
    token t is among its first ``n_new[b]`` and lies inside the ``w``-slot
    cache, else ``w``, which a ``mode="drop"`` scatter leaves unwritten."""
    live = jnp.arange(positions.shape[1])[None] < n_new[:, None]
    return jnp.where(live & (positions < w), positions, w)


def chunk_visible(positions: jnp.ndarray, w: int,
                  window: int | None) -> jnp.ndarray:
    """(B,C,w) bool: the slots the query at ``positions[b, t]`` sees, every
    one up to its own position (within ``window`` of it, if set)."""
    at = jnp.arange(w, dtype=jnp.int32)[None, None, :]
    valid = at <= positions[:, :, None]
    if window is not None:
        valid &= at > positions[:, :, None] - window
    return valid


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU FFN: silu(x@Wg) * (x@Wu) @ Wd, with TP-friendly sharding."""
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    h = constrain(h, ("batch", "seq", "ffn"))
    return h @ w_down


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
