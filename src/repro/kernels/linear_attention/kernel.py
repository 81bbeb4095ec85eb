"""Chunked gated-linear-attention Pallas TPU kernel (RWKV6 wkv / Mamba SSD).

One grid step processes one (batch*head, chunk) tile; the recurrent state
``S (dk, dv)`` lives in fp32 VMEM scratch and is carried across the chunk
dimension (grid-minor, "arbitrary" semantics), so the whole recurrence runs
without ever spilling state to HBM:

    la   = cumsum(log_w)                       # (c, dk), as tril @ log_w
    out  = (q . exp(la_q)) @ S                 # inter-chunk (MXU)
         + tril((q.exp(la_q)) @ (k.exp(-la))^T [+ diag bonus]) @ v
    S   <- exp(la_c) * S + (k . exp(la_c - la))^T @ v

``chunk`` is the Iridescent spec point: it sets the VMEM score tile (c x c)
against the number of sequential grid steps — the same trade as the paper's
matmul block size.  Per-step log-decay must be clamped (>= -1, see
models/chunk_scan.py) so the exp factors stay fp32-finite.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import pallas as pl

__all__ = ["linear_attention_pallas"]


def _gla_kernel(q_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                inclusive: bool, use_bonus: bool, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    q = q_ref[0].astype(f32)                  # (c, dk)
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)                  # (c, dv)
    lw = w_ref[0].astype(f32)                 # (c, dk)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # cumsum over the chunk as a lower-triangular matmul (Mosaic has no
    # cumsum lowering); HIGHEST keeps the running log-decay in fp32.
    la = jax.lax.dot(jnp.where(rows >= cols, 1.0, 0.0).astype(f32), lw,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=f32)       # (c, dk)
    la_q = la if inclusive else la - lw
    la_tot = la[chunk - 1]                    # (dk,)

    qt = q * jnp.exp(la_q)
    kt = k * jnp.exp(-la)
    scores = jax.lax.dot_general(qt, kt, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)   # (c, c)
    mask = (rows >= cols) if inclusive else (rows > cols)
    scores = jnp.where(mask, scores, 0.0)
    if use_bonus:
        u = u_ref[0].astype(f32)              # (1, dk), broadcast over c
        diag = jnp.sum(q * u * k, axis=-1)    # (c,)
        scores = scores + diag[:, None] * jnp.where(
            rows == cols, 1.0, 0.0)

    inter = jax.lax.dot(qt, s_ref[...], preferred_element_type=f32)
    intra = jax.lax.dot(scores, v, preferred_element_type=f32)
    o_ref[0] = (inter + intra).astype(o_ref.dtype)

    k_dec = k * jnp.exp(la_tot[None, :] - la)
    s_add = jax.lax.dot_general(k_dec, v, (((0,), (0,)), ((), ())),
                                preferred_element_type=f32)    # (dk, dv)
    s_ref[...] = jnp.exp(la_tot)[:, None] * s_ref[...] + s_add


@functools.partial(
    jax.jit,
    static_argnames=("inclusive", "chunk", "interpret"))
def linear_attention_pallas(
    q: jnp.ndarray,          # (BH, T, dk)
    k: jnp.ndarray,          # (BH, T, dk)
    v: jnp.ndarray,          # (BH, T, dv)
    log_w: jnp.ndarray,      # (BH, T, dk)  (clamped <= -1e-4, >= -1)
    bonus: jnp.ndarray | None = None,   # (BH, dk) RWKV "u"
    *,
    inclusive: bool = False,
    chunk: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    compat.require_pallas("linear_attention_pallas")
    bh, t, dk = q.shape
    dv = v.shape[-1]
    assert t % chunk == 0, (t, chunk)
    use_bonus = bonus is not None
    if bonus is None:
        bonus = jnp.zeros((bh, dk), q.dtype)
    # (BH, 1, dk): a (1, dk) block spans the array's last two dims whole,
    # which the TPU (8, 128) tiling rule accepts; a (1, dk) block of a
    # (BH, dk) array does not.
    bonus = bonus.reshape(bh, 1, dk)

    kernel = functools.partial(_gla_kernel, inclusive=inclusive,
                               use_bonus=use_bonus, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, t // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, dk), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, dv), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, dk), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, dk), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, dv), v.dtype),
        scratch_shapes=[compat.vmem((dk, dv), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, log_w, bonus)
