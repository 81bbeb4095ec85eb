"""Public attention op, registry-dispatched.

Input layout is ``(B, H, S, D)``; the Pallas path flattens (B, H) into the
grid's head dimension and folds GQA into the BlockSpec index map.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import compat
from repro.kernels import registry
from repro.kernels.attention import ref

__all__ = ["attention"]


def _xla_attention(q, k, v, *, causal, window, scale, q_offset, swa_impl,
                   **_tiles):
    if (swa_impl == "banded" and window is not None and causal
            and q.shape[2] == k.shape[2] and q.shape[2] % window == 0):
        return ref.banded_attention(q, k, v, window=window, scale=scale)
    return ref.attention(q, k, v, causal=causal, window=window,
                         scale=scale, q_offset=q_offset)


def _pallas_attention(q, k, v, *, causal, window, scale, q_offset,
                      block_q, block_kv, interpret):
    from repro.kernels.attention.kernel import flash_attention_pallas

    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    dv = v.shape[-1]
    group = h // hk
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    out = flash_attention_pallas(
        q.reshape(b * h, sq, d),
        k.reshape(b * hk, skv, d),
        v.reshape(b * hk, skv, dv),
        causal=causal, window=window, scale=scale, q_offset=q_offset,
        block_q=bq, block_kv=bkv, group=group,
        interpret=interpret,
    )
    return out.reshape(b, h, sq, dv)


def _guard(q, k, v, **kw):
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return False
    if q.shape[1] % k.shape[1] != 0:          # GQA group must divide evenly
        return False
    # kernel precondition: seq lengths divisible by the (clamped) blocks
    sq, skv = q.shape[2], k.shape[2]
    bq = min(kw.get("block_q", 128), sq)
    bkv = min(kw.get("block_kv", 128), skv)
    if bq <= 0 or bkv <= 0 or sq % bq != 0 or skv % bkv != 0:
        return False
    return all(jnp.issubdtype(a.dtype, jnp.floating) for a in (q, k, v))


@registry.register("attention", "xla_ref", priority=0,
                   description="masked-softmax reference "
                               "(+ banded sliding-window variant)")
def _attention_xla_ref(q, k, v, **kw):
    return _xla_attention(q, k, v, **kw)


@registry.register("attention", "pallas_tpu", priority=20,
                   supports_grad=False, guard=_guard,
                   available=lambda: compat.has_pallas_tpu()
                   and compat.on_tpu(),
                   description="flash attention with VMEM running softmax")
def _attention_pallas_tpu(q, k, v, **kw):
    kw.pop("swa_impl", None)
    return _pallas_attention(q, k, v, interpret=False, **kw)


@registry.register("attention", "pallas_interpret", priority=-10,
                   supports_grad=False,
                   guard=_guard, available=lambda: compat.on_cpu()
                   and compat.has_pallas_tpu(),
                   description="flash kernel under the interpreter")
def _attention_pallas_interpret(q, k, v, **kw):
    kw.pop("swa_impl", None)
    return _pallas_attention(q, k, v, interpret=True, **kw)


def attention(
    q: jnp.ndarray,            # (B, H, Sq, D)
    k: jnp.ndarray,            # (B, Hk, Skv, D)
    v: jnp.ndarray,            # (B, Hk, Skv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    impl: str | None = None,
    swa_impl: str = "full",
) -> jnp.ndarray:
    return registry.dispatch(
        "attention", impl, q, k, v, causal=causal, window=window,
        scale=scale, q_offset=q_offset, block_q=block_q, block_kv=block_kv,
        swa_impl=swa_impl)
