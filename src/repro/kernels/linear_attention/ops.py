"""Public chunked linear-attention op, registry-dispatched."""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro import compat
from repro.kernels import registry
from repro.kernels.linear_attention import ref

__all__ = ["linear_attention"]


def _guard(q, k, v, log_w, *, bonus=None, inclusive=False, chunk=64):
    """Pallas recurrence precondition: 3-D float inputs whose time axis is
    divisible by the (clamped) chunk length the kernel will tile with."""
    del bonus, inclusive
    if q.ndim != 3 or k.ndim != 3 or v.ndim != 3:
        return False
    if not jnp.issubdtype(q.dtype, jnp.floating):
        return False
    t = q.shape[1]
    c = min(chunk, t)
    return c > 0 and t % c == 0


@registry.register("linear_attention", "xla_ref", priority=0,
                   description="loop-free chunked formulation "
                               "(associative-scan reference)")
def _linatt_xla_ref(q, k, v, log_w, *, bonus=None, inclusive=False,
                    chunk=64):
    # fallback target must accept ANY input: clamp the chunk length to a
    # divisor of T (guard-missing pallas calls land here with t % chunk != 0)
    t = q.shape[1]
    c = math.gcd(t, min(chunk, t))
    return ref.linear_attention(q, k, v, log_w, bonus=bonus,
                                inclusive=inclusive, chunk=c)


def _pallas_linatt(q, k, v, log_w, *, bonus, inclusive, chunk, interpret):
    from repro.kernels.linear_attention.kernel import linear_attention_pallas

    c = min(chunk, q.shape[1])
    return linear_attention_pallas(q, k, v, log_w, bonus,
                                   inclusive=inclusive, chunk=c,
                                   interpret=interpret)


@registry.register("linear_attention", "pallas_tpu", priority=20,
                   supports_grad=False,
                   guard=_guard,
                   available=lambda: compat.has_pallas_tpu()
                   and compat.on_tpu(),
                   description="VMEM-resident state recurrence kernel")
def _linatt_pallas_tpu(q, k, v, log_w, *, bonus=None, inclusive=False,
                       chunk=64):
    return _pallas_linatt(q, k, v, log_w, bonus=bonus, inclusive=inclusive,
                          chunk=chunk, interpret=False)


@registry.register("linear_attention", "pallas_interpret", priority=-10,
                   supports_grad=False,
                   guard=_guard, available=lambda: compat.on_cpu()
                   and compat.has_pallas_tpu(),
                   description="recurrence kernel under the interpreter")
def _linatt_pallas_interpret(q, k, v, log_w, *, bonus=None, inclusive=False,
                             chunk=64):
    return _pallas_linatt(q, k, v, log_w, bonus=bonus, inclusive=inclusive,
                          chunk=chunk, interpret=True)


def linear_attention(q, k, v, log_w, *, bonus=None, inclusive: bool = False,
                     chunk: int = 64, impl: str | None = None):
    """q/k (BH,T,dk), v (BH,T,dv), log_w (BH,T,dk) or (BH,T,1),
    bonus (BH,dk)|None -> (BH,T,dv)."""
    log_w = jnp.broadcast_to(log_w, q.shape)
    return registry.dispatch("linear_attention", impl, q, k, v, log_w,
                             bonus=bonus, inclusive=inclusive, chunk=chunk)
