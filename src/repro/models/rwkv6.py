"""RWKV6 "Finch" (arXiv:2404.05892): attention-free token mixer with
data-dependent decay, + squared-relu channel mix.

Faithful structure: token-shift lerps for r/k/v/g/w, a LoRA producing the
per-step per-channel decay ``w_t`` (the Finch novelty), per-head bonus ``u``,
per-head output group-norm, gated output.  Simplifications (noted in
DESIGN.md §Arch-applicability): the r/k/v/g token-shift mix coefficients are
static learned vectors (Finch makes them data-dependent through a second
LoRA stack); log-decay is clamped to ``[-1, -1e-4]``) for fp32-safe chunked
evaluation (chunk <= 64).

Train path uses the chunked linear-attention engine (``chunk_scan``) —
sub-quadratic, loop-free; decode advances the (H, hs, hs) state directly, so
``long_500k`` decode is O(1) in sequence length; a served prompt chunk
(:func:`prefill_rwkv6`) runs the chunked form from the cached state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels.linear_attention import linear_attention
from repro.models.chunk_scan import (chunked_linear_attention,
                                     step_linear_attention)
from repro.models.common import KernelOptions, dense_init, rms_norm
from repro.models.config import ModelConfig

__all__ = ["init_rwkv6", "rwkv6_axes", "apply_rwkv6", "init_rwkv6_cache",
           "rwkv6_cache_axes", "decode_rwkv6", "prefill_rwkv6",
           "prefill_rwkv6_channel_mix", "LOG_W_MIN"]

LOG_W_MIN = -1.0        # per-step log-decay clamp (chunk-safety, see module doc)
_DECAY_LORA = 64


def init_rwkv6(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    hs = cfg.rwkv_head_size
    h = cfg.rwkv_heads
    ks = jax.random.split(key, 10)
    return {
        # token-shift mix coefficients (static lerp weights in [0,1])
        "mu_r": jnp.full((d,), 0.5, jnp.float32),
        "mu_k": jnp.full((d,), 0.5, jnp.float32),
        "mu_v": jnp.full((d,), 0.5, jnp.float32),
        "mu_g": jnp.full((d,), 0.5, jnp.float32),
        "mu_w": jnp.full((d,), 0.5, jnp.float32),
        "wr": dense_init(ks[0], (d, d)),
        "wk": dense_init(ks[1], (d, d)),
        "wv": dense_init(ks[2], (d, d)),
        "wg": dense_init(ks[3], (d, d)),
        "wo": dense_init(ks[4], (d, d)),
        # data-dependent decay: w0 + tanh(x @ A) @ B   (Finch LoRA)
        "w0": jnp.full((d,), -0.6, jnp.float32),
        "w_lora_a": dense_init(ks[5], (d, _DECAY_LORA)),
        "w_lora_b": dense_init(ks[6], (_DECAY_LORA, d)) * 0.1,
        "u": dense_init(ks[7], (h, hs)) * 0.1,           # per-head bonus
        "ln_x": jnp.ones((d,), jnp.float32),             # output group norm
        # channel mix
        "cm_mu_k": jnp.full((d,), 0.5, jnp.float32),
        "cm_wk": dense_init(ks[8], (d, cfg.d_ff)),
        "cm_wv": dense_init(ks[9], (cfg.d_ff, d)),
        "cm_wr": dense_init(jax.random.fold_in(key, 99), (d, d)),
    }


def rwkv6_axes(cfg: ModelConfig) -> dict:
    return {
        "mu_r": (None,), "mu_k": (None,), "mu_v": (None,), "mu_g": (None,),
        "mu_w": (None,),
        "wr": ("fsdp", "heads"), "wk": ("fsdp", "heads"),
        "wv": ("fsdp", "heads"), "wg": ("fsdp", "heads"),
        "wo": ("heads", "fsdp"),
        "w0": (None,), "w_lora_a": ("fsdp", None), "w_lora_b": (None, "fsdp"),
        "u": (None, None), "ln_x": (None,),
        "cm_mu_k": (None,),
        "cm_wk": ("fsdp", "ffn"), "cm_wv": ("ffn", "fsdp"), "cm_wr": ("fsdp", None),
    }


def _log_decay(p: dict, xw: jnp.ndarray) -> jnp.ndarray:
    """Finch data-dependent per-channel log decay, clamped for chunking."""
    lora = jnp.tanh(xw @ p["w_lora_a"].astype(xw.dtype)) \
        @ p["w_lora_b"].astype(xw.dtype)
    raw = -jnp.exp(jnp.clip(p["w0"].astype(jnp.float32)
                            + lora.astype(jnp.float32), -8.0, 1.0))
    return jnp.clip(raw, LOG_W_MIN, -1e-4)


def _mix(x: jnp.ndarray, x_prev: jnp.ndarray, mu: jnp.ndarray) -> jnp.ndarray:
    return x + (x_prev - x) * mu.astype(x.dtype)


def _time_mix_inputs(p: dict, x: jnp.ndarray, x_prev: jnp.ndarray,
                     cfg: ModelConfig):
    """Shared by train & decode: project r/k/v/g and decay from shifted x."""
    cdt = x.dtype
    r = _mix(x, x_prev, p["mu_r"]) @ p["wr"].astype(cdt)
    k = _mix(x, x_prev, p["mu_k"]) @ p["wk"].astype(cdt)
    v = _mix(x, x_prev, p["mu_v"]) @ p["wv"].astype(cdt)
    g = jax.nn.silu(_mix(x, x_prev, p["mu_g"]) @ p["wg"].astype(cdt))
    lw = _log_decay(p, _mix(x, x_prev, p["mu_w"]))
    return r, k, v, g, lw


def _heads(x: jnp.ndarray, h: int, hs: int) -> jnp.ndarray:
    return x.reshape(x.shape[:-1] + (h, hs))


def apply_rwkv6(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                opts: KernelOptions) -> jnp.ndarray:
    """Time-mix over the full sequence. x (B,S,d) -> (B,S,d)."""
    b, s, d = x.shape
    h, hs = cfg.rwkv_heads, cfg.rwkv_head_size
    x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    r, k, v, g, lw = _time_mix_inputs(p, x, x_prev, cfg)

    rh = _heads(r, h, hs).transpose(0, 2, 1, 3)       # (B,H,S,hs)
    kh = _heads(k, h, hs).transpose(0, 2, 1, 3)
    vh = _heads(v, h, hs).transpose(0, 2, 1, 3)
    lwh = _heads(lw, h, hs).transpose(0, 2, 1, 3)
    rh = constrain(rh, ("batch", "heads", "seq", None))

    u_b = jnp.broadcast_to(p["u"].astype(jnp.float32)[None], (b, h, hs))
    o = linear_attention(
        rh.reshape(b * h, s, hs), kh.reshape(b * h, s, hs),
        vh.reshape(b * h, s, hs), lwh.reshape(b * h, s, hs),
        bonus=u_b.reshape(b * h, hs), inclusive=False,
        chunk=min(opts.chunk_len, s),
        impl=opts.impl_for("linear_attention"))
    o = o.reshape(b, h, s, hs)                        # (B,H,S,hs)

    o = o.transpose(0, 2, 1, 3)                        # (B,S,H,hs)
    o = rms_norm(o, jnp.ones((hs,), jnp.float32), cfg.rms_eps, opts)  # per-head
    o = o.reshape(b, s, d) * p["ln_x"].astype(x.dtype) * g
    return constrain(o @ p["wo"].astype(x.dtype), ("batch", "seq", None))


def apply_rwkv6_channel_mix(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                            x_prev: jnp.ndarray | None = None) -> jnp.ndarray:
    """Squared-relu channel mix (the rwkv 'ffn'). x (B,S,d) -> (B,S,d)."""
    if x_prev is None:
        x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    cdt = x.dtype
    xk = _mix(x, x_prev, p["cm_mu_k"])
    kk = jnp.square(jax.nn.relu(xk @ p["cm_wk"].astype(cdt)))
    kk = constrain(kk, ("batch", "seq", "ffn"))
    rr = jax.nn.sigmoid(x @ p["cm_wr"].astype(cdt))
    return rr * (kk @ p["cm_wv"].astype(cdt))


# -- decode ---------------------------------------------------------------------

def init_rwkv6_cache(cfg: ModelConfig, batch: int, max_len: int = 0,
                     window=None, dtype=jnp.float32) -> dict:
    h, hs, d = cfg.rwkv_heads, cfg.rwkv_head_size, cfg.d_model
    return {
        "state": jnp.zeros((batch, h, hs, hs), jnp.float32),
        "x_tm": jnp.zeros((batch, d), dtype),    # last input (time mix shift)
        "x_cm": jnp.zeros((batch, d), dtype),    # last input (channel mix)
    }


def rwkv6_cache_axes(cfg: ModelConfig) -> dict:
    return {"state": ("batch", "heads", None, None),
            "x_tm": ("batch", None), "x_cm": ("batch", None)}


def decode_rwkv6(p: dict, cache: dict, x: jnp.ndarray, pos, cfg: ModelConfig,
                 opts: KernelOptions, **_) -> tuple[jnp.ndarray, dict]:
    """One step of time-mix. x (B,1,d) -> ((B,1,d), cache)."""
    b, _, d = x.shape
    h, hs = cfg.rwkv_heads, cfg.rwkv_head_size
    xt = x[:, 0]
    x_prev = cache["x_tm"].astype(xt.dtype)
    r, k, v, g, lw = _time_mix_inputs(p, xt[:, None], x_prev[:, None], cfg)
    r, k, v, g, lw = r[:, 0], k[:, 0], v[:, 0], g[:, 0], lw[:, 0]

    def step(q_, k_, v_, w_, s_, u_):
        return step_linear_attention(q_, k_, v_, w_, s_, bonus=u_)

    fn = jax.vmap(jax.vmap(step, in_axes=(0, 0, 0, 0, 0, 0)),
                  in_axes=(0, 0, 0, 0, 0, None))
    o, new_state = fn(_heads(r, h, hs), _heads(k, h, hs), _heads(v, h, hs),
                      _heads(lw, h, hs), cache["state"], p["u"])
    o = rms_norm(o, jnp.ones((hs,), jnp.float32), cfg.rms_eps, opts)
    o = o.reshape(b, d) * p["ln_x"].astype(x.dtype) * g
    y = (o @ p["wo"].astype(x.dtype))[:, None]
    return y, {"state": new_state, "x_tm": xt.astype(cache["x_tm"].dtype),
               "x_cm": cache["x_cm"]}


def _shifted(last: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Token shift of a chunk x (B,C,d) after the cached input ``last``
    (B,d): each token's predecessor, rounded through the cache's dtype as
    the one-token step reads it back."""
    prev = x[:, :-1].astype(last.dtype)
    return jnp.concatenate([last[:, None], prev], 1).astype(x.dtype)


def _last(x: jnp.ndarray, n_new: jnp.ndarray,
          old: jnp.ndarray) -> jnp.ndarray:
    """Row b's input at token ``n_new[b] - 1``, in ``old``'s dtype; ``old``
    where the row took no token."""
    at = jnp.clip(n_new - 1, 0)[:, None, None]
    last = jnp.take_along_axis(x, at, axis=1)[:, 0].astype(old.dtype)
    return jnp.where((n_new > 0)[:, None], last, old)


def _chunk_len(c: int, most: int) -> int:
    """The largest divisor of the chunk length ``c`` that is at most
    ``most`` (the linear-attention chunk, bounded for float32)."""
    return max(n for n in range(1, min(c, most) + 1) if c % n == 0)


def prefill_rwkv6(p: dict, cache: dict, x: jnp.ndarray, n_new: jnp.ndarray,
                  cfg: ModelConfig, opts: KernelOptions
                  ) -> tuple[jnp.ndarray, dict]:
    """Time-mix over a prompt chunk x (B,C,d) from the cached state ->
    ((B,C,d), cache).  The wkv recurrence runs in its chunked form from
    the cached state; the state and ``x_tm`` advance through row b's first
    ``n_new[b]`` tokens only (later tokens add no key and decay nothing)."""
    b, c, d = x.shape
    h, hs = cfg.rwkv_heads, cfg.rwkv_head_size
    r, k, v, g, lw = _time_mix_inputs(p, x, _shifted(cache["x_tm"], x), cfg)
    live = (jnp.arange(c)[None] < n_new[:, None])[..., None]   # (B,C,1)
    k = jnp.where(live, k, 0)
    lw = jnp.where(live, lw, 0.0)
    chunk = _chunk_len(c, opts.chunk_len)

    def one(q_, k_, v_, w_, s_, u_):
        return chunked_linear_attention(q_, k_, v_, w_, bonus=u_,
                                        chunk=chunk, init_state=s_,
                                        return_state=True)

    fn = jax.vmap(jax.vmap(one, in_axes=(1, 1, 1, 1, 0, 0)),
                  in_axes=(0, 0, 0, 0, 0, None))
    o, state = fn(_heads(r, h, hs), _heads(k, h, hs), _heads(v, h, hs),
                  _heads(lw, h, hs), cache["state"], p["u"])
    o = o.transpose(0, 2, 1, 3)                        # (B,C,H,hs)
    o = rms_norm(o, jnp.ones((hs,), jnp.float32), cfg.rms_eps, opts)
    o = o.reshape(b, c, d) * p["ln_x"].astype(x.dtype) * g
    y = o @ p["wo"].astype(x.dtype)
    return y, {"state": state, "x_tm": _last(x, n_new, cache["x_tm"]),
               "x_cm": cache["x_cm"]}


def prefill_rwkv6_channel_mix(p: dict, cache: dict, x: jnp.ndarray,
                              n_new: jnp.ndarray, cfg: ModelConfig
                              ) -> tuple[jnp.ndarray, dict]:
    """Channel mix over a prompt chunk x (B,C,d) after the cached input;
    ``x_cm`` advances to row b's token ``n_new[b] - 1``."""
    f = apply_rwkv6_channel_mix(p, x, cfg, x_prev=_shifted(cache["x_cm"], x))
    return f, dict(cache, x_cm=_last(x, n_new, cache["x_cm"]))
