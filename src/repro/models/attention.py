"""GQA attention (train/prefill + cached decode), with qk-norm and sliding
window.  Covers yi-6b, deepseek-7b (kv=H, i.e. MHA), minitron-4b, qwen3-0.6b
(qk_norm), internvl2/musicgen backbones, and hymba's attention heads (SWA).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels.attention import attention as attn_op
from repro.kernels.attention.ref import NEG_INF
from repro.models.common import (KernelOptions, apply_rope, chunk_slots,
                                 chunk_visible, dense_init, rope, rms_norm)
from repro.models.config import ModelConfig

__all__ = ["init_gqa", "gqa_axes", "apply_gqa", "init_gqa_cache",
           "gqa_cache_axes", "decode_gqa", "prefill_gqa"]


def init_gqa(key, cfg: ModelConfig) -> dict:
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, dh)),
        "wk": dense_init(ks[1], (d, hk, dh)),
        "wv": dense_init(ks[2], (d, hk, dh)),
        "wo": dense_init(ks[3], (h, dh, d), in_axis=0),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), jnp.float32)
        p["k_norm"] = jnp.ones((dh,), jnp.float32)
    return p


def gqa_axes(cfg: ModelConfig) -> dict:
    ax = {
        "wq": ("fsdp", "heads", "head_dim"),
        "wk": ("fsdp", "kv_heads", "head_dim"),
        "wv": ("fsdp", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "fsdp"),
    }
    if cfg.qk_norm:
        ax["q_norm"] = ("head_dim",)
        ax["k_norm"] = ("head_dim",)
    return ax


def _project_qkv(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                 opts: KernelOptions, positions: jnp.ndarray):
    """x (B,S,d) -> q (B,H,S,dh), k/v (B,Hk,S,dh) with rope applied."""
    cdt = x.dtype
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(cdt))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps, opts)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps, opts)
    cos, sin = rope(positions, cfg.d_head, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "heads", "seq", "head_dim"))
    k = constrain(k, ("batch", "kv_heads", "seq", "head_dim"))
    v = constrain(v, ("batch", "kv_heads", "seq", "head_dim"))
    return q, k, v


def apply_gqa(p: dict, x: jnp.ndarray, cfg: ModelConfig,
              opts: KernelOptions, *, window: int | None = None,
              positions: jnp.ndarray | None = None) -> jnp.ndarray:
    """Full-sequence (train / prefill) attention. x (B,S,d) -> (B,S,d)."""
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(p, x, cfg, opts, positions)
    out = attn_op(q, k, v, causal=True, window=window,
                  block_q=opts.block_q, block_kv=opts.block_kv,
                  impl=opts.impl_for("attention"),
                  swa_impl=opts.swa_impl)              # (B,H,S,dh)
    out = constrain(out, ("batch", "heads", "seq", "head_dim"))
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return constrain(y, ("batch", "seq", None))


# -- decode with ring-buffer cache ---------------------------------------------

def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int,
                   window: int | None = None, dtype=jnp.bfloat16) -> dict:
    """Ring-buffer KV cache.  ``window`` bounds the buffer for SWA layers."""
    w = min(window, max_len) if window else max_len
    hk, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((batch, hk, w, dh), dtype),
        "v": jnp.zeros((batch, hk, w, dh), dtype),
        "slot_pos": jnp.full((w,), -1, jnp.int32),   # absolute pos per slot
    }


def gqa_cache_axes(cfg: ModelConfig) -> dict:
    return {
        "k": ("batch", "kv_heads", "seq_kv", "head_dim"),
        "v": ("batch", "kv_heads", "seq_kv", "head_dim"),
        "slot_pos": (None,),
    }


def decode_gqa(p: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray,
               cfg: ModelConfig, opts: KernelOptions, *,
               window: int | None = None) -> tuple[jnp.ndarray, dict]:
    """One decode step. x (B,1,d) -> ((B,1,d), cache).

    ``pos`` scalar int32: the classic shared-ring path — every row is at
    the same position, the write lands in ring slot ``pos % w``, and
    validity comes from the shared ``slot_pos`` map.

    ``pos`` vector (B,) int32: per-row positions for paged per-request
    caches — row b writes slot ``pos[b]`` (contiguous layout: slot index
    == absolute position, so the cache seq capacity must be the full
    max_len) and validity is ``slot <= pos[b]``; ``slot_pos`` passes
    through untouched.  Rows whose position is out of range (>= w) write
    nothing, which is what lets chunked prefill keep inactive rows
    harmless.
    """
    if jnp.ndim(pos) == 1:
        return _decode_gqa_rows(p, cache, x, pos, cfg, opts, window=window)
    b = x.shape[0]
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    g = h // hk
    q, k, v = _project_qkv(p, x, cfg, opts, pos[None])
    w = cache["k"].shape[2]
    slot = (pos % w).astype(jnp.int32)

    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, 0, slot, 0))
    spos = jax.lax.dynamic_update_slice(cache["slot_pos"], pos[None], (slot,))

    qg = q.reshape(b, hk, g, dh)
    scores = jnp.einsum("bhgk,bhwk->bhgw", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (dh ** -0.5)
    valid = (spos >= 0) & (spos <= pos)
    if window is not None:
        valid &= spos > pos - window
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgw,bhwk->bhgk", probs, cv.astype(jnp.float32))
    out = out.reshape(b, h, 1, dh).astype(x.dtype)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": ck, "v": cv, "slot_pos": spos}


def _decode_gqa_rows(p: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray,
                     cfg: ModelConfig, opts: KernelOptions, *,
                     window: int | None = None) -> tuple[jnp.ndarray, dict]:
    """Vector-pos decode: row b at position pos[b] (see :func:`decode_gqa`)."""
    b = x.shape[0]
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    g = h // hk
    q, k, v = _project_qkv(p, x, cfg, opts, pos[:, None, None])
    w = cache["k"].shape[2]
    slots = jnp.arange(w, dtype=jnp.int32)
    at = slots[None, :] == pos[:, None]                 # (B,w) write mask
    ck = jnp.where(at[:, None, :, None], k.astype(cache["k"].dtype),
                   cache["k"])
    cv = jnp.where(at[:, None, :, None], v.astype(cache["v"].dtype),
                   cache["v"])

    qg = q.reshape(b, hk, g, dh)
    scores = jnp.einsum("bhgk,bhwk->bhgw", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (dh ** -0.5)
    valid = slots[None, :] <= pos[:, None]              # contiguous prefix
    if window is not None:
        valid &= slots[None, :] > pos[:, None] - window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgw,bhwk->bhgk", probs, cv.astype(jnp.float32))
    out = out.reshape(b, h, 1, dh).astype(x.dtype)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": ck, "v": cv, "slot_pos": cache["slot_pos"]}


def prefill_gqa(p: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray,
                n_new: jnp.ndarray, cfg: ModelConfig, opts: KernelOptions, *,
                window: int | None = None) -> tuple[jnp.ndarray, dict]:
    """A prompt chunk against per-row contiguous caches: x (B,C,d), row b's
    token t at position ``pos[b] + t`` -> ((B,C,d), cache).

    The chunk's keys and values land in slots ``pos[b] .. pos[b] +
    n_new[b] - 1`` in one scatter per leaf (nothing past the cache, nothing
    for a row with ``n_new == 0``); each query then scores every slot up to
    its own position, in float32, as :func:`_decode_gqa_rows` scores one."""
    b, c, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    g = h // hk
    positions = pos[:, None] + jnp.arange(c, dtype=jnp.int32)   # (B,C)
    q, k, v = _project_qkv(p, x, cfg, opts, positions[:, None])
    w = cache["k"].shape[2]
    slots = chunk_slots(positions, n_new, w)
    rows = jnp.arange(b)[:, None]
    ck = cache["k"].at[rows, :, slots].set(
        k.swapaxes(1, 2).astype(cache["k"].dtype), mode="drop")
    cv = cache["v"].at[rows, :, slots].set(
        v.swapaxes(1, 2).astype(cache["v"].dtype), mode="drop")

    qg = q.reshape(b, hk, g, c, dh)
    scores = jnp.einsum("bhgck,bhwk->bhgcw", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (dh ** -0.5)
    valid = chunk_visible(positions, w, window)                 # (B,C,w)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgcw,bhwk->bhgck", probs, cv.astype(jnp.float32))
    out = out.reshape(b, h, c, dh).astype(x.dtype)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": ck, "v": cv, "slot_pos": cache["slot_pos"]}
