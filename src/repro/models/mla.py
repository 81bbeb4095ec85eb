"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Train/prefill: q/k/v are materialized from low-rank latents and run through
the flash kernel with ``d_qk = nope + rope`` head dim and ``d_v = d_head``.

Decode: the **absorbed** form — scores are computed directly against the
cached ``(kv_lora + rope_head_dim)``-wide latent (W_uk is absorbed into the
query, W_uv applied after attention), so the KV cache is ~1/``n_heads`` the
size of a GQA cache.  This is the arch-level analogue of the paper's
specialization story: the decode handler is a *structurally different,
specialized implementation* of the same math, selected when the workload is
autoregressive decode.  A served prompt chunk (:func:`prefill_mla`) takes
the absorbed form too, against the same latent cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels.attention import attention as attn_op
from repro.kernels.attention.ref import NEG_INF
from repro.models.common import (KernelOptions, apply_rope, chunk_slots,
                                 chunk_visible, dense_init, rope, rms_norm,
                                 yarn_mscale)
from repro.models.config import ModelConfig

__all__ = ["init_mla", "mla_axes", "apply_mla", "init_mla_cache",
           "mla_cache_axes", "decode_mla", "prefill_mla"]


def init_mla(key, cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nd, rd, dh = cfg.nope_head_dim, cfg.rope_head_dim, cfg.d_head
    ks = jax.random.split(key, 7)
    p = {
        "w_dq": dense_init(ks[0], (d, qr)),
        "q_norm": jnp.ones((qr,), jnp.float32),
        "w_uq": dense_init(ks[1], (qr, h, nd + rd)),
        "w_dkv": dense_init(ks[2], (d, kvr)),
        "kv_norm": jnp.ones((kvr,), jnp.float32),
        "w_kr": dense_init(ks[3], (d, rd)),
        "w_uk": dense_init(ks[4], (kvr, h, nd)),
        "w_uv": dense_init(ks[5], (kvr, h, dh)),
        "wo": dense_init(ks[6], (h, dh, d), in_axis=0),
    }
    return p


def mla_axes(cfg: ModelConfig) -> dict:
    return {
        "w_dq": ("fsdp", None),
        "q_norm": (None,),
        "w_uq": ("fsdp", "heads", "head_dim"),
        "w_dkv": ("fsdp", None),
        "kv_norm": (None,),
        "w_kr": ("fsdp", None),
        "w_uk": ("fsdp", "heads", "head_dim"),
        "w_uv": ("fsdp", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "fsdp"),
    }


def _latents(p: dict, x: jnp.ndarray, cfg: ModelConfig, opts: KernelOptions,
             positions: jnp.ndarray):
    """Shared by all paths: q heads + kv latent + rotary shared key."""
    cdt = x.dtype
    cq = rms_norm(x @ p["w_dq"].astype(cdt), p["q_norm"], cfg.rms_eps, opts)
    q = jnp.einsum("bsr,rhk->bhsk", cq, p["w_uq"].astype(cdt))
    q_nope = q[..., :cfg.nope_head_dim]
    q_rope = q[..., cfg.nope_head_dim:]
    ckv = rms_norm(x @ p["w_dkv"].astype(cdt), p["kv_norm"], cfg.rms_eps, opts)
    k_rope = (x @ p["w_kr"].astype(cdt))[:, None]       # (B,1,S,rd)
    cos, sin = rope(positions, cfg.rope_head_dim, cfg.rope_theta, cfg=cfg)
    if cfg.rope_interleave:
        # rotate (even, odd) column pairs: gather them into halves first
        # (hf deepseek's apply_rotary_pos_emb); q and k move alike, so the
        # scores are those of the pairs
        q_rope, k_rope = _deinterleave(q_rope), _deinterleave(k_rope)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)
    return q_nope, q_rope, ckv, k_rope


def _deinterleave(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)


def _score_scale(cfg: ModelConfig) -> float:
    """Softmax scale: ``(nope + rope) ** -0.5``, times YaRN's mscale
    squared when the context is stretched (hf ``softmax_scale``)."""
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def apply_mla(p: dict, x: jnp.ndarray, cfg: ModelConfig,
              opts: KernelOptions, *, window: int | None = None,
              positions: jnp.ndarray | None = None) -> jnp.ndarray:
    """Materialized train/prefill path. x (B,S,d) -> (B,S,d)."""
    with jax.named_scope("iri.mla"):
        return _apply_mla(p, x, cfg, opts, window, positions)


def _apply_mla(p, x, cfg, opts, window, positions):
    b, s, d = x.shape
    h, nd, rd, dh = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.d_head
    if positions is None:
        positions = jnp.arange(s)
    q_nope, q_rope, ckv, k_rope = _latents(p, x, cfg, opts, positions)
    cdt = x.dtype
    k_nope = jnp.einsum("bsr,rhk->bhsk", ckv, p["w_uk"].astype(cdt))
    v = jnp.einsum("bsr,rhk->bhsk", ckv, p["w_uv"].astype(cdt))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, h, s, rd))], -1)
    q = constrain(q, ("batch", "heads", "seq", "head_dim"))
    k = constrain(k, ("batch", "heads", "seq", "head_dim"))
    v = constrain(v, ("batch", "heads", "seq", "head_dim"))
    out = attn_op(q, k, v, causal=True, window=window,
                  scale=_score_scale(cfg),
                  block_q=opts.block_q, block_kv=opts.block_kv,
                  impl=opts.impl_for("attention"))     # (B,H,S,dh)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(cdt))
    return constrain(y, ("batch", "seq", None))


# -- absorbed decode -------------------------------------------------------------

def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   window: int | None = None, dtype=jnp.bfloat16) -> dict:
    w = min(window, max_len) if window else max_len
    return {
        "ckv": jnp.zeros((batch, w, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, w, cfg.rope_head_dim), dtype),
        "slot_pos": jnp.full((w,), -1, jnp.int32),
    }


def mla_cache_axes(cfg: ModelConfig) -> dict:
    return {
        "ckv": ("batch", "seq_kv", None),
        "k_rope": ("batch", "seq_kv", None),
        "slot_pos": (None,),
    }


def decode_mla(p: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray,
               cfg: ModelConfig, opts: KernelOptions, *,
               window: int | None = None) -> tuple[jnp.ndarray, dict]:
    """One absorbed decode step. x (B,1,d) -> ((B,1,d), cache).

    ``pos`` scalar: shared ring slot + ``slot_pos`` validity (all rows in
    lockstep).  ``pos`` vector (B,): per-row contiguous slots for paged
    per-request caches — mirrors :func:`repro.models.attention.decode_gqa`.
    """
    with jax.named_scope("iri.mla"):
        if jnp.ndim(pos) == 1:
            return _decode_mla_rows(p, cache, x, pos, cfg, opts,
                                    window=window)
        return _decode_mla_ring(p, cache, x, pos, cfg, opts, window=window)


def _decode_mla_ring(p: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray,
                     cfg: ModelConfig, opts: KernelOptions, *,
                     window: int | None = None) -> tuple[jnp.ndarray, dict]:
    """Scalar-pos absorbed decode: one shared ring slot for all rows."""
    cdt = x.dtype
    q_nope, q_rope, ckv, k_rope = _latents(p, x, cfg, opts, pos[None])
    # Absorb W_uk into the query: q_eff (B,H,kv_lora).
    q_eff = jnp.einsum("bhsk,rhk->bhr", q_nope, p["w_uk"].astype(cdt))

    w = cache["ckv"].shape[1]
    slot = (pos % w).astype(jnp.int32)
    cckv = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, slot, 0))
    ckr = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope[:, 0].astype(cache["k_rope"].dtype),
        (0, slot, 0))
    spos = jax.lax.dynamic_update_slice(cache["slot_pos"], pos[None], (slot,))

    f32 = jnp.float32
    scores = (jnp.einsum("bhr,bwr->bhw", q_eff.astype(f32), cckv.astype(f32))
              + jnp.einsum("bhsk,bwk->bhw", q_rope.astype(f32),
                           ckr.astype(f32))) * _score_scale(cfg)
    valid = (spos >= 0) & (spos <= pos)
    if window is not None:
        valid &= spos > pos - window
    scores = jnp.where(valid[None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_latent = jnp.einsum("bhw,bwr->bhr", probs, cckv.astype(f32))
    out = jnp.einsum("bhr,rhk->bhk", o_latent.astype(cdt),
                     p["w_uv"].astype(cdt))              # (B,H,dh)
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(cdt))[:, None]
    return y, {"ckv": cckv, "k_rope": ckr, "slot_pos": spos}


def _decode_mla_rows(p: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray,
                     cfg: ModelConfig, opts: KernelOptions, *,
                     window: int | None = None) -> tuple[jnp.ndarray, dict]:
    """Vector-pos absorbed decode: row b at position pos[b]."""
    cdt = x.dtype
    q_nope, q_rope, ckv, k_rope = _latents(p, x, cfg, opts, pos[:, None, None])
    q_eff = jnp.einsum("bhsk,rhk->bhr", q_nope, p["w_uk"].astype(cdt))

    w = cache["ckv"].shape[1]
    slots = jnp.arange(w, dtype=jnp.int32)
    at = slots[None, :] == pos[:, None]                 # (B,w) write mask
    cckv = jnp.where(at[:, :, None], ckv.astype(cache["ckv"].dtype),
                     cache["ckv"])
    ckr = jnp.where(at[:, :, None],
                    k_rope[:, 0].astype(cache["k_rope"].dtype),
                    cache["k_rope"])

    f32 = jnp.float32
    scores = (jnp.einsum("bhr,bwr->bhw", q_eff.astype(f32), cckv.astype(f32))
              + jnp.einsum("bhsk,bwk->bhw", q_rope.astype(f32),
                           ckr.astype(f32))) * _score_scale(cfg)
    valid = slots[None, :] <= pos[:, None]              # contiguous prefix
    if window is not None:
        valid &= slots[None, :] > pos[:, None] - window
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_latent = jnp.einsum("bhw,bwr->bhr", probs, cckv.astype(f32))
    out = jnp.einsum("bhr,rhk->bhk", o_latent.astype(cdt),
                     p["w_uv"].astype(cdt))
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(cdt))[:, None]
    return y, {"ckv": cckv, "k_rope": ckr, "slot_pos": cache["slot_pos"]}


def prefill_mla(p: dict, cache: dict, x: jnp.ndarray, pos: jnp.ndarray,
                n_new: jnp.ndarray, cfg: ModelConfig, opts: KernelOptions, *,
                window: int | None = None) -> tuple[jnp.ndarray, dict]:
    """A prompt chunk in the absorbed form: x (B,C,d), row b's token t at
    position ``pos[b] + t`` -> ((B,C,d), cache).  The chunk's latents land
    in slots ``pos[b] .. pos[b] + n_new[b] - 1`` (as
    :func:`repro.models.attention.prefill_gqa` writes keys), and each query
    scores every slot up to its own position, as :func:`_decode_mla_rows`
    scores one."""
    with jax.named_scope("iri.mla"):
        return _prefill_mla(p, cache, x, pos, n_new, cfg, opts, window)


def _prefill_mla(p, cache, x, pos, n_new, cfg, opts, window):
    cdt = x.dtype
    b, c, _ = x.shape
    positions = pos[:, None] + jnp.arange(c, dtype=jnp.int32)   # (B,C)
    q_nope, q_rope, ckv, k_rope = _latents(p, x, cfg, opts, positions[:, None])
    q_eff = jnp.einsum("bhck,rhk->bhcr", q_nope, p["w_uk"].astype(cdt))

    w = cache["ckv"].shape[1]
    slots = chunk_slots(positions, n_new, w)
    rows = jnp.arange(b)[:, None]
    cckv = cache["ckv"].at[rows, slots].set(
        ckv.astype(cache["ckv"].dtype), mode="drop")
    ckr = cache["k_rope"].at[rows, slots].set(
        k_rope[:, 0].astype(cache["k_rope"].dtype), mode="drop")

    f32 = jnp.float32
    scores = (jnp.einsum("bhcr,bwr->bhcw", q_eff.astype(f32), cckv.astype(f32))
              + jnp.einsum("bhck,bwk->bhcw", q_rope.astype(f32),
                           ckr.astype(f32))) * _score_scale(cfg)
    valid = chunk_visible(positions, w, window)                 # (B,C,w)
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_latent = jnp.einsum("bhcw,bwr->bhcr", probs, cckv.astype(f32))
    out = jnp.einsum("bhcr,rhk->bhck", o_latent.astype(cdt),
                     p["w_uv"].astype(cdt))
    y = jnp.einsum("bhck,hkd->bcd", out, p["wo"].astype(cdt))
    return y, {"ckv": cckv, "k_rope": ckr, "slot_pos": cache["slot_pos"]}
