"""Model assembly: embeddings -> mixer/FFN layer stack -> LM head.

Covers every assigned architecture through ``ModelConfig``:

* mixer: GQA (optionally qk-norm / sliding window), MLA, RWKV6 time-mix,
  or Hymba parallel attention+SSM heads;
* FFN: dense SwiGLU, MoE (dense-prefix + MoE stack), or RWKV channel-mix;
* frontends (vlm/audio): the modality encoder is a stub per the assignment —
  ``apply`` accepts precomputed ``embeds (B,S,d)`` instead of token ids.

Layers are stacked (leading ``L`` axis) and evaluated with ``lax.scan``
(compile-time O(1) in depth) or an unrolled Python loop (``scan=False`` —
used by the roofline surrogate lowering, since XLA's cost model visits a
while-loop body only once).  Activation checkpointing policy is an
Iridescent spec point (``remat`` in {none,dots,full}).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (KernelOptions, dense_init, embed_init,
                                 rms_norm, swiglu)
from repro.models.config import ModelConfig
from repro.models.moe import MoEOptions

__all__ = ["RunOptions", "init_params", "param_axes", "apply",
           "init_cache", "cache_axes", "decode_step", "prefill_chunk",
           "lm_head_weight"]


@dataclasses.dataclass(frozen=True)
class RunOptions:
    """All step-level specialization choices, bundled.

    Populated from Iridescent spec points by the step builders; every field
    is a compile-time constant of the specialized variant.
    """

    kernels: KernelOptions = KernelOptions()
    moe: MoEOptions = MoEOptions()
    remat: str = "none"              # none | dots | full
    scan_layers: bool = True
    window: int | None = None        # sliding-window override (long-context)
    logits_dtype: str = "float32"
    decode_cache_dtype: str = "bfloat16"


# -- per-layer params ------------------------------------------------------------

def _init_mixer(key, cfg: ModelConfig) -> dict:
    if cfg.mixer == "rwkv6":
        return rwkv_mod.init_rwkv6(key, cfg)
    if cfg.mixer == "hymba":
        k1, k2 = jax.random.split(key)
        return {"attn": attn_mod.init_gqa(k1, cfg),
                "ssm": ssm_mod.init_ssm(k2, cfg),
                "norm_a": jnp.ones((cfg.d_model,), jnp.float32),
                "norm_s": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.attn_kind == "mla":
        return mla_mod.init_mla(key, cfg)
    return attn_mod.init_gqa(key, cfg)


def _mixer_axes(cfg: ModelConfig) -> dict:
    if cfg.mixer == "rwkv6":
        return rwkv_mod.rwkv6_axes(cfg)
    if cfg.mixer == "hymba":
        return {"attn": attn_mod.gqa_axes(cfg), "ssm": ssm_mod.ssm_axes(cfg),
                "norm_a": (None,), "norm_s": (None,)}
    if cfg.attn_kind == "mla":
        return mla_mod.mla_axes(cfg)
    return attn_mod.gqa_axes(cfg)


def _init_layer(key, cfg: ModelConfig, moe: bool) -> dict:
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    p = {"norm1": jnp.ones((d,), jnp.float32),
         "mixer": _init_mixer(k1, cfg),
         "norm2": jnp.ones((d,), jnp.float32)}
    if cfg.mixer == "rwkv6":
        pass  # channel-mix params live inside the mixer dict
    elif moe:
        p["moe"] = moe_mod.init_moe(k2, cfg)
    else:
        k21, k22, k23 = jax.random.split(k2, 3)
        p["ffn"] = {"wg": dense_init(k21, (d, cfg.d_ff)),
                    "wu": dense_init(k22, (d, cfg.d_ff)),
                    "wd": dense_init(k23, (cfg.d_ff, d))}
    return p


def _layer_axes(cfg: ModelConfig, moe: bool) -> dict:
    ax = {"norm1": (None,), "mixer": _mixer_axes(cfg), "norm2": (None,)}
    if cfg.mixer == "rwkv6":
        pass
    elif moe:
        ax["moe"] = moe_mod.moe_axes(cfg)
    else:
        ax["ffn"] = {"wg": ("fsdp", "ffn"), "wu": ("fsdp", "ffn"),
                     "wd": ("ffn", "fsdp")}
    return ax


def _stack_axes(ax: dict) -> dict:
    """Prefix every leaf axes tuple with the stacked 'layers' dim."""
    return jax.tree_util.tree_map(lambda t: ("layers",) + t, ax,
                                  is_leaf=lambda x: isinstance(x, tuple))


def init_params(key, cfg: ModelConfig) -> dict:
    kd, km, ke, kh = jax.random.split(key, 4)
    n_moe = cfg.n_moe_layers
    n_dense = cfg.n_layers - n_moe
    p: dict[str, Any] = {
        "embed": embed_init(ke, (cfg.padded_vocab_size, cfg.d_model)),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if n_dense:
        keys = jax.random.split(kd, n_dense)
        p["dense_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, moe=False))(keys)
    if n_moe:
        keys = jax.random.split(km, n_moe)
        p["moe_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, moe=True))(keys)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(kh, (cfg.d_model, cfg.padded_vocab_size))
    return p


def param_axes(cfg: ModelConfig) -> dict:
    n_moe = cfg.n_moe_layers
    n_dense = cfg.n_layers - n_moe
    ax: dict[str, Any] = {
        "embed": ("vocab", "embed"),
        "final_norm": (None,),
    }
    if n_dense:
        ax["dense_layers"] = _stack_axes(_layer_axes(cfg, moe=False))
    if n_moe:
        ax["moe_layers"] = _stack_axes(_layer_axes(cfg, moe=True))
    if not cfg.tie_embeddings:
        ax["lm_head"] = ("fsdp", "vocab")
    return ax


# -- forward ----------------------------------------------------------------------

def _apply_mixer(lp: dict, x: jnp.ndarray, cfg: ModelConfig,
                 opts: RunOptions) -> jnp.ndarray:
    ko = opts.kernels
    if cfg.mixer == "rwkv6":
        return rwkv_mod.apply_rwkv6(lp, x, cfg, ko)
    if cfg.mixer == "hymba":
        window = opts.window if opts.window is not None else cfg.window
        a = attn_mod.apply_gqa(lp["attn"], x, cfg, ko, window=window)
        s = ssm_mod.apply_ssm(lp["ssm"], x, cfg, ko)
        a = rms_norm(a, lp["norm_a"], cfg.rms_eps, ko)
        s = rms_norm(s, lp["norm_s"], cfg.rms_eps, ko)
        return 0.5 * (a + s)
    if cfg.attn_kind == "mla":
        return mla_mod.apply_mla(lp, x, cfg, ko, window=opts.window)
    return attn_mod.apply_gqa(lp, x, cfg, ko, window=opts.window)


def _apply_ffn(lp: dict, x: jnp.ndarray, cfg: ModelConfig, opts: RunOptions,
               moe: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.mixer == "rwkv6":
        return rwkv_mod.apply_rwkv6_channel_mix(lp["mixer"], x, cfg), 0.0
    if moe:
        return moe_mod.apply_moe(lp["moe"], x, cfg, opts.moe)
    f = lp["ffn"]
    cdt = x.dtype
    return swiglu(x, f["wg"].astype(cdt), f["wu"].astype(cdt),
                  f["wd"].astype(cdt)), 0.0


def _layer_fwd(lp: dict, x: jnp.ndarray, cfg: ModelConfig, opts: RunOptions,
               moe: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    ko = opts.kernels
    h = _apply_mixer(lp["mixer"] if cfg.mixer != "rwkv6" else lp["mixer"],
                     rms_norm(x, lp["norm1"], cfg.rms_eps, ko), cfg, opts)
    x = x + h
    f, aux = _apply_ffn(lp, rms_norm(x, lp["norm2"], cfg.rms_eps, ko),
                        cfg, opts, moe)
    return x + f, aux


def _remat_wrap(fn: Callable, remat: str) -> Callable:
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(f"unknown remat policy {remat!r}")


def _run_stack(stacked: dict, x: jnp.ndarray, cfg: ModelConfig,
               opts: RunOptions, moe: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    body = _remat_wrap(
        functools.partial(_layer_fwd, cfg=cfg, opts=opts, moe=moe),
        opts.remat)
    n_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if opts.scan_layers:
        def scan_fn(carry, lp):
            xx, aux = carry
            xx, aux_i = body(lp, xx)
            return (xx, aux + aux_i), None
        (x, aux), _ = jax.lax.scan(scan_fn, (x, jnp.float32(0.0)), stacked)
        return x, aux
    aux = jnp.float32(0.0)
    for i in range(n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], stacked)
        x, aux_i = body(lp, x)
        aux = aux + aux_i
    return x, aux


def apply(params: dict, cfg: ModelConfig, opts: RunOptions,
          tokens: jnp.ndarray | None = None,
          embeds: jnp.ndarray | None = None,
          return_hidden: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.  Returns (logits (B,S,V), moe_aux scalar) —
    or (hidden (B,S,d), aux) with ``return_hidden`` (the chunked-loss path
    applies the LM head itself, chunk by chunk)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    if embeds is None:
        assert tokens is not None
        x = params["embed"].astype(cdt)[tokens]
    else:
        x = embeds.astype(cdt)
    x = constrain(x, ("batch", "seq", None))

    aux = jnp.float32(0.0)
    if "dense_layers" in params:
        x, a = _run_stack(params["dense_layers"], x, cfg, opts, moe=False)
        aux = aux + a
    if "moe_layers" in params:
        x, a = _run_stack(params["moe_layers"], x, cfg, opts, moe=True)
        aux = aux + a

    x = rms_norm(x, params["final_norm"], cfg.rms_eps, opts.kernels)
    if return_hidden:
        return x, aux
    head = lm_head_weight(params, cfg)
    logits = x @ head
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits.astype(jnp.dtype(opts.logits_dtype)), aux


def lm_head_weight(params: dict, cfg: ModelConfig) -> jnp.ndarray:
    cdt = jnp.dtype(cfg.compute_dtype)
    return (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cdt)


# -- decode ------------------------------------------------------------------------

def _cache_fns(cfg: ModelConfig):
    if cfg.mixer == "rwkv6":
        return rwkv_mod.init_rwkv6_cache, rwkv_mod.rwkv6_cache_axes
    if cfg.mixer == "hymba":
        def init(cfg_, b, max_len, window=None, dtype=jnp.bfloat16):
            return {
                "attn": attn_mod.init_gqa_cache(
                    cfg_, b, max_len,
                    window=window if window else cfg_.window, dtype=dtype),
                "ssm": ssm_mod.init_ssm_cache(cfg_, b, dtype=dtype),
            }

        def axes(cfg_):
            return {"attn": attn_mod.gqa_cache_axes(cfg_),
                    "ssm": ssm_mod.ssm_cache_axes(cfg_)}
        return init, axes
    if cfg.attn_kind == "mla":
        return mla_mod.init_mla_cache, mla_mod.mla_cache_axes
    return attn_mod.init_gqa_cache, attn_mod.gqa_cache_axes


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               opts: RunOptions | None = None) -> dict:
    opts = opts or RunOptions()
    init, _ = _cache_fns(cfg)
    dtype = jnp.dtype(opts.decode_cache_dtype)
    one = lambda: init(cfg, batch, max_len, window=opts.window, dtype=dtype)
    # stack per layer
    caches = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *[one() for _ in range(cfg.n_layers)])
    return caches


def cache_axes(cfg: ModelConfig) -> dict:
    _, axes = _cache_fns(cfg)
    return _stack_axes(axes(cfg))


def _layer_decode(lp: dict, lc: dict, x: jnp.ndarray, pos: jnp.ndarray,
                  cfg: ModelConfig, opts: RunOptions, moe: bool,
                  count_rows: jnp.ndarray | None = None):
    """One layer's decode step -> (x, cache, counts): ``counts`` is the MoE
    layer's int32 (held experts the ``count_rows`` picked, rows they
    computed) when ``count_rows`` is given, else zeros."""
    ko = opts.kernels
    counts = jnp.zeros((2,), jnp.int32)
    xin = rms_norm(x, lp["norm1"], cfg.rms_eps, ko)
    if cfg.mixer == "rwkv6":
        h, lc = rwkv_mod.decode_rwkv6(lp["mixer"], lc, xin, pos, cfg, ko)
    elif cfg.mixer == "hymba":
        window = opts.window if opts.window is not None else cfg.window
        ha, ca = attn_mod.decode_gqa(lp["mixer"]["attn"], lc["attn"], xin,
                                     pos, cfg, ko, window=window)
        hs, cs = ssm_mod.decode_ssm(lp["mixer"]["ssm"], lc["ssm"], xin, pos,
                                    cfg, ko)
        ha = rms_norm(ha, lp["mixer"]["norm_a"], cfg.rms_eps, ko)
        hs = rms_norm(hs, lp["mixer"]["norm_s"], cfg.rms_eps, ko)
        h, lc = 0.5 * (ha + hs), {"attn": ca, "ssm": cs}
    elif cfg.attn_kind == "mla":
        h, lc = mla_mod.decode_mla(lp["mixer"], lc, xin, pos, cfg, ko,
                                   window=opts.window)
    else:
        h, lc = attn_mod.decode_gqa(lp["mixer"], lc, xin, pos, cfg, ko,
                                    window=opts.window)
    x = x + h
    xin2 = rms_norm(x, lp["norm2"], cfg.rms_eps, ko)
    if cfg.mixer == "rwkv6":
        x_prev = lc["x_cm"][:, None].astype(xin2.dtype)
        f = rwkv_mod.apply_rwkv6_channel_mix(lp["mixer"], xin2, cfg,
                                             x_prev=x_prev)
        lc = dict(lc, x_cm=xin2[:, 0].astype(lc["x_cm"].dtype))
    else:
        f, counts = _cached_ffn(lp, xin2, cfg, opts, moe, count_rows, counts)
    return x + f, lc, counts


def _cached_ffn(lp: dict, x: jnp.ndarray, cfg: ModelConfig, opts: RunOptions,
                moe: bool, count_rows: jnp.ndarray | None,
                counts: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The FFN of a layer that serves from a cache (RWKV6's channel mix
    aside) -> (out, counts): the MoE layer's when ``count_rows`` is given,
    else ``counts`` as it came."""
    if moe and count_rows is not None:
        f, _, counts = moe_mod.apply_moe(lp["moe"], x, cfg, opts.moe,
                                         count_rows=count_rows)
    elif moe:
        f, _ = moe_mod.apply_moe(lp["moe"], x, cfg, opts.moe)
    else:
        ff = lp["ffn"]
        f = swiglu(x, ff["wg"].astype(x.dtype), ff["wu"].astype(x.dtype),
                   ff["wd"].astype(x.dtype))
    return f, counts


def _run_layers(params: dict, cache: dict, x: jnp.ndarray, cfg: ModelConfig,
                opts: RunOptions, layer: Callable, counting: bool):
    """x through the dense, then the MoE, layer stack beside its stacked
    cache -> (x, new cache, counts).  ``layer(lp, lc, x, moe, count)`` runs
    one layer -> (x, lc, int32 (2,) counts); ``count`` is set on the MoE
    layers when ``counting``, and their counts are summed."""
    n_moe = cfg.n_moe_layers
    n_dense = cfg.n_layers - n_moe

    def split_cache(c):
        if n_dense and n_moe:
            head = jax.tree_util.tree_map(lambda a: a[:n_dense], c)
            tail = jax.tree_util.tree_map(lambda a: a[n_dense:], c)
            return head, tail
        return (c, None) if n_dense else (None, c)

    dense_cache, moe_cache = split_cache(cache)
    new_caches = []
    counts = jnp.zeros((2,), jnp.int32)

    def run(stacked, lcache, moe):
        # the carry holds the running counts only where they are asked
        # for and counted: every other step keeps its program
        count = counting and moe

        def scan_fn(carry, pc):
            lp, lcc = pc
            xx, cc = carry if count else (carry, None)
            xx, lcc, c = layer(lp, lcc, xx, moe, count)
            return ((xx, cc + c) if count else xx), lcc
        carry = (x_cur, counts) if count else x_cur
        if opts.scan_layers:
            carry, stacked_out = jax.lax.scan(scan_fn, carry,
                                              (stacked, lcache))
        else:
            outs = []
            n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
            for i in range(n):
                lp = jax.tree_util.tree_map(lambda a: a[i], stacked)
                lcc = jax.tree_util.tree_map(lambda a: a[i], lcache)
                carry, lcc = scan_fn(carry, (lp, lcc))
                outs.append(lcc)
            stacked_out = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                                 *outs)
        return (carry if count else (carry, counts)), stacked_out

    x_cur = x
    if n_dense:
        (x_cur, counts), dc = run(params["dense_layers"], dense_cache,
                                  moe=False)
        new_caches.append(dc)
    if n_moe:
        (x_cur, counts), mc = run(params["moe_layers"], moe_cache, moe=True)
        new_caches.append(mc)
    if len(new_caches) == 2:
        new_cache = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], 0), *new_caches)
    else:
        new_cache = new_caches[0]
    return x_cur, new_cache, counts


def decode_step(params: dict, cache: dict, tokens: jnp.ndarray,
                pos: jnp.ndarray, cfg: ModelConfig,
                opts: RunOptions, count_rows: jnp.ndarray | None = None):
    """One decode step. tokens (B,) int32, pos scalar -> (logits (B,V), cache).

    With ``count_rows``, a (B,) bool mask (MoE on the ``dense`` path), a
    third output, int32 ``(2,)`` summed over the MoE layers: the held
    experts those rows picked, and the rows the held experts computed."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(cdt)[tokens][:, None]      # (B,1,d)
    x = constrain(x, ("batch", None, None))

    def layer(lp, lc, xx, moe, count):
        return _layer_decode(lp, lc, xx, pos, cfg, opts, moe,
                             count_rows if count else None)
    x_cur, new_cache, counts = _run_layers(params, cache, x, cfg, opts,
                                           layer, count_rows is not None)

    xf = rms_norm(x_cur, params["final_norm"], cfg.rms_eps, opts.kernels)
    head = lm_head_weight(params, cfg)
    logits = (xf[:, 0] @ head).astype(jnp.float32)
    if count_rows is not None:
        return logits[:, : cfg.vocab_size], new_cache, counts
    return logits[:, : cfg.vocab_size], new_cache


def _select_rows(cfg: ModelConfig, active: jnp.ndarray, new_cache: dict,
                 old_cache: dict) -> dict:
    """Per-leaf batch-row select: active rows take the new cache, inactive
    rows keep the old.  Leaf batch axes are located via ``cache_axes`` so
    this is generic across mixers (attention KV, recurrent row state);
    leaves without a batch axis (shared maps) pass through new."""
    axes_leaves = jax.tree_util.tree_leaves(
        cache_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    new_leaves, treedef = jax.tree_util.tree_flatten(new_cache)
    old_leaves, _ = jax.tree_util.tree_flatten(old_cache)
    out = []
    for ln, lo, ax in zip(new_leaves, old_leaves, axes_leaves):
        ax = tuple(ax)
        if "batch" in ax:
            bi = ax.index("batch")
            m = active.reshape((1,) * bi + (-1,) + (1,) * (ln.ndim - bi - 1))
            out.append(jnp.where(m, ln, lo))
        else:
            out.append(ln)
    return jax.tree_util.tree_unflatten(treedef, out)


def _layer_major(cfg: ModelConfig, opts: RunOptions) -> bool:
    """Whether :func:`prefill_chunk` can push a chunk through each layer
    at once: GQA, MLA and RWKV6 mixers have a chunk form, Hymba's SSM
    heads do not; MoE only on the dropless ``dense`` path, since capacity
    would make a chunk's drops differ from a token's."""
    if cfg.mixer == "hymba":
        return False
    return not cfg.is_moe or opts.moe.impl == "dense"


def _layer_chunk(lp: dict, lc: dict, x: jnp.ndarray, pos: jnp.ndarray,
                 n_new: jnp.ndarray, cfg: ModelConfig, opts: RunOptions,
                 moe: bool, count_rows: jnp.ndarray | None = None):
    """One layer over a prompt chunk x (B,C,d) -> (x, cache, counts): the
    chunk forms of :func:`_layer_decode`'s mixers, every other part over
    the B*C rows at once."""
    ko = opts.kernels
    counts = jnp.zeros((2,), jnp.int32)
    xin = rms_norm(x, lp["norm1"], cfg.rms_eps, ko)
    if cfg.mixer == "rwkv6":
        h, lc = rwkv_mod.prefill_rwkv6(lp["mixer"], lc, xin, n_new, cfg, ko)
    elif cfg.attn_kind == "mla":
        h, lc = mla_mod.prefill_mla(lp["mixer"], lc, xin, pos, n_new, cfg,
                                    ko, window=opts.window)
    else:
        h, lc = attn_mod.prefill_gqa(lp["mixer"], lc, xin, pos, n_new, cfg,
                                     ko, window=opts.window)
    x = x + h
    xin2 = rms_norm(x, lp["norm2"], cfg.rms_eps, ko)
    if cfg.mixer == "rwkv6":
        f, lc = rwkv_mod.prefill_rwkv6_channel_mix(lp["mixer"], lc, xin2,
                                                   n_new, cfg)
    else:
        f, counts = _cached_ffn(lp, xin2, cfg, opts, moe, count_rows, counts)
    return x + f, lc, counts


def _prefill_layers(params: dict, cache: dict, tokens: jnp.ndarray,
                    pos: jnp.ndarray, n_new: jnp.ndarray, cfg: ModelConfig,
                    opts: RunOptions, counters: bool):
    """Layer-major chunked prefill: the (B,C) chunk goes through the layer
    stack once, as (B,C,d); logits are taken at each row's last token."""
    c = tokens.shape[1]
    cdt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"].astype(cdt)[tokens]               # (B,C,d)
    x = constrain(x, ("batch", None, None))
    live = jnp.arange(c)[None] < n_new[:, None]           # (B,C)

    def layer(lp, lc, xx, moe, count):
        return _layer_chunk(lp, lc, xx, pos, n_new, cfg, opts, moe,
                            live if count else None)
    x, new_cache, counts = _run_layers(params, cache, x, cfg, opts, layer,
                                       counters)

    at = jnp.clip(n_new - 1, 0)[:, None, None]
    x_last = jnp.take_along_axis(x, at, axis=1)           # (B,1,d)
    xf = rms_norm(x_last, params["final_norm"], cfg.rms_eps, opts.kernels)
    head = lm_head_weight(params, cfg)
    logits = (xf[:, 0] @ head).astype(jnp.float32)[:, : cfg.vocab_size]
    logits = jnp.where((n_new > 0)[:, None], logits, 0.0)
    return (logits, new_cache, counts) if counters else (logits, new_cache)


def _prefill_scan(params: dict, cache: dict, tokens: jnp.ndarray,
                  pos: jnp.ndarray, n_new: jnp.ndarray, cfg: ModelConfig,
                  opts: RunOptions, counters: bool):
    """Token-major chunked prefill: a ``lax.scan`` of single-token
    vector-pos decode steps with per-row masking, correct for every mixer:
    attention writes land at per-row positions (out-of-range rows write
    nothing), and recurrent state only advances while a row is active
    (:func:`_select_rows`)."""
    b, c = tokens.shape

    def step(carry, xs):
        cache_c, logits_c, *counts_c = carry
        tok_t, t = xs
        lg, stepped, *counts = decode_step(
            params, cache_c, tok_t, pos + t, cfg, opts,
            (t < n_new) if counters else None)
        cache_c = _select_rows(cfg, t < n_new, stepped, cache_c)
        logits_c = jnp.where((t == n_new - 1)[:, None], lg, logits_c)
        return (cache_c, logits_c,
                *[a + b for a, b in zip(counts_c, counts)]), None

    logits0 = jnp.zeros((b, cfg.vocab_size), jnp.float32)
    counts0 = [jnp.zeros((2,), jnp.int32)] if counters else []
    out, _ = jax.lax.scan(
        step, (cache, logits0, *counts0),
        (tokens.T, jnp.arange(c, dtype=jnp.int32)))
    cache, logits, *counts = out
    return (logits, cache, *counts)


def prefill_chunk(params: dict, cache: dict, tokens: jnp.ndarray,
                  pos: jnp.ndarray, n_new: jnp.ndarray, cfg: ModelConfig,
                  opts: RunOptions, counters: bool = False):
    """Chunked prefill: consume up to C prompt tokens per row.

    ``tokens (B,C)`` int32 (pad with any valid id), ``pos (B,)`` per-row
    start positions, ``n_new (B,)`` valid token counts (<= C; rows may
    differ — a short row goes inactive once its tokens are consumed).
    Returns ``(logits (B,V) at each row's last consumed token, cache)``;
    rows with ``n_new == 0`` get zero logits.  ``counters``: as
    :func:`decode_step`'s, over the chunk's consumed tokens.

    Two paths, one compiled program per (bucket, C) either way, chosen at
    trace time from the layer type (:func:`_layer_major`):

    * layer-major (GQA, MLA, RWKV6; MoE on the dropless ``dense`` path):
      the chunk goes through each layer once, as (B,C,d) — weights applied
      as matmuls over B*C rows, the chunk's keys/latents written in one
      scatter per leaf, its C queries scored against the whole cache, the
      wkv recurrence in its chunked form from the cached state;
    * token-major (Hymba, and MoE on a capacity path, where a chunk's
      drops would differ from a token's): :func:`_prefill_scan`, C
      single-token decode steps — the reference the other is tested
      against.
    """
    run = _prefill_layers if _layer_major(cfg, opts) else _prefill_scan
    return run(params, cache, tokens, pos, n_new, cfg, opts, counters)
