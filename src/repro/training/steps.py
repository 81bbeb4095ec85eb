"""Step builders: where the framework's Iridescent spec points live.

Each builder is *handler code* in the paper's sense: it declares
specialization points through the :class:`SpecCtx` it receives and returns
the step function.  Re-building under a different configuration bakes
different constants (tile sizes, remat policy, microbatch count, MoE
dispatch implementation, sharding profile, ...) into the traced program —
XLA's cascading optimizations then do for us what LLVM O3 does in the paper.

The step functions are pure (state in, state out), so the paper's guard
fall-back story is trivially safe here: a guard miss just re-dispatches the
same inputs to the generic variant.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core.specializer import SpecCtx
from repro.distributed.sharding import (DEFAULT_RULES, ShardingRules,
                                        constrain, mesh_context,
                                        spec_for_axes)
from repro.kernels import registry as kernel_registry
from repro.models import (KernelOptions, ModelConfig, MoEOptions, RunOptions)
from repro.models import transformer as model
from repro.optim import OptConfig, apply_updates, init_opt_state

__all__ = ["SHARDING_PROFILES", "SERVE_SEARCH_POINTS", "make_train_builder",
           "make_prefill_builder", "make_decode_builder", "make_serve_builder",
           "phase_context_fn", "run_options_from_spec", "cross_entropy",
           "chunked_cross_entropy"]


# -- sharding profiles (layout specialization points) ---------------------------

def _profile_dp(base: ShardingRules) -> ShardingRules:
    """Pure DP: params replicated (generic; only fits small models)."""
    return base.replace(fsdp=None, expert_fsdp=None, ffn="model",
                        heads="model", vocab="model", experts="model")


def _profile_fsdp(base: ShardingRules) -> ShardingRules:
    """ZeRO-3 over data axis + TP over model axis (the sane default)."""
    return base


def _profile_fsdp_pods(base: ShardingRules) -> ShardingRules:
    """ZeRO-3 over data AND pod axes (max memory savings, DCN gathers)."""
    return base.replace(fsdp=("pod", "data"))


def _profile_seq(base: ShardingRules) -> ShardingRules:
    """Sequence parallelism: long-context activations sharded over model."""
    return base.replace(seq="model")


def _profile_fsdp_noexp(base: ShardingRules) -> ShardingRules:
    """FSDP for dense params; expert weights sharded over experts(model)
    only — kills the per-layer expert-weight all-gathers at the cost of
    E/|model| experts resident per device."""
    return base.replace(expert_fsdp=None)


def _profile_serve_ep(base: ShardingRules) -> ShardingRules:
    """Inference layout: no FSDP (nothing re-gathered per token); dense
    params TP over model; experts sharded experts->data x inner-dim->model,
    so decode dispatch moves activations (KBs) instead of weights (GBs)."""
    return base.replace(fsdp=None, experts=("pod", "data"),
                        expert_fsdp="model", expert_cap=None,
                        moe_groups=None)


SHARDING_PROFILES: dict[str, Callable[[ShardingRules], ShardingRules]] = {
    "dp": _profile_dp,
    "fsdp": _profile_fsdp,
    "fsdp_pods": _profile_fsdp_pods,
    "fsdp_noexp": _profile_fsdp_noexp,
    "seq": _profile_seq,
    "serve_ep": _profile_serve_ep,
}


# -- spec-point bundles ----------------------------------------------------------

def run_options_from_spec(spec: SpecCtx, cfg: ModelConfig, *,
                          kernel_impl: str | None = None,
                          scan_layers: bool = True,
                          window: int | None = None,
                          for_decode: bool = False,
                          differentiable: bool = False,
                          dropless: bool = False) -> RunOptions:
    """Declare the model-level spec points and bundle the chosen constants.

    ``dropless``: MoE takes the ``dense`` path, which drops no token, and
    declares no MoE point (capacity and grouping change which tokens an
    expert sees, so they change results)."""
    # Implementation choice per kernel family the step exercises: the
    # candidate set is the registry entries *available on this host*, so the
    # policy only ever explores implementations that can run here; a choice
    # that still guard-misses at dispatch degrades to xla_ref inside the
    # registry (paper §4.4.3).  Differentiated steps (training) further
    # restrict to entries jax.grad can flow through.
    uses_attention = cfg.mixer in ("attn", "hymba")
    uses_linear_attention = cfg.mixer in ("rwkv6", "hymba")
    grad = differentiable
    ko = KernelOptions(
        impl=kernel_impl,
        rmsnorm_impl=kernel_registry.impl_point(spec, "rmsnorm",
                                                default=kernel_impl,
                                                require_grad=grad),
        attention_impl=(kernel_registry.impl_point(spec, "attention",
                                                   default=kernel_impl,
                                                   require_grad=grad)
                        if uses_attention else None),
        linear_attention_impl=(
            kernel_registry.impl_point(spec, "linear_attention",
                                       default=kernel_impl,
                                       require_grad=grad)
            if uses_linear_attention else None),
        block_q=spec.enum("block_q", 512, (128, 256, 512, 1024),
                          guarded=False),
        block_kv=spec.enum("block_kv", 512, (128, 256, 512, 1024),
                           guarded=False),
        norm_block_rows=spec.enum("norm_block_rows", 256, (128, 256, 512),
                                  guarded=False),
        chunk_len=(spec.enum("chunk_len", 64, (16, 32, 64), guarded=False)
                   if cfg.mixer in ("rwkv6", "hymba") else 64),
        swa_impl=(spec.enum("swa_impl", "full", ("full", "banded"),
                            guarded=False)
                  if (cfg.window or window) else "full"),
    )
    if cfg.is_moe and dropless:
        moe = MoEOptions(impl="dense")
    elif cfg.is_moe:
        moe = MoEOptions(
            impl=spec.enum("moe_impl", "einsum",
                           ("einsum", "gather", "shard"), guarded=False),
            capacity_factor=spec.enum("capacity_factor", 1.25,
                                      (1.0, 1.25, 1.5, 2.0), guarded=False),
            group_size=spec.enum("moe_group", 0, (0, 1024, 4096),
                                 guarded=False),
            ranking=spec.enum("moe_ranking", "cumsum", ("cumsum", "sort"),
                              guarded=False),
        )
    else:
        moe = MoEOptions()
    remat = (spec.enum("remat", "none", ("none", "dots", "full"),
                       guarded=False) if not for_decode else "none")
    return RunOptions(
        kernels=ko, moe=moe, remat=remat, scan_layers=scan_layers,
        window=window,
        logits_dtype=spec.enum("logits_dtype", "float32",
                               ("float32", "bfloat16"), guarded=False),
    )


def _rules_from_spec(spec: SpecCtx, default: str = "fsdp") -> ShardingRules:
    profile = spec.enum("sharding_profile", default,
                        tuple(SHARDING_PROFILES), guarded=False)
    return SHARDING_PROFILES[profile](DEFAULT_RULES)


# -- loss --------------------------------------------------------------------------

def chunked_cross_entropy(hidden: jnp.ndarray, head: jnp.ndarray,
                          labels: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Token CE without materializing the full (B,S,V) fp32 logits.

    The LM head matmul and the fp32 log-sum-exp run per sequence chunk, so
    peak logits memory is (B, chunk, V) — the beyond-paper fix for the
    big-vocab memory-bound cells (minitron 256k, qwen3 152k).  Exact same
    math as :func:`cross_entropy` (allclose-tested).
    """
    b, s, d = hidden.shape
    assert s % chunk == 0, (s, chunk)
    total = jnp.float32(0.0)
    count = jnp.float32(0.0)
    for i in range(s // chunk):
        h = jax.lax.dynamic_slice_in_dim(hidden, i * chunk, chunk, axis=1)
        lab = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        lg = (h @ head).astype(jnp.float32)
        lg = constrain(lg, ("batch", "seq", "vocab"))
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(
            lg, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        total = total + jnp.sum((lse - ll) * mask)
        count = count + mask.sum()
    return total / jnp.maximum(count, 1.0)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  gather_logits: bool = False) -> jnp.ndarray:
    """Token CE, mean over valid (label >= 0) positions.

    ``gather_logits=False`` keeps logits vocab-sharded through the loss
    (max/lse reductions lower to small all-reduces instead of an all-gather
    of the full (B,S,V) tensor — the ``logits_layout`` spec point).
    """
    if gather_logits:
        logits = constrain(logits, ("batch", "seq", None))
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(
        lg, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)


# -- train ------------------------------------------------------------------------

def make_train_builder(
    cfg: ModelConfig,
    opt_cfg: OptConfig,
    mesh=None,
    *,
    kernel_impl: str | None = None,
    scan_layers: bool = True,
    window: int | None = None,
) -> Callable[[SpecCtx], Callable]:
    """Returns the handler builder for ``train_step(state, batch)``.

    state = {"params": ..., "opt": ...}; batch = {"tokens"/"embeds",
    "labels"}.  All spec points are internal tuning parameters (any value is
    correct for every workload), so none carry guards — exactly the paper's
    block-size situation in §2.1.
    """

    def builder(spec: SpecCtx) -> Callable:
        opts = run_options_from_spec(spec, cfg, kernel_impl=kernel_impl,
                                     scan_layers=scan_layers, window=window,
                                     differentiable=True)
        micro = spec.enum("microbatch", 1, (1, 2, 4), guarded=False)
        gather_logits = spec.enum("logits_layout", "sharded",
                                  ("sharded", "gathered"),
                                  guarded=False) == "gathered"
        loss_chunk = spec.enum("loss_chunk", 0, (0, 16, 256, 512, 1024),
                               guarded=False)   # 0 = unchunked (generic)
        rules = _rules_from_spec(spec)

        def loss_fn(params, batch):
            if loss_chunk:
                hidden, aux = model.apply(
                    params, cfg, opts, tokens=batch.get("tokens"),
                    embeds=batch.get("embeds"), return_hidden=True)
                head = model.lm_head_weight(params, cfg)
                return chunked_cross_entropy(
                    hidden, head, batch["labels"], loss_chunk) + aux
            logits, aux = model.apply(
                params, cfg, opts,
                tokens=batch.get("tokens"), embeds=batch.get("embeds"))
            return cross_entropy(logits, batch["labels"], gather_logits) + aux

        def train_step(state, batch):
            with mesh_context(mesh, rules):
                ax = model.param_axes(cfg)
                params = _constrain_tree(state["params"], ax)

                def micro_slice(tree, i):
                    return jax.tree_util.tree_map(
                        lambda x: x.reshape((micro, -1) + x.shape[1:])[i],
                        tree)

                grads = None
                loss_total = jnp.float32(0.0)
                for i in range(micro):
                    mb = micro_slice(batch, i) if micro > 1 else batch
                    li, gi = jax.value_and_grad(loss_fn)(params, mb)
                    gi = jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.float32), gi)
                    grads = gi if grads is None else jax.tree_util.tree_map(
                        jnp.add, grads, gi)
                    loss_total = loss_total + li
                if micro > 1:
                    grads = jax.tree_util.tree_map(
                        lambda g: g / micro, grads)
                grads = _constrain_tree(grads, ax)
                new_params, new_opt = apply_updates(
                    params, grads, state["opt"], opt_cfg)
                new_params = _constrain_tree(new_params, ax)
                metrics = {"loss": loss_total / micro}
                return {"params": new_params, "opt": new_opt}, metrics

        return train_step

    return builder


def _constrain_tree(tree, axes_tree):
    return jax.tree_util.tree_map(
        lambda p, a: constrain(p, a), tree, axes_tree,
        is_leaf=lambda x: x is None)


# -- serving -----------------------------------------------------------------------

def make_prefill_builder(
    cfg: ModelConfig,
    mesh=None,
    *,
    kernel_impl: str | None = None,
    scan_layers: bool = True,
    window: int | None = None,
) -> Callable[[SpecCtx], Callable]:
    """Handler builder for ``prefill_step(params, batch) -> logits``."""

    def builder(spec: SpecCtx) -> Callable:
        opts = run_options_from_spec(spec, cfg, kernel_impl=kernel_impl,
                                     scan_layers=scan_layers, window=window,
                                     for_decode=True)
        rules = _rules_from_spec(spec)

        def prefill_step(params, batch):
            with mesh_context(mesh, rules):
                params = _constrain_tree(params, model.param_axes(cfg))
                logits, _ = model.apply(
                    params, cfg, opts,
                    tokens=batch.get("tokens"), embeds=batch.get("embeds"))
                return logits

        return prefill_step

    return builder


def make_decode_builder(
    cfg: ModelConfig,
    mesh=None,
    *,
    kernel_impl: str | None = None,
    scan_layers: bool = True,
    window: int | None = None,
) -> Callable[[SpecCtx], Callable]:
    """Handler builder for ``serve_step(params, cache, tokens, pos)``.

    One new token for the whole batch against the KV/state cache.
    """

    def builder(spec: SpecCtx) -> Callable:
        opts = run_options_from_spec(spec, cfg, kernel_impl=kernel_impl,
                                     scan_layers=scan_layers, window=window,
                                     for_decode=True)
        opts = RunOptions(**{**opts.__dict__, "decode_cache_dtype": spec.enum(
            "cache_dtype", "bfloat16", ("bfloat16", "float32"),
            guarded=False)})
        rules = _rules_from_spec(spec)
        # Cache partitioning: shard the KV/latent cache's sequence dim over
        # the model axis (kv head counts are rarely divisible by 16-way TP).
        cache_layout = spec.enum("cache_layout", "seq", ("seq", "batch"),
                                 guarded=False)
        if cache_layout == "seq":
            rules = rules.replace(seq_kv="model")

        def serve_step(params, cache, tokens, pos):
            with mesh_context(mesh, rules):
                params = _constrain_tree(params, model.param_axes(cfg))
                cache = _constrain_tree(cache, model.cache_axes(cfg))
                logits, new_cache = model.decode_step(
                    params, cache, tokens, pos, cfg, opts)
                return logits, new_cache

        return serve_step

    return builder


def phase_context_fn(args, kwargs) -> tuple[str, int]:
    """Context key for the phase-disaggregated serve handler:
    ``(phase, bucket)``.  The phase is read off the token rank at dispatch
    time — ``(B, C)`` is a chunked-prefill step, ``(B,)`` a decode step —
    so prefill and decode traffic land in *separate* specialization
    contexts of the same handler, each with its own dispatch snapshot and
    its own Controller search."""
    tokens = args[2]
    phase = "prefill" if getattr(tokens, "ndim", 1) == 2 else "decode"
    return (phase, int(tokens.shape[0]))


#: The serve step's searched spec points: each one changes the program the
#: step lowers to.  The step declares no cache dtype (its cache's is the
#: template's), and ``chunk_len`` is not searched: at the default prefill
#: chunk of 16 every choice lowers to the same program.
SERVE_SEARCH_POINTS = ("rmsnorm_impl",)


def make_serve_builder(
    cfg: ModelConfig,
    mesh=None,
    *,
    kernel_impl: str | None = None,
    scan_layers: bool = True,
    window: int | None = None,
) -> Callable[[SpecCtx], Callable]:
    """Handler builder for the phase-disaggregated
    ``serve_step(params, cache, tokens, pos, n_new)``.

    One registered handler serves both phases, branching at *trace* time
    on the token rank: ``tokens (B,)`` runs one vector-pos decode step,
    ``tokens (B, C)`` runs a chunked prefill
    (:func:`repro.models.transformer.prefill_chunk`).  Register it with
    ``context_fn=phase_context_fn`` and the two phases become separate
    ``(phase, bucket)`` specialization contexts sharing one variant
    cache — the Controller is free to discover that prefill and decode
    want different configs.

    ``pos (B,)`` is each row's write position (contiguous per-request
    cache semantics — the paged KV manager's materialized lengths);
    ``n_new (B,)`` the valid token count per row (at decode 1 for a real
    row and 0 for padding, read only by the MoE counters).  Returns
    ``(logits (B, V), new cache)``, and for MoE configs a third output:
    ``{"expert_rows_routed", "expert_rows_computed"}``, int32 scalars
    summed over the MoE layers (and a prefill chunk's tokens): the held
    experts the valid tokens picked, and the rows the held experts
    computed, padding included.  MoE runs dropless: no spec point changes
    what is served.
    """

    def builder(spec: SpecCtx) -> Callable:
        opts = run_options_from_spec(spec, cfg, kernel_impl=kernel_impl,
                                     scan_layers=scan_layers, window=window,
                                     for_decode=True, dropless=True)
        rules = _rules_from_spec(spec)
        cache_layout = spec.enum("cache_layout", "seq", ("seq", "batch"),
                                 guarded=False)
        if cache_layout == "seq":
            rules = rules.replace(seq_kv="model")

        def serve_step(params, cache, tokens, pos, n_new):
            with mesh_context(mesh, rules):
                params = _constrain_tree(params, model.param_axes(cfg))
                cache = _constrain_tree(cache, model.cache_axes(cfg))
                if tokens.ndim == 2:
                    out = model.prefill_chunk(params, cache, tokens, pos,
                                              n_new, cfg, opts, cfg.is_moe)
                else:
                    out = model.decode_step(
                        params, cache, tokens, pos, cfg, opts,
                        (n_new > 0) if cfg.is_moe else None)
                if not cfg.is_moe:
                    return out
                logits, new_cache, counts = out
                return logits, new_cache, {"expert_rows_routed": counts[0],
                                           "expert_rows_computed": counts[1]}

        return serve_step

    return builder
