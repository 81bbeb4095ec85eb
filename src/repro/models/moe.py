"""Mixture-of-Experts FFN (kimi-k2 384e/top-8, deepseek-v2 160e/top-6 + 2
shared), with the dispatch implementation as an Iridescent spec point.

Four dispatch implementations — einsum/gather/dense share
:func:`assign_experts` (bit-comparable under equal capacity settings);
``shard`` uses per-data-shard capacity (standard EP semantics):

* ``"einsum"``  — one-hot dispatch/combine einsums (the classic TPU MoE of
  Shazeer et al. / MaxText's dense path).  MXU-heavy: the dispatch matmuls
  cost ``T*E*C*d`` FLOPs, typically >> the expert FFN FLOPs at large E.
  This is the paper-faithful *generic* implementation.
* ``"gather"``  — scatter/gather dispatch into per-expert capacity buffers.
  No dispatch matmul FLOPs — HLO compute approaches the 6*N_active*D model
  FLOPs.  This is the specialized implementation the online policy should
  discover (§Perf hillclimb #3).
* ``"dense"``   — every held expert computes every token, gated mask
  combine: no capacity, so no token is ever dropped and a token's output
  does not depend on its batch-mates.  The serving path (at decode the
  step's tokens are few, and each held expert's weights are read once
  whatever the rows); doubles as the correctness oracle (equals the others
  when capacity is unbounded).
* ``"shard"``   — explicit expert parallelism via ``shard_map``: tokens are
  data-sharded and therefore *replicated across the model axis*, so each
  model shard locally selects + computes the entries routed to its own
  E/|model| experts and the partial outputs combine with ONE TP-style psum
  per layer.  Zero dispatch collectives (the §Perf A endgame).  Under FSDP
  profiles the entry constraint doubles as the per-layer bf16 weight
  gather (optimizer states stay data-sharded); gracefully degrades to
  ``gather`` when no mesh/model axis is active.  Capacity semantics are
  per-(data-shard, expert), the standard EP form.

Capacity factor and group size are further spec points; expert weights are
sharded over the ``model`` axis (EP) and tokens over ``data``, so dispatch
lowers to all-to-all style collectives under GSPMD.

Every path routes through the gate of :func:`route`: softmax scores over
all ``n_experts``, group-limited top-k, then renormalized or scaled.  A
config may hold only some of the experts (``first_expert``,
``n_experts_held``: one chip's share under expert parallelism): the router
still scores all of them, and the layer returns the part of the result
its held experts give, picks of the others counting as dropped.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.distributed.sharding import constrain, current_mesh
from repro.models.common import dense_init
from repro.models.config import ModelConfig

__all__ = ["init_moe", "moe_axes", "apply_moe", "assign_experts", "route",
           "MoEOptions"]

import dataclasses


@dataclasses.dataclass(frozen=True)
class MoEOptions:
    """MoE spec-point bundle (populated by the step builder)."""

    impl: str = "gather"             # gather | einsum | dense
    capacity_factor: float = 1.25
    group_size: int = 0              # 0 = one group (whole shard)
    ranking: str = "cumsum"          # cumsum (classic one-hot) | sort
    aux_coef: float = 0.01


def init_moe(key, cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.held_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.n_experts)),
        "wg": dense_init(ks[1], (e, d, f), in_axis=1),
        "wu": dense_init(ks[2], (e, d, f), in_axis=1),
        "wd": dense_init(ks[3], (e, f, d), in_axis=1),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": dense_init(k1, (d, fs)),
            "wu": dense_init(k2, (d, fs)),
            "wd": dense_init(k3, (fs, d)),
        }
    return p


def moe_axes(cfg: ModelConfig) -> dict:
    ax = {
        "router": ("fsdp", None),
        "wg": ("experts", "expert_fsdp", "expert_ffn"),
        "wu": ("experts", "expert_fsdp", "expert_ffn"),
        "wd": ("experts", "expert_ffn", "expert_fsdp"),
    }
    if cfg.n_shared_experts:
        ax["shared"] = {"wg": ("fsdp", "ffn"), "wu": ("fsdp", "ffn"),
                        "wd": ("ffn", "fsdp")}
    return ax


def _rank_positions(flat_e: jnp.ndarray, e: int, ranking: str) -> jnp.ndarray:
    """Position of each (group, slot) entry within its (group, expert).

    flat_e (G, n) int32, token-major slot order.  Two equivalent
    formulations (a spec point — same result, wildly different cost):

    * ``cumsum``: cumulative sum over the one-hot (the classic TPU MoE
      formulation) — O(n*E) reduce-window work;
    * ``sort``: stable argsort by expert id + searchsorted — preserves
      token-major order within each expert, so positions are identical.
    """
    if ranking == "sort":
        def one(fe):
            n = fe.shape[0]
            order = jnp.argsort(fe, stable=True)
            sorted_e = fe[order]
            starts = jnp.searchsorted(sorted_e, jnp.arange(e))
            pos_sorted = (jnp.arange(n, dtype=jnp.int32)
                          - starts[sorted_e].astype(jnp.int32))
            return jnp.zeros(n, jnp.int32).at[order].set(pos_sorted)
        return jax.vmap(one)(flat_e)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)       # (G, n, E)
    pos_incl = jnp.cumsum(oh, axis=1)
    return jnp.take_along_axis(pos_incl, flat_e[..., None], -1)[..., 0] - 1


def route(logits: jnp.ndarray, top_k: int, *, n_group: int = 1,
          topk_group: int = 1, norm_topk_prob: bool = True,
          scale: float = 1.0):
    """The gate (hf ``DeepseekV2MoEGate``, softmax scoring): softmax over
    all experts; with ``topk_group < n_group`` only the ``topk_group``
    groups whose best score is highest stay eligible
    (``group_limited_greedy``); top-k of the eligible; the k weights are
    renormalized to sum to one, or with ``norm_topk_prob`` off multiplied
    by ``scale`` (``routed_scaling_factor``).

    logits (T, E).  Returns ``(probs (T, E), w (T, k), idx (T, k))``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    scores = probs
    if topk_group < n_group:
        t, e = probs.shape
        best = probs.reshape(t, n_group, e // n_group).max(-1)
        _, groups = jax.lax.top_k(best, topk_group)
        eligible = jax.nn.one_hot(groups, n_group, dtype=jnp.int32).sum(1)
        scores = jnp.where(jnp.repeat(eligible, e // n_group, axis=1) > 0,
                           probs, 0.0)
    w, idx = jax.lax.top_k(scores, top_k)                 # (T, k)
    if norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    else:
        w = w * scale
    return probs, w, idx


def _gate(logits: jnp.ndarray, cfg: ModelConfig):
    return route(logits, cfg.top_k, n_group=cfg.n_group,
                 topk_group=cfg.topk_group,
                 norm_topk_prob=cfg.norm_topk_prob,
                 scale=cfg.routed_scaling_factor)


def _held(idx: jnp.ndarray, first: int, held: int, n_experts: int):
    """Picks as indices into the held experts, and which picks are held.
    A pick of an expert held elsewhere gets the sentinel index ``held``;
    with every expert held, ``idx`` itself and None."""
    if held == n_experts:
        return idx, None
    mine = (idx >= first) & (idx < first + held)
    return jnp.where(mine, idx - first, held), mine


def assign_experts(logits: jnp.ndarray, top_k: int, n_experts: int,
                   capacity: int, group_size: int = 0,
                   ranking: str = "cumsum", cfg: ModelConfig | None = None):
    """Top-k routing with capacity-based dropping, shared by all impls.

    logits (T, E) fp32.  Returns dict with (T, k) expert ids (into the
    held experts) / combine weights / position-in-expert / keep mask,
    plus aux-loss terms.  Positions are assigned in token-major order
    within each group.  ``cfg`` gives the gate and the held experts;
    without it, softmax top-k renormalized over all ``n_experts``.
    """
    t, e = logits.shape
    if cfg is None:
        probs, w, idx = route(logits, top_k)
        first, held = 0, n_experts
    else:
        probs, w, idx = _gate(logits, cfg)
        first, held = cfg.first_expert, cfg.held_experts
    local, mine = _held(idx, first, held, e)

    g = group_size if group_size > 0 else t
    assert t % g == 0, (t, g)
    n_groups = t // g
    flat_e = local.reshape(n_groups, g * top_k)           # token-major slots
    pos = _rank_positions(flat_e, e if mine is None else held + 1,
                          ranking).reshape(t, top_k)
    keep = pos < capacity
    if mine is not None:
        keep &= mine

    # Switch-style load-balance aux loss terms.
    me = probs.mean(0)                                    # (E,)
    ce = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32).mean(0)
    aux = e * jnp.sum(me * ce)
    return {"idx": local, "w": w.astype(jnp.float32), "pos": pos,
            "keep": keep, "aux": aux}


def _expert_ffn(buf: jnp.ndarray, p: dict, cdt) -> jnp.ndarray:
    """buf (..., E, C, d) -> same; per-expert swiglu."""
    wg, wu, wd = (p["wg"].astype(cdt), p["wu"].astype(cdt),
                  p["wd"].astype(cdt))
    h = jax.nn.silu(jnp.einsum("...ecd,edf->...ecf", buf, wg)) \
        * jnp.einsum("...ecd,edf->...ecf", buf, wu)
    h = constrain(h, tuple([None] * (buf.ndim - 3))
                  + ("experts", None, "expert_ffn"))
    return jnp.einsum("...ecf,efd->...ecd", h, wd)


def _capacity(t: int, top_k: int, e: int, factor: float) -> int:
    """Per-expert capacity, rounded so the capacity dim is shardable over
    the data axes: the buffer (E, C, d) shards E->model and C->pod+data —
    an unsharded C would replicate every expert matmul across data shards."""
    c = max(1, math.ceil(t * top_k * factor / e))
    mult = 512 if c >= 512 else 16
    return -(-c // mult) * mult


def _shard_moe(p: dict, xf: jnp.ndarray, cfg: ModelConfig,
               opts: MoEOptions, mesh) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit-EP dispatch under shard_map (see module docstring)."""
    e, k = cfg.n_experts, cfg.top_k
    d = cfg.d_model
    cdt = xf.dtype
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    e_loc = e // mesh.shape["model"]

    def block(xl, router, wg, wu, wd):
        # xl (T_loc, d): this data shard's tokens (replicated over model);
        # wg/wu/wd (E_loc, d, f): this model shard's experts.
        t_loc = xl.shape[0]
        cap = _capacity(t_loc, k, e, opts.capacity_factor)
        logits = (xl @ router).astype(jnp.float32)
        probs, w, idx = _gate(logits, cfg)

        my = jax.lax.axis_index("model")
        base = my * e_loc
        flat_e = idx.reshape(-1)
        flat_w = w.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t_loc), k)
        local = (flat_e >= base) & (flat_e < base + e_loc)
        le = jnp.where(local, flat_e - base, e_loc)       # sentinel e_loc
        pos = _rank_positions(le[None], e_loc + 1, "sort")[0]
        keep = local & (pos < cap)
        dest = jnp.where(keep, le * cap + pos, e_loc * cap + 7)
        buf = jnp.zeros((e_loc * cap, d), cdt).at[dest].set(
            xl[flat_t], mode="drop")
        buf = buf.reshape(e_loc, cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
            * jnp.einsum("ecd,edf->ecf", buf, wu)
        hb = jnp.einsum("ecf,efd->ecd", h, wd).reshape(-1, d)
        gathered = jnp.take(hb, jnp.where(keep, dest, 0), axis=0)
        gathered = gathered * (flat_w.astype(cdt) * keep.astype(cdt))[:, None]
        out_partial = gathered.reshape(t_loc, k, d).sum(1)
        out = jax.lax.psum(out_partial, "model")          # the ONE collective

        me = probs.mean(0)
        ce = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32).mean(0)
        aux = e * jnp.sum(me * ce)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return out, aux

    dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    fn = shard_map(
        block, mesh=mesh,
        in_specs=(P(dp, None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(dp, None), P()),
        check_vma=False)
    # Params must arrive in the layout the specs promise.  Cast to compute
    # dtype BEFORE the constraint: under FSDP profiles this constraint IS
    # the per-layer weight gather, and bf16 halves the gathered bytes.
    router = jax.lax.with_sharding_constraint(
        p["router"].astype(cdt),
        jax.sharding.NamedSharding(mesh, P(None, None)))
    args = [jax.lax.with_sharding_constraint(
        p[n].astype(cdt),
        jax.sharding.NamedSharding(mesh, P("model", None, None)))
        for n in ("wg", "wu", "wd")]
    return fn(xf, router, *args)


def _shared(p: dict, xf: jnp.ndarray, out: jnp.ndarray) -> jnp.ndarray:
    """Add the shared experts' SwiGLU (every token, every chip)."""
    if "shared" not in p:
        return out
    cdt = xf.dtype
    with jax.named_scope("iri.moe.shared"):
        sh = p["shared"]
        hs = jax.nn.silu(xf @ sh["wg"].astype(cdt)) \
            * (xf @ sh["wu"].astype(cdt))
        hs = constrain(hs, ("batch", "ffn"))
        return out + hs @ sh["wd"].astype(cdt)


def apply_moe(p: dict, x: jnp.ndarray, cfg: ModelConfig,
              opts: MoEOptions, *, count_rows: jnp.ndarray | None = None):
    """x (B,S,d) -> (out (B,S,d), aux_loss scalar).

    ``count_rows`` (the ``dense`` path only), a bool mask over the B*S
    tokens, adds an int32 pair: the held experts those tokens picked, and
    the rows the held experts computed (every token's)."""
    b, s, d = x.shape
    e, k, eh = cfg.n_experts, cfg.top_k, cfg.held_experts
    cdt = x.dtype
    xf = x.reshape(b * s, d)
    t = b * s
    if count_rows is not None and opts.impl != "dense":
        raise ValueError(f"row counts are kept on the dense path, not "
                         f"{opts.impl!r}")

    impl = opts.impl
    if impl == "shard":
        mesh = current_mesh()
        if (mesh is None or "model" not in mesh.shape
                or e % mesh.shape["model"] != 0 or eh != e):
            impl = "gather"       # guarded degrade to the generic path
        else:
            out, aux = _shard_moe(p, xf, cfg, opts, mesh)
            out = _shared(p, xf, out)
            return out.reshape(b, s, d), aux * opts.aux_coef
    opts = dataclasses.replace(opts, impl=impl)

    with jax.named_scope("iri.moe.route"):
        logits = (xf @ p["router"].astype(cdt)).astype(jnp.float32)

    if opts.impl == "dense":
        with jax.named_scope("iri.moe.route"):
            probs, w, idx = _gate(logits, cfg)
            local, mine = _held(idx, cfg.first_expert, eh, e)
            width = eh if mine is None else eh + 1        # + sentinel
            full = jnp.zeros((t, width), jnp.float32).at[
                jnp.arange(t)[:, None], local].set(w)[:, :eh]   # (T, Eh)
        with jax.named_scope("iri.moe.experts"):
            buf = jnp.broadcast_to(xf[None], (eh, t, d))  # every held expert
            h = _expert_ffn(buf, p, cdt)                  # (Eh, T, d)
            out = jnp.einsum("te,etd->td", full.astype(cdt), h)
        me = probs.mean(0)
        ce = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32).mean(0)
        aux = e * jnp.sum(me * ce)
        if count_rows is not None:
            picks = jnp.broadcast_to(count_rows.reshape(t, 1), idx.shape)
            if mine is not None:
                picks &= mine
            counts = jnp.stack([jnp.sum(picks, dtype=jnp.int32),
                                jnp.int32(t * eh)])
    else:
        g = opts.group_size if opts.group_size > 0 else t
        cap_t = g if opts.group_size > 0 else t
        cap = _capacity(cap_t, k, e, opts.capacity_factor)
        with jax.named_scope("iri.moe.route"):
            a = assign_experts(logits, k, e, cap, opts.group_size,
                               opts.ranking, cfg)
        aux = a["aux"]
        if opts.impl == "einsum":
            n_groups = t // g
            oh_e = jax.nn.one_hot(a["idx"], eh, dtype=cdt)      # (T,k,Eh)
            oh_c = jax.nn.one_hot(a["pos"], cap, dtype=cdt)     # (T,k,C)
            keep = a["keep"].astype(cdt)[..., None, None]
            disp = (oh_e[..., :, None] * oh_c[..., None, :] * keep)  # (T,k,E,C)
            disp = disp.sum(1).reshape(n_groups, g, eh, cap)    # (G,g,E,C)
            comb = (oh_e[..., :, None] * oh_c[..., None, :] * keep
                    * a["w"].astype(cdt)[..., None, None]).sum(1)
            comb = comb.reshape(n_groups, g, eh, cap)
            xg = xf.reshape(n_groups, g, d)
            buf = jnp.einsum("gtec,gtd->gecd", disp, xg)
            # grouped: shard groups over data; global: shard capacity.
            cap_axes = (("moe_groups", "experts", None, None)
                        if n_groups > 1
                        else (None, "experts", "expert_cap", None))
            buf = constrain(buf, cap_axes)
            with jax.named_scope("iri.moe.experts"):
                hbuf = _expert_ffn(buf, p, cdt)
            hbuf = constrain(hbuf, cap_axes)
            out = jnp.einsum("gtec,gecd->gtd", comb, hbuf).reshape(t, d)
        elif opts.impl == "gather":
            flat_t = jnp.repeat(jnp.arange(t), k)               # (T*k,)
            flat_e = a["idx"].reshape(-1)
            flat_pos = a["pos"].reshape(-1)
            flat_w = a["w"].reshape(-1)
            flat_keep = a["keep"].reshape(-1)
            if opts.group_size > 0:
                # group-local capacity -> global buffer offset per group
                grp = flat_t // g
                dest = (grp * eh + flat_e) * cap + flat_pos
                rows = (t // g) * eh * cap
            else:
                dest = flat_e * cap + flat_pos
                rows = eh * cap
            dest = jnp.where(flat_keep, dest, rows)             # OOB -> drop
            buf = jnp.zeros((rows, d), cdt).at[dest].set(
                xf[flat_t], mode="drop")
            if opts.group_size > 0:
                buf = buf.reshape(t // g, eh, cap, d)
                cap_axes = ("moe_groups", "experts", None, None)
            else:
                buf = buf.reshape(eh, cap, d)
                cap_axes = ("experts", "expert_cap", None)
            buf = constrain(buf, cap_axes)
            with jax.named_scope("iri.moe.experts"):
                hbuf = _expert_ffn(constrain(buf, cap_axes), p, cdt)
            hbuf = constrain(hbuf, cap_axes).reshape(rows, d)
            gathered = jnp.take(hbuf, jnp.where(flat_keep, dest, 0), axis=0)
            gathered = gathered * (flat_w.astype(cdt)
                                   * flat_keep.astype(cdt))[:, None]
            out = gathered.reshape(t, k, d).sum(1)
        else:
            raise ValueError(f"unknown moe impl {opts.impl!r}")

    out = _shared(p, xf, out).reshape(b, s, d)
    if count_rows is not None:
        return out, aux * opts.aux_coef, counts
    return out, aux * opts.aux_coef
