"""DeepSeek-V2 as the benchmark runs it: one chip's share under 16-way
expert parallelism (``config.json``'s ``deployment``).  Seeded weights in
the serving program's parameter layout, a plain reference forward pass,
and the operations and bytes one decode step needs.

The reference follows the published architecture (hf
``DeepseekV2ForCausalLM``, DeepSeek-V2 paper arXiv:2405.04434) and imports
nothing of the program.  Token embedding; per layer RMSNorm -> multi-head
latent attention, materialized: the query from a RMSNormed low-rank latent
(``q_lora_rank``), keys and values from a RMSNormed ``kv_lora_rank``
latent, each head's key its own ``qk_nope_head_dim`` part plus one shared
``qk_rope_head_dim`` part; the rope parts of query and key de-interleaved
(hf ``apply_rotary_pos_emb`` takes ``(d/2, 2)`` pairs to halves) and
rotated with YaRN's tables (``rope_scaling``), causal softmax at scale
``(nope + rope) ** -0.5`` times YaRN's ``mscale_all_dim`` factor squared,
output projection, residual; RMSNorm -> the first
``first_k_dense_replace`` layers a SwiGLU MLP, the rest a mixture of
experts, residual; final RMSNorm; the untied head.

The mixture of experts (hf ``DeepseekV2MoE``, ``MoEGate``): softmax over
the router's ``router_experts`` scores; ``group_limited_greedy``: the best
score of each of ``n_group`` groups, the ``topk_group`` best groups stay
eligible; the ``num_experts_per_tok`` best eligible experts; weights times
``routed_scaling_factor`` (``norm_topk_prob`` false) or renormalized.  Of
the routed experts only the ``n_routed_experts`` held here, from
``first_expert`` on, contribute: each picked held expert's SwiGLU times its
weight.  The shared experts (one SwiGLU of ``n_shared_experts`` times the
expert width) are added once.  Every matrix product goes through ``mm`` so
the caller sets its precision: float32 at ``highest`` for the reference,
lower for the control.  The reference casts a layer's weights to float32
inside the layer scan, and the head a slice at a time, so that it fits on
the chip beside the bfloat16 weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    return dict(L=cfg["num_hidden_layers"], Ld=cfg["first_k_dense_replace"],
                d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                qr=cfg["q_lora_rank"], kvr=cfg["kv_lora_rank"],
                nd=cfg["qk_nope_head_dim"], rd=cfg["qk_rope_head_dim"],
                dh=cfg["v_head_dim"], ff=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"], E=cfg["router_experts"],
                Eh=cfg["n_routed_experts"], first=cfg["first_expert"],
                k=cfg["num_experts_per_tok"], fs=cfg["n_shared_experts"]
                * cfg["moe_intermediate_size"], V=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"])


def padded_vocab(v: int) -> int:
    """The serving program keeps the vocabulary padded to a multiple of
    256; the rows past ``vocab_size`` are never read."""
    return -(-v // 256) * 256


def make_params(cfg: dict, key) -> dict:
    """Seeded float32 weights in the program's layout (layers stacked on a
    leading axis, the dense layers and the MoE layers apart).  Scales keep
    every activation and logit of order one: projections N(0, 1/fan_in),
    the embedding N(0, 1), norm weights 1 + N(0, 0.1^2)."""
    s = sizes(cfg)
    d, h, qr, kvr = s["d"], s["h"], s["qr"], s["kvr"]
    nd, rd, dh, f = s["nd"], s["rd"], s["dh"], s["f"]
    ks = iter(jax.random.split(key, 40))

    def normal(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) * fan_in ** -0.5

    def norm(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    def layers(n, moe):
        p = {
            "norm1": norm((n, d)),
            "mixer": {
                "w_dq": normal((n, d, qr), d),
                "q_norm": norm((n, qr)),
                "w_uq": normal((n, qr, h, nd + rd), qr),
                "w_dkv": normal((n, d, kvr), d),
                "kv_norm": norm((n, kvr)),
                "w_kr": normal((n, d, rd), d),
                "w_uk": normal((n, kvr, h, nd), kvr),
                "w_uv": normal((n, kvr, h, dh), kvr),
                "wo": normal((n, h, dh, d), h * dh),
            },
            "norm2": norm((n, d)),
        }
        if moe:
            p["moe"] = {
                "router": normal((n, d, s["E"]), d),
                "wg": normal((n, s["Eh"], d, f), d),
                "wu": normal((n, s["Eh"], d, f), d),
                "wd": normal((n, s["Eh"], f, d), f),
                "shared": {"wg": normal((n, d, s["fs"]), d),
                           "wu": normal((n, d, s["fs"]), d),
                           "wd": normal((n, s["fs"], d), s["fs"])},
            }
        else:
            p["ffn"] = {"wg": normal((n, d, s["ff"]), d),
                        "wu": normal((n, d, s["ff"]), d),
                        "wd": normal((n, s["ff"], d), s["ff"])}
        return p

    vp = padded_vocab(s["V"])
    return {
        "embed": jax.random.normal(next(ks), (vp, d), jnp.float32),
        "final_norm": norm((d,)),
        "dense_layers": layers(s["Ld"], moe=False),
        "moe_layers": layers(s["L"] - s["Ld"], moe=True),
        "lm_head": normal((d, vp), d),
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor: float, mscale: float) -> float:
    """hf ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn(cfg: dict, t: int):
    """YaRN's cos and sin tables ``(t, rope dim)`` (hf
    ``DeepseekV2YarnRotaryEmbedding``) and the softmax scale."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    exps = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    freq_extra = 1.0 / base ** exps
    freq_inter = 1.0 / (factor * base ** exps)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / \
            (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - extra_mask) + freq_extra * extra_mask
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([freqs, freqs], -1)
    m = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5 \
        * _mscale(factor, rs["mscale_all_dim"]) ** 2
    return jnp.cos(emb) * m, jnp.sin(emb) * m, scale


def _rotate(x, cos, sin):
    """hf ``apply_rotary_pos_emb``: ``x (B, T, H, d)`` read as ``d/2``
    pairs, de-interleaved to halves, then rotate-half."""
    b, t, nh, d = x.shape
    x = x.reshape(b, t, nh, d // 2, 2).swapaxes(-1, -2).reshape(b, t, nh, d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos[None, :, None] + half * sin[None, :, None]


def _swiglu(x, wg, wu, wd, mm, spec_in, spec_out):
    u = jax.nn.silu(mm(spec_in, x, wg)) * mm(spec_in, x, wu)
    return mm(spec_out, u, wd)


def _gate(scores, cfg: dict):
    """hf ``MoEGate``: softmax scores (B, T, E) -> (weights, expert ids)
    of the ``num_experts_per_tok`` picks."""
    s = sizes(cfg)
    b, t, e = scores.shape
    g = cfg["n_group"]
    if cfg["topk_method"] == "group_limited_greedy":
        group_scores = scores.reshape(b, t, g, e // g).max(-1)
        _, group_idx = jax.lax.top_k(group_scores, cfg["topk_group"])
        group_mask = jax.nn.one_hot(group_idx, g).sum(-2) > 0   # (B, T, g)
        score_mask = jnp.repeat(group_mask, e // g, axis=-1)
        scores = jnp.where(score_mask, scores, 0.0)
    w, idx = jax.lax.top_k(scores, s["k"])
    if s["k"] > 1 and cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * cfg["routed_scaling_factor"]
    return w, idx


def logits(params: dict, cfg: dict, tokens, mm):
    """Reference logits ``(B, T, vocab_size)`` in float32 for ``tokens``
    ``(B, T)``, causal over the whole sequence, no cache and no kernels.
    ``mm(spec, a, b)`` computes one einsum."""
    s = sizes(cfg)
    eps = s["eps"]
    f32 = jnp.float32
    t = tokens.shape[1]
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]
    cos, sin, scale = _yarn(cfg, t)
    x = params["embed"][tokens].astype(f32)

    def attention(x, at):
        h = s["h"]
        cq = _rms(mm("btd,dr->btr", x, at["w_dq"]), at["q_norm"], eps)
        q = mm("btr,rhk->bthk", cq, at["w_uq"])
        q_nope, q_pe = q[..., : s["nd"]], q[..., s["nd"]:]
        ckv = _rms(mm("btd,dr->btr", x, at["w_dkv"]), at["kv_norm"], eps)
        k_pe = mm("btd,dk->btk", x, at["w_kr"])[:, :, None]     # one head
        k_nope = mm("btr,rhk->bthk", ckv, at["w_uk"])
        v = mm("btr,rhk->bthk", ckv, at["w_uv"])
        q_pe, k_pe = _rotate(q_pe, cos, sin), _rotate(k_pe, cos, sin)
        qs = jnp.concatenate([q_nope, q_pe], -1)
        ks = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + (s["rd"],))],
            -1)
        sc = mm("bqhk,bshk->bhqs", qs, ks) * scale
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = mm("bhqs,bshk->bqhk", p, v)
        return mm("bthk,hkd->btd", o, at["wo"])

    def moe(x, m):
        probs = jax.nn.softmax(mm("btd,de->bte", x, m["router"]), axis=-1)
        w, idx = _gate(probs, cfg)
        held = jax.nn.one_hot(idx - s["first"], s["Eh"])      # (B,T,k,Eh)
        gates = jnp.sum(held * w[..., None], -2)               # (B,T,Eh)
        y = _swiglu(x, m["wg"], m["wu"], m["wd"], mm, "btd,edf->btef",
                    "btef,efd->bted")
        routed = jnp.einsum("bte,bted->btd", gates, y)
        sh = m["shared"]
        return routed + _swiglu(x, sh["wg"], sh["wu"], sh["wd"], mm,
                                "btd,df->btf", "btf,fd->btd")

    def layer(x, lp, is_moe):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        x = x + attention(_rms(x, lp["norm1"], eps), lp["mixer"])
        h2 = _rms(x, lp["norm2"], eps)
        if is_moe:
            return x + moe(h2, lp["moe"]), None
        f = lp["ffn"]
        return x + _swiglu(h2, f["wg"], f["wu"], f["wd"], mm, "btd,df->btf",
                           "btf,fd->btd"), None

    x, _ = jax.lax.scan(lambda c, lp: layer(c, lp, False), x,
                        params["dense_layers"])
    x, _ = jax.lax.scan(lambda c, lp: layer(c, lp, True), x,
                        params["moe_layers"])
    x = _rms(x, params["final_norm"].astype(f32), eps)
    head = params["lm_head"]
    step = -(-s["V"] // 8)
    return jnp.concatenate(
        [mm("btd,dv->btv", x, head[:, i: min(i + step, s["V"])].astype(f32))
         for i in range(0, s["V"], step)], -1)


def decode_cost(cfg: dict, lengths, weight_bytes: int = 2,
                kv_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one decode step needs for real rows whose caches
    hold ``lengths`` tokens before the step.

    Bytes: every weight read once, except the held experts: each is read
    only if one of the step's rows picks it, which under a uniform router
    is the expected share ``1 - (1 - k / E) ** B`` of them (91.3% for B =
    64, k = 6, E = 160); the rows the step's tokens look up in the
    embedding; each row's latent cache (``kv_lora_rank + rope`` a token a
    layer) read and the new token's written; float32 logits written.
    Operations: two per multiply-add of every projection, of the held
    experts' FFNs for the expected ``k * Eh / E`` held picks a token, of
    the shared experts and the head, and of absorbed attention over the
    row's ``length + 1`` latents (scores against ``kv_lora + rope``, then
    the ``kv_lora`` output)."""
    s = sizes(cfg)
    L, Ld, d, h, V = s["L"], s["Ld"], s["d"], s["h"], s["V"]
    qr, kvr, nd, rd, dh = s["qr"], s["kvr"], s["nd"], s["rd"], s["dh"]
    Lm = L - Ld
    attn = (d * qr + qr * h * (nd + rd) + d * kvr + d * rd
            + kvr * h * nd + kvr * h * dh + h * dh * d)
    norms = L * (2 * d + qr + kvr) + d
    expert = 3 * d * s["f"]
    shared = 3 * d * s["fs"]
    b = len(lengths)
    reached = 1.0 - (1.0 - s["k"] / s["E"]) ** b if b else 0.0
    weights = (L * attn + Ld * 3 * d * s["ff"]
               + Lm * (d * s["E"] + shared + reached * s["Eh"] * expert)
               + V * d + norms)
    latent = L * (kvr + rd) * kv_bytes
    nbytes = (weights * weight_bytes + b * d * weight_bytes
              + sum(lengths) * latent + b * latent + b * V * 4)
    # absorbed decode: the query's nope part through w_uk, the latent
    # output through w_uv, both already in ``attn``
    per_token = (L * attn + Ld * 3 * d * s["ff"]
                 + Lm * (d * s["E"] + shared
                         + s["k"] * s["Eh"] / s["E"] * expert) + V * d)
    flops = sum(2 * per_token + 2 * L * h * (2 * kvr + rd) * (n + 1)
                for n in lengths)
    return float(flops), float(nbytes)
