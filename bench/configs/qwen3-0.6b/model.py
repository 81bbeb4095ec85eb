"""Qwen3-0.6B as the benchmark runs it: seeded weights in the serving
program's parameter layout, a plain reference forward pass, and the
operations and bytes one decode step needs.

The reference follows the published architecture (Qwen3, hf
``Qwen3ForCausalLM``): token embedding; per layer RMSNorm -> grouped-query
attention with RMSNorm on each query and key head (qk-norm) before rotary
embedding (rotate-half, theta ``rope_theta``), causal softmax at scale
``head_dim ** -0.5``, output projection, residual; RMSNorm -> SwiGLU MLP,
residual; final RMSNorm; logits against the tied embedding.  It is written
from those equations and imports nothing of the program.  Every matrix
product goes through ``mm`` so the caller sets its precision: float32 at
``highest`` for the reference, lower for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    return dict(L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                h=cfg["num_attention_heads"], hk=cfg["num_key_value_heads"],
                dh=cfg["head_dim"], ff=cfg["intermediate_size"],
                V=cfg["vocab_size"], eps=cfg["rms_norm_eps"],
                theta=cfg["rope_theta"])


def padded_vocab(v: int) -> int:
    """The serving program keeps the embedding's rows padded to a multiple
    of 256; the rows past ``vocab_size`` are never read."""
    return -(-v // 256) * 256


def make_params(cfg: dict, key) -> dict:
    """Seeded float32 weights in the program's layout (layers stacked on a
    leading axis).  Scales keep every activation and logit of order one:
    projections are N(0, 1/fan_in), the tied embedding N(0, 1/d) so that
    logits have unit spread, norm weights 1 + N(0, 0.1^2)."""
    s = sizes(cfg)
    L, d, h, hk, dh, ff = s["L"], s["d"], s["h"], s["hk"], s["dh"], s["ff"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) * fan_in ** -0.5

    def norm(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    return {
        "embed": normal((padded_vocab(s["V"]), d), d),
        "final_norm": norm((d,)),
        "dense_layers": {
            "norm1": norm((L, d)),
            "mixer": {
                "wq": normal((L, d, h, dh), d),
                "wk": normal((L, d, hk, dh), d),
                "wv": normal((L, d, hk, dh), d),
                "wo": normal((L, h, dh, d), h * dh),
                "q_norm": norm((L, dh)),
                "k_norm": norm((L, dh)),
            },
            "norm2": norm((L, d)),
            "ffn": {
                "wg": normal((L, d, ff), d),
                "wu": normal((L, d, ff), d),
                "wd": normal((L, ff, d), ff),
            },
        },
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half rotary embedding over the last axis; ``x`` (B,T,H,dh)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params: dict, cfg: dict, tokens, mm):
    """Reference logits ``(B, T, vocab_size)`` in float32 for ``tokens``
    ``(B, T)``, causal over the whole sequence, no cache and no kernels.
    ``mm(spec, a, b)`` computes one einsum."""
    s = sizes(cfg)
    eps, g = s["eps"], s["h"] // s["hk"]
    f32 = jnp.float32
    t = tokens.shape[1]
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]
    x = params["embed"][tokens].astype(f32)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        at = lp["mixer"]
        hx = _rms(x, lp["norm1"], eps)
        q = _rope(_rms(mm("btd,dhk->bthk", hx, at["wq"]), at["q_norm"], eps),
                  pos, s["theta"])
        k = _rope(_rms(mm("btd,dhk->bthk", hx, at["wk"]), at["k_norm"], eps),
                  pos, s["theta"])
        v = mm("btd,dhk->bthk", hx, at["wv"])
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        sc = mm("bqhk,bshk->bhqs", q, k) * s["dh"] ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = mm("bhqs,bshk->bqhk", p, v)
        x = x + mm("bthk,hkd->btd", o, at["wo"])
        f = lp["ffn"]
        h2 = _rms(x, lp["norm2"], eps)
        u = jax.nn.silu(mm("btd,df->btf", h2, f["wg"])) * \
            mm("btd,df->btf", h2, f["wu"])
        return x + mm("btf,fd->btd", u, f["wd"]), None

    x, _ = jax.lax.scan(layer, x, params["dense_layers"])
    x = _rms(x, params["final_norm"].astype(f32), eps)
    head = params["embed"][: s["V"]].astype(f32)
    return mm("btd,vd->btv", x, head)


def decode_cost(cfg: dict, lengths, weight_bytes: int = 2,
                kv_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one decode step needs for real rows whose caches
    hold ``lengths`` tokens before the step.

    Bytes: every weight read once (the tied embedding once, as the output
    head, plus the rows the step's tokens look up), each row's cached keys
    and values read, the new token's written, float32 logits written.
    Operations: two per multiply-add of every projection, the head, and
    attention's two products over the row's ``length + 1`` keys."""
    s = sizes(cfg)
    L, d, h, hk, dh, ff, V = (s["L"], s["d"], s["h"], s["hk"], s["dh"],
                              s["ff"], s["V"])
    per_layer = d * h * dh + 2 * d * hk * dh + h * dh * d + 3 * d * ff
    norms = L * (2 * d + 2 * dh) + d
    b = len(lengths)
    kv_token = 2 * L * hk * dh * kv_bytes
    nbytes = ((L * per_layer + V * d + norms) * weight_bytes
              + b * d * weight_bytes
              + sum(lengths) * kv_token + b * kv_token + b * V * 4)
    flops = sum(2 * (L * per_layer + V * d) + 4 * L * h * dh * (n + 1)
                for n in lengths)
    return float(flops), float(nbytes)
