"""RWKV-6 "Finch" 1.6B as the benchmark runs it: seeded weights in the
serving program's parameter layout, a plain reference forward pass, and the
operations and bytes one decode step needs.

The reference follows the Finch block (arXiv:2404.05892, section 4) with
the departures ``config.json`` lists, which the serving program makes and
the reference therefore makes too.  Per layer, on ``x`` the residual
stream and ``x'`` the previous position's normed input (zero at the
first):

* time mix: ``n = rms(x)``; ``mix_i = n + (n' - n) * mu_i``; ``r, k, v``
  from their mixes; ``g = silu(mix_g W_g)``; log-decay
  ``lw = clip(-exp(clip(w0 + tanh(mix_w A) B, -8, 1)), lo, hi)``; per
  head of ``head_size``, state ``S_t = diag(exp(lw_t)) S_{t-1} + k_t^T v_t``
  and output ``o_t = r_t S_{t-1} + (r_t . (u * k_t)) v_t``; ``o`` normed
  per head, times ``ln_x * g``, projected by ``W_o``; residual;
* channel mix: ``m = rms(x)``; ``kk = relu(mix(m, m', mu_k) W_k)^2``;
  ``out = sigmoid(m W_r) * (kk W_v)``; residual;

then a final RMSNorm and the output head.  It is written from those
equations and imports nothing of the program.  Matrix products go through
``mm`` so the caller sets their precision; the recurrence itself is kept
in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    d, hs = cfg["hidden_size"], cfg["head_size"]
    return dict(L=cfg["num_hidden_layers"], d=d, hs=hs, H=d // hs,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                r=cfg["decay_lora_dim"], eps=cfg["rms_norm_eps"],
                lo=cfg["log_decay_min"], hi=cfg["log_decay_max"])


def padded_vocab(v: int) -> int:
    """The serving program pads the vocabulary to a multiple of 256."""
    return -(-v // 256) * 256


def make_params(cfg: dict, key) -> dict:
    """Seeded float32 weights in the program's layout (layers stacked on a
    leading axis).  Projections are N(0, 1/fan_in); token-shift mixes
    U(0, 1); ``w0`` U(-6, 0), so per-step decays span about 0.37 to 0.998;
    the decay LoRA moves the log-decay by a few tenths; norms
    1 + N(0, 0.1^2)."""
    s = sizes(cfg)
    L, d, H, hs, ff, r = s["L"], s["d"], s["H"], s["hs"], s["ff"], s["r"]
    ks = iter(jax.random.split(key, 32))

    def normal(shape, fan_in, scale=1.0):
        return scale * fan_in ** -0.5 * jax.random.normal(next(ks), shape,
                                                          jnp.float32)

    def uniform(shape, lo=0.0, hi=1.0):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    def norm(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    mixer = {name: uniform((L, d)) for name in
             ("mu_r", "mu_k", "mu_v", "mu_g", "mu_w")}
    mixer.update({name: normal((L, d, d), d) for name in
                  ("wr", "wk", "wv", "wg", "wo")})
    mixer.update({
        "w0": uniform((L, d), -6.0, 0.0),
        "w_lora_a": normal((L, d, r), d),
        "w_lora_b": normal((L, r, d), r, 0.5),
        "u": normal((L, H, hs), 1.0, 0.5),
        "ln_x": norm((L, d)),
        "cm_mu_k": uniform((L, d)),
        "cm_wk": normal((L, d, ff), d),
        "cm_wv": normal((L, ff, d), ff),
        "cm_wr": normal((L, d, d), d),
    })
    vp = padded_vocab(s["V"])
    return {
        "embed": normal((vp, d), 1.0),
        "final_norm": norm((d,)),
        "dense_layers": {"norm1": norm((L, d)), "mixer": mixer,
                         "norm2": norm((L, d))},
        "lm_head": normal((d, vp), d),
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _shift(x):
    """Each position sees the previous one's value; the first sees zero."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _wkv(r, k, v, lw, u):
    """The per-head recurrence over time; inputs ``(B, T, H, hs)``."""
    b, _, nh, hs = r.shape

    def step(state, xs):
        rt, kt, vt, lwt = xs                              # (B, H, hs)
        o = jnp.einsum("bhk,bhkv->bhv", rt, state,
                       precision=jax.lax.Precision.HIGHEST)
        o = o + jnp.sum(rt * u * kt, -1, keepdims=True) * vt
        state = jnp.exp(lwt)[..., None] * state + kt[..., None] * vt[..., None, :]
        return state, o

    state0 = jnp.zeros((b, nh, hs, hs), jnp.float32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, lw))
    _, o = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(o, 0, 1)


def logits(params: dict, cfg: dict, tokens, mm):
    """Reference logits ``(B, T, vocab_size)`` in float32 for ``tokens``
    ``(B, T)``, over the whole sequence, no cache and no kernels.
    ``mm(spec, a, b)`` computes one einsum."""
    s = sizes(cfg)
    eps, nh, hs = s["eps"], s["H"], s["hs"]
    f32 = jnp.float32
    x = params["embed"][tokens].astype(f32)
    b, t, d = x.shape

    def heads(a):
        return a.reshape(b, t, nh, hs)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        p = lp["mixer"]
        n = _rms(x, lp["norm1"], eps)
        prev = _shift(n)

        def mix(mu):
            return n + (prev - n) * mu

        r = mm("btd,de->bte", mix(p["mu_r"]), p["wr"])
        k = mm("btd,de->bte", mix(p["mu_k"]), p["wk"])
        v = mm("btd,de->bte", mix(p["mu_v"]), p["wv"])
        g = jax.nn.silu(mm("btd,de->bte", mix(p["mu_g"]), p["wg"]))
        lora = mm("btr,re->bte",
                  jnp.tanh(mm("btd,dr->btr", mix(p["mu_w"]), p["w_lora_a"])),
                  p["w_lora_b"])
        lw = jnp.clip(-jnp.exp(jnp.clip(p["w0"] + lora, -8.0, 1.0)),
                      s["lo"], s["hi"])
        o = _wkv(heads(r), heads(k), heads(v), heads(lw), p["u"])
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        o = o.reshape(b, t, d) * p["ln_x"] * g
        x = x + mm("btd,de->bte", o, p["wo"])
        m = _rms(x, lp["norm2"], eps)
        xk = m + (_shift(m) - m) * p["cm_mu_k"]
        kk = jnp.square(jax.nn.relu(mm("btd,df->btf", xk, p["cm_wk"])))
        rr = jax.nn.sigmoid(mm("btd,de->bte", m, p["cm_wr"]))
        return x + rr * mm("btf,fd->btd", kk, p["cm_wv"]), None

    x, _ = jax.lax.scan(layer, x, params["dense_layers"])
    x = _rms(x, params["final_norm"].astype(f32), eps)
    return mm("btd,dv->btv", x, params["lm_head"][:, : s["V"]].astype(f32))


def decode_cost(cfg: dict, lengths, weight_bytes: int = 2,
                kv_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one decode step needs for ``len(lengths)`` real
    rows (the recurrent state does not grow with length).

    Bytes: every weight read once (the output head whole, the embedding
    rows the step looks up), each row's float32 state and its two
    token-shift vectors read and written, float32 logits written.
    Operations: two per multiply-add of every projection and the head, and
    per head the state's decay-and-update and its readout (``2 hs^2``
    multiply-adds)."""
    s = sizes(cfg)
    L, d, H, hs, ff, V, r = (s["L"], s["d"], s["H"], s["hs"], s["ff"],
                             s["V"], s["r"])
    per_layer = 6 * d * d + 2 * d * r + 2 * d * ff
    vectors = L * (10 * d + H * hs) + d
    b = len(lengths)
    state = L * (H * hs * hs * 4 + 2 * d * kv_bytes)
    nbytes = ((L * per_layer + d * V + vectors) * weight_bytes
              + b * d * weight_bytes + 2 * b * state + b * V * 4)
    flops = b * (2 * (L * per_layer + d * V) + 4 * L * H * hs * hs)
    return float(flops), float(nbytes)
