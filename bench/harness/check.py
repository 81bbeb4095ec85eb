"""Decide whether what the engine served is correct.

The number compared is the widest gap by which a served token's logit lies
below the best logit of the reference at that position: greedy decoding
serves the arg-max of its own logits, so a sound engine serves a token the
reference ranks first or ties within rounding, and a broken one serves
tokens the reference ranks anywhere.  The reference runs once over each
sampled request's prompt and served tokens (the last served token is not
an input), after the window has closed and the engine's state is freed.

The control puts the reference, computed in a lower precision, in the
engine's place: at the same positions it takes the token the lower
precision ranks first and reads that token's gap in the float32
reference.
"""
from __future__ import annotations

import json
import random

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(spec, a, b):
    """One einsum in float32 at the highest matmul precision."""
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(x):
    """Round to float8 e4m3 under one scale per tensor (its largest
    magnitude maps to the format's largest finite value, 448)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm_fp8(spec, a, b):
    """One einsum whose operands are rounded to float8 e4m3 (per-tensor
    scale), accumulated in float32: the step below bfloat16."""
    return mm_f32(spec, _fp8(a.astype(jnp.float32)),
                  _fp8(b.astype(jnp.float32)))


def sample(served: list, seed: int, count: int) -> list:
    """``count`` of the served requests, drawn from the seed, the one with
    the most served tokens always among them.  ``served`` holds
    ``(prompt ids, served ids)`` pairs with at least one served token."""
    if not served:
        return []
    order = sorted(range(len(served)), key=lambda i: -len(served[i][1]))
    rest = order[1:]
    random.Random(seed * 7919 + 1).shuffle(rest)
    return [served[i] for i in [order[0]] + rest[: count - 1]]


_FNS: dict = {}


def _gap_fn(model, cfg: dict, mm):
    """The jitted gap computation of one configuration and precision."""
    key = (id(model), json.dumps(cfg, sort_keys=True), mm)
    if key not in _FNS:
        def fn(params, tokens, positions, compare):
            """Gap at each (position, token); ``tokens (1, T)``."""
            lg = model.logits(params, cfg, tokens, mm)[0]       # (T, V)
            at = lg[positions]                                   # (N, V)
            best = jnp.max(at, -1)
            picked = jnp.take_along_axis(at, compare[:, None], -1)[:, 0]
            return best - picked, jnp.argmax(at, -1)

        _FNS[key] = jax.jit(fn)
    return _FNS[key]


def gaps(model, cfg: dict, params, requests: list, length: int,
         mm=mm_f32, compare: str = "served") -> np.ndarray:
    """Per served token, the reference's best logit minus the logit of the
    token compared.  ``requests`` holds ``(prompt ids, served ids)``;
    sequences are padded to ``length`` (one compiled program).  With
    ``compare="ranked"`` the token compared is the one ``mm``'s precision
    ranks first, and the gap is read in the float32 reference."""
    fn = _gap_fn(model, cfg, mm)
    ref = _gap_fn(model, cfg, mm_f32)
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, served in requests:
            seq = np.concatenate([np.asarray(prompt, np.int32),
                                  np.asarray(served[:-1], np.int32)])
            if len(seq) > length:
                raise ValueError(f"sequence of {len(seq)} tokens exceeds "
                                 f"the check's length {length}")
            tokens = np.zeros((1, length), np.int32)
            tokens[0, :len(seq)] = seq
            n = len(served)
            positions = np.zeros((length,), np.int32)
            positions[:n] = len(prompt) - 1 + np.arange(n)
            toks = np.zeros((length,), np.int32)
            toks[:n] = served
            g, ranked = fn(params, tokens, positions, toks)
            if compare == "ranked":
                g, _ = ref(params, tokens, positions, ranked)
            out.append(np.asarray(g)[:n])
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


#: The numbers a configuration's ``check.json`` may name, each with its
#: limit: the widest gap, or the share of compared tokens that are not the
#: reference's first choice (a gap above zero).
NUMBERS = {
    "served_gap_max": lambda g: float(np.max(g)),
    "served_mismatch_share": lambda g: float(np.mean(g > 0)),
}


def compare(run, limits: dict) -> dict:
    """The numbers one run is judged by, each with its limit: those of
    ``NUMBERS`` that ``limits`` names, over the sampled requests' served
    tokens, and how many tokens were compared (at least
    ``min_tokens``)."""
    picked = sample(run.served, run.seed, int(limits["requests"]))
    g = gaps(run.model, run.cfg, run.params, picked,
             int(run.traffic["engine"]["max_len"]))
    out = {name: {"value": fn(g) if len(g) else None,
                  "limit": float(limits[name])}
           for name, fn in NUMBERS.items() if name in limits}
    out["tokens_compared"] = {"value": int(len(g)),
                              "limit": int(limits["min_tokens"])}
    return out


def is_correct(numbers: dict) -> bool:
    n = numbers["tokens_compared"]
    return n["value"] >= n["limit"] and all(
        numbers[k]["value"] is not None
        and numbers[k]["value"] <= numbers[k]["limit"]
        for k in NUMBERS if k in numbers)
