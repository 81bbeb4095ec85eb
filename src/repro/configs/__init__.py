"""Architecture registry: the 10 assigned configs, one chip's cut of
deepseek-v2-236b under expert parallelism, and the input-shape sets.

Every (arch x shape) cell is well-defined here; ``input_specs`` produces the
ShapeDtypeStruct stand-ins the dry-run lowers (no allocation).  ``long_500k``
is only supported for sub-quadratic archs (rwkv6, hymba) — see DESIGN.md
§Arch-applicability.
"""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp

from repro.models import ModelConfig

__all__ = ["ARCHS", "ARCH_IDS", "SHAPES", "Shape", "get_config", "get_reduced",
           "select", "supported_shapes", "input_specs"]

ARCHS = (
    "kimi_k2_1t_a32b",
    "deepseek_v2_236b",
    "deepseek_v2_236b_ep16",
    "internvl2_2b",
    "yi_6b",
    "deepseek_7b",
    "minitron_4b",
    "qwen3_0_6b",
    "musicgen_medium",
    "rwkv6_1_6b",
    "hymba_1_5b",
)

#: canonical CLI ids (the assignment's spelling) -> module names
_ALIAS = {
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "deepseek-v2-236b-ep16": "deepseek_v2_236b_ep16",
    "internvl2-2b": "internvl2_2b",
    "yi-6b": "yi_6b",
    "deepseek-7b": "deepseek_7b",
    "minitron-4b": "minitron_4b",
    "qwen3-0.6b": "qwen3_0_6b",
    "musicgen-medium": "musicgen_medium",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "hymba-1.5b": "hymba_1_5b",
}

#: canonical arch ids in assignment order
ARCH_IDS = tuple(_ALIAS)


@dataclasses.dataclass(frozen=True)
class Shape:
    name: str
    kind: str                  # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": Shape("train_4k", "train", 4_096, 256),
    "prefill_32k": Shape("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": Shape("decode_32k", "decode", 32_768, 128),
    "long_500k": Shape("long_500k", "decode", 524_288, 1),
}


def _module(name: str):
    name = _ALIAS.get(name, name)
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_ALIAS)}")
    return importlib.import_module(f"repro.configs.{name}")


def get_config(name: str) -> ModelConfig:
    return _module(name).CONFIG


def get_reduced(name: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    return _module(name).reduced()


def select(name: str, reduced: bool = False) -> ModelConfig:
    """What a launch driver runs: the published config in its own compute
    dtype, or — only when asked for with ``reduced`` — the reduced
    same-family preset in float32 (CPU recipes and tests)."""
    if reduced:
        return get_reduced(name).replace(compute_dtype="float32")
    return get_config(name)


def supported_shapes(cfg: ModelConfig) -> list[str]:
    """long_500k needs sub-quadratic attention; skip for pure full-attention
    archs (noted in DESIGN.md)."""
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.mixer in ("rwkv6", "hymba"):
        out.append("long_500k")
    return out


def input_specs(cfg: ModelConfig, shape: Shape) -> dict:
    """ShapeDtypeStruct stand-ins for the step inputs of (cfg, shape)."""
    b, s = shape.global_batch, shape.seq_len
    f = jnp.float32
    i = jnp.int32
    if shape.kind in ("train", "prefill"):
        if cfg.frontend is not None:
            specs = {"embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), f)}
        else:
            specs = {"tokens": jax.ShapeDtypeStruct((b, s), i)}
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((b, s), i)
        return specs
    # decode: one new token against a seq_len cache
    return {"tokens": jax.ShapeDtypeStruct((b,), i),
            "pos": jax.ShapeDtypeStruct((), i)}
