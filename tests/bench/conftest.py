"""Helpers for the benchmark's tests: the benchmark's configurations at the
program's reduced presets, and a short traffic mix that fits a CPU."""
from __future__ import annotations

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the program's ModelConfig fields -> each configuration file's keys
_KEYS = {
    "qwen3-0.6b": {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
                   "n_heads": "num_attention_heads",
                   "n_kv_heads": "num_key_value_heads", "d_head": "head_dim",
                   "d_ff": "intermediate_size", "vocab_size": "vocab_size"},
    "rwkv6-1.6b": {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
                   "rwkv_head_size": "head_size", "d_ff": "intermediate_size",
                   "vocab_size": "vocab_size"},
}

CONFIGS = tuple(_KEYS)


def reduced_config(name: str):
    """``(cfg, model)``: the configuration with the sizes of the program's
    reduced preset, which ``build_engine(--reduced)`` serves in float32."""
    from repro import configs

    from bench.harness import spec

    cfg, model = spec.load_config(name)
    small = configs.get_reduced(cfg["program_arch"])
    cfg = copy.deepcopy(cfg)
    for field, key in _KEYS[name].items():
        cfg[key] = getattr(small, field)
    return cfg, model


def small_traffic(kind: str = "backlog") -> dict:
    """A mix of the given kind at sizes a CPU serves in seconds."""
    base = {"engine": {"batch": 4, "max_len": 64}, "block": 4,
            "prompt": {"dist": "lognormal", "median": 10, "sigma": 0.8,
                       "min": 2, "max": 24},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 24}}
    if kind == "backlog":
        return {**base, "kind": "backlog", "max_requests": 512,
                "warmup_max_s": 120}
    return {**base, "kind": "poisson", "rate": 8.0, "warmup_s": 1.0,
            "gap_block": 16}


@pytest.fixture
def state_dir(tmp_path):
    return str(tmp_path / "state")
