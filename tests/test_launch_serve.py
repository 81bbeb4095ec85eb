"""The serving driver's entry points on the CPU: ``--reduced`` model
selection, served logits against a float32 full-sequence reference, the
compile-cache location, and the fleet's one-replica-per-device rule."""
import argparse
import os
import pathlib
import re
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro import configs
from repro.launch.jax_cache import DEFAULT_DIR, enable_compile_cache
from repro.launch.serve import (add_engine_args, build_engine, fleet_router,
                                synthetic_workload)
from repro.models import KernelOptions
from repro.models import transformer as model
from repro.models.transformer import RunOptions
from repro.serve import OpenLoopSource

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _args(*flags):
    ap = argparse.ArgumentParser()
    add_engine_args(ap)
    return ap.parse_args(["--reduced", *flags])


def test_select_published_unless_reduced():
    cfg = configs.select("qwen3-0.6b")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (28, 1024, 151936)
    assert cfg.compute_dtype == "bfloat16"
    small = configs.select("qwen3-0.6b", reduced=True)
    assert small.compute_dtype == "float32"
    assert small.d_model < cfg.d_model


def test_reduced_engine_logits_match_float32_reference():
    built = build_engine(_args("--batch", "2", "--max-len", "64",
                               "--no-safety", "--requests", "2"))
    schedule = synthetic_workload(2, 50.0, seed=3, budgets=(4,),
                                  prompts=(16,))
    built.executor.logits_log = {r.rid: [] for _, r in schedule}
    built.engine.run(source=OpenLoopSource(built.engine.queue, schedule),
                     max_steps=500)
    cfg = built.cfg
    opts = RunOptions(kernels=KernelOptions(impl="xla_ref"))
    for _, req in schedule:
        rows = built.executor.logits_log[req.rid]
        assert len(rows) == req.max_new_tokens   # prefill + cached decodes
        prompt = built.executor.prompt_fn(req)
        seq = np.concatenate([prompt, np.asarray(req.payload[:-1], np.int32)])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(model.apply(built.params, cfg, opts,
                                         tokens=seq[None])[0][0])
        for j, row in enumerate(rows):
            r = ref[len(prompt) - 1 + j, :cfg.vocab_size]
            # float32 on both sides: only the summation order differs
            np.testing.assert_allclose(row, r, rtol=1e-4,
                                       atol=1e-4 * np.abs(r).max())
    built.engine.shutdown(state_dir=None)


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == was     # JAX reads it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert DEFAULT_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_fleet_router_refuses_replicas_sharing_a_device():
    dev = jax.devices()[0]
    fake = SimpleNamespace(device=dev, engine=SimpleNamespace())
    with pytest.raises(ValueError, match="share a device"):
        fleet_router([fake, fake])


def test_fleet_serves_one_replica_per_device(tmp_path):
    # --replicas N builds N in-process engines, one per device; two fake
    # CPU devices need their own process (this one keeps a single device)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--reduced",
         "--replicas", "2", "--requests", "4", "--rate", "20", "--batch", "2"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    devices = re.findall(r"^replica \d+: device (\d+)", proc.stdout, re.M)
    assert sorted(devices) == ["0", "1"]
    # each replica gets its own 4-request substream of the root seed
    assert "fleet served 8 requests" in proc.stdout
    routed = re.search(r'"routed": \[(\d+), (\d+)\]', proc.stdout)
    assert int(routed[1]) + int(routed[2]) == 8
