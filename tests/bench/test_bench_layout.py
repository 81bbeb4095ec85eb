"""``BENCHMARK.json`` keeps to its contract, and every name in it has its
files: a configuration its file and reference, a mix its parameter file,
a metric its reader."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench.harness import check as check_mod
from bench.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_bench_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, path))
    assert os.path.isfile(os.path.join(spec.ROOT, bench["command"][1]))


def test_bench_names_units_and_sources(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert os.path.isfile(os.path.join(spec.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
        spec.load_metric(m["name"]).read       # has a reader
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["layer"] and "\n" not in m["layer"]


def test_bench_cells_find_their_parts(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(cells)
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cfg, model = spec.load_config(w["config"])
        assert configs[w["config"]]["file"] == os.path.relpath(
            os.path.join(spec.config_dir(w["config"]), "config.json"),
            spec.ROOT)
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        for fn in ("make_params", "logits", "decode_cost"):
            assert callable(getattr(model, fn))
        check = spec.load_json(os.path.join(spec.config_dir(w["config"]),
                                            "check.json"))
        assert {"min_tokens", "requests"} <= set(check)
        assert set(check) & set(check_mod.NUMBERS)
        assert spec.load_traffic(w["traffic"])["kind"] in ("backlog",
                                                           "poisson")
        cell = spec.find_cell(bench, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        # every cell reports set-up, one more end-to-end metric, and a
        # per-layer metric whose end-to-end metric it reports
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_bench_file_is_small(bench):
    size = os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024
    assert json.dumps(bench)
