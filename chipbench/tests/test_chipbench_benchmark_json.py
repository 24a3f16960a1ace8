"""BENCHMARK.json names only what the harness can find by name."""
import os
import re

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        work, config, cell = spec.find_cell(bench, w["name"])
        assert config["driver"] in ("lm_serve", "cnn_forward")
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "harness", config["driver"] + ".py"))
        assert "check" in cell
        e2e = spec.end_to_end_metrics(bench, w["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert spec.per_layer_metrics(bench, w["name"])


def test_every_metric_has_a_reader(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(spec.load_reader(m["name"]).read)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert m["moves"] in {x["name"] for x in spec.end_to_end_metrics(bench, cell)}


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
