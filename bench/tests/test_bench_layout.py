"""Every entry of BENCHMARK.json resolves to its files, by name alone."""
import json
import re
from pathlib import Path

import pytest

import harness
import traffic

REPO = Path(harness.REPO)
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_resolves(cfg):
    assert NAME.match(cfg["name"])
    path = REPO / cfg["file"]
    assert path.is_file() and cfg["file"].startswith("bench/")
    data = json.loads(path.read_text())
    assert data["name"] == cfg["name"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in data
    assert harness.build_slices(data)
    harness.build_policy(data)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200
    resolved = harness.resolve_cell(cell["name"])
    traffic.load_mix(traffic.mix_path(harness.BENCH, cell["traffic"]))
    harness.build_admission(resolved.mix)
    harness.build_system(resolved, harness.seeds(1), None)
    for name in resolved.config.get("checks", ()):
        assert callable(harness.check_reader(name))
    names = {m["name"] for m in resolved.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert resolved.per_layer


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_resolves(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert callable(harness.metric_reader(metric["name"]))
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        ends = {m["name"] for m in BENCH["end_to_end"]}
        assert metric["moves"] in ends and metric["layer"]
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_pairs_of_config_and_traffic_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
