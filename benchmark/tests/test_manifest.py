"""``BENCHMARK.json`` against the contract's rules that a test can hold, and
against the files the harness will look for."""

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("thing", MANIFEST["configs"] + MANIFEST["workloads"]
                         + METRICS, ids=lambda t: t["name"])
def test_names_units_and_lines(thing):
    assert NAME.match(thing["name"])
    for key in ("config", "traffic", "moves"):
        if key in thing:
            assert NAME.match(thing[key])
    if "unit" in thing:
        assert UNIT.match(thing["unit"])
        assert thing["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in thing:
            assert 1 <= len(thing[key]) <= 200
            assert "\n" not in thing[key] and "\t" not in thing[key]


def test_names_are_unique():
    for group in (MANIFEST["configs"], MANIFEST["workloads"], METRICS):
        names = [t["name"] for t in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_has_its_files_and_metrics(cell):
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    assert config["source"] == configs[cell["config"]]["source"]
    assert config["reduced"] == configs[cell["config"]]["reduced"]
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    assert (BENCH / "generators" / f"{mix['kind']}.py").exists()
    assert (BENCH / "runners" / f"{mix['runner']}.py").exists()
    assert (BENCH / "cells" / f"{cell['name']}.json").exists()
    e2e = [m["name"] for m in MANIFEST["end_to_end"]
           if cell["name"] in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell["name"] in cells_of(m) for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run", BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    assert run.find_reader("layer_metrics", metric["name"]).exists()
    moved = {m["name"]: m for m in MANIFEST["end_to_end"]}[metric["moves"]]
    for cell in cells_of(metric):
        assert cell in CELLS and cell in cells_of(moved)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric_has_a_reader_and_a_bound(metric):
    assert (BENCH / "end_to_end" / f"{metric['name']}.py").exists()
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1


def test_configs_cut_no_width():
    barred = re.compile(r"(hidden_size|intermediate|latent|state_|proj|head_dim|"
                        r"_dim$|_rank$|experts_per)")
    for c in MANIFEST["configs"]:
        assert not [k for k in c["reduced"] if barred.search(k)]
