"""BENCHMARK.json against the contract's limits, and every name in it
against the files the harness finds by that name."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells(bench):
    return [w["name"] for w in bench["workloads"]]


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # 2 + 14 runs for each of the full 24 cells must fit the check
    runs = 2 + 14 * 24
    assert (runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert 1 <= len(bench["paths"]) <= 16
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_whys(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    named = bench["configs"] + bench["workloads"] + metrics
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in bench[kind]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\t" not in w["why"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_resolves_to_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        conf = configs[w["config"]]
        used.add(w["config"])
        assert conf["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(ROOT, conf["file"])) as f:
            config = json.load(f)
        assert config["name"] == conf["name"]
        assert config["source"] == conf["source"]
        assert config["reduced"] == conf["reduced"]
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "datagen", config["generator"] + ".py"))
        with open(os.path.join(BENCH_DIR, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["loop"] == "closed" and traffic["clients"] == 1
        tables = set()
        for q in traffic["queries"]:
            path = os.path.join(BENCH_DIR, "queries", q + ".py")
            assert os.path.isfile(path), path
        assert set(traffic["tables"].values()) <= {"device", "parquet"}
        assert set(traffic["tables"]) <= set(config["schema"]), tables
        with open(os.path.join(BENCH_DIR, "limits",
                               w["name"] + ".json")) as f:
            limits = json.load(f)
        assert limits["rows_wrong"] == 0 and limits["failed"] == 0
        assert 0 < limits["sum_rel_err"] < 1e-3
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


def test_every_metric_has_a_reader_and_moves_what_its_cells_report(bench):
    all_cells = cells(bench)
    e2e = {m["name"]: set(m.get("workloads", all_cells))
           for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == set(all_cells)
    for m in bench["end_to_end"]:
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "end_to_end", m["name"] + ".py"))
        assert set(m.get("workloads", all_cells)) <= set(all_cells)
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e, m
        assert set(m.get("workloads", all_cells)) <= e2e[m["moves"]], m
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
    for cell in all_cells:
        mine = [n for n, ws in e2e.items() if cell in ws]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(cell in m.get("workloads", all_cells)
                   for m in bench["per_layer"]), cell


def test_file_names_under_paths_use_only_name_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".compile_cache")]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel
