"""The harness end to end off the chip: the command refuses any
platform but a TPU; through the rehearsal's hooks each cell runs to a
result line of the contract's shape; with the timed path broken
underneath, `correct` comes out false; each cell's control, the
reference in the precision below the one its configuration states,
comes out not correct, through the harness and through control.py."""

import json
import os
import subprocess
import sys

import pyarrow as pa
import pytest

from benchmark import run

SEED = 2_147_483_777
CELLS = ["tpch_q1_resident", "tpch_q6_scan_uncached"]
ROWS = 60_000


@pytest.fixture(autouse=True)
def cache_outside_the_checkout(monkeypatch):
    """The tests' compile cache is conftest's, not benchmark/'s own."""
    monkeypatch.setattr(run, "session_conf",
                        lambda config: dict(config["session_conf"]))


def leftovers(tmp_path):
    """What the harness left in TMPDIR (the program's own spill and
    shuffle directories are the program's to clean)."""
    return [f for f in os.listdir(tmp_path) if f.startswith("srtpu_bench")]


def rehearse(cell, trace=False, seconds=0.3, **kw):
    return run.run_cell(cell, SEED, seconds, trace, rows=ROWS,
                        any_platform=True, **kw)


def test_command_refuses_a_platform_that_is_not_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=run.ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "measures a TPU" in done.stderr
    assert leftovers(tmp_path) == []


def test_unknown_workload_is_an_error():
    with pytest.raises(SystemExit):
        run.load_cell("no_such_cell")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_to_a_result_line(cell, tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    res = rehearse(cell)
    json.dumps(res)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    for number in res["compared"].values():
        assert number["value"] <= number["limit"]
    assert leftovers(tmp_path) == []  # data and trace are deleted


def test_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    cell = "tpch_q6_scan_uncached"
    res = rehearse(cell, trace=True)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if cell in m.get("workloads", [cell])}
    # off the chip there is no peak memory and no peak bandwidth, so those
    # two readers find nothing and are left out, as the contract asks
    silent = {"device.hbm_peak_gb", "fused.hbm_roofline"}
    assert set(res["metrics"]) == listed - silent
    assert res["metrics"]["compile.programs_in_window"]["value"] == 0
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for part in ("device_ops", "idle_gaps"):
        assert 1 <= len(res["breakdown"][part]) <= 10
    assert res["correct"] is True


def test_the_roofline_counts_the_cells_own_rows():
    """`fused.hbm_roofline` takes Q6's bytes from the CELL's own
    configuration, 28 B a row (three doubles and a date32): over the
    half table 0.84 GB a query, 1.03 ms at the peak of peaks.json."""
    loaded = run.load_cell("tpch_q6_scan_uncached")
    peaks = run.load_json(run.HERE, "peaks.json")["device_kind"]["TPU v5 lite"]
    ctx = {"cell": loaded, "config": loaded["config"], "peaks": peaks,
           "window": {"names": ["tpch_q6"] * 100},
           "trace": {"busy_s": 100 * 0.0254}}
    share = run.load_module("layer_metrics", "fused.hbm_roofline").read(ctx)
    assert share == pytest.approx(100 * 28 * 29_999_795 / 819e9 / 0.0254)


# --- faults planted under the harness: `correct` has to read false ---

def collect_with(monkeypatch, alter):
    from spark_rapids_tpu.api.dataframe import DataFrame

    real = DataFrame.collect_arrow
    monkeypatch.setattr(DataFrame, "collect_arrow",
                        lambda self: alter(real(self)))


def scale_first(table, name, factor):
    col = table.column(name).to_pylist()
    col[0] = col[0] * factor
    return table.set_column(table.column_names.index(name), name,
                            pa.array(col))


@pytest.mark.parametrize("cell, alter", [
    ("tpch_q1_resident",
     lambda t: scale_first(t, "sum_charge", 1 + 1e-4)),
    ("tpch_q1_resident", lambda t: scale_first(t, "count_order", 2)),
    ("tpch_q1_resident", lambda t: t.slice(1)),
    ("tpch_q1_resident", lambda t: t.take([1, 0] + list(range(2, len(t))))),
    ("tpch_q6_scan_uncached", lambda t: scale_first(t, "revenue", 1 - 1e-4)),
    ("tpch_q6_scan_uncached", lambda t: t.slice(0, 0)),
], ids=["sum_off", "count_off", "row_missing", "rows_swapped",
        "revenue_off", "answer_empty"])
def test_an_altered_answer_is_not_correct(cell, alter, monkeypatch):
    collect_with(monkeypatch, alter)
    res = rehearse(cell)
    assert res["correct"] is False
    assert res["failed"] == 0  # the query ran; its answer is wrong


def test_a_part_of_the_table_left_out_is_not_correct(monkeypatch):
    """The cached relation loses a file's part: every count is short."""
    from spark_rapids_tpu.exec.relation_cache import DeviceCacheEntry

    real = DeviceCacheEntry.device_parts
    monkeypatch.setattr(DeviceCacheEntry, "device_parts",
                        lambda self: real(self)[:-1])
    res = rehearse("tpch_q1_resident")
    assert res["correct"] is False
    assert res["compared"]["rows_wrong"]["value"] > 0


def test_a_query_off_the_fused_engine_is_failed(monkeypatch):
    real = run.not_fused
    calls = []

    def sometimes(rec):
        calls.append(rec)
        # the warm-up's executions pass; then every other one "fell back"
        return "" if len(calls) <= 2 or len(calls) % 2 else "engine='eager'"

    monkeypatch.setattr(run, "not_fused", sometimes)
    res = rehearse("tpch_q1_resident", seconds=0.5)
    assert real(None) != ""
    assert res["failed"] >= 1 and res["correct"] is False
    assert res["attempted"] == res["failed"] + res["compared"][
        "rows_wrong"]["value"] + len(calls) - 2 - res["failed"]


CONTROL = {"tpch_q1_resident": "bfloat16", "tpch_q6_scan_uncached": "float32"}


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell, rows=1_000_000):
    """The reference in the cell's control precision (`control` in
    limits/<cell>.json), in the program's place: refused by
    `sum_rel_err`, at a size a test run holds (the chip's readings at
    the cells' own size are in PERF.md). Q6's keyless sum runs in
    emulated f64, so float32 is its control and has to fail, and
    bfloat16 with it. Q1's binned MXU group-by sums f32 chunk partials,
    as its configuration states: there the float32 reference reads
    like the program and passes, and bfloat16 is the control."""
    res = run.run_cell(cell, SEED, 0.2, False, rows=rows,
                       any_platform=True, controls=("bfloat16", "float32"))
    assert res["correct"] is True
    limit = res["compared"]["sum_rel_err"]["limit"]
    control = run.load_cell(cell)["limits"]["control"]
    assert control == CONTROL[cell]
    assert res["controls"][control]["sum_rel_err"] > 3 * limit
    assert res["controls"]["bfloat16"]["sum_rel_err"] > 3 * limit
    passes = res["controls"]["float32"]["sum_rel_err"] <= limit
    assert passes == (control == "bfloat16")
    assert res["controls"]["float32"]["rows_wrong"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_py_reads_the_cells_control_and_sees_it_refused(
        cell, monkeypatch, capsys):
    """benchmark/control.py, the chip's reader of the two readings, at a
    size a test run holds: one line a seed, then the summary, which
    names the control that limits/<cell>.json names and says that
    every seed's control was refused while the program was correct."""
    from benchmark import control

    def small(name, seed, seconds, trace, **kw):
        return run.run_cell(name, seed, seconds, trace, rows=200_000,
                            any_platform=True, **kw)

    monkeypatch.setattr(control, "run_cell", small)
    assert control.main(["--workload", cell, "--seeds", "2",
                         "--seconds", "0.2"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    seeds, summary = lines[:-1], lines[-1]
    assert len(seeds) == 2 and seeds[0]["seed"] != seeds[1]["seed"]
    assert all(row["correct"] for row in seeds)
    assert summary["control"] == CONTROL[cell]
    assert summary["control_refused"] == [True, True]
    assert summary["program_sum_rel_err_max"] <= summary["limit"]
    assert summary["control_sum_rel_err_min"] > 3 * summary["limit"]
