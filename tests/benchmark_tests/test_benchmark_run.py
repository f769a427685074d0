"""The harness end to end off the chip: the command refuses any
platform but a TPU; through the rehearsal's hooks each cell runs to a
result line of the contract's shape; with the timed path broken
underneath, `correct` comes out false; the float32 control comes out
not correct."""

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


@pytest.mark.parametrize("cell, rows", [
    ("tpch_q1_resident", 1_000_000), ("tpch_q6_scan_uncached", 1_000_000)])
def test_the_bfloat16_control_is_not_correct(cell, rows):
    """The reference with values and products rounded to bfloat16, in
    the program's place: refused by `sum_rel_err`, at a size a test
    run holds (the chip's readings at the cells' own size are in
    PERF.md). The float32 reference is no control: it reads below what
    the engine reads on a v5e, whose f64 arithmetic is f32."""
    res = run.run_cell(cell, SEED, 0.2, False, rows=rows,
                       any_platform=True, controls=("bfloat16", "float32"))
    assert res["correct"] is True
    limit = res["compared"]["sum_rel_err"]["limit"]
    assert res["controls"]["bfloat16"]["sum_rel_err"] > 3 * limit
    assert res["controls"]["float32"]["sum_rel_err"] < limit
    assert res["controls"]["float32"]["rows_wrong"] == 0
