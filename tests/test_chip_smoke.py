"""CPU rehearsal of chip_smoke.py, and the proof that neither it nor
bench.py passes for a chip run off the chip.

The script itself refuses a platform other than `tpu` at its first
phase and prints no result there, so the phases are driven here by
importing them, at a small --rows, on the virtual CPU devices: wrong
paths, arguments and control flow are found before any chip time is
spent. What the phases print is what a chip run prints; nothing here
is a device number.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # chip_smoke.py and bench.py live at the root
    sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402

ROWS = 160_000


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    root = tmp_path_factory.mktemp("smoke_data")
    return chip_smoke.phase_data(str(root), ROWS, seed=3)


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_phases_on_cpu_match_pyarrow(data, capsys):
    """data -> three fused queries -> served, as main() chains them.
    Each phase raises on a wrong answer, a fallback, a degradation or
    a NOT_ON_TPU placement; the printed lines say the same."""
    from spark_rapids_tpu.api.session import TpuSparkSession

    spark = TpuSparkSession({**bench._session_conf(),
                             **chip_smoke.SMOKE_CONF})
    try:
        chip_smoke.phase_queries(spark, data)
        chip_smoke.phase_served(spark, data)
    finally:
        spark.stop()
    lines = _lines(capsys)
    queries = [ln for ln in lines if ln["phase"] == "query"]
    assert [q["name"] for q in queries] == [
        "scan_filter_agg_uncached", "q5_lookup_join_string_groupby",
        "dupkey_join_expanded"]
    for q in queries:
        assert q["engine"] == "fused" and q["correct"] is True, q
        comp = q["compile"]
        assert comp["programsCompiled"] + comp["cacheHits"] > 0, q
    # the uncached query really uploaded the fact columns it reads
    assert queries[0]["bytesMoved"]["h2d"] >= ROWS * (8 + 1 + 2)
    served = [ln for ln in lines if ln["phase"] == "served"]
    assert len(served) == 1 and served[0]["correct"] is True
    assert served[0]["answered"] == 2 * len(chip_smoke.SERVE_TENANTS)
    assert not any("ok" in ln for ln in lines)


def test_wrong_answer_fails_the_phase(data):
    """The comparison is live: an oracle that disagrees raises."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu.api.session import TpuSparkSession

    spark = TpuSparkSession(bench._session_conf())
    try:
        fact = spark.read.parquet(data.fact_dir)
        want = chip_smoke.cpu_scan_query(pq.read_table(data.fact_dir))
        bad = want.set_column(
            want.schema.get_field_index("store_count"), "store_count",
            [[c + 1 for c in want.column("store_count").to_pylist()]])
        with pytest.raises(AssertionError):
            chip_smoke.run_query(
                spark, "scan", chip_smoke.scan_query(fact),
                lambda out: chip_smoke.check_scan(out, bad))
    finally:
        spark.stop()


def test_lower_rung_fails_the_phase(data):
    """A query that ran, but not on the fused engine, has not passed."""
    from spark_rapids_tpu.api.session import TpuSparkSession

    spark = TpuSparkSession({
        **bench._session_conf(),
        "spark.rapids.sql.fusedExec.enabled": False})
    try:
        fact = spark.read.parquet(data.fact_dir)
        with pytest.raises(chip_smoke.SmokeFailure, match="engine="):
            chip_smoke.run_query(spark, "scan",
                                 chip_smoke.scan_query(fact),
                                 lambda out: None)
    finally:
        spark.stop()


def test_mesh_phase_on_four_virtual_devices(data, capsys):
    """--chips 4's only phase: q5 as one SPMD program against the
    one-chip fused answer and pyarrow, shards on four devices."""
    chip_smoke.phase_mesh(
        data, {**bench._session_conf(), **chip_smoke.SMOKE_CONF}, 4)
    lines = _lines(capsys)
    assert [ln.get("name") for ln in lines if ln["phase"] == "query"] \
        == ["q5_one_chip", "q5_mesh_4"]
    mesh = [ln for ln in lines if ln["phase"] == "mesh"][0]
    assert mesh["iciBytes"] > 0 and mesh["shuffleHostBytes"] == 0
    assert len(set(mesh["resultShardDevices"])) == 4


# --------------------------------------- off the chip, nothing passes

def _run(args, cwd):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("args", [
    ["chip_smoke.py", "--rows", "80000"],
    ["chip_smoke.py", "--chips", "4", "--rows", "80000"],
    ["bench.py"],
    ["bench.py", "--cold-probe"],
    ["bench.py", "--fleet"],
    ["-m", "spark_rapids_tpu.tools.multichip_bench"],
], ids=lambda a: " ".join(a[:3]))
def test_entry_points_fail_without_a_chip(args):
    """No CPU number under a device's name: every measuring entry point
    exits non-zero off the chip and prints no result at all."""
    r = _run(args, REPO)
    assert r.returncode != 0, r.stdout
    assert '"ok"' not in r.stdout and "{" not in r.stdout, r.stdout
    assert "device_kind" not in r.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script proves the repo, so without the repo it proves
    nothing: alone in a directory it exits non-zero, no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run(["chip_smoke.py"], str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
