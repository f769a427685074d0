"""Shim loader (ShimLoader/SparkShimServiceProvider analog), plugin
lifecycle (Plugin.scala:412-684), api validation
(ApiValidation.scala), and dist packaging (parallel-worlds layout)."""

import json
import os

import pytest


def test_shim_loader_picks_current_jax():
    import jax

    from spark_rapids_tpu.shims import detect_shim_provider, get_shim

    mod = detect_shim_provider()
    assert mod.matches(jax.__version__)
    assert get_shim() is detect_shim_provider()


def test_shim_provider_selection_by_version():
    from spark_rapids_tpu.shims import detect_shim_provider

    current = detect_shim_provider("0.9.0")
    assert "current" in current.__name__


@pytest.mark.parametrize("version", ["0.4.30", "0.8.2", "0.10.0", "1.0.0"])
def test_unserved_jax_version_raises(version):
    """One installation is supported and pinned (pyproject.toml): any
    other jax raises ShimError naming what was probed, instead of
    running lowerings nothing has tested."""
    from spark_rapids_tpu.shims import ShimError, detect_shim_provider

    with pytest.raises(ShimError, match="jax_current"):
        detect_shim_provider(version)


def test_shim_worlds_export_identical_api():
    from spark_rapids_tpu.tools.api_validation import validate_shims

    assert validate_shims() == []


def test_operator_pair_signatures():
    from spark_rapids_tpu.tools.api_validation import (
        validate_operator_pairs,
    )

    assert validate_operator_pairs() == []


def test_shimmed_shard_map_runs():
    """The active world's shard_map executes a collective program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from spark_rapids_tpu.shims import get_shim

    devs = jax.devices()[:4]
    mesh = get_shim().make_mesh(devs, "x")

    def f(a):
        return jax.lax.psum(a, "x")

    out = get_shim().shard_map(f, mesh, (P("x"),), P())(
        jnp.arange(8.0))
    assert float(out.sum()) == float(jnp.arange(8.0).sum()) * 1

    # matches per-shard psum: every element equals total of its column
    # pairs across shards; just sanity-check shape/finite
    assert out.shape == (2,)


def test_plugin_lifecycle():
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.plugin import (
        ColumnarOverrideRules,
        TpuDriverPlugin,
        _is_fatal_device_error,
    )

    spark = TpuSparkSession({})
    try:
        assert spark._executor_plugin.initialized
        assert isinstance(spark._conf_map, dict)
        conf_map = TpuDriverPlugin().init(spark.rapids_conf)
        assert isinstance(conf_map, dict)
        rules = ColumnarOverrideRules()
        assert rules.pre_columnar_transitions(
            spark.rapids_conf) is not None
        # fatal classification: OOM-ish errors are NOT fatal
        assert not _is_fatal_device_error(MemoryError("oom"))
        assert not spark._executor_plugin.on_task_failed(
            ValueError("x"))
    finally:
        spark.stop()


def test_driver_plugin_warns_unknown_rapids_keys():
    import warnings

    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.plugin import TpuDriverPlugin

    conf = rc.RapidsConf({"spark.rapids.sql.noSuchKnob": 1})
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        TpuDriverPlugin().init(conf)
    assert any("noSuchKnob" in str(x.message) for x in w)


def test_package_dist(tmp_path):
    from spark_rapids_tpu.tools.package_dist import build_dist

    target = build_dist(str(tmp_path))
    manifest = json.load(open(os.path.join(target, "MANIFEST.json")))
    assert manifest["version"]
    assert "jax_current" in manifest["shim_worlds"]
    assert os.path.isdir(os.path.join(target, "spark_rapids_tpu",
                                      "shims"))
    # the packaged tree is importable standalone
    import subprocess
    import sys

    code = ("import spark_rapids_tpu, spark_rapids_tpu.shims as s; "
            "print(s.get_shim().description())")
    env = dict(os.environ, PYTHONPATH=target, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr
    assert "world" in out.stdout


def test_fatal_policy_invoked_on_task_failure(monkeypatch):
    """exec/base.py routes task exceptions through
    TpuExecutorPlugin.on_task_failed (Plugin.scala onTaskFailed)."""
    import pyarrow as pa

    from spark_rapids_tpu import plugin as plugin_mod
    from spark_rapids_tpu.api.session import TpuSparkSession
    from spark_rapids_tpu.api import functions as F

    seen = []
    orig = plugin_mod.TpuExecutorPlugin.on_task_failed

    def spy(self, exc):
        seen.append(type(exc).__name__)
        return orig(self, exc)

    monkeypatch.setattr(plugin_mod.TpuExecutorPlugin, "on_task_failed",
                        spy)
    spark = TpuSparkSession({})
    try:
        df = spark.createDataFrame(pa.table({"x": pa.array([1, 2])}))
        bad = df.select(
            F.udf(lambda v: 1 // 0, "bigint")(F.col("x")).alias("y"))
        with pytest.raises(Exception):
            bad.collect_arrow()
        assert seen, "on_task_failed was not invoked"
    finally:
        spark.stop()
