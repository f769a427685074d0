"""Test configuration: force the CPU backend with a virtual 8-device mesh.

Mirrors the reference's testing stance (SURVEY.md section 4): correctness
suites run without special hardware; distributed semantics are tested on a
virtual device mesh. The tests never take a chip, however pytest was
started: the platform is pinned to `cpu` through jax.config after import,
before any backend initializes (the driver's command also sets
JAX_PLATFORMS=cpu). What only the TPU's compiler can say is asked of a
DESCRIBED device, inside tests/test_chip_compile.py's own fixture.
"""

import os
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Isolate the persistent compile cache per test run: the layer stays
# ENABLED (cross-module recompiles load from disk after the per-module
# jit_cache clear below), but state never leaks between runs — tests
# asserting XLA compile counts must not see a previous run's entries.
# Explicit per-test dirs (test_compile_cache.py) still win: env-derived
# conf values are defaults, not overrides. JAX_COMPILATION_CACHE_DIR
# outranks both (runtime/compile_cache.py resolve_dirs) and jax reads
# it at import, so a run's isolation starts by dropping it here.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ.setdefault(
    "SPARK_RAPIDS_TPU_CONF_spark__rapids__tpu__compileCache__dir",
    tempfile.mkdtemp(prefix="srtpu_test_compile_cache_"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert devs[0].platform == "cpu", devs
    return devs


@pytest.fixture(autouse=True, scope="module")
def _clear_jit_cache_between_modules():
    """Release compiled programs after each test module.

    The full suite compiles 700+ XLA CPU executables in one process;
    keeping them all loaded segfaulted XLA's JIT late in the run
    (deterministic SIGSEGV inside backend_compile_and_load at ~97%).
    Bounding the live-executable set per module avoids the crash and
    caps memory; programs shared across modules simply recompile."""
    yield
    from spark_rapids_tpu.runtime import jit_cache

    jit_cache.clear()
    jax.clear_caches()
