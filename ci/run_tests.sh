#!/usr/bin/env bash
# Premerge gate — the jenkins/spark-premerge-build.sh role.
# Runs the suite and the gates on the virtual 8-device CPU mesh (no
# hardware needed). What needs a chip is not here: `python chip_smoke.py`
# and `python bench.py` run on a machine with a TPU and exit non-zero
# anywhere else (README "Running").
set -euo pipefail
cd "$(dirname "$0")/.."

export XLA_FLAGS="--xla_force_host_platform_device_count=8"
export JAX_PLATFORMS=cpu

echo "== static-analysis gate (srtpu-lint, zero findings) =="
ci/static_check.sh

echo "== unit + differential suite (virtual 8-device mesh) =="
python -m pytest tests/ -q

echo "== chaos gate (seeded fault injection at every site) =="
ci/chaos_check.sh

echo "== event-log gate (schema, round-trip, qualification) =="
ci/eventlog_check.sh

echo "== concurrency gate (admission + chaos + cancel storm) =="
ci/concurrency_check.sh

echo "== telemetry gate (ledger/eventlog consistency + HTTP) =="
ci/telemetry_check.sh

echo "== encoded-execution gate (bytes moved + oracle equality) =="
ci/encoded_check.sh

echo "== streaming gate (out-of-core window + overlap + chaos) =="
ci/streaming_check.sh

echo "== write gate (exactly-once commit + crash-safe overwrite + Delta OCC) =="
ci/write_check.sh

echo "== device-failure gate (fence + warm recovery + epoch) =="
ci/devicefail_check.sh

echo "== multichip gate (SPMD oracle + ICI bytes + chip loss) =="
ci/multichip_check.sh

echo "== multi-host gate (gloo cluster + DCN placement + host loss) =="
ci/multihost_check.sh

echo "== serving gate (multi-tenant daemon + plan cache + drain) =="
ci/serve_check.sh

echo "== fleet gate (replica supervisor + front door + failover) =="
ci/fleet_check.sh

echo "== packaging =="
python -m spark_rapids_tpu.tools.package_dist --check 2>/dev/null || \
    python -c "import spark_rapids_tpu; print('import ok')"

echo "CI PASS"
