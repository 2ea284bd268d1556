#!/usr/bin/env bash
# CI gate: tier-1 tests + a quick benchmark smoke run.
#
#   bash scripts/ci.sh
#
# Dependency install is best-effort so the script also works in
# air-gapped containers that bake the toolchain into the image.
set -uo pipefail
cd "$(dirname "$0")/.."

pip install -r requirements.txt \
    || echo "ci: pip install failed; assuming preinstalled deps" >&2
# property-based modules importorskip on hypothesis — install it
# explicitly so the 4 property tests run in CI instead of skipping
pip install hypothesis \
    || echo "ci: hypothesis install failed; property tests will skip" >&2

set -e
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests (CPU; Pallas in interpret mode) =="
JAX_PLATFORMS=cpu python -m pytest -x -q

echo "== benchmark smoke (analytic, no roofline) =="
python -m benchmarks.run --quick --skip-roofline > /dev/null

# the machine-model cycles gate (benchmarks/roofline.py --smoke) and
# the simulator perf-trajectory gate (benchmarks/bench_sim.py --smoke)
# run as their own named CI jobs (machine-smoke / bench-smoke in
# ci.yml) so a drift failure is legible at a glance; run them here
# manually when iterating locally

echo "ci: OK"
