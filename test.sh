#!/usr/bin/env bash
# Tiered verification — one entry point, one environment setup, two tiers.
# CI's gate runs the FULL suite (PYTHONPATH=src python -m pytest -x -q);
# locally run the fast tier while iterating and the slow tier before
# shipping — together they are exactly CI's coverage.
#
#   ./test.sh              # fast tier: slow marker excluded; includes
#                          #   the checkpoint/resume roundtrip suite
#                          #   (tests/test_persistence.py: golden resume
#                          #   parity, estimator save/load)
#   ./test.sh --slow       # slow tier: multi-device subprocesses
#                          #   (incl. elastic re-mesh resume), launchers,
#                          #   streaming smoke, and the perf smokes
#                          #   (kernels_bench/checkpoint_bench --smoke,
#                          #   emitting BENCH_*.json)
#   ./test.sh --kernels    # kernel tier: the kernel-facing suites
#                          #   (kernels v1/v2, conformance, bounds,
#                          #   locality, hierarchy) in interpret mode,
#                          #   plus the Mosaic compile rehearsals for a
#                          #   described v5e (tests/test_tpu_compile.py)
#   ./test.sh -m 'conformance'   # any extra pytest args pass through
#   ./test.sh -m 'perf'          # just the benchmark-harness smokes
#   ./test.sh tests/test_persistence.py   # just the persistence suite
#
# Notes:
#   * PYTHONPATH=src — the package is not installed in the container.
#   * XLA_FLAGS forces 8 virtual host devices so mesh-shaped code paths are
#     exercised; tests that need a specific device count (test_distributed)
#     spawn subprocesses that override XLA_FLAGS themselves.
set -euo pipefail
cd "$(dirname "$0")"

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"
# Containers with libtpu installed stall for minutes probing GCP instance
# metadata unless the platform is pinned.  The tests run on the CPU, the
# Pallas kernels in interpret mode; the chip is exercised by
# chip_smoke.py (README.md).
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

if [[ "${1:-}" == "--kernels" ]]; then
    shift
    exec python -m pytest -x -q -m 'not slow' \
        tests/test_kernels.py tests/test_kernels_v2.py \
        tests/test_conformance.py tests/test_bounds.py \
        tests/test_locality.py tests/test_hierarchy.py \
        tests/test_tpu_compile.py "$@"
fi
if [[ "${1:-}" == "--slow" ]]; then
    shift
    exec python -m pytest -x -q -m slow "$@"
fi
exec python -m pytest -x -q -m 'not slow' "$@"
