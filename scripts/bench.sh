#!/usr/bin/env bash
# Full benchmark refresh: runs BOTH workload tiers (smoke + full) of
# both suites and rewrites the committed baselines at the repo root,
# BENCH_kernels.json and BENCH_e2e.json.
#
# Run this (and commit the result) whenever a PR intentionally changes
# performance or adds workloads; CI's bench-smoke job gates every PR's
# smoke-tier wall-clock against these files (DESIGN.md §7).
#
# Usage: scripts/bench.sh [extra args for python -m repro.bench]
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== full benchmark run =="
python -m repro.bench --out-dir . "$@"

echo "== validate emitted artifacts =="
python -m repro.bench --validate BENCH_kernels.json BENCH_e2e.json
