"""Benchmark subsystem: registry-run workloads and BENCH_*.json artifacts
(DESIGN.md §7).

Entry points:

  * ``python -m repro.bench [--smoke]`` — run the suites, write
    ``BENCH_kernels.json`` / ``BENCH_e2e.json`` (see ``--help``).
  * ``scripts/bench.sh`` — full refresh of the baselines.
"""
from repro.bench.registry import WorkloadSpec, register, specs
from repro.bench.schema import SCHEMA_VERSION, SchemaError, validate

__all__ = [
    "SCHEMA_VERSION",
    "SchemaError",
    "WorkloadSpec",
    "register",
    "specs",
    "validate",
]
