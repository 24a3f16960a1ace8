"""Workload registry: named, deterministic benchmark specs.

A :class:`WorkloadSpec` is a concrete, fully-parameterized measurement
(fixed shapes, fixed seeds) — never "whatever the script felt like
printing". Specs belong to a suite (``kernels`` / ``e2e``) and a tier:

  * ``smoke`` — small shapes; run by CI on every PR, gated against the
    committed baselines. Deterministic keys/shapes by construction.
  * ``full``  — the real measurement shapes; run by ``scripts/bench.sh``
    when refreshing baselines (CPU wall-clock for pallas interpret mode
    is skipped per-workload where the grid is too large to be useful).

``--smoke`` selects the smoke tier; a full run executes both tiers, so
committed ``BENCH_*.json`` baselines are a superset of what CI
re-measures and the regression gate always finds its keys.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from repro.bench.schema import SUITES, TIERS


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """One named measurement.

    ``run`` takes the harness iteration budget ``(iters, warmup)`` and
    returns the entry body: a dict with ``workload``, ``shape``,
    ``wall_us`` (impl-keyed timings), and optional ``hlo`` / ``quality``
    / ``bytes`` blocks (see :mod:`repro.bench.schema`).
    """

    name: str
    suite: str
    tier: str
    run: Callable[[int, int], dict]
    tags: tuple[str, ...] = ()

    def __post_init__(self):
        if self.suite not in SUITES:
            raise ValueError(f"unknown suite {self.suite!r} for {self.name!r}")
        if self.tier not in TIERS:
            raise ValueError(f"unknown tier {self.tier!r} for {self.name!r}")


_REGISTRY: dict[str, WorkloadSpec] = {}


def register(spec: WorkloadSpec) -> WorkloadSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"duplicate workload name {spec.name!r}")
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> WorkloadSpec:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def specs(
    suite: str | None = None, *, smoke_only: bool = False, only: str | None = None
) -> list[WorkloadSpec]:
    """Registered specs, name-sorted (run order is part of determinism)."""
    _ensure_loaded()
    out = []
    for name in sorted(_REGISTRY):
        s = _REGISTRY[name]
        if suite is not None and s.suite != suite:
            continue
        if smoke_only and s.tier != "smoke":
            continue
        if only is not None and only not in s.name:
            continue
        out.append(s)
    return out


def _ensure_loaded() -> None:
    # Workload definitions import models/kernels, which import this
    # module's consumers — registration is deferred to first query.
    from repro.bench import workloads  # noqa: F401
