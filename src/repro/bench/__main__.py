"""``python -m repro.bench`` — run / validate / gate the benchmark suites.

Modes:

  run (default)      execute registered workloads, write BENCH_*.json
    --smoke          smoke tier only (CI entry; deterministic keys)
    --suite S        kernels | e2e | all (default all)
    --only SUBSTR    filter workloads by name substring
    --out-dir DIR    where BENCH_*.json land (default: repo root)
    --iters/--warmup harness budget per measurement

  --list             print registered workload names and exit
  --validate F [F..] schema-check existing BENCH json files and exit
  --gate-against DIR compare this run's wall-clock to the baselines in
                     DIR; fail (exit 1) on regression > --tolerance
                     (default 0.20) after machine-drift normalization

Gate semantics (DESIGN.md §7): CI runners differ in absolute speed
from whatever host produced the committed baselines, and single
ms-scale CPU timings carry 30%+ run-to-run noise — so the gate neither
compares raw wall-clock nor gates single entries at the tolerance.
Instead it:

  a. compares ``min_us`` (the minimum over iters estimates the noise
     floor; medians absorb every scheduler hiccup),
  b. skips interpret-mode pallas timings when the baseline backend is
     CPU (recorded for the trend, but not a perf signal there),
  c. aggregates entry ratios into per-workload-kind groups (conv2d,
     matmul, cnn_fwd, ...) by geometric mean — noise averages out,
     while a real kernel regression moves its whole group,
  d. normalizes each group by the leave-one-group-out geomean over the
     OTHER groups' entries, pooled across suites (uniform machine
     drift cancels, but a group cannot hide its own regression inside
     the drift estimate), and
  e. fails when a group's normalized geomean exceeds
     ``1 + tolerance * (1 + 2/sqrt(n))`` — the 1/sqrt(n) term widens
     the bound for small groups, whose geomean is itself noisy — or
     when any single entry exceeds 1 + 4*tolerance (catastrophic
     check).

Entries faster than ``--min-us`` in the baseline are skipped as timer
noise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import numpy as np

from repro.bench import registry, schema
from repro.runtime.compile_cache import enable_compile_cache

REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def bench_filename(suite: str) -> str:
    return f"BENCH_{suite}.json"


def run_suite(
    suite: str, *, smoke_only: bool, only: str | None, iters: int, warmup: int
) -> dict:
    entries = {}
    for spec in registry.specs(suite, smoke_only=smoke_only, only=only):
        print(f"[bench] {suite}: {spec.name}", file=sys.stderr)
        body = spec.run(iters, warmup)
        body["tier"] = spec.tier
        entries[spec.name] = body
    return {
        "schema_version": schema.SCHEMA_VERSION,
        "suite": suite,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "smoke_only": smoke_only,
        "entries": entries,
    }


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _collect_ratios(new_doc: dict, base: dict, min_us: float) -> list[tuple]:
    """``(group, name, impl, base_us, new_us, ratio, entry_gate)`` per
    comparable timing.

    ``entry_gate`` marks timings eligible for the single-entry
    catastrophic check. A ``selected`` timing whose auto-dispatch picked
    the SAME impl as the baseline run is exempt: its wall-clock
    duplicates that impl's own (already gated) key, so re-checking it at
    the x1.8 cliff only doubles one noisy timing's flake exposure. It
    still votes in the group geomean — and when the dispatch FLIPPED
    impls between runs, the full check applies: a flip that loses 80%
    is exactly the dispatch regression the ``selected`` key exists to
    catch."""
    skip_pallas = base.get("backend") == "cpu"
    out = []
    for name, new_e in new_doc["entries"].items():
        base_e = base["entries"].get(name)
        if base_e is None:
            continue
        for impl, new_t in new_e["wall_us"].items():
            base_t = base_e["wall_us"].get(impl)
            if not new_t or not base_t or base_t["min_us"] < min_us:
                continue
            if impl == "pallas" and skip_pallas:
                continue  # interpret-mode wall-clock: trend data, not a signal
            entry_gate = True
            if impl == "selected" and new_t.get("impl") == base_t.get("impl"):
                entry_gate = False
            if impl == "selected" and new_t.get("impl") == "pallas" and skip_pallas:
                continue
            out.append(
                (new_e["workload"], name, impl, base_t["min_us"], new_t["min_us"],
                 new_t["min_us"] / base_t["min_us"], entry_gate)
            )
    return out


def _geomean(xs) -> float:
    return float(np.exp(np.mean(np.log(xs))))


def _gate(ratios: list[tuple], tolerance: float) -> list[str]:
    """Failure messages for wall-clock regressions (see module docstring)."""
    if not ratios:
        return ["no comparable entries between this run and the baselines"]
    groups: dict[str, list[float]] = {}
    for group, _name, _impl, _base, _new, ratio, _eg in ratios:
        groups.setdefault(group, []).append(ratio)
    # Drift per group is estimated leave-one-group-out: a group's own
    # regression must not inflate the drift it is normalized by (with 7
    # of 15 timings in one group, a real 30% regression there would
    # otherwise self-mask to ~15%). Single-group runs (--only) have no
    # outside reference at all, so they gate on RAW ratios (drift=1.0)
    # — normalizing by the group's own geomean would pass any uniform
    # regression unconditionally.
    pooled = _geomean([r[5] for r in ratios])
    drift_logo = {
        g: _geomean([r[5] for r in ratios if r[0] != g]) if len(groups) > 1 else 1.0
        for g in groups
    }
    print(f"[gate] pooled drift x{pooled:.2f} over {len(ratios)} timings; "
          f"per-group LOGO drift {{{', '.join(f'{g}: x{d:.2f}' for g, d in sorted(drift_logo.items()))}}}",
          file=sys.stderr)

    failures = []
    for group, name, impl, base_us, new_us, ratio, entry_gate in ratios:
        normalized = ratio / drift_logo[group]
        line = (
            f"{name} [{impl}]: {base_us:.0f}us -> {new_us:.0f}us "
            f"(x{ratio:.2f} raw, x{normalized:.2f} drift-normalized)"
        )
        if entry_gate and normalized > 1.0 + 4.0 * tolerance:
            failures.append(f"REGRESSION (entry, >x{1 + 4 * tolerance:.1f}) " + line)
        else:
            print("[gate] ok " + line, file=sys.stderr)

    for group, rs in sorted(groups.items()):
        g_norm = _geomean(rs) / drift_logo[group]
        # The geomean of n noisy timings has ~1/sqrt(n) the spread of a
        # single one: small groups get a proportionally wider threshold
        # so ms-scale CPU variance doesn't flake CI, while a whole-group
        # regression well beyond its noise still fails.
        tol_eff = tolerance * (1.0 + 2.0 / len(rs) ** 0.5)
        line = (
            f"group {group}: x{g_norm:.2f} drift-normalized geomean over "
            f"{len(rs)} timings (threshold x{1 + tol_eff:.2f})"
        )
        if g_norm > 1.0 + tol_eff:
            failures.append("REGRESSION (group) " + line)
        else:
            print("[gate] ok " + line, file=sys.stderr)
    return failures


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.bench")
    ap.add_argument("--smoke", action="store_true", help="smoke tier only")
    ap.add_argument("--suite", default="all", choices=["kernels", "e2e", "all"])
    ap.add_argument("--only", default=None, help="substring filter on workload names")
    ap.add_argument("--out-dir", default=REPO_ROOT)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--validate", nargs="+", default=None, metavar="FILE")
    ap.add_argument("--gate-against", default=None, metavar="DIR")
    ap.add_argument("--tolerance", type=float, default=0.20)
    ap.add_argument("--min-us", type=float, default=200.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    suites = list(schema.SUITES) if args.suite == "all" else [args.suite]

    if args.list:
        for suite in suites:
            for spec in registry.specs(suite, smoke_only=args.smoke, only=args.only):
                print(f"{suite:8s} {spec.tier:6s} {spec.name}")
        return 0

    if args.validate:
        for path in args.validate:
            doc = _load(path)
            schema.validate(doc)
            print(f"[schema] ok: {path} ({len(doc['entries'])} entries, "
                  f"suite={doc['suite']})")
        return 0

    iters = args.iters if args.iters is not None else 5
    warmup = args.warmup if args.warmup is not None else (1 if args.smoke else 2)

    failures: list[str] = []
    ratios: list[tuple] = []
    for suite in suites:
        doc = run_suite(
            suite, smoke_only=args.smoke, only=args.only, iters=iters, warmup=warmup
        )
        if not doc["entries"]:
            print(f"[bench] {suite}: no workloads selected, skipping", file=sys.stderr)
            continue
        schema.validate(doc, suite=suite)
        out_path = os.path.join(args.out_dir, bench_filename(suite))
        os.makedirs(args.out_dir, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[bench] wrote {out_path} ({len(doc['entries'])} entries)")
        if args.gate_against:
            base_path = os.path.join(args.gate_against, bench_filename(suite))
            if not os.path.exists(base_path):
                failures.append(f"baseline {base_path} missing (commit via scripts/bench.sh)")
                continue
            base = _load(base_path)
            schema.validate(base, suite=suite)
            ratios += _collect_ratios(doc, base, args.min_us)

    # Drift normalization pools every suite's ratios: more samples make
    # the machine-speed estimate stable and keep a regression in one
    # group from hiding inside its own suite's drift.
    if args.gate_against and not failures:
        failures += _gate(ratios, args.tolerance)

    if failures:
        for msg in failures:
            print("[gate] " + msg, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
