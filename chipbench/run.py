"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights drawn from the seed on the device, compiles, warm-up)
counts as ``setup_s``; then the cell's traffic runs for ``--seconds``;
then what the window produced is checked against the plain reference.
With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window and from the harness's own spans and counts.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
and last ``compared``: each number the check compared with its limit,
which also close stderr. Without the TPUs the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH_DIR)

from harness import spec  # noqa: E402
from harness.record import Run  # noqa: E402


def measure(
    cell_name: str,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    bench: dict | None = None,
    work: tuple[dict, dict, dict] | None = None,
    chip: bool = True,
    control: bool = False,
    t_start: float | None = None,
) -> tuple[Run, dict, list[dict]]:
    """Set up, measure and check one cell; the run and its metrics.

    ``chip=False`` skips the look for a TPU (the CPU tests drive the rest
    of a run at a small size with it); every device number then reads as
    not measured.
    """
    import importlib

    from harness import device
    from harness.peaks import peaks_for

    bench = bench or spec.load_benchmark()
    workload, config, cell = work or spec.find_cell(bench, cell_name)
    if chip:
        info = device.check_devices(int(workload["chips"]))
        peaks = peaks_for(info["kind"])
    else:
        import jax

        d = jax.devices()[0]
        info = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
        peaks = {}
    if chip:
        device.enable_compile_cache(ROOT)
    run = Run(config, cell, peaks, seed, float(seconds), traced, t_start=t_start or T_START)
    driver = importlib.import_module(f"harness.{config['driver']}")
    driver.run(run, control=control)
    info["memory_peak_bytes"] = run.memory_peak
    if traced and run.trace is not None:
        info["busy_s"] = run.trace.busy_s
        info["window_s"] = run.trace.window_s
    kind = "per_layer" if traced else "end_to_end"
    wanted = (spec.per_layer_metrics if traced else spec.end_to_end_metrics)(bench, cell_name)
    metrics = {}
    for m in wanted:
        value = spec.load_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {
        "correct": run.correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": info,
    }
    if traced and run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
    result["compared"] = run.compared
    run.note(f"[{kind}] {len(metrics)} of {len(wanted)} metrics read")
    return run, result, wanted


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a non-negative whole number")
    run, result, _ = measure(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in result["compared"].items():
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} {'ok' if ok else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
