"""Find the highest open-loop rate an LM cell's engine sustains.

    python3 chipbench/tools/sweep.py --workload qwen3-8b.chat --rates 1,2,3 \
        --seconds 30 --seed 5

One engine, set up and warmed once, serves the cell's traffic at each
rate in turn for ``--seconds`` (then drains). Per rate it prints the
requests due and finished, TTFT p50/p95 of the first and the last third
of the arrivals (a queue that grows shows as the last third's TTFT
running away from the first's), the ITL p95 and the output tokens/s.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
sys.path.insert(0, BENCH_DIR)

from harness import device, lm_serve, spec, tracing  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402
from harness.record import Run  # noqa: E402
from harness.stats import percentile  # noqa: E402


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--grace", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    work, config, cell = spec.find_cell(bench, args.workload)
    info = device.check_devices(int(work["chips"]))
    device.enable_compile_cache(spec.ROOT)
    peaks = peaks_for(info["kind"])
    base = Run(config, cell, peaks, args.seed, args.seconds, False, t_start=t_start)
    served = lm_serve.setup(base)
    print(f"SWEEP setup {time.perf_counter() - t_start:.1f} s", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        c = dict(cell, rate_per_s=rate, grace_s=args.grace)
        run = Run(config, c, base.peaks, args.seed, args.seconds, False)
        spans = tracing.Spans(False)
        lm_serve._open_window(served.engine, run, c, args.seed, served.cfg.vocab, spans)
        served.engine.run()
        reqs = sorted(run.requests, key=lambda r: r.due)
        third = max(1, len(reqs) // 3)
        ttft = lambda rs, q: 1e3 * percentile([r.ttft_s for r in rs], q)  # noqa: E731
        gaps = [g for r in reqs for g in r.gaps_s()]
        row = {
            "rate": rate, "due": len(reqs), "unfinished": run.failed,
            "ttft_p50_first": ttft(reqs[:third], 50), "ttft_p95_first": ttft(reqs[:third], 95),
            "ttft_p50_last": ttft(reqs[-third:], 50), "ttft_p95_last": ttft(reqs[-third:], 95),
            "ttft_p95": ttft(reqs, 95), "itl_p50": 1e3 * percentile(gaps, 50),
            "itl_p95": 1e3 * percentile(gaps, 95), "tokens_per_s": run.tokens / run.window_s,
            "window_s": run.window_s,
        }
        print("SWEEP " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
