"""Device time of the collectives in one chip's decode step: every
all-reduce, all-gather, reduce-scatter, collective-permute and
all-to-all (async start and done included) of the engine's meshed
``decode_greedy`` program, over the runs of that program in the traced
window. The part under the program's ``tp_collective`` scope (the sums
of the row-parallel packed GEMMs) is printed beside it; the rest is
what the partitioner adds (the embedding lookup and the arg-max over
the vocabulary split over the chips)."""
import sys

from harness.scopes_tp import collective_time_in, per_run_ms

PROGRAM = "decode_greedy"
SCOPE = "tp_collective"


def read(run):
    if run.trace is None:
        return None
    runs, _ = run.trace.module_time(PROGRAM)
    total, by_op = collective_time_in(run.trace, PROGRAM)
    if not runs or total <= 0:
        return None
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:4]
    print(
        f"[collective_ms.lm_tp] {len(by_op)} collective ops, under {SCOPE} "
        f"{per_run_ms(run, PROGRAM, SCOPE)} ms a run; largest: "
        + "; ".join(f"{op[:90]} {1e3 * t / runs:.4f} ms" for op, t in top),
        file=sys.stderr,
    )
    return 1e3 * total / runs
