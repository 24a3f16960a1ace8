"""Device time of the packed GEMMs in one chip's decode step: the ops of
the engine's meshed ``decode_greedy`` program under the program's
``elp_bsd_matmul`` scope (each chip's kernel on its own shard, with the
pads and slices around it), over the runs of that program in the traced
window. The sum that a row-parallel GEMM's partial outputs take across
the chips is under ``tp_collective``, outside this scope: it counts in
``collective_ms.lm_tp``.

An op counts under the scope of its fusion's root.
"""
from harness.scopes_tp import per_run_ms

PROGRAM = "decode_greedy"
SCOPE = "elp_bsd_matmul"


def read(run):
    return per_run_ms(run, PROGRAM, SCOPE)
