"""Device time of the packed GEMMs in one decode step: the ops of the
engine's ``decode_greedy`` program under the program's
``elp_bsd_matmul`` scope, which every packed matmul opens whatever
implements it (the tiled or fused Pallas kernel, or XLA's decode and
dot), over the runs of that program in the traced window. The kernels
decode codes to weights inside themselves, so their decode counts
here too, as do the pads and slices around them.

An op counts under the scope of its fusion's root: an activation fused
into a GEMM's operand counts as that GEMM, and a GEMM's output fused
into the op after it counts as that op.
"""
from harness.scopes import per_run_ms

PROGRAM = "decode_greedy"
SCOPE = "elp_bsd_matmul"


def read(run):
    return per_run_ms(run, PROGRAM, SCOPE)
