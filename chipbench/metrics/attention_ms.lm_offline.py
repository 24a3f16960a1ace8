"""Device time of attention's core in one decode step: the ops of the
engine's ``decode_greedy`` program under the program's ``attn_core``
scope (the cache write, ``repeat_kv``, scores, softmax and the weighted
sum over the cache; the q/k/v/o projections are outside it), over the
runs of that program in the traced window.

An op counts under the scope of its fusion's root: a projection's
output fused into the cache write counts as attention, and attention's
output fused into the ``wo`` GEMM's operand counts as that GEMM.
"""
from harness.scopes import per_run_ms

PROGRAM = "decode_greedy"
SCOPE = "attn_core"


def read(run):
    return per_run_ms(run, PROGRAM, SCOPE)
