"""Whole-step share of the mesh's bf16 peak: output tokens per second
times the matmul operations a token needs (every projection and the
head), over the peak of every chip of the cell's mesh."""
from harness.counts import decoder_flops_per_token
from harness.counts_tp import mesh_chips


def read(run):
    if not run.tokens or not run.peaks:
        return None
    rate = run.tokens / run.window_s
    peak = mesh_chips(run.cell) * run.peaks["bf16_flops_per_s"]
    return 100.0 * rate * decoder_flops_per_token(run.config) / peak
