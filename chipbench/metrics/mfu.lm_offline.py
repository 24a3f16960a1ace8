"""Whole-step share of the chip's bf16 peak: output tokens per second
times the matmul operations a token needs (every projection and the
head), over the peak."""
from harness.counts import decoder_flops_per_token


def read(run):
    if not run.tokens or not run.peaks:
        return None
    rate = run.tokens / run.window_s
    return 100.0 * rate * decoder_flops_per_token(run.config) / run.peaks["bf16_flops_per_s"]
