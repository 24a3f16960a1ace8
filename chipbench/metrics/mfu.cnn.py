"""Whole-forward share of the chip's bf16 peak: images per second times
the forward's operations per image, over the peak."""
from harness.counts import cnn_forward_flops


def read(run):
    if not run.images or not run.peaks:
        return None
    rate = run.images / run.window_s
    return 100.0 * rate * cnn_forward_flops(run.config) / run.peaks["bf16_flops_per_s"]
