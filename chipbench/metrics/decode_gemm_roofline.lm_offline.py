"""Packed decode GEMMs' share of their roofline in the traced window.

The time is the summed device time of the packed-GEMM kernels inside
the decode-step programs: the custom calls that the program's
``_quantized_matmul`` jit lowers to a Pallas kernel
(``tpu_custom_call``). The least time, per GEMM, is the larger of
its operations at the live rows over the bf16 peak and its bytes
(codes, scales, activations in and out) over HBM bandwidth, counted
from the shapes by ``harness.counts``; summed over the step's GEMMs and
the traced steps. Every slot is live in the cell this reads.
"""
from harness.counts import decode_step_gemm_bounds

PROGRAM = "decode_greedy"
KERNEL = ("_quantized_matmul", "tpu_custom_call")


def read(run):
    if run.trace is None or not run.peaks:
        return None
    steps, _ = run.trace.module_time(PROGRAM)
    kernel_s = run.trace.op_time_in(PROGRAM, KERNEL)
    if not steps or kernel_s <= 0:
        return None
    least = decode_step_gemm_bounds(run.config, int(run.cell["n_slots"]), run.peaks)["least_s"]
    return 100.0 * steps * least / kernel_s
