"""Each chip's packed decode GEMMs' share of their roofline in the traced
window.

The time is the summed device time of the packed-GEMM kernels inside the
meshed decode-step programs: the custom calls that the program's
``_quantized_matmul`` jit lowers to a Pallas kernel (``tpu_custom_call``),
one per shard. The least time is one chip's share of the step, counted
from the shapes by ``harness.counts_tp``: per local GEMM the larger of
its operations at the live rows over the chip's bf16 peak and its bytes
(its codes, the scale, activations in and out) over the chip's HBM
bandwidth, summed over the step's GEMMs. The trace sums kernel time and
program runs over the chips, so the least time counts once per run of
the program on each chip. Every slot is live in the cell this reads.
"""
from harness.counts_tp import decode_step_gemm_bounds, mesh_chips

PROGRAM = "decode_greedy"
KERNEL = ("_quantized_matmul", "tpu_custom_call")


def read(run):
    if run.trace is None or not run.peaks:
        return None
    runs, _ = run.trace.module_time(PROGRAM)
    kernel_s = run.trace.op_time_in(PROGRAM, KERNEL)
    if not runs or kernel_s <= 0:
        return None
    least = decode_step_gemm_bounds(
        run.config, int(run.cell["n_slots"]), run.peaks, mesh_chips(run.cell)
    )["least_s"]
    return 100.0 * runs * least / kernel_s
