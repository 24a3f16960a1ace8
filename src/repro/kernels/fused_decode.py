"""Fused decode-step Pallas kernel: shift-add ELP_BSD decode + GEMV-ish matmul.

The serve hot path is a ``[B, 1]`` hidden state against a packed weight
— M is tiny (the slot batch), K·N is the whole layer. The general
:mod:`repro.kernels.elp_bsd_matmul` kernel tiles M too; this kernel
specializes the decode step:

  * the full M strip rides along in VMEM (no M grid dimension),
  * per (n, k) tile the packed codes are unpacked from their
    VMEM-resident tiles, a slab of K rows at a time, and the level
    table is applied via *shift-add*
    (:func:`repro.kernels.ref.decode_values_shift_add`): each digit's
    ``±2^shift`` term is built by one integer construction of the
    float32 sign/exponent fields — the VPU reading of the paper's
    shift-add MAC (Sec. IV-4) — and the digit terms accumulate into the
    weight slab, which feeds the MXU directly (bf16 operands where
    exact, :func:`repro.kernels.elp_bsd_matmul.mxu_operand_dtype`). No
    float weight tensor ever exists outside the current VMEM slab,
  * a float32 VMEM accumulator carries the K loop, scale applied once
    at the end.

Tiles default to 128 multiples. On a TPU, ``quantized_matmul(impl=
"auto")`` sizes them from the shape (:func:`repro.kernels.ops.tpu_blocks`):
a grid step has a fixed cost (about 0.35 µs on a v5e), so each step
carries a panel of about 1 MB of codes.

On non-TPU backends the public entry lowers to the single-pass XLA form
of the same datapath (see ``quantized_matmul(impl="pallas_fused")`` in
:mod:`repro.kernels.ops`); the Pallas kernel itself is parity-gated
bit-level in interpret mode against :mod:`repro.kernels.ref`
(DESIGN.md §14).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.elp_bsd import ElpBsdFormat
from repro.kernels.elp_bsd_matmul import accumulate_panel, nibble_plane_order

Array = jax.Array

# The whole M strip sits in VMEM per grid step; decode batches are tiny
# (slots × spec_k ≲ 64). Past this, use elp_bsd_matmul's M tiling.
MAX_FUSED_M = 256


def _fused_kernel(
    x_ref, c_ref, sf_ref, o_ref, acc_ref, *, fmt: ElpBsdFormat, nibble: bool, n_k: int
):
    """One (M, bn) output strip; grid = (n, k) with k innermost."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate_panel(x_ref, c_ref, acc_ref, fmt=fmt, nibble=nibble)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * sf_ref[0, 0]).astype(o_ref.dtype)


def fused_decode_matmul(
    x: Array,
    codes: Array,
    sf: Array,
    fmt: ElpBsdFormat,
    *,
    nibble: bool = False,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=None,
    interpret: bool | None = None,
) -> Array:
    """``x[M,K] @ dequant(codes)[K,N]`` for decode-step M (≤ MAX_FUSED_M).

    K and N must tile evenly by the block sizes (the ops wrapper pads);
    M rides whole. ``sf`` is the per-layer scale as a ``(1, 1)`` float32
    array (per-channel scales factor out in the wrapper).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if x.ndim != 2 or codes.ndim != 2:
        raise ValueError(
            f"fused_decode_matmul takes x[M, K] and codes[K', N]; got x{tuple(x.shape)}, "
            f"codes{tuple(codes.shape)}"
        )
    m, kdim = x.shape
    if m > MAX_FUSED_M:
        raise ValueError(
            f"fused decode kernel holds the whole M strip in VMEM; M={m} exceeds "
            f"{MAX_FUSED_M} — use elp_bsd_matmul for prefill-sized batches"
        )
    if block_n <= 0 or block_k <= 0:
        raise ValueError(f"block sizes must be positive; got ({block_n}, {block_k})")
    if nibble:
        k2, n = codes.shape
        if k2 * 2 != kdim:
            raise ValueError(
                f"nibble codes pack two K rows per byte: expected codes[K/2={kdim // 2}, N], "
                f"got codes{tuple(codes.shape)} against x{tuple(x.shape)}"
            )
        if block_k % 2 != 0:
            raise ValueError(f"nibble mode needs an even block_k (two codes/byte); got {block_k}")
        c_block = (block_k // 2, block_n)
    else:
        kc, n = codes.shape
        if kc != kdim:
            raise ValueError(
                f"codes K dim must match x: got codes{tuple(codes.shape)} "
                f"against x{tuple(x.shape)}"
            )
        c_block = (block_k, block_n)
    if n % block_n or kdim % block_k:
        raise ValueError(
            f"K/N must tile evenly: (K, N)=({kdim}, {n}) vs "
            f"(block_k, block_n)=({block_k}, {block_n}) (the ops wrapper pads)"
        )
    out_dtype = out_dtype or x.dtype
    n_k = kdim // block_k
    grid = (n // block_n, n_k)
    if nibble:
        x = nibble_plane_order(x, block_k)

    return pl.pallas_call(
        functools.partial(_fused_kernel, fmt=fmt, nibble=nibble, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_k), lambda j, k: (0, k)),
            pl.BlockSpec(c_block, lambda j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            # float32 accumulator strip held in VMEM across the K steps
            pltpu.VMEM((m, block_n), jnp.float32)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, codes, jnp.asarray(sf, jnp.float32).reshape(1, 1))
