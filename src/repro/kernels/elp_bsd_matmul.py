"""Pallas TPU kernel: fused ELP_BSD decode + matmul.

This is the TPU adaptation of the paper's shift-based MAC unit
(Sec. IV-4). Weights live in HBM as packed ELP_BSD codes (4–8 bits
each); per (block_m, block_n, block_k) tile the kernel

  1. streams a code block into VMEM,
  2. expands codes to float32 *in VMEM* via shift-add decode
     (:func:`repro.kernels.ref.decode_values_shift_add`) — per digit:
     extract sign/index fields, map index → shift count (an affine
     ``a + b·index`` for arithmetic-progression LUTs, a ≤ 8-entry
     vselect chain otherwise), and build the signed ``±2^shift`` term
     in one integer write of the float32 sign+exponent fields (the VPU
     analogue of the barrel shift; bit-identical to the select-chain
     decoder, DESIGN.md §14),
  3. feeds the decoded tile straight to the MXU
     (``jnp.dot(..., preferred_element_type=float32)``),
  4. accumulates in a float32 VMEM scratch across the K grid dimension.

The HBM side therefore moves 2–4x fewer weight bytes than a bf16
matmul — on memory-bound decode steps that is the roofline win the
paper's energy claim translates to (see DESIGN.md §2).

Storage modes:
  * ``u8``: one code per byte, any format up to 8 bits/weight.
  * ``nibble``: FORMAT_A (4-bit) packed two-per-byte along K
    (``[K//2, N]``; low nibble = even row). Halves HBM bytes again.

Block shapes default to MXU-aligned 128 multiples; the K block for
nibble mode must be even. Validated in ``interpret=True`` on CPU against
:mod:`repro.kernels.ref` (this container has no TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.elp_bsd import ElpBsdFormat
from repro.kernels.ref import decode_values_shift_add, unpack_nibbles_k

Array = jax.Array


def _mm_kernel(x_ref, c_ref, sf_ref, o_ref, acc_ref, *, fmt: ElpBsdFormat, nibble: bool, n_k: int):
    """One (bm, bn) output tile; grid = (m, n, k) with k innermost."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = c_ref[...]
    if nibble:
        codes = unpack_nibbles_k(codes)
    w = decode_values_shift_add(codes, fmt)  # [bk, bn] float32, unscaled
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * sf_ref[0, 0]).astype(o_ref.dtype)


def elp_bsd_matmul(
    x: Array,
    codes: Array,
    sf: Array,
    fmt: ElpBsdFormat,
    *,
    nibble: bool = False,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=None,
    interpret: bool | None = None,
) -> Array:
    """``x[M,K] @ dequant(codes)[K,N]`` with in-kernel ELP_BSD decode.

    Shapes must tile evenly by the block sizes (the ops wrapper pads).
    ``sf`` is the per-layer scale factor as a ``(1, 1)`` float32 array.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Shape/block validation raises (not assert: asserts vanish under
    # ``python -O``, and a silently mis-tiled kernel reads garbage codes).
    if x.ndim != 2 or codes.ndim != 2:
        raise ValueError(
            f"elp_bsd_matmul takes x[M, K] and codes[K', N]; got x{tuple(x.shape)}, "
            f"codes{tuple(codes.shape)}"
        )
    m, kdim = x.shape
    if block_m <= 0 or block_n <= 0 or block_k <= 0:
        raise ValueError(f"block sizes must be positive; got ({block_m}, {block_n}, {block_k})")
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
    if m % block_m or n % block_n or kdim % block_k:
        raise ValueError(
            f"shapes must tile evenly: (M, K, N)=({m}, {kdim}, {n}) vs "
            f"(block_m, block_k, block_n)=({block_m}, {block_k}, {block_n}) "
            "(the ops wrapper pads to block multiples)"
        )
    out_dtype = out_dtype or x.dtype
    n_k = kdim // block_k
    grid = (m // block_m, n // block_n, n_k)

    return pl.pallas_call(
        functools.partial(_mm_kernel, fmt=fmt, nibble=nibble, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec(c_block, lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            # float32 accumulator tile held in VMEM across the K steps
            pltpu.VMEM((block_m, block_n), jnp.float32)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, codes, jnp.asarray(sf, jnp.float32).reshape(1, 1))
