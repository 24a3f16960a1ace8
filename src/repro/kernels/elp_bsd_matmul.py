"""Pallas TPU kernel: fused ELP_BSD decode + matmul.

This is the TPU adaptation of the paper's shift-based MAC unit
(Sec. IV-4). Weights live in HBM as packed ELP_BSD codes (4–8 bits
each); per (block_m, block_n, block_k) tile the kernel

  1. streams a code block into VMEM,
  2. expands codes, a slab of K rows at a time, to float32 *in VMEM*
     via shift-add decode
     (:func:`repro.kernels.ref.decode_values_shift_add`) — per digit:
     extract sign/index fields, map index → shift count (an affine
     ``a + b·index`` for arithmetic-progression LUTs, a ≤ 8-entry
     vselect chain otherwise), and build the signed ``±2^shift`` term
     in one integer write of the float32 sign+exponent fields (the VPU
     analogue of the barrel shift; bit-identical to the select-chain
     decoder, DESIGN.md §14),
  3. feeds the decoded slab straight to the MXU, in bf16 where that is
     exact (``jnp.dot(..., preferred_element_type=float32)``),
  4. accumulates in a float32 VMEM scratch across the K grid dimension.

The HBM side therefore moves 2–4x fewer weight bytes than a bf16
matmul — on memory-bound decode steps that is the roofline win the
paper's energy claim translates to (see DESIGN.md §2).

Storage modes:
  * ``u8``: one code per byte, any format up to 8 bits/weight.
  * ``nibble``: FORMAT_A (4-bit) packed two-per-byte along K
    (``[K//2, N]``; low nibble = even row). Halves HBM bytes again.

Block shapes default to 128 multiples; the K block for nibble mode
must be even. On a TPU, ``quantized_matmul(impl="auto")`` sizes them
from the shape alone, by one rule
(:func:`repro.kernels.ops.tpu_blocks`): code panels of about 1 MB per
grid step, decoded ``SLAB_K`` rows of K at a time inside the step.
The dot takes bf16 operands where that is exact
(:func:`mxu_operand_dtype`), float32 otherwise; it accumulates in
float32 either way. Validated in ``interpret=True`` on CPU against
:mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.elp_bsd import ElpBsdFormat, decode_codes
from repro.kernels.ref import decode_values_shift_add

Array = jax.Array

# K rows decoded at a time inside a grid step: bounds the decode's VMEM
# temporaries to a few [SLAB_K, block_n] tiles whatever block_k is.
SLAB_K = 512


@functools.lru_cache(maxsize=None)
def decodes_exactly_to_bf16(fmt: ElpBsdFormat) -> bool:
    """Whether every code of ``fmt`` decodes to a value bf16 holds exactly."""
    vals = decode_codes(np.arange(1 << fmt.bits_per_weight), fmt)
    return bool(np.all(vals.astype(jnp.bfloat16).astype(np.float64) == vals))


def mxu_operand_dtype(fmt: ElpBsdFormat, x_dtype) -> jnp.dtype:
    """The dtype both operands of the kernels' dot take.

    bf16 where that is exact: ``x`` arrives in bf16 and every level of
    ``fmt`` is a bf16 value, so every product is the float32 path's and
    only the order of the float32 sums differs. Otherwise float32.
    """
    if jnp.dtype(x_dtype) == jnp.bfloat16 and decodes_exactly_to_bf16(fmt):
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def slab_k(block_k: int) -> int:
    """K rows decoded at a time in a ``block_k`` panel: ``SLAB_K``, or the
    largest power of two below it that divides ``block_k``."""
    return math.gcd(block_k, SLAB_K)


def nibble_plane_order(x: Array, block_k: int) -> Array:
    """``x[M, K]`` with each slab's even K columns first, then its odd
    ones: the order in which :func:`accumulate_panel` stacks a nibble
    slab's low and high planes."""
    m, k = x.shape
    slab = slab_k(block_k)
    return x.reshape(m, k // slab, slab // 2, 2).swapaxes(2, 3).reshape(m, k)


def accumulate_panel(x_ref, c_ref, acc_ref, *, fmt: ElpBsdFormat, nibble: bool):
    """``acc_ref += x_ref @ decode(c_ref)`` for one code panel, decoded a
    slab of K rows at a time, each slab straight into the dot.

    Nibble codes unpack as two planes, the low nibbles (even K rows)
    stacked above the high ones (odd K rows), against ``x`` in
    :func:`nibble_plane_order`: the stack is sublane-aligned, where
    :func:`repro.kernels.ref.unpack_nibbles_k`'s row interleave costs a
    relayout of every code (3.5 times the kernel's time at qwen3-8b's
    ``w1`` on a v5e, M = 16).
    """
    block_k = x_ref.shape[1]
    slab = slab_k(block_k)
    rows = slab // 2 if nibble else slab
    operand = mxu_operand_dtype(fmt, x_ref.dtype)

    def slab_dot(s):
        codes = c_ref[pl.ds(pl.multiple_of(s * rows, rows), rows), :]
        if nibble:
            wide = codes.astype(jnp.int32)  # no 8-bit vector shift on v5e
            codes = jnp.concatenate([wide & 0x0F, (wide >> 4) & 0x0F], axis=0)
        w = decode_values_shift_add(codes, fmt).astype(operand)  # unscaled
        x = x_ref[:, pl.ds(pl.multiple_of(s * slab, slab), slab)].astype(operand)
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    if block_k == slab:
        slab_dot(0)
    else:
        pl.loop(0, block_k // slab)(slab_dot)


def _mm_kernel(x_ref, c_ref, sf_ref, o_ref, acc_ref, *, fmt: ElpBsdFormat, nibble: bool, n_k: int):
    """One (bm, bn) output tile; grid = (m, n, k) with k innermost."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate_panel(x_ref, c_ref, acc_ref, fmt=fmt, nibble=nibble)

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
    if nibble:
        x = nibble_plane_order(x, block_k)

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
