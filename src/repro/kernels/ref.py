"""Pure-jnp oracles for the ELP_BSD kernels.

``decode_values`` is the single source of truth for the bit-level
decode; both the XLA fallback path and the Pallas kernel body call it on
their blocks, and the kernel tests assert against the matmul oracle here.

Decode strategy (TPU-native reading of the paper's barrel shifter): the
per-digit shift-count LUT has ≤ 8 entries, so the lookup is a short
*select chain* (vselects, no gather), and ``2^shift`` is built by
integer-constructing the float32 exponent field — a TPU VPU-friendly
"exponent add" standing in for the ASIC shift.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.elp_bsd import ElpBsdFormat

Array = jax.Array


def _exp2_int(shift: Array) -> Array:
    """2.0**shift for integer ``shift`` via float32 exponent construction."""
    bits = (shift + 127).astype(jnp.int32) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def decode_values(codes: Array, fmt: ElpBsdFormat) -> Array:
    """Decode raw ELP_BSD codes (integer array) to unscaled float32 values."""
    codes = codes.astype(jnp.int32)
    out = jnp.zeros(codes.shape, dtype=jnp.float32)
    for (off, sbits, ibits), tab in zip(fmt.field_layout(), fmt.shift_tables()):
        field = (codes >> off) & ((1 << (sbits + ibits)) - 1)
        idx = field & ((1 << ibits) - 1)
        # Select-chain LUT: tab has <= 2**ibits entries, all compile-time.
        shift = jnp.full(codes.shape, int(tab[0]), dtype=jnp.int32)
        for e in range(1, len(tab)):
            shift = jnp.where(idx == e, int(tab[e]), shift)
        mag = _exp2_int(shift)
        if sbits:
            sign = 1.0 - 2.0 * ((field >> ibits) & 1).astype(jnp.float32)
            out = out + sign * mag
        else:
            out = out + mag
    return out


def decode_values_shift_add(codes: Array, fmt: ElpBsdFormat) -> Array:
    """Shift-add decode: bit-identical to :func:`decode_values`, fewer ops.

    Per digit the signed power-of-two term ``±2^shift`` is built in ONE
    integer construction — the shift count goes into the float32
    exponent field and the digit's sign bit is OR'd straight into the
    float sign bit — instead of a shift LUT select chain followed by a
    float sign multiply. Digits whose shift LUT is an arithmetic
    progression (``affine`` in
    :meth:`~repro.core.elp_bsd.ElpBsdFormat.shift_add_decomposition`)
    skip the select chain entirely: ``shift = a + b·index``.

    Bit-exactness (property-tested in ``tests/test_fused_decode.py``):
    the shift integers are equal by construction, ``sign<<31 | exp``
    is the bit pattern of ``sign * 2^shift`` exactly, and summing the
    ≤ 2 exact power-of-two terms in digit order rounds identically to
    :func:`decode_values`'s ``0 + t₀ + t₁`` chain. This is the decoder
    the fused kernels and the single-pass XLA path consume.
    """
    codes = codes.astype(jnp.int32)
    out = None
    for off, sbits, ibits, tab, affine in fmt.shift_add_decomposition():
        field = (codes >> off) & ((1 << (sbits + ibits)) - 1)
        idx = field & ((1 << ibits) - 1)
        if affine is not None:
            a, b = affine
            shift = a + idx * b if b else jnp.full(codes.shape, a, jnp.int32)
        else:
            shift = jnp.full(codes.shape, int(tab[0]), dtype=jnp.int32)
            for e in range(1, len(tab)):
                shift = jnp.where(idx == e, int(tab[e]), shift)
        bits = (shift + 127) << 23
        if sbits:
            bits = bits | (((field >> ibits) & 1) << 31)
        term = jax.lax.bitcast_convert_type(bits, jnp.float32)
        out = term if out is None else out + term
    return out


def unpack_nibbles_k(packed: Array) -> Array:
    """Unpack ``[..., K//2, N] uint8`` (two 4-bit codes along K per byte)
    to ``[..., K, N]``. Row ``2r`` is the low nibble, ``2r+1`` the high.

    The bytes widen to int32 before the mask and shift: Mosaic has no
    8-bit vector shift on TPU v5e, so a uint8 ``>>`` does not compile.
    """
    wide = packed.astype(jnp.int32)
    lo = wide & 0x0F
    hi = (wide >> 4) & 0x0F
    out = jnp.stack([lo, hi], axis=-2)  # [..., K//2, 2, N]
    return out.reshape(*packed.shape[:-2], 2 * packed.shape[-2], packed.shape[-1])


def dequantize_ref(codes: Array, sf: Array, fmt: ElpBsdFormat, *, nibble: bool = False) -> Array:
    """Oracle dequantization: codes → float32 weights ``[K, N]``."""
    if nibble:
        codes = unpack_nibbles_k(codes)
    return decode_values(codes, fmt) * sf


def elp_bsd_matmul_ref(
    x: Array,
    codes: Array,
    sf: Array,
    fmt: ElpBsdFormat,
    *,
    nibble: bool = False,
    out_dtype=jnp.float32,
) -> Array:
    """Oracle: ``x @ dequantize(codes)`` with float32 accumulation."""
    w = dequantize_ref(codes, sf, fmt, nibble=nibble)
    return jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32).astype(out_dtype)
