"""Decode and matmul shared by the references."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.weights import CODE_TABLES

HIGHEST = jax.lax.Precision.HIGHEST
# e4m3 with IEEE-style exponent bias 7: largest normal 1.875 * 2^7
F8_MAX = 240.0


def decode(codes: jax.Array, scale, fmt: str, k: int) -> jax.Array:
    """``codes[..., ceil(K/2), N]`` (two 4-bit codes a byte along K, low
    nibble first) times ``scale`` → float32 ``[..., K, N]``."""
    c = codes.astype(jnp.int32)
    both = jnp.stack([c & 15, c >> 4], axis=-2)
    full = both.reshape(*c.shape[:-2], 2 * c.shape[-2], c.shape[-1])[..., :k, :]
    return jnp.asarray(CODE_TABLES[fmt])[full] * scale


def fp8(t: jax.Array) -> jax.Array:
    """Round to an 8-bit float (4 exponent, 3 mantissa bits) against the
    tensor's own max. ``reduce_precision`` and not a round trip through
    ``float8_e4m3fn``: the TPU compiler may fold a narrowing convert and
    its widening inverse away, and the control would then read as exact."""
    s = jnp.max(jnp.abs(t)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(t / s, exponent_bits=4, mantissa_bits=3) * s


def operands(mode: str, *ts: jax.Array) -> tuple[jax.Array, ...]:
    if mode == "fp8":
        return tuple(fp8(t) for t in ts)
    if mode != "highest":
        raise ValueError(f"unknown reference mode {mode!r}")
    return ts


def mm(x: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    x, w = operands(mode, x, w)
    return jnp.matmul(x, w, precision=HIGHEST)
