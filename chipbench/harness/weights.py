"""Weights and inputs drawn from the seed, on the device, in one call each.

A served model loads a converted checkpoint: conversion is offline
work. So set-up draws the packed leaves directly, as uint8 codes that
are valid for the format and one float32 scale per layer, in a neutral
layout that both the program's tree and the plain reference read:

* a matrix ``[K, N]`` of a 4-bit format is ``codes[ceil(K/2), N]``,
  two codes a byte along K, the low nibble first (row ``2r`` low,
  ``2r+1`` high), and a scale; its value is ``table[code] * scale``;
* each scale keeps the layer's output at unit scale under uniform
  codes, ``gain / sqrt(K * E[table^2])``, so the logits stay finite
  through every layer.

The decode inside the kernels does the same work whatever the code
values are.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Value of each 4-bit code, from the paper's format definition:
# elp_bsd_a4 is one signed power-of-two digit, bits 0-2 the shift
# (0..7) and bit 3 the sign, so code c is (-1)^(c>>3) * 2^(c&7).
CODE_TABLES = {
    "elp_bsd_a4": np.array(
        [(-1.0 if c >> 3 else 1.0) * 2.0 ** (c & 7) for c in range(16)], np.float32
    ),
}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, all of its bits used."""
    words = np.random.SeedSequence(int(seed)).generate_state(2).astype(np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def code_mean_square(fmt: str) -> float:
    t = CODE_TABLES[fmt].astype(np.float64)
    return float(np.mean(t * t))


def unit_scale(fmt: str, k: int, gain: float = 1.0) -> float:
    return gain / math.sqrt(k * code_mean_square(fmt))


def draw_codes(key: jax.Array, k: int, n: int, lead: tuple[int, ...] = ()) -> jax.Array:
    return jax.random.bits(key, (*lead, (k + 1) // 2, n), jnp.uint8)


def lm_weights(cfg: dict, key: jax.Array) -> dict:
    """Neutral decoder weights: embedding and head in the model's dtype, and each
    layer projection as codes ``[L, K/2, N]`` with scales ``[L]``."""
    from harness.counts import decoder_layer_gemms

    fmt = cfg["weights"]["format"]
    d, v, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    names = [g[0] for g in decoder_layer_gemms(cfg)]
    keys = jax.random.split(key, len(names) + 2)
    layers = {}
    for (name, k, n), kk in zip(decoder_layer_gemms(cfg), keys[2:]):
        layers[name] = {
            "codes": draw_codes(kk, k, n, (L,)),
            "scale": jnp.full((L,), unit_scale(fmt, k), jnp.float32),
        }
    dt = jnp.dtype(cfg["torch_dtype"])
    return {
        "embed": (jax.random.normal(keys[0], (v, d), jnp.float32) / math.sqrt(v)).astype(dt),
        "head": (jax.random.normal(keys[1], (d, v), jnp.float32) / math.sqrt(d)).astype(dt),
        "layers": layers,
    }


def cnn_weights(cfg: dict, key: jax.Array) -> dict:
    """Neutral CNN weights: each conv ``[kh*kw*cin, cout]`` (im2col rows
    in ``(kh, kw, cin)`` order, ``cin`` fastest) and fc ``[in, out]`` as
    codes with one scale, He gain for ReLU inputs, and a float32 bias."""
    from harness.counts import cnn_layers

    fmt = cfg["weights"]["format"]
    layers = list(cnn_layers(cfg))
    keys = jax.random.split(key, 2 * len(layers))
    out = []
    for i, layer in enumerate(layers):
        if layer[0] == "conv":
            _, _, _, kk, cin, cout = layer
            k, n = kk * kk * cin, cout
        else:
            _, k, n = layer
        out.append(
            {
                "codes": draw_codes(keys[2 * i], k, n),
                "scale": jnp.float32(unit_scale(fmt, k, math.sqrt(2.0))),
                "bias": 0.05 * jax.random.normal(keys[2 * i + 1], (n,), jnp.float32),
            }
        )
    return {"layers": out}


def images(key: jax.Array, index, batch: int, hw: int, ch: int) -> jax.Array:
    """Batch ``index`` of the seeded image stream: uint8 pixels mapped
    to ``[-1, 1]``, ``[batch, hw, hw, ch]`` float32."""
    k = jax.random.fold_in(key, index)
    px = jax.random.bits(k, (batch, hw, hw, ch), jnp.uint8)
    return px.astype(jnp.float32) / 127.5 - 1.0
