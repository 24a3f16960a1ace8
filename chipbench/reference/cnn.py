"""Plain float32 reference of a VGG-style CNN on 4-bit codes.

Convolutions with SAME padding in NHWC/HWIO, bias, ReLU; max pools
with VALID windows; the map flattened row-major (NHWC) into the fc
layers, ReLU after every fc layer but the last. Each layer's codes
decode to float32 inside its own call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.common import HIGHEST, decode, mm, operands


@functools.partial(jax.jit, static_argnames=("k", "cin", "cout", "fmt", "mode"))
def _conv(x, p, *, k, cin, cout, fmt, mode):
    w = decode(p["codes"], p["scale"], fmt, k * k * cin).reshape(k, k, cin, cout)
    xo, wo = operands(mode, x, w)
    y = jax.lax.conv_general_dilated(
        xo, wo, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST
    )
    return jax.nn.relu(y + p["bias"])


@functools.partial(jax.jit, static_argnames=("k", "stride"))
def _pool(x, *, k, stride):
    window, strides = (1, k, k, 1), (1, stride, stride, 1)
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides, "VALID")


@functools.partial(jax.jit, static_argnames=("fan_in", "fmt", "mode", "relu"))
def _fc(x, p, *, fan_in, fmt, mode, relu):
    w = decode(p["codes"], p["scale"], fmt, fan_in)
    y = mm(x.reshape(x.shape[0], -1), w, mode) + p["bias"]
    return jax.nn.relu(y) if relu else y


def forward(weights: dict, cfg: dict, x: jax.Array, mode: str = "highest") -> jax.Array:
    """Logits ``[B, classes]`` of images ``x[B, H, W, C]``."""
    fmt = cfg["weights"]["format"]
    params = iter(weights["layers"])
    n_fc = sum(1 for layer in cfg["layers"] if layer[0] == "fc")
    seen_fc = 0
    ch = cfg["input_channels"]
    flat = None
    for layer in cfg["layers"]:
        if layer[0] == "conv":
            _, cout, k, stride = layer
            if stride != 1:
                raise ValueError("the reference convolves at stride 1 only")
            x = _conv(x, next(params), k=k, cin=ch, cout=cout, fmt=fmt, mode=mode)
            ch = cout
        elif layer[0] == "pool":
            x = _pool(x, k=layer[1], stride=layer[2])
        else:
            fan_in = flat if flat is not None else x.shape[1] * x.shape[2] * x.shape[3]
            seen_fc += 1
            x = _fc(x, next(params), fan_in=fan_in, fmt=fmt, mode=mode, relu=seen_fc < n_fc)
            flat = layer[1]
    return x
