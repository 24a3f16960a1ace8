"""CNNs for the faithful paper reproduction: AlexNet, VGG-16 (+ minis).

The paper evaluates on AlexNet/VGG-16 (ImageNet). ImageNet is not
available offline, so the repro pipeline trains *mini* variants of the
same families on a deterministic synthetic image task and validates the
paper's *relative* claims (error-compensation gains vs bit-width,
format ranking); the full-size defs exist for parameter-statistics
experiments (Fig. 3 distributions) and energy accounting (MAC counts).

Weights: conv ``[H, W, Cin, Cout]`` (quantization group = spatial dims
per (Cin, Cout) channel — exactly the paper's Algorithm 1 grouping),
fc ``[in, out]`` (group = contracting rows).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init

Array = jax.Array
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Conv:
    ch: int
    k: int
    stride: int = 1


@dataclasses.dataclass(frozen=True)
class Pool:
    k: int = 2
    stride: int = 2


@dataclasses.dataclass(frozen=True)
class Fc:
    out: int


@dataclasses.dataclass(frozen=True)
class CnnSpec:
    name: str
    layers: tuple[Any, ...]
    input_hw: int
    input_ch: int = 3

    def macs(self) -> int:
        """Multiply-accumulates per inference (for the energy model)."""
        hw, ch = self.input_hw, self.input_ch
        total = 0
        for l in self.layers:
            if isinstance(l, Conv):
                hw = hw // l.stride
                total += hw * hw * l.k * l.k * ch * l.ch
                ch = l.ch
            elif isinstance(l, Pool):
                hw = hw // l.stride
            elif isinstance(l, Fc):
                total += (hw * hw * ch if hw else ch) * l.out
                hw = 0
                ch = l.out
        return total


ALEXNET = CnnSpec(
    "alexnet",
    (
        Conv(96, 11, 4),
        Pool(),
        Conv(256, 5),
        Pool(),
        Conv(384, 3),
        Conv(384, 3),
        Conv(256, 3),
        Pool(),
        Fc(4096),
        Fc(4096),
        Fc(1000),
    ),
    input_hw=224,
)

VGG16 = CnnSpec(
    "vgg16",
    (
        Conv(64, 3), Conv(64, 3), Pool(),
        Conv(128, 3), Conv(128, 3), Pool(),
        Conv(256, 3), Conv(256, 3), Conv(256, 3), Pool(),
        Conv(512, 3), Conv(512, 3), Conv(512, 3), Pool(),
        Conv(512, 3), Conv(512, 3), Conv(512, 3), Pool(),
        Fc(4096), Fc(4096), Fc(1000),
    ),
    input_hw=224,
)

# CPU-trainable mini variants (same family shape, same code paths).
ALEXNET_MINI = CnnSpec(
    "alexnet_mini",
    (Conv(16, 5, 2), Pool(), Conv(32, 3), Pool(), Conv(32, 3), Fc(128), Fc(10)),
    input_hw=32,
)
VGG_MINI = CnnSpec(
    "vgg_mini",
    (Conv(16, 3), Conv(16, 3), Pool(), Conv(32, 3), Conv(32, 3), Pool(), Fc(128), Fc(10)),
    input_hw=32,
)


def init_params(spec: CnnSpec, key: Array, dtype=F32) -> dict[str, Array]:
    params: dict[str, Array] = {}
    ch = spec.input_ch
    hw = spec.input_hw
    idx = 0
    flat: int | None = None
    for l in spec.layers:
        key, sub = jax.random.split(key)
        if isinstance(l, Conv):
            params[f"conv{idx}_w"] = dense_init(sub, (l.k, l.k, ch, l.ch), dtype) * np.sqrt(
                1.0 / (l.k * l.k)
            )
            params[f"conv{idx}_b"] = jnp.zeros((l.ch,), dtype)
            ch = l.ch
            hw = hw // l.stride
            idx += 1
        elif isinstance(l, Pool):
            hw = hw // l.stride
        elif isinstance(l, Fc):
            fan_in = flat if flat is not None else hw * hw * ch
            params[f"fc{idx}_w"] = dense_init(sub, (fan_in, l.out), dtype)
            params[f"fc{idx}_b"] = jnp.zeros((l.out,), dtype)
            flat = l.out
            idx += 1
    return params


def forward(
    params: dict[str, Array],
    spec: CnnSpec,
    x: Array,
    act_bits: int | None = None,
    *,
    calib=None,
    tap=None,
    impl: str = "auto",
    block_sizes: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
) -> Array:
    """x: [B, H, W, C] images → logits [B, n_classes].

    Activation quantization, by site (``"input"``, then ``"conv{i}"`` /
    ``"fc{i}"`` after each hidden relu):

      * ``act_bits`` alone — uniform fixed-point with a *dynamic*
        per-tensor range (Sec. V step 1 as the paper's FP baseline runs
        it; one ``max|x|`` reduction per site at run time);
      * ``calib`` (a :class:`~repro.calib.policy.CalibrationTable`) —
        *static* per-site scales measured offline: the scales embed as
        compile-time constants, so the traced graph contains no range
        reductions at all (DESIGN.md §6). ``act_bits`` then overrides
        the table's bit-width (the CBW_A search sweeps it).

    ``tap`` is the activation-tap hook (calibration contract): called as
    ``x = tap(site, x)`` on the pre-quantization value at every site.

    Weights may be float arrays OR :class:`~repro.kernels.ops.PackedWeight`
    leaves (see :func:`quantize_params`): packed convs run through
    :func:`~repro.kernels.conv.quantized_conv2d` and packed fc layers
    through ``quantized_matmul``, so the whole network executes on
    ELP_BSD codes end-to-end. ``impl`` selects the packed execution path:
    the default ``"auto"`` resolves each layer by its op's own rule (on a
    TPU the fc layers take the decode+matmul kernel at the tiles
    ``kernels/ops.select_impl`` derives from the shape, the convs take
    XLA's conv; off a TPU both take XLA), ``"xla"`` decodes then calls
    XLA's dot or conv, ``"pallas"`` / ``"pallas_fused"`` force a kernel.
    ``block_sizes`` forwards a ``(block_m, block_n, block_k)`` tuple to
    the packed kernels.
    """
    from repro.core.quantize import fake_quant_dynamic, fake_quant_uniform
    from repro.kernels.conv import quantized_conv2d
    from repro.kernels.ops import PackedWeight, quantized_matmul

    def q(t, site):
        if tap is not None:
            t = tap(site, t)
        if calib is not None:
            sc = calib.site(site)
            return fake_quant_uniform(t, act_bits or sc.bits, sc.amax)
        return fake_quant_dynamic(t, act_bits) if act_bits else t

    idx = 0
    flat = False
    n_layers = sum(isinstance(l, (Conv, Fc)) for l in spec.layers)
    x = q(x, "input")
    for l in spec.layers:
        if isinstance(l, Conv):
            with jax.named_scope(f"conv{idx}"):
                w = params[f"conv{idx}_w"]
                if isinstance(w, PackedWeight):
                    x = quantized_conv2d(
                        x.astype(F32),
                        w,
                        stride=l.stride,
                        padding="SAME",
                        impl=impl,
                        block_sizes=block_sizes,
                        interpret=interpret,
                        out_dtype=F32,
                    )
                else:
                    x = jax.lax.conv_general_dilated(
                        x.astype(F32),
                        w.astype(F32),
                        window_strides=(l.stride, l.stride),
                        padding="SAME",
                        dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    )
                x = x + params[f"conv{idx}_b"].astype(F32)
                x = q(jax.nn.relu(x), f"conv{idx}")
            idx += 1
        elif isinstance(l, Pool):
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, l.k, l.k, 1), (1, l.stride, l.stride, 1), "VALID"
            )
        elif isinstance(l, Fc):
            if not flat:
                x = x.reshape(x.shape[0], -1)
                flat = True
            with jax.named_scope(f"fc{idx}"):
                w = params[f"fc{idx}_w"]
                if isinstance(w, PackedWeight):
                    x = quantized_matmul(
                        x.astype(F32),
                        w,
                        impl=impl,
                        block_sizes=block_sizes,
                        interpret=interpret,
                        out_dtype=F32,
                    )
                else:
                    x = jnp.dot(x, w.astype(F32))
                x = x + params[f"fc{idx}_b"].astype(F32)
                if idx + 1 < n_layers:
                    x = q(jax.nn.relu(x), f"fc{idx}")
            idx += 1
    return x


def quantize_params(
    params: dict[str, Array],
    fmt,
    *,
    compensate: bool = True,
    granularity: str = "per_tensor",
    nibble: bool | None = None,
) -> dict[str, Array]:
    """Deprecated wrapper: pack every conv/fc weight as a PackedWeight.

    Use :func:`repro.api.quantize` instead — it drives the same packing
    walk (:func:`repro.api_schemes.pack_cnn_params`) from a
    :class:`~repro.api_schemes.QuantScheme` and returns a servable,
    serializable :class:`~repro.api.QuantizedModel`.
    """
    import warnings

    warnings.warn(
        "models.cnn.quantize_params is deprecated; use repro.api.quantize",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.api_schemes import pack_cnn_params

    return pack_cnn_params(
        params, fmt, compensate=compensate, granularity=granularity, nibble=nibble
    )


def packed_weight_bytes(params: dict[str, Array]) -> int:
    """Code+sf bytes of the packed weights (compression accounting).

    Delegates to :func:`repro.kernels.ops.packed_tree_bytes` — the one
    packed-size accounting walk.
    """
    from repro.kernels.ops import packed_tree_bytes

    return packed_tree_bytes(params, packed_only=True)


def weight_group_axes(params: dict[str, Array]) -> dict[str, tuple[int, ...]]:
    """Quantization/compensation groups per weight (paper Sec. III-B.4:
    intra-channel = spatial dims for convs)."""
    out = {}
    for name, w in params.items():
        if name.endswith("_b"):
            continue
        out[name] = (0, 1) if name.startswith("conv") else (0,)
    return out


def loss_fn(params, spec: CnnSpec, x: Array, y: Array) -> Array:
    logits = forward(params, spec, x)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def accuracy(params, spec: CnnSpec, x: Array, y: Array) -> Array:
    return jnp.mean((jnp.argmax(forward(params, spec, x), -1) == y).astype(F32))
