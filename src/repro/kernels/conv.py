"""Packed quantized 2-D convolution: im2col → fused ELP_BSD matmul.

This is what routes the paper's own workload (AlexNet/VGG convs)
through the packed execution path. The conv weight is stored as ELP_BSD
codes in ``[K=kh*kw*cin, N=cout]`` im2col layout (see
:func:`repro.kernels.ops.pack_conv_weight`); at run time activations are
patch-extracted to ``[B*Ho*Wo, K]`` and fed to the existing fused
decode+matmul Pallas kernel — the conv never materializes float weights
in HBM, which is the paper's energy story on the conv workload.

``impl="xla"`` is the fallback: dequantize in-graph and call
``lax.conv_general_dilated`` on the decoded weight.

Patch layout contract: patches are ordered ``(kh, kw, cin)`` with
``cin`` fastest — exactly the row-major flattening of an ``HWIO``
weight, so ``patches @ w.reshape(kh*kw*cin, cout)`` equals the conv.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.ops import PackedWeight, dequantize_nd, quantized_matmul

Array = jax.Array
F32 = jnp.float32


def _out_size_and_pads(size: int, k: int, stride: int, padding: str) -> tuple[int, tuple[int, int]]:
    """Output length and (lo, hi) pads for one spatial dim (XLA semantics)."""
    if padding == "SAME":
        out = -(-size // stride)  # ceil
        total = max((out - 1) * stride + k - size, 0)
        return out, (total // 2, total - total // 2)
    if padding == "VALID":
        return (size - k) // stride + 1, (0, 0)
    raise ValueError(f"unknown padding {padding!r}")


def extract_patches(
    x: Array, kh: int, kw: int, *, stride: int = 1, padding: str = "SAME"
) -> Array:
    """``x[B, H, W, C]`` → patches ``[B, Ho, Wo, kh*kw*C]`` (im2col).

    Pure jnp (strided slices over the static kernel window), so it
    traces into jit and fuses with the downstream matmul.
    """
    _, h, w, _ = x.shape
    ho, (pt, pb) = _out_size_and_pads(h, kh, stride, padding)
    wo, (pl_, pr) = _out_size_and_pads(w, kw, stride, padding)
    x = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(
                x[
                    :,
                    i : i + (ho - 1) * stride + 1 : stride,
                    j : j + (wo - 1) * stride + 1 : stride,
                    :,
                ]
            )
    return jnp.concatenate(cols, axis=-1)


def quantized_conv2d(
    x: Array,
    pw: PackedWeight,
    *,
    stride: int = 1,
    padding: str = "SAME",
    impl: str = "auto",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    block_sizes: tuple[int, int, int] | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> Array:
    """``conv2d(x[B,H,W,Cin], pw)`` → ``[B, Ho, Wo, Cout]`` on packed codes.

    ``pw`` must come from :func:`repro.kernels.ops.pack_conv_weight`
    (``source_shape`` carries the conv layout). ``impl="pallas"`` /
    ``"pallas_fused"`` run patch extraction into the corresponding
    decode+matmul kernel; ``impl="xla"`` dequantizes and calls
    ``lax.conv_general_dilated``. The default ``impl="auto"`` is
    ``"xla"``. ``block_sizes`` forwards a ``(block_m, block_n, block_k)``
    tuple to :func:`quantized_matmul`.
    """
    with jax.named_scope("elp_bsd_conv"):
        if pw.source_shape is None or len(pw.source_shape) != 4:
            raise ValueError("quantized_conv2d needs a pack_conv_weight-packed weight")
        kh, kw, _, cout = pw.source_shape
        out_dtype = out_dtype or x.dtype
        if impl == "auto":
            # No chip measurement has chosen a conv route yet, so none
            # replaces XLA's own conv (DESIGN.md §14).
            impl = "xla"
        if impl == "xla":
            out = lax.conv_general_dilated(
                x.astype(F32),
                dequantize_nd(pw),
                window_strides=(stride, stride),
                padding=padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            return out.astype(out_dtype)
        if impl not in ("pallas", "pallas_fused"):
            raise ValueError(f"unknown impl {impl!r}")
        patches = extract_patches(x.astype(F32), kh, kw, stride=stride, padding=padding)
        return quantized_matmul(
            patches,
            pw,
            impl=impl,
            block_m=block_m,
            block_n=block_n,
            block_k=block_k,
            block_sizes=block_sizes,
            out_dtype=out_dtype,
            interpret=interpret,
        )
