"""Convert model parameter pytrees to packed ELP_BSD for serving.

The conversion is the paper's Sec. V methodology applied per stacked
layer slice — per-slice scale factor ``SF = max|W|/2^max_shift``,
nearest-neighbour quantization against the format's level table, and
Algorithm 1 compensation over the contracting-dim rows. It is a thin
wrapper over the unified engine (:mod:`repro.core.convert`, granularity
``per_slice``), so it both (a) jits for real conversions and (b)
``eval_shape``s for the allocation-free dry-run (a 1T-param Kimi-K2
conversion is "performed" abstractly in milliseconds).

What gets encoded: every matmul weight that flows through
``layers.matmul`` or the MoE expert einsums. Embeddings, the LM head,
depthwise convs, RG-LRU gate matrices, routers, norms and biases stay
in the model dtype (they are a negligible byte fraction and/or
accuracy-critical; DESIGN.md §4).

The model-level entry point is :func:`repro.api.quantize`
(:class:`~repro.api_schemes.LmAdapter` packs through
:func:`repro.api_schemes.pack_lm_params`, which owns the tree walk);
:func:`quantize_params_for_serving` remains as a deprecated wrapper.
"""
from __future__ import annotations

import warnings
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.elp_bsd import ElpBsdFormat, resolve_format
from repro.kernels.ops import PackedWeight, pack_weight, packed_tree_bytes

Array = jax.Array
F32 = jnp.float32

# Leaf names whose trailing [K, N] dims are matmul weights to encode.
QUANTIZABLE = {
    "wq", "wk", "wv", "wo", "w1", "w2", "w3", "xq", "xk", "xv", "xo",
    "in_proj", "out_proj", "w_gate", "w_rec", "w_out", "frontend_proj",
    "we1", "we2", "we3",
}

# Which calibration tap site measures each matmul leaf's *input*
# (transformer.forward's collection sites, DESIGN.md §6). Leaves with
# no measured site (cross-attention xq/xk/xv/xo — their inputs are the
# encoder output / post-ln_x stream, which the decoder-LM calibration
# pass never sees — plus rg-lru / mamba projections and routers) are
# served without static activation quantization rather than with a
# wrong-distribution scale.
ACT_SITE_BY_LEAF = {
    "wq": "attn_in", "wk": "attn_in", "wv": "attn_in",
    "wo": "attn_mix",
    "w1": "ffn_in", "w3": "ffn_in", "we1": "ffn_in", "we3": "ffn_in",
    "w2": "ffn_hidden", "we2": "ffn_hidden",
}


def quantize_stacked(
    w: Array, fmt: ElpBsdFormat, *, compensate: bool = True, nibble: bool | None = None
) -> PackedWeight:
    """Encode ``w[..., K, N]`` with per-stack-slice scale factors.

    Thin wrapper over the unified conversion engine: per-slice scale
    granularity, Algorithm 1 over the contracting-dim rows. A stack
    converts one leading-axis slice at a time (``lax.map``), so its
    float32 copy and Algorithm 1's sort temporaries exist for one slice
    only — qwen3_8b's ``w1`` stack would need 7.2 GB for the f32 copy
    alone. Scales and compensation groups never span slices, so the
    codes equal a whole-stack conversion bit for bit.
    """

    def convert(s: Array) -> PackedWeight:
        pw, _ = pack_weight(
            s.astype(F32), fmt, compensate=compensate, granularity="per_slice", nibble=nibble
        )
        return pw

    return convert(w) if w.ndim == 2 else jax.lax.map(convert, w)


def quantize_params_for_serving(
    params: Any,
    cfg: ArchConfig,
    fmt: ElpBsdFormat | str,
    *,
    compensate: bool = True,
    calib=None,
) -> Any:
    """Deprecated wrapper: replace every quantizable matmul leaf with a
    PackedWeight.

    Use :func:`repro.api.quantize` instead — it drives the same packing
    walk (:func:`repro.api_schemes.pack_lm_params`) from a
    :class:`~repro.api_schemes.QuantScheme` and returns a servable,
    serializable :class:`~repro.api.QuantizedModel`.
    """
    warnings.warn(
        "runtime.quantized_params.quantize_params_for_serving is deprecated; "
        "use repro.api.quantize",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.api_schemes import pack_lm_params

    return pack_lm_params(
        params, cfg, resolve_format(fmt), compensate=compensate, calib=calib
    )


def abstract_quantize_tree(aparams: Any, cfg: ArchConfig, fmt: ElpBsdFormat | str) -> Any:
    """ShapeDtypeStruct tree of the quantized params (no allocation).

    ``fmt`` is a real :class:`ElpBsdFormat` or any spelling
    :func:`repro.core.elp_bsd.resolve_format` accepts; unknown tags
    raise ``ValueError`` here, before any tracing happens.
    """
    from repro.api_schemes import pack_lm_params

    fmt = resolve_format(fmt)
    return jax.eval_shape(
        lambda p: pack_lm_params(p, cfg, fmt, compensate=False), aparams
    )


def packed_bytes(params: Any) -> int:
    """Total weight bytes of a (possibly partially) packed tree.

    Delegates to :func:`repro.kernels.ops.packed_tree_bytes` — the one
    packed-size accounting walk.
    """
    return packed_tree_bytes(params)
