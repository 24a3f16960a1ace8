"""Plain float32 reference of a Qwen3-style decoder LM on 4-bit codes.

The published equations, layer by layer: RMSNorm (gain 1, the neutral
weights' norms), Q/K/V projections, per-head RMSNorm of Q and K
(``qk_norm``), rotate-half RoPE with ``rope_theta``, causal grouped-query
attention, the output projection, a residual add, RMSNorm, a SwiGLU MLP
(``silu(x W1) * (x W3) W2``) and a residual add; then a final RMSNorm
and the head. No cache, no batching of requests into slots, no kernels.

It runs one layer at a time over a block of whole sequences padded at
the end to one fixed length (causal, so the padding never reaches a real
position), decoding that layer's codes inside the call: a layer's
float32 weights are the largest thing it holds besides the activations.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference.common import HIGHEST, decode, mm, operands

F32 = jnp.float32


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """Rotate-half RoPE over ``x[B, S, H, hd]`` at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=F32) / (hd // 2))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer(x, layers, l, *, cfg, mode):
    c = dict(cfg)
    fmt, eps = c["format"], c["rms_norm_eps"]
    b, s, d = x.shape
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    dims = {
        "wq": d, "wk": d, "wv": d, "wo": h * hd,
        "w1": d, "w3": d, "w2": c["intermediate_size"],
    }

    def w(name):
        p = layers[name]
        codes = jax.lax.dynamic_index_in_dim(p["codes"], l, keepdims=False)
        return decode(codes, p["scale"][l], fmt, dims[name])

    a = _rms(x, eps)
    q = mm(a, w("wq"), mode).reshape(b, s, h, hd)
    k = mm(a, w("wk"), mode).reshape(b, s, kv, hd)
    v = mm(a, w("wv"), mode).reshape(b, s, kv, hd)
    if c["qk_norm"]:
        q, k = _rms(q, eps), _rms(k, eps)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    g = h // kv
    q = q.reshape(b, s, kv, g, hd)
    qo, ko = operands(mode, q, k)
    att = jnp.einsum("bqkgd,bskd->bkgqs", qo, ko, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    po, vo = operands(mode, att, v)
    o = jnp.einsum("bkgqs,bskd->bqkgd", po, vo, precision=HIGHEST).reshape(b, s, h * hd)
    x = x + mm(o, w("wo"), mode)
    a = _rms(x, eps)
    hid = jax.nn.silu(mm(a, w("w1"), mode)) * mm(a, w("w3"), mode)
    return x + mm(hid, w("w2"), mode)


def _static(cfg: dict) -> tuple:
    keys = (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "rms_norm_eps", "rope_theta", "qk_norm",
    )
    return tuple((k, cfg[k]) for k in keys) + (("format", cfg["weights"]["format"]),)


def hidden(weights: dict, cfg: dict, tokens: jax.Array, mode: str = "highest") -> jax.Array:
    """Final-normed hidden states ``[B, S, D]`` of ``tokens[B, S]``."""
    x = weights["embed"][tokens].astype(F32)
    static = _static(cfg)
    for l in range(cfg["num_hidden_layers"]):
        x = _layer(x, weights["layers"], l, cfg=static, mode=mode)
    return _rms(x, cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("mode",))
def _row_logits(h, head, *, mode):
    return mm(h, head.astype(F32), mode)


def logit_gaps(
    h_ref: jax.Array, head: jax.Array, picks: jax.Array, h_ctl: jax.Array | None = None
) -> tuple[jax.Array, jax.Array | None]:
    """Per position, by how much the reference's logit of the picked
    token lies below the reference's best: ``[B, S]``.

    With ``h_ctl`` (the control's hidden states) also the same gap for
    the token the control puts first at each position.
    """
    gaps, ctl_gaps = [], []
    for b in range(h_ref.shape[0]):
        ref = _row_logits(h_ref[b], head, mode="highest")
        best = jnp.max(ref, axis=-1)
        gaps.append(best - jnp.take_along_axis(ref, picks[b][:, None], -1)[:, 0])
        if h_ctl is not None:
            top = jnp.argmax(_row_logits(h_ctl[b], head, mode="fp8"), axis=-1)
            ctl_gaps.append(best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0])
    return jnp.stack(gaps), (jnp.stack(ctl_gaps) if h_ctl is not None else None)
