"""Shared neural layers: norms, RoPE, GQA attention, MLPs.

Pure functions over parameter dicts. Conventions:
  * activations ``[B, S, D]``; attention heads ``[B, S, H, hd]``,
  * weights are ``[in, out]`` matmul matrices (ELP_BSD quantization
    groups along the contracting ``in`` axis, see DESIGN.md §4),
  * float32 accumulation everywhere (``preferred_element_type``),
  * long sequences use a chunked (flash-style) attention built from
    ``lax.scan`` so the lowered HLO stays small and memory O(S·chunk)
    — the TPU kernel analogue is a Pallas splash kernel; on this
    CPU-lowered dry-run the scan form keeps compile time tractable.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
F32 = jnp.float32


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    # Variance is accumulated in f32 via the reduction dtype WITHOUT
    # materializing a full f32 copy of x: a bare ``x.astype(f32)`` on the
    # layer input gets hoisted out of the backward scan by XLA's loop-
    # invariant code motion, materializing an [L, B, S, D] f32 buffer
    # (measured: +30 GiB/device on deepseek-7b train_4k).
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=F32)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + scale).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    mu = jnp.mean(x, axis=-1, keepdims=True, dtype=F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=F32) - jnp.square(mu)
    inv = jax.lax.rsqrt(var + eps)
    return ((x - mu.astype(x.dtype)) * inv.astype(x.dtype)) * scale.astype(
        x.dtype
    ) + bias.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_embed(positions: Array, head_dim: int, theta: float = 1e4) -> tuple[Array, Array]:
    """cos/sin tables ``[..., head_dim/2]`` for given positions."""
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, head_dim // 2, dtype=F32) / (head_dim // 2)
    )
    ang = positions.astype(F32)[..., None] * freqs  # [..., hd/2]
    return jnp.cos(ang), jnp.sin(ang)


@functools.lru_cache(maxsize=None)
def _rot_half_matrix(d: int) -> np.ndarray:
    """Constant ``R`` with ``x @ R == concat([-x2, x1])`` (rotate-half).

    RoPE is applied as a contraction instead of slice+concatenate on the
    head_dim axis: the SPMD partitioner miscompiles concatenations of
    slices of a sharded dim (observed on the CPU backend when kv*hd
    shards split inside a head), while dot contractions reshard exactly.
    """
    d2 = d // 2
    r = np.zeros((d, d), np.float32)
    r[np.arange(d2) + d2, np.arange(d2)] = -1.0
    r[np.arange(d2), np.arange(d2) + d2] = 1.0
    return r


def _tile2(t: Array) -> Array:
    """``concat([t, t], -1)`` via broadcast+reshape: a concatenate built
    inside a scan body miscompiles under the SPMD partitioner when its
    product is multiplied with a sharded operand (same bug family as the
    rotate-half concat — see :func:`_rot_half_matrix`)."""
    d = t.shape[-1]
    return jnp.broadcast_to(t[..., None, :], t.shape[:-1] + (2, d)).reshape(
        t.shape[:-1] + (2 * d,)
    )


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate ``x[B, S, H, hd]`` with tables ``[B?, S, hd/2]``."""
    while cos.ndim < x.ndim:  # broadcast over head dim
        cos, sin = cos[..., None, :], sin[..., None, :]
    xf = x.astype(F32)
    rot = jnp.dot(xf, jnp.asarray(_rot_half_matrix(x.shape[-1])))
    return (xf * _tile2(cos) + rot * _tile2(sin)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def repeat_kv(k: Array, n_rep: int) -> Array:
    """[B, S, KV, hd] -> [B, S, KV*n_rep, hd]: the MHA view of a GQA cache.

    The attention functions below take k/v un-repeated; this is the
    reference the tests compare them against.
    """
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(
        b, s, kv * n_rep, hd
    )


def gqa_scores(q: Array, k: Array) -> Array:
    """Scaled scores ``[B, KV, H/KV, Sq, Sk]`` of ``q[B, Sq, H, hd]``
    against ``k[B, Sk, KV, hd]``; query head ``h`` attends KV head
    ``h // (H/KV)``, the order :func:`repeat_kv` makes (``KV == H`` is MHA).

    Both operands enter in their stored dtype and accumulate in f32: a
    bf16 product is exact in f32, so this rounds nothing an f32 upcast
    would keep, and each KV head is read once per query group, with no
    repeated or f32 copy of ``k`` written out.
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, sq, kv, h // kv, hd)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=F32)
    return logits / math.sqrt(hd)


def gqa_values(p: Array, v: Array) -> Array:
    """``p[B, KV, H/KV, Sq, Sk]`` (f32 probabilities) times
    ``v[B, Sk, KV, hd]`` -> ``[B, Sq, H, hd]`` in f32.

    ``HIGHEST`` keeps the probabilities unrounded (a single bf16 pass
    would round them); ``v``'s upcast fuses into the product.
    """
    b, kv, rep, sq, _ = p.shape
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", p, v, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=F32,
    )
    return out.reshape(b, sq, kv * rep, v.shape[-1])


def _query_mask(q_offset: int | Array, sq: int, kpos: Array, causal: bool, window: int) -> Array:
    """Which keys each query may see, broadcastable against scores
    ``[B, KV, H/KV, Sq, Sk]``. A scalar ``q_offset`` places every row's
    queries alike (mask ``[Sq, Sk]``); a ``[B]`` vector places each row
    at its own depth (mask ``[B, 1, 1, Sq, Sk]``)."""
    q_offset = jnp.asarray(q_offset)
    qpos = (q_offset[:, None] if q_offset.ndim == 1 else q_offset) + jnp.arange(sq)
    mask = jnp.ones(qpos.shape + kpos.shape, bool)
    if causal:
        mask &= qpos[..., None] >= kpos
    if window:
        mask &= qpos[..., None] - kpos < window
    return mask[:, None, None] if mask.ndim == 3 else mask


def attention_dot(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int | Array = 0,
) -> Array:
    """Plain O(S^2) attention. q[B,Sq,H,hd], k/v[B,Sk,KV,hd], KV | H.

    GQA attends per query group over k/v as stored (:func:`gqa_scores`,
    :func:`gqa_values`); softmax in f32, the output in q's dtype.
    ``q_offset`` positions the queries for causal/window masking: a
    scalar offsets every row identically; a ``[B]`` vector gives each
    row its own offset (continuous-batching decode, where every slot
    sits at a different depth into its own cache).
    """
    sq, sk = q.shape[1], k.shape[1]
    mask = _query_mask(q_offset, sq, jnp.arange(sk), causal, window)
    logits = jnp.where(mask, gqa_scores(q, k), -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return gqa_values(probs, v).astype(q.dtype)


def attention_chunked(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
    q_offset: int | Array = 0,
) -> Array:
    """Flash-style attention: scan over KV chunks with running max/sum.

    Memory O(Sq · chunk); HLO is one scan body regardless of S. Takes
    k/v ``[B, Sk, KV, hd]`` and equals :func:`attention_dot` to float
    tolerance (property-tested), at the same precision.
    ``q_offset`` positions the queries exactly as in
    :func:`attention_dot` (scalar shared offset or per-row ``[B]``).
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert sk % chunk == 0, (sk, chunk)
    n_chunks = sk // chunk
    kc = k.reshape(b, n_chunks, chunk, kv, hd)
    vc = v.reshape(b, n_chunks, chunk, kv, hd)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, c_idx = inp
        msk = _query_mask(q_offset, sq, c_idx * chunk + jnp.arange(chunk), causal, window)
        logits = jnp.where(msk, gqa_scores(q, kb), -1e30)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum(
            "bgrqk,bkgd->bgrqd", p, vb, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=F32,
        )
        return (m_new, l_new, acc * corr[..., None] + pv), None

    stat = (b, kv, h // kv, sq)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (jnp.full(stat, -1e30, F32), jnp.zeros(stat, F32), jnp.zeros(stat + (hd,), F32)),
        (
            jnp.moveaxis(kc, 1, 0),
            jnp.moveaxis(vc, 1, 0),
            jnp.arange(n_chunks),
        ),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bgrqd->bqgrd", out).reshape(b, sq, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_apply(p: dict[str, Array], x: Array, kind: str, acts: dict | None = None) -> Array:
    """``kind``: 'swiglu'/'geglu' (w1,w3,w2) or 'gelu' (w1,w2).

    ``acts`` (calibration collection, DESIGN.md §6) records the hidden
    activation entering ``w2`` under ``"ffn_hidden"``.
    """
    with jax.named_scope("mlp"):
        if kind == "swiglu":
            h = jax.nn.silu(matmul(x, p["w1"])) * matmul(x, p["w3"])
        elif kind == "geglu":
            h = jax.nn.gelu(matmul(x, p["w1"])) * matmul(x, p["w3"])
        elif kind == "gelu":
            h = jax.nn.gelu(matmul(x, p["w1"]))
        else:
            raise ValueError(kind)
        if acts is not None:
            acts["ffn_hidden"] = h
        return matmul(h, p["w2"])


def matmul(x: Array, w) -> Array:
    """x[..., in] @ w[in, out] with f32 accumulation, output in x.dtype.

    ``w`` may be a packed ELP_BSD weight (serving path): the codes are
    decoded in-graph via ``impl="auto"``: the shape rule
    (``kernels/ops.select_impl``) picks the fused decode-step kernel or
    the tiled Pallas kernel and their tiles on TPU, the XLA dequant path
    elsewhere, at trace time. A 2-D weight on a mesh of
    several devices runs that choice per shard, at the shape one device
    computes, under ``shard_map``: a column-parallel weight (N split)
    returns its output split, a row-parallel one (K split) adds its
    partial sums with a ``psum`` (``kernels/ops.sharded_matmul``,
    DESIGN.md §5). Stacked (expert) weights stay on the XLA path.
    Either way HBM moves only the code bytes.
    """
    from repro.kernels.ops import PackedWeight, quantized_matmul

    if isinstance(w, PackedWeight):
        return quantized_matmul(x, w, impl="auto", out_dtype=x.dtype)
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=F32).astype(x.dtype)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def cross_entropy(logits: Array, labels: Array) -> Array:
    """Mean next-token CE, safe for a vocab-sharded logits tensor.

    Uses an iota-compare select instead of ``take_along_axis`` so the
    SPMD partitioner keeps the vocab dim sharded (a label gather across
    the sharded vocab would all-gather the full logits — measured at
    ~26 GB/device on deepseek-7b train_4k before this fix).
    """
    lf = logits.astype(F32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.squeeze(m, -1) + jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], lf, 0.0), axis=-1)
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key: Array, shape: tuple[int, ...], dtype: Any, scale: float = 1.0) -> Array:
    """Truncated-normal fan-in init (He-style)."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) * std).astype(dtype)


def split_keys(key: Array, names: list[str]) -> dict[str, Array]:
    ks = jax.random.split(key, len(names))
    return dict(zip(names, ks))
