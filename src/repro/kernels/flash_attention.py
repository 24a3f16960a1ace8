"""Pallas TPU kernel: causal flash attention (prefill path).

The models' long-sequence attention uses a ``lax.scan`` chunked form
(`layers.attention_chunked`) so the CPU-lowered dry-run compiles fast;
THIS kernel is the TPU-target replacement for that scan — one fused
pallas_call that keeps the running softmax statistics in VMEM scratch
and never materializes the [S, S] score matrix in HBM.

Tiling: grid = (B·H, S/bq, S/bk) with the key dimension innermost
("arbitrary" semantics — the scratch carries m/l/acc across k steps);
q/k/v blocks are [bq, hd] / [bk, hd] VMEM tiles, MXU-aligned (bq, bk
multiples of 128, hd is the lane dim). Causality is applied per element
inside the tile; fully-masked tiles are cheap (the mask zeroes them)
and a production refinement would skip them via the index map.

Validated in interpret mode against `layers.attention_dot` (no TPU in
this container).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array
F32 = jnp.float32

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, n_k, bq, bk, scale, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(F32) * scale  # [bq, hd]
    k = k_ref[0].astype(F32)  # [bk, hd]
    logits = jnp.dot(q, k.T, preferred_element_type=F32)  # [bq, bk]
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        logits = jnp.where(qpos >= kpos, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1))
    p = jnp.exp(logits - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    m_ref[...] = m_new
    v = v_ref[0].astype(F32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(p, v, preferred_element_type=F32)

    @pl.when(ki == n_k - 1)
    def _store():
        o_ref[0, ...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> Array:
    """Fused attention. q/k/v: [B, H, S, hd] (KV already GQA-repeated).

    S must tile by the block sizes (callers pad); returns [B, H, S, hd].
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, s, hd = q.shape
    sk = k.shape[2]
    if s % block_q != 0 or sk % block_k != 0:
        raise ValueError(
            f"sequence lengths must tile by the block sizes: s={s} "
            f"block_q={block_q}, sk={sk} block_k={block_k} (callers pad)"
        )
    scale = 1.0 / math.sqrt(hd)
    n_k = sk // block_k
    grid = (b * h, s // block_q, n_k)

    qr = q.reshape(b * h, s, hd)
    kr = k.reshape(b * h, sk, hd)
    vr = v.reshape(b * h, sk, hd)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, n_k=n_k, bq=block_q, bk=block_k, scale=scale, causal=causal
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), F32),  # running max
            pltpu.VMEM((block_q,), F32),  # running sum
            pltpu.VMEM((block_q, hd), F32),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, s, hd)
