"""Grouped GQA attention against the repeat-then-attend formula.

``attention_dot`` and ``attention_chunked`` take k/v with KV heads and
attend each query group over them as stored. The reference here is the
formula they replace: ``repeat_kv`` up to H heads, then scores, softmax
and PV all in f32 at the highest precision.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.layers import attention_chunked, attention_dot, repeat_kv

F32 = jnp.float32
B, SK, HD, KV, CHUNK = 2, 16, 8, 2, 8
# output tolerance from the dtype: f32 to reordered sums, bf16 to one
# rounding of the same f32 value (one ulp, 2**-7 relative at worst)
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-6), jnp.bfloat16: dict(rtol=2**-7, atol=2**-12)}


def reference(q, k, v, *, causal, window, q_offset):
    """Repeat k/v to H heads, then attend in f32 at HIGHEST."""
    b, sq, h, hd = q.shape
    kf = repeat_kv(k, h // k.shape[2]).astype(F32)
    vf = repeat_kv(v, h // v.shape[2]).astype(F32)
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32), kf, precision=hi) / math.sqrt(hd)
    q_offset = jnp.asarray(q_offset)
    qpos = (q_offset[:, None] if q_offset.ndim == 1 else q_offset) + jnp.arange(sq)
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones(qpos.shape + kpos.shape, bool)
    if causal:
        mask &= qpos[..., None] >= kpos
    if window:
        mask &= qpos[..., None] - kpos < window
    logits = jnp.where(mask[:, None] if mask.ndim == 3 else mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vf, precision=hi)


def _qkv(group, sq, dtype, seed=0):
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, sq, KV * group, HD), F32).astype(dtype)
    k = jax.random.normal(kk, (B, SK, KV, HD), F32).astype(dtype)
    v = jax.random.normal(kv_, (B, SK, KV, HD), F32).astype(dtype)
    return q, k, v


def _offset(kind, sq):
    # every query sees at least its own position, inside the keys
    return SK - sq if kind == "scalar" else jnp.array([3, SK - sq], jnp.int32)


def _check(out, want, dtype):
    assert out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out.astype(F32)), np.asarray(want.astype(dtype).astype(F32)), **TOL[dtype]
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("mask", ["causal", "window"])
@pytest.mark.parametrize("offset", ["scalar", "row"])
@pytest.mark.parametrize("sq", [1, 5], ids=["decode", "prefill"])
@pytest.mark.parametrize("group", [1, 4, 7])
@pytest.mark.parametrize("fn", ["dot", "chunked"])
def test_grouped_attention_matches_repeated_reference(fn, group, sq, offset, mask, dtype):
    q, k, v = _qkv(group, sq, dtype)
    kw = dict(causal=True, window=5 if mask == "window" else 0, q_offset=_offset(offset, sq))
    if fn == "dot":
        out = attention_dot(q, k, v, **kw)
    else:
        out = attention_chunked(q, k, v, chunk=CHUNK, **kw)
    _check(out, reference(q, k, v, **kw), dtype)


def _caches(layout, k):
    """An empty cache of ``layout`` for rows of ``SK`` positions, and
    what ``_attend`` reads it back as (the reference's k/v)."""
    zeros = jnp.zeros((B, SK) + k.shape[2:], k.dtype)
    if layout == "dense":
        return (zeros, zeros), lambda c: (c[0], c[1])
    if layout == "quant":
        kq, ks = T._cache_q(zeros)
        return (kq, kq, ks, ks), lambda c: (
            T._cache_dq(c[0], c[2], k.dtype), T._cache_dq(c[1], c[3], k.dtype)
        )
    page = 4
    pool = jnp.zeros((B * SK // page + 1, page) + k.shape[2:], k.dtype)
    pages = jnp.arange(B * SK // page, dtype=jnp.int32).reshape(B, SK // page)
    return (pool, pool, pages), lambda c: (T._paged_view(c[0], pages), T._paged_view(c[1], pages))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("sq", [1, 3], ids=["decode", "verify"])
@pytest.mark.parametrize("layout", ["dense", "quant", "paged"])
def test_attend_reads_each_layout_grouped(layout, sq, dtype):
    """``_attend`` writes this step's k/v and attends the cache as its
    layout reads it back, without repeating it, at per-row depths."""
    group = 4
    q, k, v = _qkv(group, sq, dtype, seed=1)
    k, v = k[:, :sq], v[:, :sq]
    cache, read = _caches(layout, k)
    pos = jnp.array([2, SK - sq], jnp.int32)
    out, new_cache = T._attend(
        q, k, v, dtype, causal=True, window=0, kv_cache=cache, kv_layout=layout,
        cache_pos=pos, pctx=None,
    )
    ck, cv = read(new_cache)
    _check(out, reference(q, ck, cv, causal=True, window=0, q_offset=pos), dtype)
