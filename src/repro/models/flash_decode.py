"""Flash-decoding: single-token attention over a sequence-sharded KV cache.

Baseline decode shards the cache over heads (or head_dim when heads do
not divide the model axis) — the head_dim fallback makes the QK
contraction *partial* per shard and XLA inserts a full
``[B, H, 1, S]`` f32 all-reduce per layer (measured ~72 GB wire/token
on qwen3-8b decode_32k; DESIGN.md §7).

Flash-decoding instead shards the cache SEQUENCE over the model axis:
each shard computes attention over its seq slice and the shards
exchange only the softmax statistics —

    per shard:  m, l, acc  =  max / sum-exp / weighted V  over s_loc
    combine:    M = pmax(m);  out = psum(acc·e^{m−M}) / psum(l·e^{m−M})

which is ``[B, H, 1(+hd)]`` — ~S/hd times fewer wire bytes. Implemented
with ``shard_map`` (manual collectives); used when
``pctx.flash_decode`` is on and the arch's kv-head count does not
divide the model axis (divisible archs keep head-sharded decode, which
is already collective-free).

Paged caches (DESIGN.md §12) feed this path through their LOGICAL
views: ``_attention`` gathers (and, for int8 codes, dequantizes) the
``[B, Pmax*page, KV, hd]`` view from the page pool first, then calls
:func:`flash_decode_attention` on it exactly as for a dense cache — the
seq-slicing here never sees page boundaries.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.context import ParallelCtx

Array = jax.Array
F32 = jnp.float32


def _chunk_stats(qf, kf, vf, kpos, qpos, *, window: int):
    """Softmax stats (m, l, acc) of ``qf`` against one key/value chunk."""
    logits = jnp.einsum("bqhd,bshd->bhqs", qf, kf)  # [B,H,sq,s_chunk]
    mask = kpos.reshape((1, 1, 1, -1)) <= qpos  # [B|1,1,sq,s_chunk], broadcasts over H
    if window:
        mask &= (qpos - kpos.reshape((1, 1, 1, -1))) < window
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1)  # [B,H,sq]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)  # [B,H,sq]
    acc = jnp.einsum("bhqs,bshd->bhqd", p, vf)  # [B,H,sq,hd]
    return m, l, acc


def _local_attn(
    q, k, v, ks, vs, pos, *, axis: str, window: int, n_rep: int, block_s: int | None = None
):
    """Per-shard body. q[B,sq,H,hd]; k/v[B,s_loc,KV,hd] = this shard's
    slice (optionally int8 with per-token-head scales ks/vs). ``pos`` is
    a scalar (lockstep batch) or a per-row ``[B]`` vector (continuous
    batching: each slot masked to its own depth). ``sq > 1`` is the
    speculative verify run: query ``i`` of row ``b`` sits at position
    ``pos[b] + i`` and is masked causally within the run.

    ``block_s`` streams the shard-local slice through the softmax-stats
    combine in seq chunks; ``None`` is the one-shot slice."""
    b, sq, h, hd = q.shape
    s_loc = k.shape[1]
    idx = jax.lax.axis_index(axis)
    # query positions: [B|1, 1, sq, 1], broadcasting against kpos below
    qpos = pos.reshape((-1, 1, 1, 1)) + jnp.arange(sq).reshape((1, 1, sq, 1))

    kf = k.astype(F32) if ks is None else k.astype(F32) * ks
    vf = v.astype(F32) if vs is None else v.astype(F32) * vs
    kf = jnp.repeat(kf, n_rep, axis=2)  # [B,s,H,hd]
    vf = jnp.repeat(vf, n_rep, axis=2)
    qf = q.astype(F32) * (1.0 / math.sqrt(hd))
    if block_s is None or block_s >= s_loc or s_loc % block_s:
        kpos = idx * s_loc + jnp.arange(s_loc)
        m, l, acc = _chunk_stats(qf, kf, vf, kpos, qpos, window=window)
    else:
        # Streaming combine over seq chunks — same running-max rescale
        # as the cross-shard combine below, applied chunk-by-chunk.
        m = jnp.full((b, h, sq), -jnp.inf, F32)
        l = jnp.zeros((b, h, sq), F32)
        acc = jnp.zeros((b, h, sq, hd), F32)
        for c in range(s_loc // block_s):
            sl = slice(c * block_s, (c + 1) * block_s)
            kpos = idx * s_loc + c * block_s + jnp.arange(block_s)
            mc, lc, ac = _chunk_stats(qf, kf[:, sl], vf[:, sl], kpos, qpos, window=window)
            mn = jnp.maximum(m, mc)
            cr, crc = jnp.exp(m - mn), jnp.exp(mc - mn)
            m = mn
            l = l * cr + lc * crc
            acc = acc * cr[..., None] + ac * crc[..., None]

    # combine softmax stats across seq shards — the ONLY collective
    mg = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - mg)
    lg = jax.lax.psum(l * corr, axis)
    accg = jax.lax.psum(acc * corr[..., None], axis)
    out = accg / jnp.maximum(lg, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # [B,sq,H,hd]


def flash_decode_attention(
    q: Array,
    ck: Array,
    cv: Array,
    pos: Array,
    *,
    pctx: ParallelCtx,
    window: int = 0,
    ks: Array | None = None,
    vs: Array | None = None,
    block_s: int | None = None,
) -> Array:
    """q[B,1,H,hd] against cache ck/cv[B,S,KV,hd] seq-sharded over model.

    ``ks``/``vs`` are per-(token, head) scales for an int8 cache
    (dequantized per shard, inside the map — HBM moves int8).

    ``block_s`` chunks each shard's seq slice through a streaming
    softmax combine; the default ``None`` is the one-shot slice."""
    axis = pctx.model_axis
    h = q.shape[2]
    kv = ck.shape[2]
    n_rep = h // kv
    ba = pctx.batch_axes
    b = q.shape[0]
    import numpy as np

    nb = int(np.prod([pctx.mesh.shape[a] for a in ba]))
    bspec = ba if (b % nb == 0 and b >= nb) else None
    qspec = P(bspec, None, None, None)
    cspec = P(bspec, axis, None, None)
    pos = jnp.asarray(pos, jnp.int32)
    # a per-row [B] position vector shards with the batch; a scalar is
    # replicated
    pspec = P(bspec) if pos.ndim == 1 else P()
    if ks is not None:
        fn = partial(_local_attn, axis=axis, window=window, n_rep=n_rep, block_s=block_s)
        mapped = jax.shard_map(
            fn,
            mesh=pctx.mesh,
            in_specs=(qspec, cspec, cspec, cspec, cspec, pspec),
            out_specs=qspec,
            check_vma=False,
        )
        return mapped(q, ck, cv, ks, vs, pos)

    def fn4(q_, k_, v_, pos_):
        return _local_attn(
            q_, k_, v_, None, None, pos_,
            axis=axis, window=window, n_rep=n_rep, block_s=block_s,
        )

    mapped = jax.shard_map(
        fn4,
        mesh=pctx.mesh,
        in_specs=(qspec, cspec, cspec, pspec),
        out_specs=qspec,
        check_vma=False,
    )
    return mapped(q, ck, cv, pos)
