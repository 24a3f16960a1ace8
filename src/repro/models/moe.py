"""Mixture-of-Experts FFN: top-k routing with two interchangeable impls.

* ``dense`` — every expert processes every token, masked by the gate.
  O(E/topk) FLOP overhead; used only for tiny smoke configs and as the
  correctness oracle for the EP path.
* ``ep`` — production expert parallelism under ``shard_map``: tokens are
  bucketed by destination shard, exchanged with ``all_to_all`` over the
  model axis, processed by the shard's local experts as one batched
  einsum (static shapes, capacity-factor token dropping), and returned.
  FLOPs scale with top-k, not E — this is what makes the 384-expert
  Kimi-K2 cell compilable with a truthful cost model.

Both paths share the router; the property test asserts they agree when
capacity is not exceeded.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.configs.base import ArchConfig
from repro.models.context import ParallelCtx
from repro.models.layers import matmul

Array = jax.Array
F32 = jnp.float32


def router_gates(x2d: Array, router: Array, topk: int) -> tuple[Array, Array]:
    """Top-k routing. Returns (gate weights [T,k] f32, expert ids [T,k])."""
    logits = jnp.dot(x2d.astype(F32), router.astype(F32))
    topv, topi = jax.lax.top_k(logits, topk)
    gates = jax.nn.softmax(topv, axis=-1)
    return gates, topi


def _dq(w, dtype) -> Array:
    """Expert weights may arrive ELP_BSD-packed (serving path)."""
    from repro.kernels.ops import PackedWeight, dequantize

    if isinstance(w, PackedWeight):
        return dequantize(w).astype(dtype)
    return w.astype(dtype)


def _expert_ffn(h: Array, w1, w3, w2, kind: str) -> Array:
    """Batched expert FFN: h[E, C, D] with weights [E, D, ff] / [E, ff, D]."""
    a = jnp.einsum("ecd,edf->ecf", h, _dq(w1, h.dtype), preferred_element_type=F32)
    if kind == "swiglu":
        b = jnp.einsum("ecd,edf->ecf", h, _dq(w3, h.dtype), preferred_element_type=F32)
        z = (jax.nn.silu(a) * b).astype(h.dtype)
    else:
        z = jax.nn.gelu(a).astype(h.dtype)
    return jnp.einsum("ecf,efd->ecd", z, _dq(w2, h.dtype), preferred_element_type=F32).astype(
        h.dtype
    )


# ---------------------------------------------------------------------------
# Dense (oracle) path
# ---------------------------------------------------------------------------
def moe_dense(p: dict[str, Array], x2d: Array, cfg: ArchConfig) -> Array:
    gates, topi = router_gates(x2d, p["router"], cfg.topk)
    t, d = x2d.shape
    e = cfg.n_experts
    # [T, E] combine matrix
    combine = jnp.zeros((t, e), F32)
    combine = combine.at[jnp.arange(t)[:, None], topi].add(gates)
    h = jnp.broadcast_to(x2d[None], (e, t, d))
    y = _expert_ffn(h, p["we1"], p.get("we3"), p["we2"], cfg.mlp_kind)  # [E, T, D]
    return jnp.einsum("etd,te->td", y.astype(F32), combine).astype(x2d.dtype)


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map)
# ---------------------------------------------------------------------------
def _moe_ep_local(
    x_loc: Array,
    router: Array,
    we1: Array,
    we3: Array | None,
    we2: Array,
    *,
    cfg: ArchConfig,
    axis: str,
    n_shards: int,
) -> Array:
    """Per-shard body. x_loc[t, D]; we*[E_loc, ...] (this shard's experts)."""
    t, d = x_loc.shape
    k = cfg.topk
    e = cfg.n_experts
    e_loc = e // n_shards
    cap = max(8, int(math.ceil(t * k / e * cfg.moe_capacity_factor)))

    gates, topi = router_gates(x_loc, router, k)  # [t, k]
    e_flat = topi.reshape(-1)  # [t*k] global expert ids
    g_flat = gates.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(t), k)

    dst = e_flat // e_loc  # destination shard
    le = e_flat % e_loc  # local expert there
    # Slot within each (dst, le) bucket = rank among equal expert ids.
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    start = jnp.searchsorted(e_sorted, e_flat, side="left")
    rank_sorted = jnp.arange(t * k) - start[order]
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    send = jnp.zeros((n_shards, e_loc, cap, d), x_loc.dtype)
    send = send.at[dst, le, slot].set(x_loc[tok_flat], mode="drop")
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=True)
    # recv[src, e_loc, cap, d] -> experts on dim 0
    h = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_shards * cap, d)
    y = _expert_ffn(h, we1, we3, we2, cfg.mlp_kind)
    y = y.reshape(e_loc, n_shards, cap, d).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0, tiled=True)
    # Combine: contributions land back at (dst, le, slot).
    contrib = back[dst, le, slot] * g_flat[:, None].astype(x_loc.dtype)
    dropped = slot >= cap
    contrib = jnp.where(dropped[:, None], 0, contrib)
    out = jnp.zeros_like(x_loc).at[tok_flat].add(contrib)
    return out


def moe_ep(p: dict[str, Array], x2d: Array, cfg: ArchConfig, pctx: ParallelCtx) -> Array:
    axis = pctx.model_axis
    n_shards = pctx.model_size
    assert cfg.n_experts % n_shards == 0, (cfg.n_experts, n_shards)
    assert "we3" in p, "EP MoE assumes gated (swiglu) experts"
    fn = partial(_moe_ep_local, cfg=cfg, axis=axis, n_shards=n_shards)
    # Divisibility-aware token sharding: prefer all axes (full sharding);
    # decode batches may be smaller than the mesh — fall back to the
    # batch axes only (tokens then replicated over the model axis, which
    # the EP math handles: every model shard routes the same tokens and
    # keeps only its local experts' results).
    t = x2d.shape[0]
    tok_axes: tuple = ()
    axes_options = [pctx.all_axes, tuple(pctx.batch_axes), ()]
    for cand in axes_options:
        n = 1
        for a in cand:
            n *= pctx.mesh.shape[a]
        if t % n == 0 and t >= n:
            tok_axes = cand
            break
    tok = P(tok_axes, None) if tok_axes else P(None, None)

    def espec(w):
        # Plain [E, D, ff] arrays shard the expert dim; PackedWeight
        # shards codes AND per-expert sf the same way (both lead with E).
        return jax.tree.map(lambda _: P(axis), w)

    mapped = jax.shard_map(
        fn,
        mesh=pctx.mesh,
        in_specs=(tok, P(None, None), espec(p["we1"]), espec(p["we3"]), espec(p["we2"])),
        out_specs=tok,
        check_vma=False,
    )
    return mapped(x2d, p["router"], p["we1"], p["we3"], p["we2"])


def moe_apply(
    p: dict[str, Array], x2d: Array, cfg: ArchConfig, pctx: ParallelCtx | None
) -> Array:
    if pctx is not None and pctx.moe_impl == "ep":
        return moe_ep(p, x2d, cfg, pctx)
    return moe_dense(p, x2d, cfg)


def load_balance_loss(x2d: Array, router: Array, topk: int, n_experts: int) -> Array:
    """Switch-style auxiliary load-balancing loss (f·P dot product)."""
    logits = jnp.dot(x2d.astype(F32), router.astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    _, topi = jax.lax.top_k(logits, topk)
    f = jnp.mean(
        jax.nn.one_hot(topi, n_experts, dtype=F32).sum(1), axis=0
    )  # fraction routed per expert
    pbar = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * pbar) / topk
