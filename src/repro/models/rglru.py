"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local attention.

Layer pattern repeats ``cfg.period`` (for recurrentgemma-2b:
``("rec", "rec", "attn")`` — the paper's 1 attention per 2 recurrent).
The stack is scanned over *pattern groups* so the lowered HLO holds one
group body; leftover layers (26 = 8×3 + 2) are unrolled as a tail.

RG-LRU (train/prefill uses ``lax.associative_scan``, decode a 1-step
update):

    r_t = σ(W_a x_t + b_a)            recurrence gate
    i_t = σ(W_x x_t + b_x)            input gate
    log a_t = -c · softplus(Λ) · r_t   (c = 8)
    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ x_t)

Attention layers are GQA (MQA for the 2b config) with a sliding window,
so decode state is O(window) — this arch qualifies for ``long_500k``.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import (
    cross_entropy,
    dense_init,
    gqa_scores,
    gqa_values,
    matmul,
    mlp_apply,
    rms_norm,
    rope_embed,
)
from repro.models import transformer as T

Array = jax.Array
F32 = jnp.float32
LRU_C = 8.0


def _layer_kinds(cfg: ArchConfig) -> list[str]:
    return [cfg.period[i % len(cfg.period)] for i in range(cfg.n_layers)]


def _counts(cfg: ArchConfig) -> tuple[int, int, int, list[str]]:
    """(n_groups, n_rec, n_attn, tail_kinds)."""
    kinds = _layer_kinds(cfg)
    plen = len(cfg.period)
    g = cfg.n_layers // plen
    tail = kinds[g * plen :]
    n_rec = sum(k == "rec" for k in kinds)
    n_attn = sum(k == "attn" for k in kinds)
    return g, n_rec, n_attn, tail


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_rec_stack(cfg: ArchConfig, key: Array, n: int) -> dict[str, Array]:
    d, lru, ff = cfg.d_model, cfg.lru_width or cfg.d_model, cfg.d_ff
    dt = cfg.dtype
    ks = jax.random.split(key, 10)

    def stack(k, shape):
        keys = jax.random.split(k, n)
        return jax.vmap(lambda kk: dense_init(kk, shape, dt))(keys)

    # Λ init so a^(c·softplus) sits in (0.9, 0.999) at r=1 (griffin init)
    u = jax.random.uniform(ks[6], (n, lru), F32, 0.9, 0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / LRU_C))
    return {
        "ln1": jnp.zeros((n, d), dt),
        "w_gate": stack(ks[0], (d, lru)),
        "w_rec": stack(ks[1], (d, lru)),
        "conv_w": stack(ks[2], (cfg.conv_width, lru)),
        "conv_b": jnp.zeros((n, lru), dt),
        "wa": stack(ks[3], (lru, lru)),
        "ba": jnp.zeros((n, lru), F32),
        "wx": stack(ks[4], (lru, lru)),
        "bx": jnp.zeros((n, lru), F32),
        "lam": lam,
        "w_out": stack(ks[5], (lru, d)),
        "ln2": jnp.zeros((n, d), dt),
        "w1": stack(ks[7], (d, ff)),
        "w3": stack(ks[8], (d, ff)),
        "w2": stack(ks[9], (ff, d)),
    }


def _init_attn_stack(cfg: ArchConfig, key: Array, n: int) -> dict[str, Array]:
    return T.init_block_params(cfg, key, n)


def init_params(cfg: ArchConfig, key: Array) -> dict[str, Any]:
    g, n_rec, n_attn, tail = _counts(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "embed": dense_init(k1, (cfg.vocab, cfg.d_model), cfg.dtype),
        "rec": _init_rec_stack(cfg, k2, n_rec),
        "attn": _init_attn_stack(cfg, k3, n_attn),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
        "lm_head": dense_init(k4, (cfg.d_model, cfg.vocab), cfg.dtype),
    }


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------
def _rg_lru(
    lp: dict[str, Array], x: Array, h0: Array | None
) -> tuple[Array, Array]:
    """x [B,S,lru] (post-conv). Returns (y, final h [B,lru])."""
    xf = x.astype(F32)
    r = jax.nn.sigmoid(jnp.dot(xf, lp["wa"].astype(F32)) + lp["ba"])
    i = jax.nn.sigmoid(jnp.dot(xf, lp["wx"].astype(F32)) + lp["bx"])
    log_a = -LRU_C * jax.nn.softplus(lp["lam"])[None, None] * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * xf)
    if x.shape[1] == 1 and h0 is not None:
        h = a[:, 0] * h0 + b[:, 0]
        return h[:, None].astype(x.dtype), h
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)

    def comb(p, q):
        a1, b1 = p
        a2, b2 = q
        return a1 * a2, a2 * b1 + b2

    _, hs = jax.lax.associative_scan(comb, (a, b), axis=1)
    return hs.astype(x.dtype), hs[:, -1]


def _causal_conv(x: Array, w: Array, b: Array) -> Array:
    cw = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp.astype(F32),
        w.astype(F32)[:, None, :],
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    return (out + b.astype(F32)).astype(x.dtype)


def rec_block(
    lp: dict[str, Array],
    cfg: ArchConfig,
    x: Array,
    state: tuple[Array, Array] | None = None,
) -> tuple[Array, tuple[Array, Array] | None]:
    """Recurrent block + MLP. state = (conv window [B,cw-1,lru], h [B,lru])."""
    xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
    gate = jax.nn.gelu(matmul(xn, lp["w_gate"]).astype(F32)).astype(x.dtype)
    y = matmul(xn, lp["w_rec"])
    new_state = None
    if state is None:
        y = _causal_conv(y, lp["conv_w"], lp["conv_b"])
        y, _ = _rg_lru(lp, y, None)
    else:
        conv_win, h0 = state
        cw = cfg.conv_width
        if y.shape[1] == 1:  # decode: sliding conv window
            window = jnp.concatenate([conv_win, y], axis=1)[:, -cw:]
            y = (
                jnp.einsum("bwc,wc->bc", window.astype(F32), lp["conv_w"].astype(F32))
                + lp["conv_b"].astype(F32)
            )[:, None, :].astype(x.dtype)
            new_win = window[:, 1:].astype(conv_win.dtype)
        else:  # prefill: conv with the cached left context, keep last window
            ypad = jnp.concatenate([conv_win.astype(y.dtype), y], axis=1)
            out = jax.lax.conv_general_dilated(
                ypad.astype(F32),
                lp["conv_w"].astype(F32)[:, None, :],
                window_strides=(1,),
                padding="VALID",
                dimension_numbers=("NWC", "WIO", "NWC"),
                feature_group_count=y.shape[-1],
            )
            new_win = ypad[:, -(cw - 1) :].astype(conv_win.dtype)
            y = (out + lp["conv_b"].astype(F32)).astype(x.dtype)
        y, h = _rg_lru(lp, y, h0)
        new_state = (new_win, h)
    y = matmul(y * gate, lp["w_out"])
    x = x + y
    x = x + mlp_apply(lp, rms_norm(x, lp["ln2"], cfg.norm_eps), "geglu")
    return x, new_state


def _ring_qkv(lp, cfg, xn, positions):
    from repro.models.layers import apply_rope

    b, s, _ = xn.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = matmul(xn, lp["wq"]).reshape(b, s, h, hd)
    k = matmul(xn, lp["wk"]).reshape(b, s, kv, hd)
    v = matmul(xn, lp["wv"]).reshape(b, s, kv, hd)
    cos, sin = rope_embed(positions, hd, cfg.rope_theta)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _ring_prefill(lp, cfg, xn, kv_cache, ) -> tuple[Array, tuple[Array, Array]]:
    """Windowed prefill: normal local attention over S, then fill the
    ring with the last W keys/values (slot for position p = p mod W)."""
    from repro.models.layers import attention_chunked, attention_dot

    ck, cv = kv_cache
    w = ck.shape[1]
    b, s, _ = xn.shape
    q, k, v = _ring_qkv(lp, cfg, xn, jnp.arange(s)[None])
    if s >= T.CHUNKED_ATTN_THRESHOLD:
        out = attention_chunked(q, k, v, causal=True, window=cfg.window)
    else:
        out = attention_dot(q, k, v, causal=True, window=cfg.window)
    if s >= w:
        ring_k = jnp.roll(k[:, -w:], s % w, axis=1).astype(ck.dtype)
        ring_v = jnp.roll(v[:, -w:], s % w, axis=1).astype(cv.dtype)
        new = (ring_k, ring_v)
    else:
        new = (
            jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, 0, 0)),
            jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, 0, 0)),
        )
    return matmul(out.reshape(b, s, -1), lp["wo"]), new


def _ring_decode(lp, cfg, xn, kv_cache, pos) -> tuple[Array, tuple[Array, Array]]:
    """One-token decode against the W-slot ring (O(window) memory —
    what makes recurrentgemma long_500k constant-state)."""
    ck, cv = kv_cache
    w = ck.shape[1]
    b = xn.shape[0]
    q, k, v = _ring_qkv(lp, cfg, xn, pos[None, None] if jnp.ndim(pos) == 0 else pos)
    slot = pos % w
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
    # absolute position stored in slot i: p_i = pos - ((pos - i) mod W)
    i = jnp.arange(w)
    p_i = pos - jnp.mod(pos - i, w)
    valid = p_i >= 0
    logits = jnp.where(valid, gqa_scores(q, ck), -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = gqa_values(probs, cv).astype(xn.dtype)
    return matmul(out.reshape(b, 1, -1), lp["wo"]), (ck, cv)


def attn_block(
    lp: dict[str, Array],
    cfg: ArchConfig,
    x: Array,
    *,
    rope,
    kv_cache=None,
    cache_pos=None,
) -> tuple[Array, Any]:
    ring = (
        kv_cache is not None
        and cfg.window > 0
        and kv_cache[0].shape[1] == min(cfg.window, kv_cache[0].shape[1])
        and kv_cache[0].shape[1] <= cfg.window
    )
    if ring:
        xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if x.shape[1] == 1:
            out, new_cache = _ring_decode(lp, cfg, xn, kv_cache, cache_pos)
        else:
            out, new_cache = _ring_prefill(lp, cfg, xn, kv_cache)
    else:
        out, new_cache = T._attention(
            lp,
            cfg,
            rms_norm(x, lp["ln1"], cfg.norm_eps),
            rope=rope,
            causal=True,
            window=cfg.window,
            kv_cache=kv_cache,
            cache_pos=cache_pos,
        )
    x = x + out
    x = x + mlp_apply(lp, rms_norm(x, lp["ln2"], cfg.norm_eps), cfg.mlp_kind)
    return x, new_cache


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------
def _split_groups(tree, g: int, per: int):
    """[n, ...] stacked params -> grouped [g, per, ...] + tail [rest, ...]."""
    grouped = jax.tree.map(lambda a: a[: g * per].reshape(g, per, *a.shape[1:]), tree)
    tail = jax.tree.map(lambda a: a[g * per :], tree)
    return grouped, tail


def _run(params, cfg: ArchConfig, x: Array, cache=None, cache_pos=None, remat=False):
    g, n_rec, n_attn, tail_kinds = _counts(cfg)
    rec_per = sum(k == "rec" for k in cfg.period)
    attn_per = sum(k == "attn" for k in cfg.period)
    rec_g, rec_tail = _split_groups(params["rec"], g, rec_per)
    attn_g, attn_tail = _split_groups(params["attn"], g, attn_per)

    b, s, _ = x.shape
    positions = (
        jnp.arange(s)[None, :] if cache_pos is None else (cache_pos + jnp.arange(s))[None, :]
    )
    cos, sin = rope_embed(positions, cfg.hd, cfg.rope_theta)
    rope = (cos, sin, cos, sin)

    use_cache = cache is not None
    if use_cache:
        conv_g, conv_tail = (
            cache["conv"][: g * rec_per].reshape(g, rec_per, *cache["conv"].shape[1:]),
            cache["conv"][g * rec_per :],
        )
        h_g, h_tail = (
            cache["h"][: g * rec_per].reshape(g, rec_per, *cache["h"].shape[1:]),
            cache["h"][g * rec_per :],
        )
        k_g = cache["k"][: g * attn_per].reshape(g, attn_per, *cache["k"].shape[1:])
        v_g = cache["v"][: g * attn_per].reshape(g, attn_per, *cache["v"].shape[1:])

    def body(carry, xs):
        xc = carry
        if use_cache:
            rp, ap, conv, h, kc, vc = xs
            new_conv, new_h, new_k, new_v = [], [], [], []
            ri = ai = 0
            for kind in cfg.period:
                if kind == "rec":
                    lp = jax.tree.map(lambda a: a[ri], rp)
                    xc, st = rec_block(lp, cfg, xc, state=(conv[ri], h[ri]))
                    new_conv.append(st[0])
                    new_h.append(st[1])
                    ri += 1
                else:
                    lp = jax.tree.map(lambda a: a[ai], ap)
                    xc, kv = attn_block(
                        lp, cfg, xc, rope=rope, kv_cache=(kc[ai], vc[ai]), cache_pos=cache_pos
                    )
                    new_k.append(kv[0])
                    new_v.append(kv[1])
                    ai += 1
            return xc, (
                jnp.stack(new_conv),
                jnp.stack(new_h),
                jnp.stack(new_k),
                jnp.stack(new_v),
            )
        rp, ap = xs
        ri = ai = 0
        for kind in cfg.period:
            if kind == "rec":
                lp = jax.tree.map(lambda a: a[ri], rp)
                xc, _ = rec_block(lp, cfg, xc)
                ri += 1
            else:
                lp = jax.tree.map(lambda a: a[ai], ap)
                xc, _ = attn_block(lp, cfg, xc, rope=rope)
                ai += 1
        return xc, None

    fn = jax.checkpoint(body) if remat else body
    new_cache = None
    if use_cache:
        x, (conv_o, h_o, k_o, v_o) = jax.lax.scan(fn, x, (rec_g, attn_g, conv_g, h_g, k_g, v_g))
        conv_o = conv_o.reshape(-1, *conv_o.shape[2:])
        h_o = h_o.reshape(-1, *h_o.shape[2:])
        k_o = k_o.reshape(-1, *k_o.shape[2:])
        v_o = v_o.reshape(-1, *v_o.shape[2:])
    else:
        x, _ = jax.lax.scan(fn, x, (rec_g, attn_g))

    # tail layers (unrolled; <= len(period)-1 of them)
    ti_rec = ti_attn = 0
    tail_conv, tail_h, tail_k, tail_v = [], [], [], []
    for kind in tail_kinds:
        if kind == "rec":
            lp = jax.tree.map(lambda a: a[ti_rec], rec_tail)
            st = (conv_tail[ti_rec], h_tail[ti_rec]) if use_cache else None
            x, stn = rec_block(lp, cfg, x, state=st)
            if use_cache:
                tail_conv.append(stn[0])
                tail_h.append(stn[1])
            ti_rec += 1
        else:
            lp = jax.tree.map(lambda a: a[ti_attn], attn_tail)
            kvc = (
                (cache["k"][g * attn_per + ti_attn], cache["v"][g * attn_per + ti_attn])
                if use_cache
                else None
            )
            x, kv = attn_block(lp, cfg, x, rope=rope, kv_cache=kvc, cache_pos=cache_pos)
            if use_cache:
                tail_k.append(kv[0])
                tail_v.append(kv[1])
            ti_attn += 1
    if use_cache:
        new_cache = {
            "conv": jnp.concatenate([conv_o, jnp.stack(tail_conv)]) if tail_conv else conv_o,
            "h": jnp.concatenate([h_o, jnp.stack(tail_h)]) if tail_h else h_o,
            "k": jnp.concatenate([k_o, jnp.stack(tail_k)]) if tail_k else k_o,
            "v": jnp.concatenate([v_o, jnp.stack(tail_v)]) if tail_v else v_o,
        }
    return x, new_cache


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict[str, Array]:
    g, n_rec, n_attn, _ = _counts(cfg)
    lru = cfg.lru_width or cfg.d_model
    # Local attention never looks farther back than the window, so the
    # KV cache is a W-slot RING (slot = position mod W): decode state is
    # O(window) regardless of sequence length — 256x less cache at
    # long_500k than a full-length cache.
    kv_len = min(max_len, cfg.window or max_len)
    return {
        "conv": jnp.zeros((n_rec, batch, cfg.conv_width - 1, lru), cfg.dtype),
        "h": jnp.zeros((n_rec, batch, lru), F32),
        "k": jnp.zeros((n_attn, batch, kv_len, cfg.n_kv_heads, cfg.hd), cfg.dtype),
        "v": jnp.zeros((n_attn, batch, kv_len, cfg.n_kv_heads, cfg.hd), cfg.dtype),
    }


def forward(params, cfg: ArchConfig, tokens: Array, *, remat: bool = False, **_) -> Array:
    x = params["embed"][tokens].astype(cfg.dtype)
    x, _ = _run(params, cfg, x, remat=remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.dot(x, params["lm_head"].astype(x.dtype), preferred_element_type=F32)


def loss_fn(params, cfg: ArchConfig, tokens, labels, *, remat=True, **_) -> Array:
    logits = forward(params, cfg, tokens, remat=remat)
    return cross_entropy(logits, labels)


def prefill(params, cfg: ArchConfig, tokens: Array, cache, **_):
    x = params["embed"][tokens].astype(cfg.dtype)
    x, cache = _run(params, cfg, x, cache=cache, cache_pos=jnp.int32(0))
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return (
        jnp.dot(x, params["lm_head"].astype(x.dtype), preferred_element_type=F32),
        cache,
    )


def decode_step(params, cfg: ArchConfig, token: Array, cache, pos, **_):
    x = params["embed"][token].astype(cfg.dtype)
    x, cache = _run(params, cfg, x, cache=cache, cache_pos=pos)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (
        jnp.dot(x, params["lm_head"].astype(x.dtype), preferred_element_type=F32),
        cache,
    )
