"""Decoder LM and the generic block stack.

One stack implementation serves dense GQA archs, MoE archs (FFN swapped
for :mod:`repro.models.moe`), the VLM/audio backbones (stub frontend
embeddings prepended), and the enc-dec model (two stacks, the decoder
one with cross-attention).

Layer stacking uses ``lax.scan`` over parameters stacked on a leading
``[L, ...]`` axis: the lowered HLO contains ONE layer body regardless of
depth, which keeps 61-layer × 512-device dry-run compiles tractable and
is also what a production TPU deployment wants (XLA pipelining across
scan iterations). Training wraps the body in ``jax.checkpoint`` (full
remat — the baseline activation-memory policy; DESIGN.md §7 tracks the
perf iterations on top of it).

KV caches are dicts threaded through the scan as per-layer xs/ys, in
one of four layouts (:func:`stack_apply` dispatches on the dict keys;
DESIGN.md §9/§12):

  * dense — ``{"k", "v"}`` of ``[L, B, Smax, KV, hd]`` arrays (one
    private row per batch slot);
  * dynamic int8 — ``{"k", "v", "ks", "vs"}``: int8 codes plus
    per-(token, head) float scales computed at write time;
  * static int8 — ``{"k", "v", "k_scale", "v_scale"}``: int8 codes
    against per-(layer, head) scales calibrated offline
    (:func:`repro.calib.runner.calibrate_kv_cache`) — zero runtime
    range reductions, the §6 contract applied to the cache;
  * paged — ``{"k", "v", "pages"[, "k_scale", "v_scale"]}``: a shared
    physical page pool ``[L, n_pages, page, KV, hd]`` addressed through
    a per-slot page table ``pages[B, Pmax]``; slots serving the same
    prompt prefix reference the same physical pages (DESIGN.md §12).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.context import ParallelCtx
from repro.models import moe as moe_lib
from repro.models.layers import (
    apply_rope,
    attention_chunked,
    attention_dot,
    cross_entropy,
    dense_init,
    matmul,
    mlp_apply,
    rms_norm,
    rope_embed,
)

Array = jax.Array
F32 = jnp.float32

# KV length at/above which attention switches to the chunked (flash-style)
# form: O(S·chunk) memory instead of the O(S²) score tensor.
CHUNKED_ATTN_THRESHOLD = 4096
ATTN_CHUNK = 1024


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_block_params(
    cfg: ArchConfig, key: Array, n_layers: int, *, cross: bool = False
) -> dict[str, Array]:
    """Stacked parameters for ``n_layers`` transformer blocks."""
    d, hd, h, kv, ff = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    dt = cfg.dtype
    ks = jax.random.split(key, 16)

    def stack(k, shape, scale=1.0):
        keys = jax.random.split(k, n_layers)
        return jax.vmap(lambda kk: dense_init(kk, shape, dt, scale))(keys)

    p: dict[str, Array] = {
        "ln1": jnp.zeros((n_layers, d), dt),
        "ln2": jnp.zeros((n_layers, d), dt),
        "wq": stack(ks[0], (d, h * hd)),
        "wk": stack(ks[1], (d, kv * hd)),
        "wv": stack(ks[2], (d, kv * hd)),
        "wo": stack(ks[3], (h * hd, d)),
    }
    if cfg.qk_norm:
        p["qnorm"] = jnp.zeros((n_layers, hd), dt)
        p["knorm"] = jnp.zeros((n_layers, hd), dt)
    if cross:
        p["ln_x"] = jnp.zeros((n_layers, d), dt)
        p["xq"] = stack(ks[4], (d, h * hd))
        p["xk"] = stack(ks[5], (d, kv * hd))
        p["xv"] = stack(ks[6], (d, kv * hd))
        p["xo"] = stack(ks[7], (h * hd, d))
    if cfg.is_moe:
        p["router"] = stack(ks[8], (d, cfg.n_experts))

        def estack(k2, shape):
            keys = jax.random.split(k2, n_layers)
            return jax.vmap(lambda kk: dense_init(kk, shape, dt))(keys)

        p["we1"] = estack(ks[9], (cfg.n_experts, d, ff))
        p["we3"] = estack(ks[10], (cfg.n_experts, d, ff))
        p["we2"] = estack(ks[11], (cfg.n_experts, ff, d))
    else:
        p["w1"] = stack(ks[12], (d, ff))
        p["w2"] = stack(ks[13], (ff, d))
        if cfg.mlp_kind in ("swiglu", "geglu"):
            p["w3"] = stack(ks[14], (d, ff))
    return p


def init_params(cfg: ArchConfig, key: Array) -> dict[str, Any]:
    """Full decoder-LM parameter pytree."""
    k_emb, k_blocks, k_head, k_fe = jax.random.split(key, 4)
    p = {
        "embed": dense_init(k_emb, (cfg.vocab, cfg.d_model), cfg.dtype, scale=1.0),
        "blocks": init_block_params(cfg, k_blocks, cfg.n_layers),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab), cfg.dtype)
    if cfg.frontend_tokens:
        # Stub modality frontend projection (assignment: frontend is a stub;
        # input_specs() provides precomputed frame/patch embeddings).
        p["frontend_proj"] = dense_init(k_fe, (cfg.d_model, cfg.d_model), cfg.dtype)
    return p


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------
def _attention(
    lp: dict[str, Array],
    cfg: ArchConfig,
    x: Array,
    *,
    rope: tuple[Array, Array] | None,
    causal: bool,
    window: int = 0,
    kv_cache: tuple[Array, ...] | None = None,
    kv_layout: str = "dense",
    cache_pos: Array | None = None,
    prefix: str = "w",
    kv_override: Array | None = None,
    pctx: ParallelCtx | None = None,
    acts: dict | None = None,
    tap_kv: bool = False,
) -> tuple[Array, tuple[Array, ...] | None]:
    """GQA attention, optionally reading/updating a KV cache.

    ``kv_layout`` names the cache tuple's contents (set by
    :func:`stack_apply` from the cache dict's keys): ``"dense"``
    ``(ck, cv)``; ``"quant"`` ``(ck, cv, cks, cvs)`` dynamic int8 with
    per-(token, head) scales; ``"static"`` ``(ck, cv, ksc, vsc)`` int8
    with calibrated per-head scales ``[KV]``; ``"paged"`` /
    ``"paged_static"`` ``(ck, cv, pages[, ksc, vsc])`` with a shared
    physical pool ``ck[P, page, KV, hd]`` addressed through the rows'
    page table (DESIGN.md §12). Writes always happen before the read
    (write-before-attend), so each row's own position is valid by the
    time it is attended.

    ``kv_override`` supplies encoder output for cross-attention.
    Returns (output, updated cache tuple or None). ``acts``
    (calibration collection) records the attention mix entering the
    output projection under ``"attn_mix"``; ``tap_kv`` additionally
    records the post-RoPE k/v — the exact values a serving cache would
    store — under ``"k_cache"``/``"v_cache"`` (cache-less calibration
    forward only; :func:`repro.calib.runner.calibrate_kv_cache`).
    """
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    with jax.named_scope("attn"):
        kv_src = x if kv_override is None else kv_override
        q = matmul(x, lp[prefix + "q"]).reshape(b, s, h, hd)
        k = matmul(kv_src, lp[prefix + "k"]).reshape(b, kv_src.shape[1], kv, hd)
        v = matmul(kv_src, lp[prefix + "v"]).reshape(b, kv_src.shape[1], kv, hd)
        if cfg.qk_norm and prefix == "w":
            q = rms_norm(q, lp["qnorm"], cfg.norm_eps)
            k = rms_norm(k, lp["knorm"], cfg.norm_eps)
        if rope is not None and kv_override is None:
            cos_q, sin_q, cos_k, sin_k = rope
            q = apply_rope(q, cos_q, sin_q)
            k = apply_rope(k, cos_k, sin_k)
        if tap_kv and acts is not None:
            acts["k_cache"] = k
            acts["v_cache"] = v
        with jax.named_scope("attn_core"):
            out, new_cache = _attend(
                q, k, v, x.dtype, causal=causal, window=window, kv_cache=kv_cache,
                kv_layout=kv_layout, cache_pos=cache_pos, pctx=pctx,
            )
        mix = out.reshape(b, s, h * hd)
        if acts is not None:
            acts["attn_mix"] = mix
        return matmul(mix, lp[prefix + "o"]), new_cache


def _attend(
    q: Array,
    k: Array,
    v: Array,
    dtype,
    *,
    causal: bool,
    window: int,
    kv_cache: tuple[Array, ...] | None,
    kv_layout: str,
    cache_pos: Array | None,
    pctx: ParallelCtx | None,
) -> tuple[Array, tuple[Array, ...] | None]:
    """The core of :func:`_attention` between its projections: write this
    step's k/v into the cache (if any), then attend ``q`` over the
    cache's (or the run's own) k/v. Returns ``(out[b, s, h, hd],
    updated cache tuple or None)``."""
    s = q.shape[1]
    new_cache = None
    if kv_cache is not None and kv_layout == "quant":
        # int8-quantized cache (per-token-head scales)
        ck, cv, cks, cvs = kv_cache
        kq, ksf = _cache_q(k)
        vq, vsf = _cache_q(v)
        ck = _cache_set(ck, kq, cache_pos)
        cv = _cache_set(cv, vq, cache_pos)
        cks = _cache_set(cks, ksf, cache_pos)
        cvs = _cache_set(cvs, vsf, cache_pos)
        new_cache = (ck, cv, cks, cvs)
        if (
            (s == 1 or jnp.ndim(cache_pos) == 1)
            and pctx is not None
            and pctx.flash_decode
        ):
            from repro.models.flash_decode import flash_decode_attention

            out = flash_decode_attention(
                q, ck, cv, cache_pos, pctx=pctx, window=window, ks=cks, vs=cvs
            )
            return out, new_cache
        k = _cache_dq(ck, cks, dtype)
        v = _cache_dq(cv, cvs, dtype)
    elif kv_cache is not None and kv_layout in ("paged", "paged_static"):
        # paged pool: write this step's k/v through the page table, then
        # gather the rows' logical views back for the read. Shared
        # (prefix) pages are never written: every write lands at a
        # position >= the row's own prompt length, which the admission
        # contract keeps inside privately-owned pages (DESIGN.md §12).
        if kv_layout == "paged_static":
            ck, cv, pages, ksc, vsc = kv_cache
            kq = _static_q(k, ksc)
            vq = _static_q(v, vsc)
        else:
            ck, cv, pages = kv_cache
            kq, vq = k, v
        ck = _cache_set_paged(ck, kq, cache_pos, pages)
        cv = _cache_set_paged(cv, vq, cache_pos, pages)
        new_cache = (ck, cv)
        k = _paged_view(ck, pages)
        v = _paged_view(cv, pages)
        if kv_layout == "paged_static":
            k = _static_dq(k, ksc, dtype)
            v = _static_dq(v, vsc, dtype)
    elif kv_cache is not None and kv_layout == "static":
        # calibrated int8 cache: per-(layer, head) scales chosen offline
        # — quantize-on-write with ZERO runtime range reductions, the
        # DESIGN.md §6 static-quant contract applied to the cache.
        ck, cv, ksc, vsc = kv_cache
        ck = _cache_set(ck, _static_q(k, ksc), cache_pos)
        cv = _cache_set(cv, _static_q(v, vsc), cache_pos)
        new_cache = (ck, cv)
        k = _static_dq(ck, ksc, dtype)
        v = _static_dq(cv, vsc, dtype)
    elif kv_cache is not None:
        ck, cv = kv_cache
        ck = _cache_set(ck, k, cache_pos)
        cv = _cache_set(cv, v, cache_pos)
        k, v = ck, cv
        new_cache = (ck, cv)

    q_offset = cache_pos if kv_cache is not None else 0
    # "decode" = querying the cache at per-row depth: one token per row
    # (s == 1) or a K-token speculative verify run over a per-slot
    # position vector (DESIGN.md §10). Prefill (scalar cache_pos 0,
    # s == prompt) takes the chunked/dot paths below.
    decode = kv_cache is not None and (s == 1 or jnp.ndim(cache_pos) == 1)
    if decode and pctx is not None and pctx.flash_decode:
        # §Perf: flash-decoding over the seq-sharded cache (stats-only
        # collective instead of a [B,H,1,S] partial-sum all-reduce).
        from repro.models.flash_decode import flash_decode_attention

        out = flash_decode_attention(q, k, v, cache_pos, pctx=pctx, window=window)
        return out, new_cache
    # GQA attends per query group over k/v as they are (the cache as
    # stored): no repeated or f32 copy of either is made. Decode's s
    # queries per row are each masked to their own row's depth.
    if not decode and k.shape[1] >= CHUNKED_ATTN_THRESHOLD:
        out = attention_chunked(
            q, k, v, causal=causal, window=window, chunk=ATTN_CHUNK, q_offset=q_offset
        )
    else:
        out = attention_dot(q, k, v, causal=causal, window=window, q_offset=q_offset)
    return out, new_cache


def block_apply(
    lp: dict[str, Array],
    cfg: ArchConfig,
    x: Array,
    *,
    rope: tuple[Array, ...] | None,
    causal: bool,
    window: int = 0,
    kv_cache: tuple[Array, ...] | None = None,
    kv_layout: str = "dense",
    cache_pos: Array | None = None,
    enc_out: Array | None = None,
    pctx: ParallelCtx | None = None,
    acts: dict | None = None,
    tap_kv: bool = False,
) -> tuple[Array, tuple[Array, ...] | None]:
    """Pre-norm transformer block: attn + (cross-attn) + FFN/MoE.

    ``acts`` (calibration collection, DESIGN.md §6) records the inputs
    of this block's matmuls: ``"attn_in"`` (post-ln1, feeds wq/wk/wv),
    ``"attn_mix"`` (feeds wo), ``"ffn_in"`` (post-ln2, feeds w1/w3) and
    ``"ffn_hidden"`` (feeds w2; dense FFN only).
    """
    if pctx is not None and pctx.seq_parallel and x.shape[1] > 1:
        # §Perf: Megatron-style sequence parallelism — the residual
        # stream (and hence the remat stash the backward scan saves) is
        # sharded over the model axis on seq; XLA turns the per-block
        # all-reduces into reduce-scatter + all-gather pairs.
        from jax.sharding import PartitionSpec as _P

        x = jax.lax.with_sharding_constraint(
            x, _P(pctx.batch_axes, pctx.model_axis, None)
        )
    attn_in = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if acts is not None:
        acts["attn_in"] = attn_in
    attn_out, new_cache = _attention(
        lp,
        cfg,
        attn_in,
        rope=rope,
        causal=causal,
        window=window,
        kv_cache=kv_cache,
        kv_layout=kv_layout,
        cache_pos=cache_pos,
        pctx=pctx,
        acts=acts,
        tap_kv=tap_kv,
    )
    x = x + attn_out
    if pctx is not None and pctx.seq_parallel and x.shape[1] > 1:
        # mid-block boundary: keep the residual seq-sharded so the MLP's
        # collectives also become reduce-scatter/all-gather pairs.
        from jax.sharding import PartitionSpec as _P

        x = jax.lax.with_sharding_constraint(
            x, _P(pctx.batch_axes, pctx.model_axis, None)
        )
    if enc_out is not None:
        xa_in = rms_norm(x, lp["ln_x"], cfg.norm_eps)
        xa_out, _ = _attention(
            lp, cfg, xa_in, rope=None, causal=False, prefix="x", kv_override=enc_out
        )
        x = x + xa_out
    ffn_in = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if acts is not None:
        acts["ffn_in"] = ffn_in
    if cfg.is_moe:
        b, s, d = ffn_in.shape
        y = moe_lib.moe_apply(
            {k: lp[k] for k in ("router", "we1", "we3", "we2")},
            ffn_in.reshape(b * s, d),
            cfg,
            pctx,
        ).reshape(b, s, d)
    else:
        y = mlp_apply(lp, ffn_in, cfg.mlp_kind, acts=acts)
    return x + y, new_cache


# ---------------------------------------------------------------------------
# Stack (scan over layers)
# ---------------------------------------------------------------------------
def stack_apply(
    blocks: dict[str, Array],
    cfg: ArchConfig,
    x: Array,
    *,
    causal: bool = True,
    window: int = 0,
    positions: Array | None = None,
    cache: dict[str, Array] | None = None,
    cache_pos: Array | None = None,
    enc_out: Array | None = None,
    pctx: ParallelCtx | None = None,
    remat: bool = False,
    collect: bool = False,
    tap_kv: bool = False,
) -> tuple[Array, dict[str, Array] | None]:
    """Run the block stack via ``lax.scan`` over the stacked layer axis.

    ``collect=True`` (cache-less forward only) returns, in the second
    slot, a dict of stacked per-layer activations: ``"block_out"``
    (``[L, B, S, D]`` residual stream) plus the per-matmul inputs
    ``block_apply`` records (``attn_in``/``attn_mix``/``ffn_in``/
    ``ffn_hidden``) — the calibration runner's view (DESIGN.md §6).
    ``tap_kv`` adds the post-RoPE ``k_cache``/``v_cache`` sites (the KV
    cache's write values, stacked ``[L, B, S, KV, hd]``) — gated off by
    default so the LM calibration site census stays fixed.

    The cache layout is dispatched on the dict's keys (see the module
    docstring): per-layer leaves (``k``/``v``/``ks``/``vs`` and the
    static ``k_scale``/``v_scale``) thread through the scan as xs/ys,
    while the paged ``pages`` table — shared by every layer — is closed
    over and passed back through the output dict unchanged.
    """
    if collect and cache is not None:
        raise ValueError("collect=True is for the cache-less training forward")
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    cos, sin = rope_embed(positions, cfg.hd, cfg.rope_theta)
    # New K entries share the query positions (they are written at the
    # same offsets), so one table serves both.
    rope = (cos, sin, cos, sin)

    layout = "dense" if cache is None else cache_layout(cache)
    pages = cache["pages"] if layout.startswith("paged") else None

    def body(carry, xs):
        xc = carry
        if cache is not None:
            if layout == "quant":
                lp, ck, cv, cks, cvs = xs
                kvc = (ck, cv, cks, cvs)
            elif layout == "static":
                lp, ck, cv, ksc, vsc = xs
                kvc = (ck, cv, ksc, vsc)
            elif layout == "paged_static":
                lp, ck, cv, ksc, vsc = xs
                kvc = (ck, cv, pages, ksc, vsc)
            elif layout == "paged":
                lp, ck, cv = xs
                kvc = (ck, cv, pages)
            else:
                lp, ck, cv = xs
                kvc = (ck, cv)
            out, new_kv = block_apply(
                lp,
                cfg,
                xc,
                rope=rope,
                causal=causal,
                window=window,
                kv_cache=kvc,
                kv_layout=layout,
                cache_pos=cache_pos,
                enc_out=enc_out,
                pctx=pctx,
            )
            return out, new_kv
        lp = xs
        acts: dict | None = {} if (collect or tap_kv) else None
        out, _ = block_apply(
            lp, cfg, xc, rope=rope, causal=causal, window=window, enc_out=enc_out,
            pctx=pctx, acts=acts, tap_kv=tap_kv,
        )
        return out, ({"block_out": out, **acts} if collect else None)

    fn = jax.checkpoint(body) if remat else body
    if cache is not None:
        if layout == "quant":
            xs = (blocks, cache["k"], cache["v"], cache["ks"], cache["vs"])
            x, kv_out = jax.lax.scan(fn, x, xs)
            return x, {"k": kv_out[0], "v": kv_out[1], "ks": kv_out[2], "vs": kv_out[3]}
        if layout in ("static", "paged_static"):
            xs = (blocks, cache["k"], cache["v"], cache["k_scale"], cache["v_scale"])
            x, kv_out = jax.lax.scan(fn, x, xs)
            out = {
                "k": kv_out[0],
                "v": kv_out[1],
                "k_scale": cache["k_scale"],
                "v_scale": cache["v_scale"],
            }
            if layout == "paged_static":
                out["pages"] = pages
            return x, out
        xs = (blocks, cache["k"], cache["v"])
        x, kv_out = jax.lax.scan(fn, x, xs)
        out = {"k": kv_out[0], "v": kv_out[1]}
        if layout == "paged":
            out["pages"] = pages
        return x, out
    x, ys = jax.lax.scan(fn, x, blocks)
    return x, (ys if collect else None)


def cache_layout(cache: dict[str, Array]) -> str:
    """Name a KV-cache dict's layout from its keys (the dispatch
    :func:`stack_apply` and the serve engine share): ``"dense"``,
    ``"quant"`` (dynamic int8), ``"static"`` (calibrated int8),
    ``"paged"`` or ``"paged_static"``."""
    if "pages" in cache:
        return "paged_static" if "k_scale" in cache else "paged"
    if "ks" in cache:
        return "quant"
    if "k_scale" in cache:
        return "static"
    return "dense"


# ---------------------------------------------------------------------------
# Decoder LM public API
# ---------------------------------------------------------------------------
def embed_tokens(params, cfg: ArchConfig, tokens: Array, frontend: Array | None) -> Array:
    x = params["embed"][tokens].astype(cfg.dtype)
    if frontend is not None:
        fe = matmul(frontend.astype(cfg.dtype), params["frontend_proj"])
        x = jnp.concatenate([fe, x], axis=1)
    return x


def unembed(params, cfg: ArchConfig, x: Array) -> Array:
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return jnp.dot(x, head.astype(x.dtype), preferred_element_type=F32)


def forward(
    params,
    cfg: ArchConfig,
    tokens: Array,
    *,
    frontend: Array | None = None,
    pctx: ParallelCtx | None = None,
    remat: bool = False,
    tap=None,
    tap_kv: bool = False,
) -> Array:
    """Training forward: logits ``[B, S(+F), V]`` (float32).

    ``tap`` is the activation-tap hook (calibration contract): sites are
    ``"embed"`` (post-embedding), ``"blocks"`` (stacked per-layer block
    outputs ``[L, B, S, D]``), the stacked per-matmul inputs
    (``"attn_in"``/``"attn_mix"``/``"ffn_in"``/``"ffn_hidden"`` — what
    the calibrated serve path quantizes against, DESIGN.md §6) and
    ``"final"`` (pre-unembed). ``tap_kv=True`` adds the post-RoPE
    ``"k_cache"``/``"v_cache"`` sites (``[L, B, S, KV, hd]`` — the
    values a serving KV cache stores, DESIGN.md §12); it is opt-in so
    the default LM site census stays exactly the seven sites above.
    """
    x = embed_tokens(params, cfg, tokens, frontend)
    if tap is not None:
        x = tap("embed", x)
    x, ys = stack_apply(
        params["blocks"],
        cfg,
        x,
        causal=True,
        window=cfg.window,
        pctx=pctx,
        remat=remat,
        collect=tap is not None,
        tap_kv=tap_kv,
    )
    if tap is not None:
        tap("blocks", ys.pop("block_out"))
        for site, act in ys.items():
            tap(site, act)
        x = tap("final", x)
    return unembed(params, cfg, x)


def loss_fn(
    params,
    cfg: ArchConfig,
    tokens: Array,
    labels: Array,
    *,
    frontend: Array | None = None,
    pctx: ParallelCtx | None = None,
    remat: bool = True,
) -> Array:
    """Mean next-token cross entropy (labels already shifted by the data
    pipeline). Frontend positions (if any) are excluded from the loss."""
    logits = forward(params, cfg, tokens, frontend=frontend, pctx=pctx, remat=remat)
    if frontend is not None:
        logits = logits[:, frontend.shape[1] :]
    return cross_entropy(logits, labels)


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------
def init_cache(
    cfg: ArchConfig,
    batch: int,
    max_len: int,
    dtype=None,
    quant: bool = False,
    kv_scales: tuple[Array, Array] | None = None,
) -> dict[str, Array]:
    """Dense (per-slot-row) KV cache ``[L, B, Smax, KV, hd]``.

    ``quant=True`` stores int8 entries with per-(token, head) float
    scales computed at write time — 2x less HBM per read, but a runtime
    range reduction per step. ``kv_scales=(k_scale, v_scale)`` (each
    ``[L, KV]``, from :func:`repro.calib.runner.calibrate_kv_cache`)
    instead stores int8 codes against CALIBRATED per-(layer, head)
    scales — 4x less HBM than float and zero runtime range reductions,
    the DESIGN.md §6 contract applied to the cache (§12). The two quant
    modes are mutually exclusive."""
    dtype = dtype or cfg.dtype
    shape = (cfg.n_dec_layers or cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    if quant and kv_scales is not None:
        raise ValueError("quant=True (dynamic) and kv_scales (static) are exclusive")
    if kv_scales is not None:
        k_scale, v_scale = kv_scales
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.asarray(k_scale, jnp.float32),
            "v_scale": jnp.asarray(v_scale, jnp.float32),
        }
    if quant:
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "ks": jnp.zeros(sshape, jnp.float32),
            "vs": jnp.zeros(sshape, jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(
    cfg: ArchConfig,
    batch: int,
    max_len: int,
    *,
    page_size: int,
    n_pages: int | None = None,
    kv_scales: tuple[Array, Array] | None = None,
    dtype=None,
) -> dict[str, Array]:
    """Paged KV cache (DESIGN.md §12): a shared physical page pool plus
    a per-slot page table.

    ``k``/``v`` are ``[L, n_pages, page_size, KV, hd]`` — int8 codes
    when ``kv_scales`` is given (calibrated per-(layer, head) scales,
    ``[L, KV]`` each), else ``dtype``. ``pages[batch, Pmax]`` maps each
    slot's logical page ``p`` (positions ``p*page_size ..``) to a
    physical page; slots admitted with a matching prompt prefix point
    at the SAME physical pages (:class:`repro.serve.paging.PageTable`
    owns the refcounts). ``n_pages`` defaults to
    ``batch * Pmax + batch``: enough for every slot to be fully private
    plus one reserved scratch page per slot (where a free slot's
    ride-along decode writes land)."""
    page_size = int(page_size)
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    n_layers = cfg.n_dec_layers or cfg.n_layers
    pmax = -(-max_len // page_size)
    if n_pages is None:
        n_pages = batch * pmax + batch
    shape = (n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.hd)
    store_dt = jnp.int8 if kv_scales is not None else (dtype or cfg.dtype)
    cache = {
        "k": jnp.zeros(shape, store_dt),
        "v": jnp.zeros(shape, store_dt),
        "pages": jnp.zeros((batch, pmax), jnp.int32),
    }
    if kv_scales is not None:
        k_scale, v_scale = kv_scales
        cache["k_scale"] = jnp.asarray(k_scale, jnp.float32)
        cache["v_scale"] = jnp.asarray(v_scale, jnp.float32)
    return cache


def _cache_set(c: Array, u: Array, pos: Array) -> Array:
    """Write ``u[B, s, ...]`` into cache ``c[B, S, ...]`` at ``pos``.

    A scalar ``pos`` is the static-batch layout: one contiguous
    ``dynamic_update_slice`` at the same offset for every row (prefill,
    lockstep decode). A vector ``pos[B]`` is the continuous-batching
    layout — ``s`` tokens per row, each row starting at its OWN slot
    position (``s == 1`` for the plain decode step; ``s == K`` for the
    speculative verify step, row ``b`` writing ``pos[b] .. pos[b]+s-1``)
    — written as a per-row scatter (row indices are iota, so only row
    ``b`` changes, at its own offsets; ~5x cheaper than a one-hot
    select of the whole cache, and multi-device parity tests pin that
    the SPMD partitioner handles it).
    """
    pos = jnp.asarray(pos)
    u = u.astype(c.dtype)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(c, u, (0, pos) + (0,) * (c.ndim - 2))
    if u.shape[1] == 1:
        return c.at[jnp.arange(c.shape[0]), pos].set(u[:, 0])
    rows = jnp.arange(c.shape[0])[:, None]
    cols = pos[:, None] + jnp.arange(u.shape[1])[None, :]
    return c.at[rows, cols].set(u)


def _cache_set_paged(c: Array, u: Array, pos: Array, pages: Array) -> Array:
    """Write ``u[B, s, KV, hd]`` into the physical page pool
    ``c[P, page, KV, hd]`` through the rows' page table ``pages[B, Pmax]``.

    Row ``b``'s token at logical position ``p`` lands in physical page
    ``pages[b, p // page_size]`` at offset ``p % page_size`` — the paged
    analogue of :func:`_cache_set`'s per-row scatter. Distinct rows
    never scatter into the same physical page: shared (refcount > 1)
    pages hold only full prompt-prefix positions, strictly below every
    sharer's write position (DESIGN.md §12's copy-on-write contract),
    and each free slot's table points at its own reserved scratch page.
    """
    pos = jnp.asarray(pos)
    b, s = u.shape[0], u.shape[1]
    page = c.shape[1]
    if pos.ndim == 0:
        positions = jnp.broadcast_to(pos + jnp.arange(s)[None, :], (b, s))
    else:
        positions = pos[:, None] + jnp.arange(s)[None, :]
    pidx = jnp.take_along_axis(pages, positions // page, axis=1)  # [B, s]
    poff = positions % page
    return c.at[pidx, poff].set(u.astype(c.dtype))


def _paged_view(c: Array, pages: Array) -> Array:
    """Gather the rows' logical dense views out of the page pool:
    ``c[P, page, KV, hd]`` + ``pages[B, Pmax]`` →
    ``[B, Pmax*page, KV, hd]``. Logical position ``p`` of row ``b`` is
    element ``p`` of the view, so the mask-past-pos read contract is
    unchanged from the dense layout (positions beyond the row's depth
    hold garbage and are masked, exactly as dense slot reuse relies on).
    """
    v = c[pages]  # [B, Pmax, page, KV, hd]
    b, pmax, page = v.shape[:3]
    return v.reshape(b, pmax * page, *v.shape[3:])


def _cache_q(x: Array) -> tuple[Array, Array]:
    """Symmetric int8 quantization over head_dim: x[B,S,KV,hd]."""
    sf = jnp.max(jnp.abs(x.astype(F32)), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(F32) / sf), -127, 127).astype(jnp.int8)
    return q, sf


def _cache_dq(q: Array, sf: Array, dtype) -> Array:
    return (q.astype(F32) * sf).astype(dtype)


def _static_q(x: Array, scale: Array) -> Array:
    """Symmetric int8 quantization of ``x[B, S, KV, hd]`` against
    calibrated per-head scales ``scale[KV]`` — no runtime reduction."""
    sf = scale[None, None, :, None].astype(F32)
    return jnp.clip(jnp.round(x.astype(F32) / sf), -127, 127).astype(jnp.int8)


def _static_dq(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(F32) * scale[None, None, :, None].astype(F32)).astype(dtype)


def prefill(
    params,
    cfg: ArchConfig,
    tokens: Array,
    cache: dict[str, Array],
    *,
    frontend: Array | None = None,
    pctx: ParallelCtx | None = None,
) -> tuple[Array, dict[str, Array]]:
    """Fill the cache with the prompt; return last-position logits."""
    x = embed_tokens(params, cfg, tokens, frontend)
    x, cache = stack_apply(
        params["blocks"],
        cfg,
        x,
        causal=True,
        window=cfg.window,
        cache=cache,
        cache_pos=jnp.int32(0),
        pctx=pctx,
    )
    return unembed(params, cfg, x[:, -1:]), cache


def decode_step(
    params,
    cfg: ArchConfig,
    token: Array,
    cache: dict[str, Array],
    pos: Array,
    *,
    pctx: ParallelCtx | None = None,
) -> tuple[Array, dict[str, Array]]:
    """One decode step: token ``[B, s]`` at position ``pos`` → logits.

    ``pos`` is a scalar (static batch: every row at the same position)
    or a ``[B]`` vector of per-slot positions (continuous batching,
    DESIGN.md §9): each row's KV is written at its own offset and its
    attention masked to its own past. ``s > 1`` is the speculative
    verify step (DESIGN.md §10): row ``b``'s tokens occupy positions
    ``pos[b] .. pos[b]+s-1``, causal within the run.
    """
    pos = jnp.asarray(pos)
    s = token.shape[1]
    if pos.ndim == 0:
        positions = pos[None, None] + jnp.arange(s)[None, :]
    elif pos.ndim == 1:
        positions = pos[:, None] + jnp.arange(s)[None, :]
    else:
        positions = pos
    x = params["embed"][token].astype(cfg.dtype)
    x, cache = stack_apply(
        params["blocks"],
        cfg,
        x,
        causal=True,
        window=cfg.window,
        positions=positions,
        cache=cache,
        cache_pos=pos,
        pctx=pctx,
    )
    return unembed(params, cfg, x), cache
