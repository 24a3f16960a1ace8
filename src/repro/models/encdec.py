"""Encoder-decoder transformer (seamless-m4t backbone).

Per the assignment the modality frontend is a STUB: ``input_specs()``
supplies precomputed audio-frame embeddings, which feed a bidirectional
encoder stack; the decoder is a causal stack with cross-attention over
the encoder output. Reuses the generic block machinery from
:mod:`repro.models.transformer` (``cross=True`` adds xq/xk/xv/xo).

Decode-shape semantics: ``serve_step`` = one decoder token against the
decoder KV cache + the (pre-computed) encoder output.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.context import ParallelCtx
from repro.models.layers import cross_entropy, dense_init, matmul, rms_norm
from repro.models import transformer as T

Array = jax.Array
F32 = jnp.float32


def init_params(cfg: ArchConfig, key: Array) -> dict[str, Any]:
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        "embed": dense_init(k1, (cfg.vocab, cfg.d_model), cfg.dtype),
        "frontend_proj": dense_init(k5, (cfg.d_model, cfg.d_model), cfg.dtype),
        "encoder": T.init_block_params(cfg, k2, cfg.n_layers),
        "enc_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
        "decoder": T.init_block_params(cfg, k3, cfg.n_dec_layers, cross=True),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
        "lm_head": dense_init(k4, (cfg.d_model, cfg.vocab), cfg.dtype),
    }


def encode(params, cfg: ArchConfig, src: Array, *, pctx=None, remat: bool = False) -> Array:
    """src: precomputed frame embeddings [B, S_enc, D] (stub frontend)."""
    x = matmul(src.astype(cfg.dtype), params["frontend_proj"])
    x, _ = T.stack_apply(params["encoder"], cfg, x, causal=False, pctx=pctx, remat=remat)
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(
    params,
    cfg: ArchConfig,
    tokens: Array,
    *,
    frontend: Array,
    pctx: ParallelCtx | None = None,
    remat: bool = False,
) -> Array:
    enc_out = encode(params, cfg, frontend, pctx=pctx, remat=remat)
    x = params["embed"][tokens].astype(cfg.dtype)
    x, _ = T.stack_apply(
        params["decoder"], cfg, x, causal=True, enc_out=enc_out, pctx=pctx, remat=remat
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.dot(x, params["lm_head"].astype(x.dtype), preferred_element_type=F32)


def loss_fn(params, cfg, tokens, labels, *, frontend, pctx=None, remat=True, **_) -> Array:
    logits = forward(params, cfg, tokens, frontend=frontend, pctx=pctx, remat=remat)
    return cross_entropy(logits, labels)


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict[str, Array]:
    shape = (cfg.n_dec_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def prefill(params, cfg: ArchConfig, tokens: Array, cache, *, frontend, pctx=None, **_):
    """Encode source + prefill decoder prompt. Returns (logits, state)."""
    enc_out = encode(params, cfg, frontend, pctx=pctx)
    x = params["embed"][tokens].astype(cfg.dtype)
    x, cache = T.stack_apply(
        params["decoder"],
        cfg,
        x,
        causal=True,
        cache=cache,
        cache_pos=jnp.int32(0),
        enc_out=enc_out,
        pctx=pctx,
    )
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x, params["lm_head"].astype(x.dtype), preferred_element_type=F32)
    return logits, (cache, enc_out)


def decode_step(params, cfg: ArchConfig, token: Array, state, pos, *, pctx=None, **_):
    cache, enc_out = state
    x = params["embed"][token].astype(cfg.dtype)
    x, cache = T.stack_apply(
        params["decoder"],
        cfg,
        x,
        causal=True,
        positions=pos[None, None] if jnp.ndim(pos) == 0 else pos,
        cache=cache,
        cache_pos=pos,
        enc_out=enc_out,
        pctx=pctx,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x, params["lm_head"].astype(x.dtype), preferred_element_type=F32)
    return logits, (cache, enc_out)
