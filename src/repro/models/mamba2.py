"""Mamba-2 (SSD — state-space duality) backbone.

Chunked SSD forward (Dao & Gu 2024): the sequence is split into chunks
of ``Q`` tokens; intra-chunk interactions are dense matmuls (MXU
friendly: the ``[Q, Q]`` semiseparable block), inter-chunk state is
carried by a short ``lax.scan``. A single-token recurrent step serves
decode — constant memory per token, which is why this arch (and only
the sub-quadratic archs) runs the ``long_500k`` cell.

Layout: scalar-per-head A (SSD), one B/C group shared across heads.
Params per layer:
  in_proj  [d, 2*d_in + 2*state + nh]   (z | x | B | C | dt)
  conv_w   [cw, d_in + 2*state], conv_b  (depthwise causal conv)
  A_log, D, dt_bias [nh]
  gnorm    [d_in]                        (gated RMSNorm)
  out_proj [d_in, d]

Einsum index legend: b batch, c chunk, t/s intra-chunk positions,
n heads, d head_dim, m ssm_state.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import cross_entropy, dense_init, matmul, rms_norm

Array = jax.Array
F32 = jnp.float32

SSD_CHUNK = 256


def dims(cfg: ArchConfig) -> tuple[int, int, int, int]:
    d_in = cfg.expand * cfg.d_model
    nh = d_in // cfg.ssm_head_dim
    return d_in, nh, cfg.ssm_head_dim, cfg.ssm_state


def init_layer_stack(cfg: ArchConfig, key: Array, n_layers: int) -> dict[str, Array]:
    d = cfg.d_model
    d_in, nh, hd, st = dims(cfg)
    conv_ch = d_in + 2 * st
    dt = cfg.dtype
    ks = jax.random.split(key, 4)

    def stack(k, shape):
        keys = jax.random.split(k, n_layers)
        return jax.vmap(lambda kk: dense_init(kk, shape, dt))(keys)

    # dt_bias init so softplus(dt_bias) spans [1e-3, 1e-1] (mamba2 default)
    u = jax.random.uniform(ks[2], (n_layers, nh), F32)
    dt0 = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    return {
        "ln": jnp.zeros((n_layers, d), dt),
        "in_proj": stack(ks[0], (d, 2 * d_in + 2 * st + nh)),
        "conv_w": stack(ks[1], (cfg.conv_width, conv_ch)),
        "conv_b": jnp.zeros((n_layers, conv_ch), dt),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=F32))[None].repeat(n_layers, 0),
        "D": jnp.ones((n_layers, nh), F32),
        "dt_bias": dt_bias.astype(F32),
        "gnorm": jnp.zeros((n_layers, d_in), dt),
        "out_proj": stack(ks[3], (d_in, d)),
    }


def init_params(cfg: ArchConfig, key: Array) -> dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "embed": dense_init(k1, (cfg.vocab, cfg.d_model), cfg.dtype),
        "blocks": init_layer_stack(cfg, k2, cfg.n_layers),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k3, (cfg.d_model, cfg.vocab), cfg.dtype)
    return p


def _causal_conv(x: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv1d: x[B,S,C], w[cw,C]."""
    cw = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp.astype(F32),
        w.astype(F32)[:, None, :],  # [W, I=1, C]
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    return (out + b.astype(F32)).astype(x.dtype)


def _ssd_chunked(
    xh: Array, dt: Array, A: Array, Bm: Array, Cm: Array, h0: Array | None = None
) -> tuple[Array, Array]:
    """Chunked SSD scan.

    xh [B,S,nh,hd] f32, dt [B,S,nh] (post-softplus), A [nh] (negative),
    Bm/Cm [B,S,st] f32. Returns (y [B,S,nh,hd] f32, state [B,nh,hd,st]).
    """
    b, s, nh, hd = xh.shape
    st = Bm.shape[-1]
    q = min(SSD_CHUNK, s)
    assert s % q == 0, (s, q)
    nc = s // q
    xc = xh.reshape(b, nc, q, nh, hd)
    dtc = dt.reshape(b, nc, q, nh)
    bc = Bm.reshape(b, nc, q, st)
    cc = Cm.reshape(b, nc, q, st)

    la = dtc * A[None, None, None, :]  # per-step log decay [b,nc,q,nh]
    cum = jnp.cumsum(la, axis=2)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # l_t - l_s [b,nc,t,s,nh]
    mask = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(mask[None, None, :, :, None], jnp.exp(seg), 0.0)

    # intra-chunk: Y[t] = sum_{s<=t} (C_t . B_s) decay(t,s) dt_s x_s
    cb = jnp.einsum("bctm,bcsm->bcts", cc, bc, preferred_element_type=F32)
    w_ts = cb[..., None] * decay  # [b,nc,t,s,nh]
    xdt = xc * dtc[..., None]  # [b,nc,s,nh,hd]
    y_intra = jnp.einsum("bctsn,bcsnd->bctnd", w_ts, xdt, preferred_element_type=F32)

    # chunk summary state: S_c = sum_s exp(l_Q - l_s) dt_s B_s (x) x_s
    tail = jnp.exp(cum[:, :, -1:, :] - cum)  # [b,nc,q,nh]
    sc = jnp.einsum(
        "bcsnd,bcsm->bcndm", xdt * tail[..., None], bc, preferred_element_type=F32
    )  # [b,nc,nh,hd,st]

    chunk_decay = jnp.exp(cum[:, :, -1, :])  # [b,nc,nh]

    def scan_body(h, inp):
        s_c, dec = inp  # [b,nh,hd,st], [b,nh]
        h_prev = h
        h_new = h * dec[..., None, None] + s_c
        return h_new, h_prev

    h_init = jnp.zeros((b, nh, hd, st), F32) if h0 is None else h0
    h_final, h_prevs = jax.lax.scan(
        scan_body, h_init, (jnp.moveaxis(sc, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # [b,nc,nh,hd,st]

    # inter-chunk: Y[t] += exp(l_t) * C_t . h_prev
    y_inter = jnp.einsum("bctm,bcndm->bctnd", cc, h_prevs, preferred_element_type=F32)
    y_inter = y_inter * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(b, s, nh, hd)
    return y, h_final


def init_state(cfg: ArchConfig, batch: int) -> tuple[Array, Array]:
    """Decode-time state: (conv window cache, SSD state) per layer, stacked."""
    d_in, nh, hd, st = dims(cfg)
    conv_ch = d_in + 2 * st
    conv = jnp.zeros((cfg.n_layers, batch, cfg.conv_width - 1, conv_ch), cfg.dtype)
    ssd = jnp.zeros((cfg.n_layers, batch, nh, hd, st), F32)
    return conv, ssd


def block_apply(
    lp: dict[str, Array], cfg: ArchConfig, x: Array, state: tuple[Array, Array] | None = None
):
    """One mamba2 block. ``state = (conv_cache [B,cw-1,C], h0 [B,nh,hd,st])``
    enables single-token decode; ``None`` runs the chunked parallel form."""
    d_in, nh, hd, st = dims(cfg)
    res = x
    xn = rms_norm(x, lp["ln"], cfg.norm_eps)
    proj = matmul(xn, lp["in_proj"])
    z, xs, bm, cm, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + st, 2 * d_in + 2 * st], axis=-1
    )
    xbc = jnp.concatenate([xs, bm, cm], -1)

    new_state = None
    if state is None:
        xbc = _causal_conv(xbc, lp["conv_w"], lp["conv_b"])
    else:
        conv_cache, h0 = state
        cw = cfg.conv_width
        window = jnp.concatenate([conv_cache, xbc], axis=1)[:, -cw:]
        xbc = (
            jnp.einsum("bwc,wc->bc", window.astype(F32), lp["conv_w"].astype(F32))
            + lp["conv_b"].astype(F32)
        )[:, None, :].astype(x.dtype)
        new_conv = window[:, 1:].astype(conv_cache.dtype)
    xbc = jax.nn.silu(xbc)
    xs, bm, cm = jnp.split(xbc, [d_in, d_in + st], axis=-1)

    b, s, _ = xs.shape
    xh = xs.reshape(b, s, nh, hd).astype(F32)
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"][None, None])
    A = -jnp.exp(lp["A_log"])

    if state is None:
        y, _ = _ssd_chunked(xh, dt, A, bm.astype(F32), cm.astype(F32))
    else:
        dec = jnp.exp(dt[:, 0, :] * A[None])  # [b,nh]
        upd = jnp.einsum("bnd,bm->bndm", xh[:, 0] * dt[:, 0, :, None], bm[:, 0].astype(F32))
        h_new = h0 * dec[..., None, None] + upd
        y = jnp.einsum("bndm,bm->bnd", h_new, cm[:, 0].astype(F32))[:, None]
        new_state = (new_conv, h_new)

    y = y + xh * lp["D"][None, None, :, None]
    y = y.reshape(b, s, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(F32)).astype(x.dtype), lp["gnorm"], cfg.norm_eps)
    out = res + matmul(y, lp["out_proj"])
    return out, new_state


def _run_stack(params, cfg: ArchConfig, x: Array, state=None, remat: bool = False):
    def body(carry, xs):
        if state is not None:
            lp, conv, h = xs
            out, new_s = block_apply(lp, cfg, carry, state=(conv, h))
            return out, new_s
        out, _ = block_apply(xs, cfg, carry)
        return out, None

    fn = jax.checkpoint(body) if remat else body
    if state is not None:
        conv, ssd = state
        x, (conv_out, ssd_out) = jax.lax.scan(fn, x, (params["blocks"], conv, ssd))
        return x, (conv_out, ssd_out)
    x, _ = jax.lax.scan(fn, x, params["blocks"])
    return x, None


def forward(params, cfg: ArchConfig, tokens: Array, *, remat: bool = False, **_) -> Array:
    x = params["embed"][tokens].astype(cfg.dtype)
    x, _ = _run_stack(params, cfg, x, remat=remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.dot(x, head.astype(x.dtype), preferred_element_type=F32)


def loss_fn(params, cfg: ArchConfig, tokens: Array, labels: Array, *, remat=True, **_) -> Array:
    logits = forward(params, cfg, tokens, remat=remat)
    return cross_entropy(logits, labels)


def prefill(params, cfg: ArchConfig, tokens: Array, state, **_):
    """Chunked-parallel prefill: the SSD chunk scan already produces the
    final recurrent state, so prefill = one parallel forward that also
    returns the conv window + SSD state for subsequent decode steps."""
    x = params["embed"][tokens].astype(cfg.dtype)
    conv, ssd = state
    d_in, nh, hd, st = dims(cfg)

    def body2(carry, xs):
        lp, conv_l, ssd_l = xs
        xin = carry
        xn = rms_norm(xin, lp["ln"], cfg.norm_eps)
        proj = matmul(xn, lp["in_proj"])
        z, xs_, bm, cm, dtp = jnp.split(
            proj, [d_in, 2 * d_in, 2 * d_in + st, 2 * d_in + 2 * st], axis=-1
        )
        xbc = jnp.concatenate([xs_, bm, cm], -1)
        xbc_c = _causal_conv(xbc, lp["conv_w"], lp["conv_b"])
        new_conv = xbc[:, -(cfg.conv_width - 1) :].astype(conv_l.dtype)
        xbc_c = jax.nn.silu(xbc_c)
        xs2, bm2, cm2 = jnp.split(xbc_c, [d_in, d_in + st], axis=-1)
        b, s, _ = xs2.shape
        xh = xs2.reshape(b, s, nh, hd).astype(F32)
        dtv = jax.nn.softplus(dtp.astype(F32) + lp["dt_bias"][None, None])
        A = -jnp.exp(lp["A_log"])
        y, h_fin = _ssd_chunked(xh, dtv, A, bm2.astype(F32), cm2.astype(F32))
        y = y + xh * lp["D"][None, None, :, None]
        y = y.reshape(b, s, d_in).astype(xin.dtype)
        y = rms_norm(y * jax.nn.silu(z.astype(F32)).astype(xin.dtype), lp["gnorm"], cfg.norm_eps)
        out = xin + matmul(y, lp["out_proj"])
        return out, (new_conv, h_fin)

    x, (conv_out, ssd_out) = jax.lax.scan(body2, x, (params["blocks"], conv, ssd))
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.dot(x, head.astype(x.dtype), preferred_element_type=F32)
    return logits, (conv_out, ssd_out)


def decode_step(params, cfg: ArchConfig, token: Array, state, pos=None, **_):
    x = params["embed"][token].astype(cfg.dtype)
    x, state = _run_stack(params, cfg, x, state=state)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.dot(x, head.astype(x.dtype), preferred_element_type=F32), state
