"""The traced calibration pass: tap activations, stream statistics.

Activation-tap contract (DESIGN.md §6): a model forward accepts
``tap: Callable[[str, Array], Array] | None`` and, at every activation
quantization site, calls ``x = tap(site_name, x)`` on the
*pre-quantization* value, using the return value in its place. Taps are
trace-time objects — :class:`TapCollector` just records the traced
arrays by name — so a tapped forward stays a pure jittable function
``batch -> dict[site, activation]``.

:func:`collect_stats` scans that function over stacked calibration
batches inside ONE jit (streaming observer updates as the scan carry),
so calibration is deterministic under tracing and never materializes
more than one batch of activations.

:func:`calibrate_cnn` / :func:`calibrate_lm` are the model front-ends:
stats pass → policy (scales, rho gates) → optional second pass that
measures per-channel mean error under the chosen quantizers for bias
folding.
"""
from __future__ import annotations

from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.calib.observers import (
    ObserverState,
    ObserverSummary,
    init_observer,
    summarize,
    update,
)
from repro.calib.policy import (
    CalibrationTable,
    attach_errors,
    build_table,
    fold_cnn_bias,
)

Array = jax.Array


class TapCollector:
    """Records tapped activations by site name during one trace."""

    def __init__(self) -> None:
        self.acts: dict[str, Array] = {}

    def __call__(self, name: str, x: Array) -> Array:
        if name in self.acts:
            raise ValueError(f"duplicate tap site {name!r}")
        self.acts[name] = x
        return x


TappedForward = Callable[[Any], dict[str, Array]]


def collect_stats(
    tapped_forward: TappedForward,
    batches: Any,
    *,
    quant_for: Mapping[str, tuple[int, float]] | None = None,
) -> dict[str, ObserverSummary]:
    """One traced pass: scan ``tapped_forward`` over stacked batches.

    ``batches`` is a pytree whose leaves stack calibration batches on a
    leading axis. ``quant_for`` maps site → (bits, amax) to additionally
    accumulate per-channel quantization error under that static
    quantizer (the compensation pass).
    """
    first = jax.tree.map(lambda b: jax.ShapeDtypeStruct(b.shape[1:], b.dtype), batches)
    abstract_acts = jax.eval_shape(tapped_forward, first)
    states = {
        name: init_observer(int(a.shape[-1])) for name, a in abstract_acts.items()
    }

    def step(states, batch):
        acts = tapped_forward(batch)
        new = {
            name: update(
                states[name],
                act,
                quant=quant_for.get(name) if quant_for is not None else None,
            )
            for name, act in acts.items()
        }
        return new, None

    states = jax.jit(lambda s, b: jax.lax.scan(step, s, b)[0])(states, batches)
    return {name: summarize(st) for name, st in states.items()}


# ---------------------------------------------------------------------------
# Model front-ends
# ---------------------------------------------------------------------------
def calibrate_cnn(
    params: dict,
    spec,
    images: Array,
    *,
    bits: int = 8,
    clip: str = "percentile",
    pct: float = 99.9,
    rho_threshold: float = 0.25,
    compensate: bool = True,
) -> tuple[CalibrationTable, dict]:
    """Calibrate a CNN on ``images[n_batches, B, H, W, C]``.

    Returns ``(table, folded_params)``: the static activation quantizers
    plus the params with compensation terms folded into biases (equal to
    ``params`` when ``compensate=False`` or every rho gate is off).
    """
    from repro.models import cnn

    def tapped(x):
        tc = TapCollector()
        cnn.forward(params, spec, x, tap=tc)
        return tc.acts

    stats = collect_stats(tapped, images)
    table = build_table(
        stats, bits=bits, clip=clip, pct=pct, rho_threshold=rho_threshold
    )
    if not compensate:
        return table, dict(params)
    quant_for = {name: (s.bits, s.amax) for name, s in table.sites}
    errs = collect_stats(tapped, images, quant_for=quant_for)
    table = attach_errors(table, errs)
    return table, fold_cnn_bias(params, spec, table)


def calibrate_lm(
    params: Any,
    cfg,
    token_batches: Array,
    *,
    bits: int = 8,
    clip: str = "percentile",
    pct: float = 99.9,
    rho_threshold: float = 0.25,
) -> CalibrationTable:
    """Calibrate a decoder LM on ``token_batches[n_batches, B, S]``.

    Taps the embedding output, the stacked per-layer residual streams
    (site ``"blocks"``, ``[L, B, S, D]``), the per-matmul input sites
    (``"attn_in"``/``"attn_mix"``/``"ffn_in"``/``"ffn_hidden"`` — each
    matmul's *actual* input distribution, e.g. post-RMSNorm for QKV,
    not the growing residual stream) and the final pre-unembed
    activation. The serve path resolves each packed weight's static
    activation scale against these sites
    (``repro.api_schemes.pack_lm_params``).
    """
    from repro.models import transformer

    def tapped(tokens):
        tc = TapCollector()
        transformer.forward(params, cfg, tokens, tap=tc)
        return tc.acts

    stats = collect_stats(tapped, token_batches)
    return build_table(
        stats, bits=bits, clip=clip, pct=pct, rho_threshold=rho_threshold
    )


def calibrate_kv_cache(
    params: Any,
    cfg,
    token_batches: Array,
    *,
    bits: int = 8,
) -> tuple[np.ndarray, np.ndarray]:
    """Calibrate static per-head K/V cache scales on ``[n, B, S]`` tokens.

    Runs the same one-jit observer scan as :func:`calibrate_lm` over the
    gated ``k_cache`` / ``v_cache`` tap sites (post-RoPE keys and values,
    exactly what the serve engine writes to its cache — DESIGN.md §12).
    The tapped ``[L, B, S, KV, hd]`` stacks are reshaped channels-last to
    ``[B, S, hd, L*KV]`` so the observers' per-channel running max lands
    one amax per (layer, kv_head) pair.

    Returns ``(k_scale, v_scale)``, each ``[L, KV]`` float32 — symmetric
    quantization steps ``amax / (2^(bits-1) - 1)`` ready for
    ``transformer.init_paged_cache(..., kv_scales=...)`` or
    ``ServeEngine(kv_scales=...)``. Zero runtime range reductions: the
    serving path only ever divides by these constants (same static-quant
    contract as the activation sites, DESIGN.md §6).
    """
    from repro.models import transformer

    n_layers = cfg.n_dec_layers or cfg.n_layers
    n_kv = cfg.n_kv_heads

    def tapped(tokens):
        tc = TapCollector()
        transformer.forward(params, cfg, tokens, tap=tc, tap_kv=True)

        def chan(x):  # [L, B, S, KV, hd] -> [B, S, hd, L*KV]
            x = jnp.transpose(x, (1, 2, 4, 0, 3))
            return x.reshape(x.shape[0], x.shape[1], x.shape[2], -1)

        return {
            "k_cache": chan(tc.acts["k_cache"]),
            "v_cache": chan(tc.acts["v_cache"]),
        }

    stats = collect_stats(tapped, token_batches)
    qmax = float(2 ** (bits - 1) - 1)

    def scales(summary: ObserverSummary) -> np.ndarray:
        amax = np.maximum(np.asarray(summary.ch_amax, np.float32), 1e-8)
        return (amax.reshape(n_layers, n_kv) / qmax).astype(np.float32)

    return scales(stats["k_cache"]), scales(stats["v_cache"])


# ---------------------------------------------------------------------------
# Evaluation helpers (benchmarks + tests)
# ---------------------------------------------------------------------------
def per_layer_output_mse(
    params: dict,
    quant_params: dict,
    spec,
    x: Array,
    table: CalibrationTable,
    *,
    metrics=None,
) -> dict[str, float]:
    """Per-site MSE of the calibrated-quantized forward vs the fp run.

    ``quant_params`` lets the caller pass bias-folded params; each tap
    site's error reflects everything quantized upstream of it, so the
    effect of folding site N's compensation shows up at site N+1.

    ``metrics`` (an obs :class:`~repro.obs.metrics.Registry`) records
    each site's error as a ``calib.mse.<site>`` gauge, so calibration
    quality exports through the same snapshot as serve/train telemetry.
    """
    from repro.models import cnn

    def run(p, calib):
        tc = TapCollector()
        cnn.forward(p, spec, x, calib=calib, tap=tc)
        return tc.acts

    acts_fp = jax.jit(lambda: run(params, None))()
    acts_q = jax.jit(lambda: run(quant_params, table))()
    out = {
        name: float(jnp.mean(jnp.square(acts_q[name] - acts_fp[name])))
        for name in acts_fp
    }
    if metrics is not None:
        for name, mse in out.items():
            metrics.gauge(f"calib.mse.{name}").set(mse)
    return out


def count_range_reductions(fn: Callable, *args, **kwargs) -> int:
    """Number of ``reduce_max`` primitives in ``fn``'s jaxpr (recursive).

    The acceptance gauge for static activation quantization: a dynamic
    ``max|x|`` range reduction lowers to ``reduce_max``, while model ops
    (max-pool → ``reduce_window_max``, relu → elementwise ``max``) do
    not, so a calibrated CNN forward must count zero.
    """
    from jax.extend import core as jcore

    def subjaxprs(v):
        if isinstance(v, jcore.Jaxpr):
            yield v
        elif isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from subjaxprs(item)

    def walk(jx) -> int:
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "reduce_max":
                n += 1
            for v in eqn.params.values():
                n += sum(walk(sub) for sub in subjaxprs(v))
        return n

    return walk(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr)
