"""Calibration subsystem tests (DESIGN.md §6).

Covers: streaming observer correctness, percentile clipping, the
determinism of the traced calibration pass, static-vs-dynamic activation
quantization parity, correlation-gated bias-fold compensation reducing
per-layer output MSE, the zero-runtime-reduction property of the
calibrated graphs (CNN forward and packed serve matmul), the calibrated
methodology step-1 search, table persistence, and the degenerate
bit-width guards in core/quantize.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.calib import (
    CalibrationTable,
    TapCollector,
    build_table,
    calibrate_cnn,
    calibrate_lm,
    collect_stats,
    count_range_reductions,
    init_observer,
    per_layer_output_mse,
    summarize,
    update,
)
from repro.core.quantize import fake_quant_dynamic, fake_quant_uniform, uniform_levels
from repro.data.pipeline import CnnDataset
from repro.models import cnn

SPEC = cnn.ALEXNET_MINI


@pytest.fixture(scope="module")
def mini_setup():
    params = cnn.init_params(SPEC, jax.random.PRNGKey(0))
    ds = CnnDataset(SPEC.input_hw, SPEC.input_ch, 10, 64, seed=0)
    images = jnp.stack([jnp.asarray(ds.np_batch(i)[0]) for i in range(6)])
    return params, images


# ---------------------------------------------------------------------------
# Observers
# ---------------------------------------------------------------------------
def test_observer_streaming_matches_numpy():
    rng = np.random.default_rng(0)
    # AR(1)-correlated rows so rho is meaningfully nonzero
    noise = rng.standard_normal((4, 64, 8)).astype(np.float32)
    x = np.copy(noise)
    for i in range(1, 64):
        x[:, i] = 0.8 * x[:, i - 1] + 0.6 * noise[:, i]
    state = init_observer(8)
    for b in range(4):
        # keep ndim >= 3 so adjacency runs along the sequence axis
        state = update(state, jnp.asarray(x[b : b + 1]))
    s = summarize(state)
    assert s.count == x.size
    np.testing.assert_allclose(s.amax, np.abs(x).max(), rtol=1e-6)
    np.testing.assert_allclose(s.mean, x.mean(), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s.std, x.std(), rtol=1e-3)
    rho_np = np.corrcoef(x[:, :-1, :].ravel(), x[:, 1:, :].ravel())[0, 1]
    np.testing.assert_allclose(s.rho, rho_np, atol=0.02)
    assert s.rho > 0.5  # the injected correlation is visible


def test_percentile_amax_clips_outliers():
    rng = np.random.default_rng(1)
    x = rng.standard_normal(20_000).astype(np.float32)
    x[:10] = 100.0  # outliers
    state = update(init_observer(1), jnp.asarray(x[:, None]))
    s = summarize(state)
    assert s.percentile_amax(100.0) == pytest.approx(100.0)
    p99 = s.percentile_amax(99.0)
    assert p99 < 10.0  # outliers clipped away
    assert p99 > 1.0  # but the bulk is covered
    assert s.percentile_amax(90.0) <= p99  # monotone in pct


# ---------------------------------------------------------------------------
# Calibration runs
# ---------------------------------------------------------------------------
def test_cnn_tap_sites_and_shapes(mini_setup):
    params, images = mini_setup
    tc = TapCollector()
    cnn.forward(params, SPEC, images[0], tap=tc)
    assert list(tc.acts) == ["input", "conv0", "conv1", "conv2", "fc3"]
    assert tc.acts["input"].shape == images[0].shape
    assert tc.acts["fc3"].shape == (64, 128)
    with pytest.raises(ValueError):
        tc("input", images[0])  # duplicate site


def test_calibration_deterministic_under_jit(mini_setup):
    params, images = mini_setup
    t1, f1 = calibrate_cnn(params, SPEC, images, bits=6)
    t2, f2 = calibrate_cnn(params, SPEC, images, bits=6)
    assert t1 == t2  # frozen dataclasses: exact float equality
    for k in f1:
        np.testing.assert_array_equal(np.asarray(f1[k]), np.asarray(f2[k]))


def test_static_matches_dynamic_when_range_covered(mini_setup):
    """With max-clipping on the eval data itself, the static path is as
    close to fp as the dynamic per-batch path (the ranges coincide)."""
    params, images = mini_setup
    table, _ = calibrate_cnn(params, SPEC, images, bits=8, clip="max", compensate=False)
    x = images[0]
    lg_fp = cnn.forward(params, SPEC, x)
    lg_dyn = cnn.forward(params, SPEC, x, act_bits=8)
    lg_static = cnn.forward(params, SPEC, x, calib=table)
    err_dyn = float(jnp.max(jnp.abs(lg_dyn - lg_fp)))
    err_static = float(jnp.max(jnp.abs(lg_static - lg_fp)))
    scale = float(jnp.max(jnp.abs(lg_fp)))
    assert err_static <= 1.5 * err_dyn + 0.02
    assert err_static < 0.05 * scale  # 8-bit noise, not a broken path


def test_compensation_reduces_output_mse(mini_setup):
    params, images = mini_setup
    table, folded = calibrate_cnn(
        params, SPEC, images, bits=4, clip="percentile", pct=99.0
    )
    # some site must pass the rho gate for the claim to be about the gate
    assert any(s.compensate for _, s in table.sites)
    x = images[0]
    mse_plain = per_layer_output_mse(params, params, SPEC, x, table)
    mse_comp = per_layer_output_mse(params, folded, SPEC, x, table)
    assert sum(mse_comp.values()) < sum(mse_plain.values())
    # and no individual site explodes
    for k in mse_plain:
        assert mse_comp[k] <= mse_plain[k] * 1.05 + 1e-9


def test_lm_calibration_sites():
    from repro.configs.base import ArchConfig
    from repro.models import transformer as tr

    cfg = ArchConfig(
        name="t", family="dense", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab=64, head_dim=8, dtype_str="float32",
    )
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 4, 16), 0, 64)
    table = calibrate_lm(params, cfg, toks, bits=8)
    assert set(table.names()) == {
        "embed", "blocks", "attn_in", "attn_mix", "ffn_in", "ffn_hidden", "final",
    }
    assert all(s.amax > 0 for _, s in table.sites)


# ---------------------------------------------------------------------------
# Zero runtime reductions
# ---------------------------------------------------------------------------
def test_no_runtime_range_reductions(mini_setup):
    params, images = mini_setup
    table, _ = calibrate_cnn(params, SPEC, images, bits=8, compensate=False)
    x = images[0]
    dyn = count_range_reductions(
        lambda xx: cnn.forward(params, SPEC, xx, act_bits=8), x
    )
    static = count_range_reductions(
        lambda xx: cnn.forward(params, SPEC, xx, calib=table), x
    )
    assert dyn == len(table.sites)  # one max|x| per site in the old path
    assert static == 0


def test_packed_matmul_static_act_quant():
    from repro.kernels.ops import pack_weight, quantized_matmul

    w = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    pw, _ = pack_weight(w, "elp_bsd_c6")
    pw_q = dataclasses.replace(pw, act_scale=3.0, act_bits=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    got = quantized_matmul(x, pw_q, impl="xla")
    want = quantized_matmul(fake_quant_uniform(x, 8, 3.0), pw, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    assert count_range_reductions(lambda xx: quantized_matmul(xx, pw_q, impl="xla"), x) == 0


def test_serving_conversion_attaches_act_scales():
    from repro.configs.base import ArchConfig
    from repro.kernels.ops import PackedWeight
    from repro.models import transformer as tr
    from repro.runtime.quantized_params import quantize_params_for_serving

    cfg = ArchConfig(
        name="t", family="dense", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab=64, head_dim=8, dtype_str="float32",
    )
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 16), 0, 64)
    table = calibrate_lm(params, cfg, toks, bits=8, clip="max")
    qp = quantize_params_for_serving(params, cfg, "elp_bsd_c6", calib=table)
    # each matmul's scale comes from the site measuring ITS input
    # distribution (post-norm for qkv/ffn-in, the hidden for w2) — not
    # the depth-growing residual stream
    blocks = qp["blocks"]
    assert blocks["wq"].act_scale == table.site("attn_in").amax
    assert blocks["wo"].act_scale == table.site("attn_mix").amax
    assert blocks["w1"].act_scale == table.site("ffn_in").amax
    assert blocks["w2"].act_scale == table.site("ffn_hidden").amax
    packed = [
        l
        for l in jax.tree.leaves(qp, is_leaf=lambda l: isinstance(l, PackedWeight))
        if isinstance(l, PackedWeight)
    ]
    assert packed and all(l.act_scale is not None and l.act_bits == 8 for l in packed)
    # calibrated serving stays close to serving without activation quant
    qp_noact = quantize_params_for_serving(params, cfg, "elp_bsd_c6")
    cache = tr.init_cache(cfg, 2, 16)
    prefill = jax.jit(lambda p, t, c: tr.prefill(p, cfg, t, c))
    logits, _ = prefill(qp, toks[0][:2], cache)
    logits_ref, _ = prefill(qp_noact, toks[0][:2], tr.init_cache(cfg, 2, 16))
    assert bool(jnp.all(jnp.isfinite(logits)))
    rel = float(jnp.linalg.norm(logits - logits_ref) / jnp.linalg.norm(logits_ref))
    assert rel < 0.1  # 8-bit activation noise, not a wrong scale
    # and greedy decoding is unchanged by calibrated activation quant at
    # every position of both calibration batches (128 decisions) that is
    # not a near-tie. The 8-bit noise is about a tenth of the logit scale
    # (rel above), so a decision whose reference top-2 gap is under 0.1
    # of that position's logit std may flip; random-init logits hold a few
    # such ties (the smallest gap here is 0.006 against a std of 0.86).
    fwd = jax.jit(lambda p, t: tr.forward(p, cfg, t))
    got = np.concatenate([np.asarray(fwd(qp, t)).reshape(-1, cfg.vocab) for t in toks])
    ref = np.concatenate([np.asarray(fwd(qp_noact, t)).reshape(-1, cfg.vocab) for t in toks])
    top2 = np.sort(ref, -1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] >= 0.1 * ref.std(-1)
    assert clear.mean() >= 0.75, clear.mean()  # the check is not vacuous
    np.testing.assert_array_equal(got.argmax(-1)[clear], ref.argmax(-1)[clear])


# ---------------------------------------------------------------------------
# Methodology integration (Sec. V step 1 on the calibrated path)
# ---------------------------------------------------------------------------
def test_methodology_calibrated_search(mini_setup):
    from repro.core.elp_bsd import PRESET_FORMATS
    from repro.core.methodology import convert

    params, images = mini_setup
    table, _ = calibrate_cnn(params, SPEC, images, bits=8, compensate=False)
    seen = []

    def eval_fn(weights, act_quant):
        if act_quant is None:
            return 1.0
        assert isinstance(act_quant, CalibrationTable)
        bits = act_quant.site("input").bits
        seen.append(bits)
        assert all(s.bits == bits for _, s in act_quant.sites)
        return 1.0 - max(0, 6 - bits) * 0.02  # degrades below 6 bits

    weights = {k: v for k, v in params.items()}
    group_axes = cnn.weight_group_axes(params)
    res = convert(
        weights, group_axes, PRESET_FORMATS["elp_bsd_c6"], eval_fn,
        ac=0.01, bw_max=8, bw_min=4, calib=table,
    )
    assert seen and min(seen) >= 4
    assert res.act_bits == 6  # the constraint bites exactly below 6
    assert res.accuracy_loss <= 0.01 + 1e-9


# ---------------------------------------------------------------------------
# Table plumbing + quantize guards
# ---------------------------------------------------------------------------
def test_table_roundtrip_and_with_bits(tmp_path, mini_setup):
    params, images = mini_setup
    table, _ = calibrate_cnn(params, SPEC, images, bits=6)
    p = str(tmp_path / "table.json")
    table.save(p)
    assert CalibrationTable.load(p) == table
    t4 = table.with_bits(4)
    assert all(s.bits == 4 for _, s in t4.sites)
    assert [n for n, _ in t4.sites] == [n for n, _ in table.sites]
    assert hash(t4) != hash(table)  # usable (and distinct) as jit static args


def test_degenerate_bits_guard():
    x = jnp.ones((4,))
    for bits in (1, 0, -3):
        with pytest.raises(ValueError):
            uniform_levels(bits, 1.0)
        with pytest.raises(ValueError):
            fake_quant_uniform(x, bits, 1.0)
        with pytest.raises(ValueError):
            fake_quant_dynamic(x, bits)
    with pytest.raises(TypeError):
        fake_quant_uniform(x, 4.0, 1.0)
    # bits=2 is the smallest valid width: 3 levels, finite step
    lv = uniform_levels(2, 1.0)
    np.testing.assert_allclose(lv, [-1.0, 0.0, 1.0])
    assert bool(jnp.all(jnp.isfinite(fake_quant_uniform(x, 2, 1.0))))
