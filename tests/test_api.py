"""The repro.api front door (DESIGN.md §8).

Covers: the public surface importing cleanly, QuantScheme validation +
JSON round-trip, format resolution at the API boundary (clear errors
for unknown tags), bit-exact parity of the façade against every legacy
entry point it replaces (CNN pack, CNN static-calibrated pack, LM serve
pack with and without calibration, the Sec. V methodology search),
DeprecationWarnings on the legacy wrappers, the single packed-size
accounting walk, and QuantizedModel save/load — bit-identical forwards
after reload (including under ``jax.jit`` and ``jax.device_put``) and
rejection of corrupted artifacts.
"""
import glob
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import ArchConfig
from repro.core import FORMAT_A, PRESET_FORMATS
from repro.core.elp_bsd import resolve_format
from repro.kernels.ops import PackedWeight, packed_tree_bytes
from repro.models import cnn, get_model

SPEC = cnn.ALEXNET_MINI

LM_CFG = ArchConfig(
    name="api-lm", family="dense", n_layers=2, d_model=32, n_heads=4,
    n_kv_heads=2, d_ff=64, vocab=64, head_dim=8, dtype_str="float32",
)


@pytest.fixture(scope="module")
def cnn_setup():
    params = cnn.init_params(SPEC, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, SPEC.input_hw, SPEC.input_hw, SPEC.input_ch)),
                    jnp.float32)
    images = jnp.asarray(
        rng.normal(size=(3, 8, SPEC.input_hw, SPEC.input_hw, SPEC.input_ch)), jnp.float32
    )
    return params, x, images


@pytest.fixture(scope="module")
def lm_setup():
    mapi = get_model(LM_CFG)
    params = mapi.init_params(LM_CFG, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, LM_CFG.vocab)
    calib_toks = jax.random.randint(jax.random.PRNGKey(2), (2, 4, 16), 0, LM_CFG.vocab)
    return mapi, params, toks, calib_toks


def assert_trees_bitwise_equal(a, b):
    la, _ = jax.tree_util.tree_flatten_with_path(a)
    lb, _ = jax.tree_util.tree_flatten_with_path(b)
    assert len(la) == len(lb)
    for (pa, va), (pb, vb) in zip(la, lb):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb), err_msg=str(pa))


# ---------------------------------------------------------------------------
# Public surface
# ---------------------------------------------------------------------------
def test_api_all_imports_cleanly():
    assert api.__all__
    for name in api.__all__:
        assert getattr(api, name) is not None, name


def test_quant_scheme_validation_and_json():
    s = api.QuantScheme(fmt="elp4", act="static", act_bits=6, block_sizes=[64, 64, 64])
    assert s.fmt == "elp_bsd_a4" and s.block_sizes == (64, 64, 64)
    assert s.format is PRESET_FORMATS["elp_bsd_a4"]
    assert api.QuantScheme.from_json(s.to_json()) == s
    assert api.QuantScheme(fmt=FORMAT_A).fmt == "elp_bsd_a4"
    with pytest.raises(ValueError):
        api.QuantScheme(act="sometimes")
    with pytest.raises(ValueError):
        api.QuantScheme(fmt="int8")
    with pytest.raises(ValueError):
        api.QuantScheme(block_sizes=(64, 64))
    with pytest.raises(ValueError):
        api.QuantScheme(act_bits=1)
    with pytest.raises(ValueError):
        api.QuantScheme.from_json({"fmt": "elp_bsd_a4", "bogus_field": 1})


def test_resolve_format_boundary():
    assert resolve_format("elp4") is PRESET_FORMATS["elp_bsd_a4"]
    assert resolve_format("elp8") is PRESET_FORMATS["elp_bsd_c6"]
    assert resolve_format(FORMAT_A) is FORMAT_A
    with pytest.raises(ValueError, match="unknown ELP_BSD format.*elp_bsd_a4"):
        resolve_format("elp99")
    with pytest.raises(TypeError):
        resolve_format(4)


def test_abstract_quantize_tree_rejects_unknown_tag(lm_setup):
    from repro.runtime.quantized_params import abstract_quantize_tree

    mapi, params, _, _ = lm_setup
    aparams = jax.eval_shape(lambda: mapi.init_params(LM_CFG, jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="unknown ELP_BSD format"):
        abstract_quantize_tree(aparams, LM_CFG, "elp99")
    at = abstract_quantize_tree(aparams, LM_CFG, "elp4")  # alias still resolves
    assert any(
        isinstance(l, PackedWeight)
        for l in jax.tree.leaves(at, is_leaf=lambda x: isinstance(x, PackedWeight))
    )


def test_as_adapter_dispatch():
    assert api.as_adapter(SPEC).kind == "cnn"
    assert api.as_adapter(LM_CFG).kind == "lm"
    ad = api.as_adapter(SPEC)
    assert api.as_adapter(ad) is ad
    with pytest.raises(TypeError):
        api.as_adapter({"not": "a model"})


# ---------------------------------------------------------------------------
# Deprecated wrappers: they warn AND match the new path bit-for-bit
# ---------------------------------------------------------------------------
def test_deprecated_wrappers_warn(cnn_setup, lm_setup):
    params, _, _ = cnn_setup
    _, lm_params, _, _ = lm_setup
    with pytest.warns(DeprecationWarning, match="repro.api.quantize"):
        cnn.quantize_params(params, FORMAT_A)
    with pytest.warns(DeprecationWarning, match="repro.api.quantize"):
        from repro.runtime.quantized_params import quantize_params_for_serving

        quantize_params_for_serving(lm_params, LM_CFG, "elp4")
    with pytest.warns(DeprecationWarning, match="repro.api.quantize"):
        from repro.core.methodology import convert

        w = {"fc": jnp.ones((8, 4)) * 0.3}
        convert(w, {"fc": (0,)}, FORMAT_A, lambda ww, ab: 1.0)


def test_cnn_facade_parity_with_legacy(cnn_setup):
    params, x, _ = cnn_setup
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = cnn.quantize_params(params, FORMAT_A, compensate=True)
    qm = api.quantize(SPEC, params, api.QuantScheme(fmt="elp_bsd_a4"))
    assert_trees_bitwise_equal(legacy, qm.params)
    np.testing.assert_array_equal(
        np.asarray(cnn.forward(legacy, SPEC, x)), np.asarray(qm.forward(x))
    )


def test_cnn_static_facade_parity_with_legacy(cnn_setup):
    from repro.calib import calibrate_cnn

    params, x, images = cnn_setup
    table, folded = calibrate_cnn(params, SPEC, images, bits=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = cnn.quantize_params(folded, FORMAT_A)
    qm = api.quantize(
        SPEC,
        params,
        api.QuantScheme(fmt="elp_bsd_a4", act="static", act_bits=8),
        calib_data=images,
    )
    assert qm.table == table
    assert_trees_bitwise_equal(legacy, qm.params)
    np.testing.assert_array_equal(
        np.asarray(cnn.forward(legacy, SPEC, x, calib=table)),
        np.asarray(qm.forward(x)),
    )


def test_lm_facade_parity_with_legacy(lm_setup):
    from repro.calib import calibrate_lm
    from repro.runtime.quantized_params import quantize_params_for_serving

    mapi, params, toks, calib_toks = lm_setup
    table = calibrate_lm(params, LM_CFG, calib_toks, bits=8, clip="max")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = quantize_params_for_serving(params, LM_CFG, "elp_bsd_c6", calib=table)
    qm = api.quantize(
        LM_CFG,
        params,
        api.QuantScheme(fmt="elp_bsd_c6", act="static", act_bits=8, clip="max"),
        calib_data=calib_toks,
    )
    assert_trees_bitwise_equal(legacy, qm.params)
    # packed leaves carry the same static activation quantizers
    for la, lb in zip(
        jax.tree.leaves(legacy, is_leaf=lambda l: isinstance(l, PackedWeight)),
        jax.tree.leaves(qm.params, is_leaf=lambda l: isinstance(l, PackedWeight)),
    ):
        if isinstance(la, PackedWeight):
            assert (la.act_scale, la.act_bits) == (lb.act_scale, lb.act_bits)
    cache = mapi.init_cache(LM_CFG, toks.shape[0], toks.shape[1])
    legacy_logits, _ = mapi.prefill(legacy, LM_CFG, {"tokens": toks}, cache)
    np.testing.assert_array_equal(np.asarray(legacy_logits), np.asarray(qm.forward(toks)))


def test_weights_map_drives_methodology(lm_setup, cnn_setup):
    """The ModelAdapter weights_map quartet is what lets run_methodology
    convert any model without knowing its pytree shape (DESIGN.md §8)."""
    from repro.core.methodology import run_methodology

    _, params, _, _ = lm_setup
    flat, group_axes, skip, rebuild = api.as_adapter(LM_CFG).weights_map(params)
    assert group_axes and skip and set(group_axes).isdisjoint(skip)
    assert set(flat) == set(group_axes) | set(skip)
    # quantizable [..., K, N] leaves group along the contracting dim
    assert all(ax == (flat[k].ndim - 2,) for k, ax in group_axes.items())
    assert any(k.startswith("blocks/") for k in group_axes)
    assert "embed" in skip  # embeddings stay full precision (DESIGN.md §4)

    def eval_fn(wmap, act_quant):
        tree = rebuild(wmap)  # any same-keyed map rebuilds the native pytree
        assert jax.tree_util.tree_structure(tree) == jax.tree_util.tree_structure(params)
        return 1.0

    res = run_methodology(
        flat, group_axes, PRESET_FORMATS["elp_bsd_a4"], eval_fn, skip=skip
    )
    assert set(res.quantized) == set(group_axes)
    for k in skip:  # skipped leaves pass through untouched
        np.testing.assert_array_equal(np.asarray(res.weights[k]), np.asarray(flat[k]))
    for k in group_axes:  # quantized leaves actually moved
        assert not np.array_equal(np.asarray(res.weights[k]), np.asarray(flat[k]))
    # the CNN adapter's map is the identity walk over the flat dict
    cnn_params, _, _ = cnn_setup
    flat2, axes2, skip2, rebuild2 = api.as_adapter(SPEC).weights_map(cnn_params)
    assert flat2 == dict(cnn_params) and skip2 == ()
    assert axes2 == cnn.weight_group_axes(cnn_params)
    assert rebuild2(flat2) == dict(cnn_params)


def test_methodology_search_parity(cnn_setup):
    """api.quantize(eval_fn=...) runs the same Sec. V loop as legacy convert."""
    from repro.core.methodology import convert

    params, _, _ = cnn_setup

    def eval_fn(weights, act_quant):
        err = float(
            sum(jnp.sum(jnp.abs(weights[k] - params[k])) for k in weights)
            / sum(p.size for p in params.values())
        )
        penalty = 0.0 if act_quant is None else max(0, 7 - int(act_quant)) * 0.03
        return max(0.0, 0.95 - 40.0 * err - penalty)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        res = convert(
            params, cnn.weight_group_axes(params), FORMAT_A, eval_fn,
            ac=0.05, bw_max=8, bw_min=4,
        )
    qm = api.quantize(
        SPEC,
        params,
        api.QuantScheme(fmt="elp_bsd_a4", act="dynamic", ac=0.05, bw_max=8, bw_min=4),
        eval_fn=eval_fn,
    )
    assert qm.report.act_bits == res.act_bits
    assert qm.report.accuracy == pytest.approx(res.accuracy)
    assert qm.report.baseline_accuracy == pytest.approx(res.baseline_accuracy)


# ---------------------------------------------------------------------------
# Packed-size accounting: one walk, two delegating names
# ---------------------------------------------------------------------------
def test_packed_byte_accounting_delegates(cnn_setup, lm_setup):
    from repro.runtime.quantized_params import packed_bytes

    params, _, _ = cnn_setup
    qm = api.quantize(SPEC, params)
    manual = sum(
        w.nbytes + w.sf.size * 4 for w in qm.params.values() if isinstance(w, PackedWeight)
    )
    assert cnn.packed_weight_bytes(qm.params) == manual
    assert packed_tree_bytes(qm.params, packed_only=True) == manual
    bias_bytes = sum(
        int(np.prod(w.shape)) * 4 for k, w in qm.params.items() if not isinstance(w, PackedWeight)
    )
    assert packed_bytes(qm.params) == manual + bias_bytes
    assert qm.report.packed_bytes == manual + bias_bytes
    assert qm.report.packed_weight_bytes == manual
    # the walk also works on abstract trees (dry-run accounting)
    _, lm_params, _, _ = lm_setup
    ab = jax.eval_shape(lambda: lm_params)
    assert packed_bytes(ab) == packed_bytes(lm_params)


# ---------------------------------------------------------------------------
# Save / load
# ---------------------------------------------------------------------------
def test_cnn_save_load_roundtrip_bit_identical(cnn_setup, tmp_path):
    params, x, images = cnn_setup
    qm = api.quantize(
        SPEC,
        params,
        api.QuantScheme(fmt="elp_bsd_a4", act="static", act_bits=8),
        calib_data=images,
    )
    path = os.path.join(tmp_path, "alexnet4b")
    qm.save(path)
    qm2 = api.load(path)
    assert qm2.scheme == qm.scheme
    assert qm2.table == qm.table
    assert qm2.report == qm.report
    # a report saved before the tile cache went still loads
    old = {**qm.report.to_json(), "tuned_blocks": [["cpu|pallas|elp_bsd_a4|nib|8x64x64", [128]]]}
    assert api.ConversionReport.from_json(old) == qm.report
    assert qm2.model == SPEC
    assert_trees_bitwise_equal(qm.params, qm2.params)
    ref = np.asarray(qm.forward(x))
    np.testing.assert_array_equal(ref, np.asarray(qm2.forward(x)))
    # PackedWeight pytrees survive jit and device_put on the reloaded model
    jitted = jax.jit(lambda m, a: m.forward(a))
    np.testing.assert_array_equal(ref, np.asarray(jitted(qm2, x)))
    np.testing.assert_array_equal(ref, np.asarray(jitted(jax.device_put(qm2), x)))


def test_lm_save_load_roundtrip_bit_identical(lm_setup, tmp_path):
    _, params, toks, _ = lm_setup
    qm = api.quantize(LM_CFG, params, api.QuantScheme(fmt="elp4"))
    path = os.path.join(tmp_path, "lm4b")
    qm.save(path)
    qm2 = api.load(path)
    assert qm2.model == LM_CFG
    np.testing.assert_array_equal(np.asarray(qm.forward(toks)), np.asarray(qm2.forward(toks)))
    out = qm.generate(toks, max_new_tokens=4)
    out2 = qm2.generate(toks, max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_corrupted_artifacts_rejected(cnn_setup, tmp_path):
    params, _, _ = cnn_setup
    qm = api.quantize(SPEC, params)

    # missing artifact
    with pytest.raises(api.ArtifactError, match="unreadable"):
        api.load(os.path.join(tmp_path, "nope"))

    # corrupted params payload
    p1 = os.path.join(tmp_path, "corrupt_npz")
    qm.save(p1)
    npz = glob.glob(os.path.join(p1, "params", "step_*", "arrays.npz"))[0]
    raw = bytearray(open(npz, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(npz, "wb").write(bytes(raw))
    with pytest.raises(api.ArtifactError):
        api.load(p1)

    # checksum mismatch (payload readable but bits changed)
    p2 = os.path.join(tmp_path, "bad_checksum")
    qm.save(p2)
    mf = os.path.join(p2, "manifest.json")
    doc = json.load(open(mf))
    key = next(iter(doc["checksums"]))
    doc["checksums"][key] = "0" * 64
    json.dump(doc, open(mf, "w"))
    with pytest.raises(api.ArtifactError, match="checksum mismatch"):
        api.load(p2)

    # wrong format version
    p3 = os.path.join(tmp_path, "bad_version")
    qm.save(p3)
    mf = os.path.join(p3, "manifest.json")
    doc = json.load(open(mf))
    doc["format_version"] = 999
    json.dump(doc, open(mf, "w"))
    with pytest.raises(api.ArtifactError, match="format_version"):
        api.load(p3)

    # truncated manifest
    p4 = os.path.join(tmp_path, "bad_manifest")
    qm.save(p4)
    with open(os.path.join(p4, "manifest.json"), "w") as f:
        f.write('{"format_version": 1, "kind": "cnn"')
    with pytest.raises(api.ArtifactError, match="unreadable"):
        api.load(p4)


def test_quantized_model_pytree_roundtrip(cnn_setup):
    params, _, _ = cnn_setup
    qm = api.quantize(SPEC, params)
    leaves, treedef = jax.tree_util.tree_flatten(qm)
    qm2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(qm2, api.QuantizedModel)
    assert qm2.scheme == qm.scheme and qm2.report == qm.report
    assert_trees_bitwise_equal(qm.params, qm2.params)


def test_generate_raises_for_cnn(cnn_setup):
    params, _, _ = cnn_setup
    qm = api.quantize(SPEC, params)
    with pytest.raises(NotImplementedError, match="forward"):
        qm.generate(jnp.zeros((1, 4), jnp.int32), max_new_tokens=2)


def test_static_requires_calib_data(cnn_setup):
    params, _, _ = cnn_setup
    with pytest.raises(ValueError, match="calib_data"):
        api.quantize(SPEC, params, api.QuantScheme(act="static"))


def test_lm_dynamic_act_rejected(lm_setup):
    _, params, _, _ = lm_setup
    with pytest.raises(ValueError, match="dynamic"):
        api.quantize(LM_CFG, params, api.QuantScheme(fmt="elp4", act="dynamic"))


def test_lm_forward_rejects_cnn_execution_overrides(lm_setup):
    _, params, toks, _ = lm_setup
    qm = api.quantize(LM_CFG, params, api.QuantScheme(fmt="elp4"))
    with pytest.raises(ValueError, match="serve path"):
        qm.forward(toks, block_sizes=(64, 64, 64))


def test_malformed_report_rejected(cnn_setup, tmp_path):
    params, _, _ = cnn_setup
    qm = api.quantize(SPEC, params)
    p = os.path.join(tmp_path, "bad_report")
    qm.save(p)
    mf = os.path.join(p, "manifest.json")
    doc = json.load(open(mf))
    doc["report"] = {"bogus": 1}
    json.dump(doc, open(mf, "w"))
    with pytest.raises(api.ArtifactError, match="report"):
        api.load(p)
