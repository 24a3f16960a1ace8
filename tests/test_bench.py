"""Benchmark subsystem: schema, registry, gate; explicit tiles and auto dispatch.

The committed ``BENCH_*.json`` baselines are load-bearing (CI's
bench-smoke job gates wall-clock against them), so their schema is
tested here against the real files, not just synthetic documents.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench import registry, schema
from repro.core.elp_bsd import FORMAT_A
from repro.kernels.ops import pack_weight, quantized_matmul

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Explicit tiles that keep block_k are bit-exact against the default tiles
# ---------------------------------------------------------------------------
def test_auto_blocks_bit_exact_vs_default():
    """Re-tiling M and N leaves every output's K sum in the same order."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 512)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(512, 128)) * 0.05, jnp.float32)
    pw, _ = pack_weight(w, FORMAT_A)
    want = np.asarray(quantized_matmul(x, pw, impl="pallas"))
    got = np.asarray(quantized_matmul(x, pw, impl="pallas", block_sizes=(256, 256, 128)))
    np.testing.assert_array_equal(got, want)

    # The conv path forwards the tiles to its im2col matmul, bit-exactly.
    from repro.kernels.conv import quantized_conv2d
    from repro.kernels.ops import pack_conv_weight

    xc = jnp.asarray(rng.normal(size=(2, 8, 8, 8)), jnp.float32)
    wc = jnp.asarray(rng.normal(size=(3, 3, 8, 16)) * 0.1, jnp.float32)
    pwc, _ = pack_conv_weight(wc, FORMAT_A)
    ref = np.asarray(quantized_conv2d(xc, pwc, impl="pallas"))
    got = np.asarray(quantized_conv2d(xc, pwc, impl="pallas", block_sizes=(256, 256, 128)))
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# Gate: selected-vs-selected comparison and the same-impl exemption
# ---------------------------------------------------------------------------
def test_gate_selected_same_impl_skips_entry_check():
    """A ``selected`` timing with an unchanged impl votes in the group
    geomean but is exempt from the single-entry catastrophic check (its
    wall duplicates the impl's own gated key); an impl FLIP restores
    the full check."""
    from repro.bench.__main__ import _collect_ratios, _gate

    def doc(sel_us, sel_impl):
        return {
            "entries": {
                "decode_step_fused/x": {
                    "workload": "decode_step_fused",
                    "wall_us": {
                        "fused": {"min_us": 1000.0},
                        "selected": {"min_us": sel_us, "impl": sel_impl},
                    },
                }
            },
            "backend": "cpu",
        }

    base = doc(1000.0, "pallas_fused")
    same = _collect_ratios(doc(5000.0, "pallas_fused"), base, 200.0)
    sel = [r for r in same if r[2] == "selected"]
    assert len(sel) == 1 and sel[0][6] is False  # in ratios, exempt from entry check
    assert not any("(entry" in f for f in _gate(same, 0.20))

    flipped = _collect_ratios(doc(5000.0, "xla"), base, 200.0)
    sel = [r for r in flipped if r[2] == "selected"]
    assert len(sel) == 1 and sel[0][6] is True
    assert any("(entry" in f for f in _gate(flipped, 0.20))


# ---------------------------------------------------------------------------
# impl="auto" dispatch off TPU: conv takes XLA; flash-decode chunking
# ---------------------------------------------------------------------------
def test_conv_auto_is_xla():
    """Conv ``impl="auto"`` takes XLA's conv — never interpret-mode Pallas."""
    from repro.kernels.conv import quantized_conv2d
    from repro.kernels.ops import pack_conv_weight

    rng = np.random.default_rng(3)
    xc = jnp.asarray(rng.normal(size=(2, 8, 8, 8)), jnp.float32)
    pwc, _ = pack_conv_weight(
        jnp.asarray(rng.normal(size=(3, 3, 8, 16)) * 0.1, jnp.float32), FORMAT_A
    )
    want = np.asarray(quantized_conv2d(xc, pwc, impl="xla"))
    got = np.asarray(quantized_conv2d(xc, pwc, impl="auto"))
    np.testing.assert_array_equal(got, want)


def test_flash_decode_chunked_matches_oneshot():
    """block_s streaming combine == the one-shot slice of the default
    block_s=None."""
    from repro.models.context import ParallelCtx
    from repro.models.flash_decode import flash_decode_attention

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model", flash_decode=True)
    key = jax.random.PRNGKey(4)
    b, smax, h, kv, hd = 2, 64, 4, 2, 16
    q = jax.random.normal(key, (b, 1, h, hd))
    ck = jax.random.normal(jax.random.PRNGKey(5), (b, smax, kv, hd))
    cv = jax.random.normal(jax.random.PRNGKey(6), (b, smax, kv, hd))
    pos = jnp.int32(49)
    with mesh:
        oneshot = flash_decode_attention(q, ck, cv, pos, pctx=pctx)
        chunked = flash_decode_attention(q, ck, cv, pos, pctx=pctx, block_s=16)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(oneshot), rtol=2e-6, atol=2e-6)


def test_explicit_block_sizes_tuple_and_bad_value():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    pw, _ = pack_weight(jnp.asarray(rng.normal(size=(128, 64)) * 0.1, jnp.float32), FORMAT_A)
    want = np.asarray(quantized_matmul(x, pw, impl="pallas"))
    got = np.asarray(quantized_matmul(x, pw, impl="pallas", block_sizes=(256, 128, 128)))
    np.testing.assert_array_equal(got, want)
    # Misuse raises on the xla fallback too, not only once on TPU; "auto"
    # tiles are no longer a value (the shape rule picks them under impl="auto").
    for impl in ("pallas", "xla", "auto"):
        for bad in ("fastest", "auto"):
            with pytest.raises(ValueError, match="block_sizes"):
                quantized_matmul(x, pw, impl=impl, block_sizes=bad)
        with pytest.raises(ValueError, match="even block_k"):
            quantized_matmul(x, pw, impl=impl, block_sizes=(128, 128, 127))


# ---------------------------------------------------------------------------
# Schema: the committed baselines and the validator itself
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fname", ["BENCH_kernels.json", "BENCH_e2e.json"])
def test_committed_baselines_validate(fname):
    path = os.path.join(REPO_ROOT, fname)
    assert os.path.exists(path), f"{fname} must be committed at the repo root (scripts/bench.sh)"
    with open(path) as f:
        doc = json.load(f)
    schema.validate(doc, suite=fname.split("_")[1].split(".")[0])
    # Smoke-tier entries are what CI re-measures and gates on.
    smoke = [n for n, e in doc["entries"].items() if e["tier"] == "smoke"]
    assert smoke, f"{fname} has no smoke-tier entries for the CI gate"


def _minimal_doc():
    return {
        "schema_version": schema.SCHEMA_VERSION,
        "suite": "kernels",
        "backend": "cpu",
        "jax_version": "0.0.test",
        "smoke_only": True,
        "entries": {
            "matmul/x": {
                "workload": "matmul",
                "tier": "smoke",
                "shape": {"m": 8, "k": 16, "n": 4, "fmt": "f", "dims": [8, 16, 4]},
                "wall_us": {
                    "xla": {"median_us": 1.0, "min_us": 0.5, "iters": 3, "warmup": 1},
                    "pallas": None,
                },
                "hlo": {"flops": 1.0, "bytes_accessed": None, "collective_bytes": 0.0},
                "quality": {"out_mse": 0.1},
                "bytes": None,
            }
        },
    }


def test_schema_accepts_minimal_doc():
    schema.validate(_minimal_doc())


@pytest.mark.parametrize(
    "mutate",
    [
        lambda d: d.update(schema_version=2),
        lambda d: d.update(suite="vibes"),
        lambda d: d.update(entries={}),
        lambda d: d.pop("smoke_only"),
        lambda d: d["entries"]["matmul/x"].update(tier="warm"),
        lambda d: d["entries"]["matmul/x"].update(shape={}),
        lambda d: d["entries"]["matmul/x"]["wall_us"]["xla"].update(median_us=-1),
        lambda d: d["entries"]["matmul/x"]["wall_us"]["xla"].pop("iters"),
        lambda d: d["entries"]["matmul/x"].update(hlo={"flops": 1.0}),
        lambda d: d["entries"]["matmul/x"].update(quality={"mse": "tiny"}),
    ],
    ids=[
        "version", "suite", "no-entries", "no-smoke-flag", "bad-tier",
        "empty-shape", "negative-median", "missing-iters", "hlo-missing-keys",
        "non-numeric-quality",
    ],
)
def test_schema_rejects_malformed(mutate):
    doc = _minimal_doc()
    mutate(doc)
    with pytest.raises(schema.SchemaError):
        schema.validate(doc)


def test_schema_validates_suite_mismatch():
    with pytest.raises(schema.SchemaError, match="expected suite"):
        schema.validate(_minimal_doc(), suite="e2e")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_registry_names_sorted_unique_and_tiered():
    for suite in ("kernels", "e2e"):
        all_specs = registry.specs(suite)
        names = [s.name for s in all_specs]
        assert names == sorted(names) and len(names) == len(set(names))
        smoke = registry.specs(suite, smoke_only=True)
        assert smoke and len(smoke) < len(all_specs)
        assert all(s.tier == "smoke" for s in smoke)
    assert registry.specs("kernels", only="conv2d/")
    with pytest.raises(KeyError):
        registry.get("not/a/workload")


def test_smallest_workload_entry_is_deterministic():
    """Two runs of one workload agree on everything but wall-clock."""
    spec = registry.get("matmul/elp_bsd_a4/nib/8x128x10")

    def strip(entry):
        e = json.loads(json.dumps(entry))  # deep copy
        for impl, t in e["wall_us"].items():
            e["wall_us"][impl] = sorted(t) if t else None
        return e

    a, b = spec.run(1, 1), spec.run(1, 1)
    assert strip(a) == strip(b)
    assert a["quality"]["out_mse"] == b["quality"]["out_mse"]
