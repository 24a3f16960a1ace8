"""Fused decode-step kernel + shift-add decoder parity.

Grid: fmt ∈ {elp4 (=elp_bsd_a4), elp8 (=elp_bsd_c6)} × layout ∈
{nibble, u8} × odd K/N tails. elp8 is 6 bits/weight, so its nibble cell
is structurally empty (nibble packing is 4-bit-only) — the grid is
a4×{nib, u8} + c6×{u8}, same as the storage layer supports.

The shift-add decoder's contract is BIT-exactness against the
select-chain decoder (``decode_values``): exhaustively over every raw
code per format here, property-tested over random arrays under
hypothesis when installed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.elp_bsd import PRESET_FORMATS, resolve_format
from repro.kernels import ref as kref
from repro.kernels.fused_decode import MAX_FUSED_M, fused_decode_matmul
from repro.kernels.ops import pack_weight, quantized_matmul

from lm_shapes import QWEN3_8B_KN, YI_34B_LOCAL_KN

# (fmt alias, nibble) — the storable layout grid
GRID = [("elp4", True), ("elp4", False), ("elp8", False)]
GRID_IDS = ["elp4-nib", "elp4-u8", "elp8-u8"]


def _random_stored(rng, fmt, k, n, nibble):
    if nibble:
        return rng.integers(0, 256, size=(k // 2, n)).astype(np.uint8)
    return rng.integers(0, 2**fmt.bits_per_weight, size=(k, n)).astype(np.uint8)


# ---------------------------------------------------------------------------
# shift-add decode ≡ select-chain decode, bit-exact
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt_name", sorted(PRESET_FORMATS))
def test_shift_add_decode_bit_exact_exhaustive(fmt_name):
    """Every raw code of every preset format decodes to the identical
    float32 bit pattern under both decoders."""
    fmt = PRESET_FORMATS[fmt_name]
    codes = jnp.arange(2**fmt.bits_per_weight, dtype=jnp.int32)
    chain = np.asarray(kref.decode_values(codes, fmt))
    shift_add = np.asarray(kref.decode_values_shift_add(codes, fmt))
    np.testing.assert_array_equal(chain.view(np.int32), shift_add.view(np.int32))


@pytest.mark.parametrize("fmt_name", sorted(PRESET_FORMATS))
def test_shift_add_terms_match_numpy_oracle(fmt_name):
    """The per-digit (sign, shift) decomposition reproduces the numpy
    decode oracle: sum of sign·2^shift over digits."""
    fmt = PRESET_FORMATS[fmt_name]
    total = np.zeros(2**fmt.bits_per_weight, np.float64)
    for sign, shift in fmt.shift_add_terms():
        total += sign.astype(np.float64) * np.exp2(shift.astype(np.float64))
    from repro.core.elp_bsd import decode_codes

    np.testing.assert_array_equal(
        total, decode_codes(np.arange(2**fmt.bits_per_weight), fmt)
    )


def test_shift_add_decomposition_affine_flags():
    """Arithmetic-progression LUTs carry an affine (a, b); others don't."""
    for fmt_name, fmt in PRESET_FORMATS.items():
        for off, sbits, ibits, tab, affine in fmt.shift_add_decomposition():
            tabl = [int(t) for t in tab]
            is_ap = len(tabl) == 1 or all(
                tabl[i] == tabl[0] + i * (tabl[1] - tabl[0]) for i in range(len(tabl))
            )
            assert (affine is not None) == is_ap, (fmt_name, tabl, affine)
            if affine is not None and len(tabl) > 1:
                a, b = affine
                assert [a + i * b for i in range(len(tabl))] == tabl


def test_shift_add_property_hypothesis():
    """Property test: shift-add ≡ select-chain bit-exactly on arbitrary
    code arrays (any format, any shape)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(deadline=None, max_examples=50)
    @given(
        fmt_name=st.sampled_from(sorted(PRESET_FORMATS)),
        data=st.data(),
    )
    def inner(fmt_name, data):
        fmt = PRESET_FORMATS[fmt_name]
        shape = data.draw(st.tuples(st.integers(1, 8), st.integers(1, 8)))
        codes = data.draw(
            st.lists(
                st.integers(0, 2**fmt.bits_per_weight - 1),
                min_size=shape[0] * shape[1],
                max_size=shape[0] * shape[1],
            )
        )
        arr = jnp.asarray(np.array(codes, np.int32).reshape(shape))
        a = np.asarray(kref.decode_values(arr, fmt))
        b = np.asarray(kref.decode_values_shift_add(arr, fmt))
        np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32))

    inner()


# ---------------------------------------------------------------------------
# fused kernel vs the matmul oracle (interpret mode)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt_alias,nibble", GRID, ids=GRID_IDS)
@pytest.mark.parametrize("m,k,n", [(1, 128, 128), (4, 256, 384), (8, 384, 256)])
def test_fused_kernel_matches_ref(fmt_alias, nibble, m, k, n):
    fmt = resolve_format(fmt_alias)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    stored = jnp.asarray(_random_stored(rng, fmt, k, n, nibble))
    sf = jnp.float32(0.017)
    got = fused_decode_matmul(x, stored, sf, fmt, nibble=nibble, interpret=True)
    want = kref.elp_bsd_matmul_ref(x, stored, sf, fmt, nibble=nibble)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("fmt_alias,nibble", GRID, ids=GRID_IDS)
def test_fused_kernel_block_shapes(fmt_alias, nibble):
    """Non-default n/k tiles hit the same numbers (output tiling only
    regroups the N dimension; K split order is fixed per block_k)."""
    fmt = resolve_format(fmt_alias)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(4, 512)), jnp.float32)
    stored = jnp.asarray(_random_stored(rng, fmt, 512, 256, nibble))
    sf = jnp.float32(0.03)
    want = kref.elp_bsd_matmul_ref(x, stored, sf, fmt, nibble=nibble)
    got = fused_decode_matmul(
        x, stored, sf, fmt, nibble=nibble, block_n=256, block_k=256, interpret=True
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_fused_kernel_raises_not_asserts():
    fmt = resolve_format("elp4")
    x = jnp.zeros((4, 256), jnp.float32)
    codes = jnp.zeros((256, 128), jnp.uint8)
    sf = jnp.float32(1.0)
    with pytest.raises(ValueError, match="tile evenly"):
        fused_decode_matmul(x, codes, sf, fmt, block_n=96, interpret=True)
    with pytest.raises(ValueError, match="K dim must match"):
        fused_decode_matmul(x, jnp.zeros((128, 128), jnp.uint8), sf, fmt, interpret=True)
    with pytest.raises(ValueError, match="two K rows per byte"):
        fused_decode_matmul(x, codes, sf, fmt, nibble=True, interpret=True)
    with pytest.raises(ValueError, match="even block_k"):
        fused_decode_matmul(
            x, jnp.zeros((128, 128), jnp.uint8), sf, fmt, nibble=True, block_k=129,
            interpret=True,
        )
    with pytest.raises(ValueError, match="must be positive"):
        fused_decode_matmul(x, codes, sf, fmt, block_k=0, interpret=True)
    with pytest.raises(ValueError, match="x\\[M, K\\]"):
        fused_decode_matmul(jnp.zeros((2, 4, 256)), codes, sf, fmt, interpret=True)
    with pytest.raises(ValueError, match="whole M strip"):
        fused_decode_matmul(
            jnp.zeros((MAX_FUSED_M + 1, 256), jnp.float32), codes, sf, fmt, interpret=True
        )


# ---------------------------------------------------------------------------
# quantized_matmul impl="pallas_fused": odd tails, per-channel sf, parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt_alias,nibble", GRID, ids=GRID_IDS)
@pytest.mark.parametrize("k,n", [(131, 90), (257, 130), (512, 256)])
def test_pallas_fused_odd_tails_match_xla(fmt_alias, nibble, k, n):
    """The ops wrapper pads odd K/N to the fused kernel's tiling; outputs
    must match the XLA dequant path within kernel tolerance."""
    fmt = resolve_format(fmt_alias)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    pw, _ = pack_weight(w, fmt, nibble=nibble)
    want = quantized_matmul(x, pw, impl="xla")
    got = quantized_matmul(x, pw, impl="pallas_fused", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("fmt_alias,nibble", GRID, ids=GRID_IDS)
def test_pallas_fused_xla_form_bit_identical(fmt_alias, nibble):
    """Off-TPU (no explicit interpret), impl="pallas_fused" lowers to the
    single-pass shift-add XLA form — bit-identical to impl="xla", so the
    serve path can flip impls freely without touching token streams."""
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU lowering under test")
    fmt = resolve_format(fmt_alias)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(6, 384)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(384, 200)) * 0.04, jnp.float32)
    pw, _ = pack_weight(w, fmt, nibble=nibble)
    a = np.asarray(quantized_matmul(x, pw, impl="xla"))
    b = np.asarray(quantized_matmul(x, pw, impl="pallas_fused"))
    np.testing.assert_array_equal(a, b)


def test_pallas_fused_per_channel_sf():
    """Per-channel scales factor out of the kernel and reapply exactly."""
    fmt = resolve_format("elp4")
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)) * np.linspace(0.01, 0.2, 128), jnp.float32)
    pw, _ = pack_weight(w, fmt, granularity="per_channel")
    assert pw.sf.size > 1  # actually per-channel
    want = quantized_matmul(x, pw, impl="xla")
    got = quantized_matmul(x, pw, impl="pallas_fused", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_pallas_fused_rejects_stacked_codes():
    fmt = resolve_format("elp4")
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(size=(2, 128, 64)) * 0.05, jnp.float32)
    pw, _ = pack_weight(w, fmt)
    with pytest.raises(ValueError, match="single \\[K, N\\] weight"):
        quantized_matmul(jnp.zeros((2, 4, 128)), pw, impl="pallas_fused")


# ---------------------------------------------------------------------------
# the TPU tile rule and bf16 operands (pure functions; interpret mode)
# ---------------------------------------------------------------------------
# a decode step's seven packed GEMMs per layer: wq, wk, wv, wo, w1, w3, w2
QWEN3_8B_LAYER = [(4096, 4096), (4096, 1024), (4096, 1024), (4096, 4096),
                  (4096, 12288), (4096, 12288), (12288, 4096)]


@pytest.fixture
def on_tpu(monkeypatch):
    """``select_impl`` as on a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _tpu_rule_tiles(m, k, n, nibble):
    """The rule's tiles for one shape, checked against what ``tpu_blocks``
    promises: the impl M calls for, every tile dividing its dimension (no
    pad of codes or activations on any call), a code panel of at most
    1 MB and an activation block of at most 2 MB (4 bytes an element)."""
    from repro.kernels.ops import select_impl

    impl, (bm, bn, bk), source = select_impl(m, k, n, nibble=nibble, stacked=False)
    assert source == "heuristic"
    assert impl == ("pallas_fused" if m <= MAX_FUSED_M else "pallas")
    assert k % bk == 0 and n % bn == 0 and bn % 128 == 0 and bk % 128 == 0
    assert impl == "pallas_fused" or m % bm == 0
    panel = bk * bn // (2 if nibble else 1)
    assert panel <= (1 << 20) and 4 * bm * bk <= (2 << 20)
    return panel


@pytest.mark.parametrize("nibble", [True, False], ids=["nib", "u8"])
@pytest.mark.parametrize("m", [16, 512])
@pytest.mark.parametrize("k,n", QWEN3_8B_KN)
def test_tpu_rule_tiles_divide_qwen3_8b_shapes(on_tpu, k, n, m, nibble):
    panel = _tpu_rule_tiles(m, k, n, nibble)
    assert panel >= (256 << 10)  # a grid step carries far more than 128³'s 8 KB


@pytest.mark.parametrize("nibble", [True, False], ids=["nib", "u8"])
@pytest.mark.parametrize("m", [32, 1024], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", YI_34B_LOCAL_KN)
def test_tpu_rule_tiles_divide_yi_34b_shard_shapes(on_tpu, k, n, m, nibble):
    """One chip's share of each yi-34b GEMM on a 1x4 model mesh: 32 slots
    decoding, a 1024-token prefill."""
    _tpu_rule_tiles(m, k, n, nibble)


@pytest.mark.parametrize("nibble", [True, False], ids=["nib", "u8"])
def test_tpu_rule_decode_step_grid(nibble):
    """At the cell's M = 16 a layer's seven GEMMs take at most 200 grid
    steps (11,776 at 128³ tiles), each with at least 0.5 MB of codes."""
    from repro.kernels.ops import tpu_blocks

    steps = 0
    for k, n in QWEN3_8B_LAYER:
        impl, (_, bn, bk) = tpu_blocks(16, k, n, nibble=nibble)
        assert impl == "pallas_fused"
        assert bk * bn // (2 if nibble else 1) >= (512 << 10)
        steps += (k // bk) * (n // bn)
    assert steps <= 200


def test_select_impl_off_tpu_keeps_xla_and_default_blocks():
    """Off TPU the rule names XLA and no tiles, so an explicit kernel call
    keeps the kernels' default 128³ tiles."""
    from repro.kernels.ops import select_impl

    for m in (16, 512):
        got = select_impl(m, 4096, 4096, nibble=True, stacked=False)
        assert got == ("xla", None, "heuristic")


@pytest.mark.parametrize("fmt_name", sorted(PRESET_FORMATS))
def test_bf16_operands_only_where_every_level_is_exact(fmt_name):
    import ml_dtypes

    from repro.core.elp_bsd import decode_codes
    from repro.kernels.elp_bsd_matmul import decodes_exactly_to_bf16, mxu_operand_dtype

    fmt = PRESET_FORMATS[fmt_name]
    vals = decode_codes(np.arange(2**fmt.bits_per_weight), fmt)
    exact = bool(np.all(vals.astype(ml_dtypes.bfloat16).astype(np.float64) == vals))
    assert decodes_exactly_to_bf16(fmt) == exact
    assert mxu_operand_dtype(fmt, jnp.bfloat16) == (jnp.bfloat16 if exact else jnp.float32)
    assert mxu_operand_dtype(fmt, jnp.float32) == jnp.float32


def test_bf16_operands_refused_for_an_inexact_format():
    from repro.core.elp_bsd import DigitSpec, ElpBsdFormat
    from repro.kernels.elp_bsd_matmul import decodes_exactly_to_bf16, mxu_operand_dtype

    wide = ElpBsdFormat((DigitSpec((0, 1)), DigitSpec((9,))), name="wide_span")  # 2^9 ± 1
    assert not decodes_exactly_to_bf16(wide)
    assert mxu_operand_dtype(wide, jnp.bfloat16) == jnp.float32


@pytest.mark.parametrize("fmt_alias,nibble", GRID, ids=GRID_IDS)
def test_rule_geometry_matches_ref_in_interpret(fmt_alias, nibble):
    """The kernel at the rule's geometry: a 2048-row K panel decoded in
    four 512-row slabs, bf16 operands, through the ops wrapper's no-pad
    path."""
    from repro.kernels.ops import tpu_blocks

    fmt = resolve_format(fmt_alias)
    m, k, n = 16, 4096, 256
    impl, blocks = tpu_blocks(m, k, n, nibble=nibble)
    blocks = (blocks[0], blocks[1], 2048)  # two K panels at a small N
    assert impl == "pallas_fused" and blocks[1] == 256
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    pw, _ = pack_weight(w, fmt, nibble=nibble)
    got = quantized_matmul(
        x, pw, impl=impl, block_sizes=blocks, out_dtype=jnp.float32, interpret=True
    )
    want = kref.elp_bsd_matmul_ref(x, pw.codes, pw.sf, fmt, nibble=nibble)
    # Each product is exact in both (a bf16 x times a bf16 level), so the
    # two differ only by the order of K float32 additions and the place
    # the scale is applied: at most K·2⁻²⁴ of the sum of |terms| per
    # output (the standard bound for a reordered float32 sum).
    terms = np.abs(np.asarray(x, np.float32)) @ np.abs(np.asarray(kref.dequantize_ref(
        pw.codes, pw.sf, fmt, nibble=nibble)))
    bound = k * 2.0**-24 * terms
    err = np.abs(np.asarray(got) - np.asarray(want))
    assert np.all(err <= bound), float((err / bound).max())
