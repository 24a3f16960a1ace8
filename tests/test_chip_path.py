"""The path ``chip_smoke.py`` drives, rehearsed on the CPU.

Covers what bring-up on one chip changed: the int32 nibble unpack, the
slice-by-slice stack conversion, the leaf-by-leaf packed initializer,
the mesh-driven kernel dispatch, ``force_impl``, the compile-cache
directory, and ``chip_smoke``'s phases at reduced widths with the
Pallas kernels in interpret mode.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.elp_bsd import PRESET_FORMATS
from repro.core.quantize import second_neighbor_idx
from repro.kernels import ref as kref
from repro.kernels.ops import force_impl, pack_weight, quantized_matmul, select_impl
from repro.models import cnn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LM_CFG = dataclasses.replace(get_config("qwen3_8b").reduced(), dtype_str="bfloat16")
TINY_CNN = cnn.CnnSpec("tiny_vgg", (cnn.Conv(8, 3), cnn.Pool(), cnn.Conv(16, 3), cnn.Fc(10)), 16)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(body: str, *, devices: int = 1, timeout: int = 400) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, env=env, timeout=timeout, cwd=REPO,
    )


def test_unpack_nibbles_int32_matches_uint8_unpack():
    """Widening to int32 before the mask and shift keeps every value."""
    packed = jnp.asarray(np.arange(256, dtype=np.uint8).reshape(8, 32))
    old = jnp.stack(
        [(packed & 0x0F).astype(jnp.int32), ((packed >> 4) & 0x0F).astype(jnp.int32)], axis=-2
    ).reshape(16, 32)
    got = kref.unpack_nibbles_k(packed)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))


@pytest.mark.parametrize("fmt_name", ["elp_bsd_a4", "elp_bsd_c6", "elp_bsd_b7"])
@pytest.mark.parametrize("shape", [(3, 64, 96), (2, 3, 32, 48), (4, 33, 16)])
def test_quantize_stacked_slicewise_equals_whole_stack(shape, fmt_name):
    from repro.runtime.quantized_params import quantize_stacked

    w = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
    got = quantize_stacked(w, PRESET_FORMATS[fmt_name])
    want, _ = pack_weight(w.astype(jnp.float32), PRESET_FORMATS[fmt_name], granularity="per_slice")
    assert (got.fmt_name, got.nibble, got.shape) == (want.fmt_name, want.nibble, want.shape)
    np.testing.assert_array_equal(np.asarray(got.codes), np.asarray(want.codes))
    np.testing.assert_array_equal(np.asarray(got.sf), np.asarray(want.sf))


def _compensate_one_group_by_rank(w, nn_idx, levels_j):
    """Algorithm 1 as first written: argsort, permutation gathers, and a
    scatter that inverts the order into ranks."""
    n = w.shape[0]
    q = levels_j[nn_idx]
    mean_err = jnp.mean(q - w)
    flip_idx = second_neighbor_idx(w, levels_j, nn_idx)
    q_flip = levels_j[flip_idx]
    delta = q_flip - q
    candidate = (jnp.sign(delta) == -jnp.sign(mean_err)) & (delta != 0.0)
    cost = jnp.where(candidate, jnp.abs(w - q_flip), jnp.inf)
    order = jnp.argsort(cost)
    delta_sorted = jnp.where(candidate[order], delta[order], 0.0)
    traj = jnp.abs(jnp.concatenate([mean_err[None], mean_err + jnp.cumsum(delta_sorted) / n]))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    return jnp.where(candidate & (rank < jnp.argmin(traj)), flip_idx, nn_idx)


@pytest.mark.parametrize("fmt_name", ["elp_bsd_a4", "elp_bsd_c6", "elp_bsd_b7"])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "tied"])
def test_compensation_sort_equals_rank_scatter(fmt_name, ties):
    """The gather- and scatter-free Algorithm 1 picks the same flips,
    including among equal costs (``tied`` repeats a few raw values)."""
    from repro.core.compensate import compensate_groups
    from repro.core.quantize import nn_quantize_idx

    levels = PRESET_FORMATS[fmt_name].levels()
    lv = jnp.asarray(levels, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(7), (64, 301)) * float(np.max(levels)) / 3
    if ties:
        w = jnp.round(w * 2) / 2
    idx = nn_quantize_idx(w, levels)
    want = jax.vmap(_compensate_one_group_by_rank, in_axes=(0, 0, None))(w, idx, lv)
    got = compensate_groups(w, idx, levels)
    assert int(jnp.sum(want != idx)) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_init_packed_lm_params_equals_pack_of_init():
    from repro.api_schemes import init_packed_lm_params, pack_lm_params
    from repro.models import get_model

    key = jax.random.PRNGKey(3)
    got = init_packed_lm_params(LM_CFG, key, "elp4")
    want = pack_lm_params(get_model(LM_CFG).init_params(LM_CFG, key), LM_CFG, "elp4")
    g, gdef = jax.tree.flatten(got)
    w, wdef = jax.tree.flatten(want)
    assert gdef == wdef
    for a, b in zip(g, w):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_select_impl_structural_cases():
    """Only stacked weights are structural: a weight on a mesh is
    resolved at its per-device shape like any one-device weight."""
    kw = dict(nibble=True)
    assert select_impl(8, 64, 64, stacked=True, **kw)[::2] == ("xla", "structural")
    assert select_impl(8, 64, 64, stacked=False, **kw)[2] != "structural"
    assert select_impl(8, 64, 16, stacked=False, **kw)[2] != "structural"


def test_force_impl_pins_the_traced_impl():
    pw, _ = pack_weight(jax.random.normal(jax.random.PRNGKey(0), (96, 80)), "elp_bsd_a4")
    x = jnp.ones((8, 96), jnp.float32)

    def traced(impl_ctx):
        with force_impl(impl_ctx):
            return str(jax.make_jaxpr(lambda a: quantized_matmul(a, pw, impl="auto"))(x))

    assert "pallas_call" in traced("pallas")
    assert "pallas_call" not in traced("xla")
    with force_impl("pallas"):
        got = quantized_matmul(x, pw, impl="auto")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(quantized_matmul(x, pw, impl="xla")), rtol=1e-5, atol=1e-5
    )


def test_dispatch_follows_the_weights_mesh_not_the_device_count():
    """On a 4-device host a one-device engine keeps the per-weight
    choice; an engine whose mesh spans the devices runs every packed
    GEMM per shard: column-parallel weights at N / 4, row-parallel ones
    at K / 4."""
    out = _run(
        """
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.api_schemes import init_packed_lm_params
        from repro.configs import get_config
        from repro.kernels.ops import weight_mesh
        from repro.runtime.elastic import make_mesh
        from repro.serve import ServeEngine

        assert jax.device_count() == 4
        mesh = make_mesh()
        a = jax.device_put(np.ones((8, 16), np.float32), NamedSharding(mesh, P()))
        b = jax.device_put(np.ones((8, 16), np.float32), jax.devices()[2])
        seen = []
        jax.jit(lambda v: seen.append(weight_mesh(v)) or v)(a)
        jax.jit(lambda v: seen.append(weight_mesh(v)) or v)(b)
        assert seen[0].size == 4 and seen[1] is None
        cfg = get_config("qwen3_8b").reduced()
        params = init_packed_lm_params(cfg, jax.random.PRNGKey(0), "elp4")
        one = ServeEngine(cfg, params, n_slots=2, max_len=16, mesh=None)
        many = ServeEngine(cfg, params, n_slots=2, max_len=16, mesh=mesh)
        assert {d["source"] for d in one.kernel_dispatch().values()} == {"heuristic"}
        disp = many.kernel_dispatch()
        assert {d["source"] for d in disp.values()} == {"sharded"}
        for key, d in disp.items():
            m, k, n = map(int, key.split("|")[0].split("x"))
            want = (m, k, n // 4) if d["split"] == "col" else (m, k // 4, n)
            assert d["split"] in ("col", "row") and d["local"] == "x".join(map(str, want)), d
        assert {d["split"] for d in disp.values()} == {"col", "row"}
        assert many.params["blocks"]["wo"].split == ("model", None)
        assert many.params["blocks"]["w1"].split == (None, "model")
        print("OK")
        """,
        devices=4,
    )
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]


def test_compile_cache_dir(monkeypatch):
    from repro.runtime import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        assert compile_cache.enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_devices_phase_refuses_cpu(chip_smoke):
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.phase_devices(1)


def test_chip_smoke_exits_nonzero_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_cnn_phase_rehearsal(chip_smoke):
    res = chip_smoke.phase_cnn(TINY_CNN, seed=0, batch=4)
    assert set(res) == {"default", "pallas"}
    assert all(r["rel_err"] <= chip_smoke.CNN_TOL for r in res.values())


def test_chip_smoke_lm_phase_rehearsal(chip_smoke, monkeypatch):
    """Reduced qwen3-8b in bf16 through the Pallas kernels (interpret
    mode), checked against the XLA datapath as on the chip. Interpret
    mode emits no ``tpu_custom_call`` and the CPU heuristic reports XLA,
    so the phase's kernel check must refuse what it sees here."""
    seen = []
    monkeypatch.setattr(chip_smoke, "check_kernels", lambda *a: seen.append(a))
    with force_impl("pallas"):
        res = chip_smoke.phase_lm(
            LM_CFG, seed=0, n_slots=4, prompt_lens=(16, 40, 24, 16), max_new=4, check_len=16
        )
    assert max(res["errs"].values()) <= chip_smoke.LM_TOL
    [(tag, dispatch, hlo)] = seen
    assert dispatch == res["dispatch"] and dispatch
    monkeypatch.undo()
    with pytest.raises(RuntimeError, match="not on a Pallas kernel"):
        chip_smoke.check_kernels(tag, dispatch, hlo)


@pytest.mark.parametrize(
    "impl, hlo, error",
    [
        ("pallas_fused", "custom-call(...), custom_call_target=\"tpu_custom_call\"", None),
        ("pallas", "fusion(...)", "no tpu_custom_call"),
        ("xla", "custom_call_target=\"tpu_custom_call\"", "not on a Pallas kernel"),
    ],
)
def test_chip_smoke_kernel_check(chip_smoke, impl, hlo, error):
    dispatch = {"8x64x64|elp_bsd_a4|nib": {"impl": impl, "source": "heuristic", "count": 1}}
    if error is None:
        chip_smoke.check_kernels("lm", dispatch, hlo)
    else:
        with pytest.raises(RuntimeError, match=error):
            chip_smoke.check_kernels("lm", dispatch, hlo)
    with pytest.raises(RuntimeError, match="no packed GEMM"):
        chip_smoke.check_kernels("lm", {}, hlo)


def test_chip_smoke_structural_check(chip_smoke):
    """The four-chip phase's check: every GEMM on a Pallas kernel per
    shard, and the compiled decode step calls one."""
    ok = {"8x64x64|elp_bsd_a4|nib": {"impl": "pallas_fused", "source": "sharded", "count": 1}}
    chip_smoke.check_sharded("lm_sharded", ok, "custom_call_target=\"tpu_custom_call\"")
    with pytest.raises(RuntimeError, match="no tpu_custom_call"):
        chip_smoke.check_sharded("lm_sharded", ok, "fusion(...)")
    xla = {k: dict(d, impl="xla") for k, d in ok.items()}
    with pytest.raises(RuntimeError, match="not on a Pallas kernel"):
        chip_smoke.check_sharded("lm_sharded", xla, "custom_call_target=\"tpu_custom_call\"")
    whole = {k: dict(d, source="heuristic") for k, d in ok.items()}
    with pytest.raises(RuntimeError, match="not run per shard"):
        chip_smoke.check_sharded("lm_sharded", whole, "custom_call_target=\"tpu_custom_call\"")


def test_chip_smoke_sharded_phase_rehearsal():
    """Reduced qwen3-8b on a 4-device mesh through the Pallas kernels
    per shard (interpret mode) against the one-device engine. Interpret
    mode emits no ``tpu_custom_call`` and the CPU heuristic reports XLA,
    so the phase's check must refuse what it sees here."""
    out = _run(
        """
        import dataclasses, importlib.util
        from repro.configs import get_config
        from repro.kernels.ops import force_impl
        spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        check, seen = cs.check_sharded, []
        cs.check_sharded = lambda *a: seen.append(a)
        cfg = dataclasses.replace(get_config("qwen3_8b").reduced(), dtype_str="bfloat16")
        with force_impl("pallas"):
            res = cs.phase_lm_sharded(cfg, seed=0, n_slots=4, prompt_lens=(16, 40, 24, 16),
                                      max_new=4, check_len=16, chips=4)
        assert max(res["errs"].values()) <= cs.LM_TOL
        [(tag, dispatch, hlo)] = seen
        assert {d["source"] for d in dispatch.values()} == {"sharded"}
        try:
            check(tag, dispatch, hlo)
        except RuntimeError as e:
            assert "not on a Pallas kernel" in str(e), e
        else:
            raise AssertionError("the CPU's XLA dispatch passed the kernel check")
        print("OK", res["token_match"])
        """,
        devices=4,
    )
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]
