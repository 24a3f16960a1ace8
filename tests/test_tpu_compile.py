"""Compile the Pallas kernels for a described TPU v5e at real widths.

Interpret mode cannot show what the chip's compiler refuses (8-bit
vector shifts, unaligned code tiles, VMEM limits). These tests hand the
kernels to the TPU compiler for a v5e that is described, not attached:
qwen3-8b's four GEMM shapes at decode (M = 8 slots) and prefill
(M = 512 tokens), in nibble (elp_bsd_a4) and u8 (elp_bsd_c6) codes, the
same shapes through ``impl="auto"`` as a TPU resolves them from the
shape (M = 16 and 512, the tile rule's blocks), one
VGG-16 im2col conv through the ops wrapper, VGG-16's fc layers through
``impl="auto"`` at batch 1 and 64, and yi-34b's GEMMs as one
chip of a 1x4 model mesh computes them: each local shape alone, and the
per-shard path whole on four described chips; and the decode attention
over the dense bf16 cache at both LM cells' shapes, where GQA must read
the cache as stored. Nothing runs, so these say nothing about results
or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import dataclasses
import math
import re

import pytest

import jax
import jax.numpy as jnp

from repro.core.elp_bsd import PRESET_FORMATS
from repro.kernels.elp_bsd_matmul import elp_bsd_matmul
from repro.kernels.fused_decode import fused_decode_matmul
from repro.kernels.ops import PackedWeight, quantized_matmul, tpu_blocks

from lm_shapes import QWEN3_8B_KN, YI_34B_LOCAL_KN

LAYOUTS = [("elp_bsd_a4", True), ("elp_bsd_c6", False)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The program's backend checks read a TPU. A CPU process otherwise
    takes its CPU branches, and the compile for the described chip would
    not see the kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_args(sharding, m, k, n, nibble, x_dtype=jnp.bfloat16):
    return (
        jax.ShapeDtypeStruct((m, k), x_dtype, sharding=sharding),
        jax.ShapeDtypeStruct((k // 2 if nibble else k, n), jnp.uint8, sharding=sharding),
        jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=sharding),
    )


@pytest.mark.parametrize("fmt_name,nibble", LAYOUTS)
@pytest.mark.parametrize("k,n", QWEN3_8B_KN)
def test_fused_decode_compiles_at_qwen3_8b_decode(one_chip, k, n, fmt_name, nibble):
    fmt = PRESET_FORMATS[fmt_name]
    text = _compiled_text(
        lambda x, c, s: fused_decode_matmul(x, c, s, fmt, nibble=nibble, interpret=False),
        *_kernel_args(one_chip, 8, k, n, nibble),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("fmt_name,nibble", LAYOUTS)
@pytest.mark.parametrize("k,n", QWEN3_8B_KN)
def test_tiled_kernel_compiles_at_qwen3_8b(one_chip, k, n, fmt_name, nibble, m):
    fmt = PRESET_FORMATS[fmt_name]
    block_m = min(m, 128)  # the decode strip rides as one (8, block_k) x block
    text = _compiled_text(
        lambda x, c, s: elp_bsd_matmul(
            x, c, s, fmt, nibble=nibble, block_m=block_m, interpret=False
        ),
        *_kernel_args(one_chip, m, k, n, nibble),
    )
    assert "tpu_custom_call" in text


def test_vgg16_im2col_conv_compiles(one_chip):
    """VGG-16 conv1_2 at 224x224, batch 1: patches [50176, 3*3*64]
    against nibble codes for [576, 64], padded by the ops wrapper."""
    k, n = 3 * 3 * 64, 64
    pw = PackedWeight(
        codes=jax.ShapeDtypeStruct((k // 2, n), jnp.uint8, sharding=one_chip),
        sf=jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=one_chip),
        fmt_name="elp_bsd_a4",
        nibble=True,
        shape=(k, n),
        source_shape=(3, 3, 64, n),
    )
    x = jax.ShapeDtypeStruct((224 * 224, k), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x, pw: quantized_matmul(x, pw, impl="pallas", interpret=False), x, pw
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [16, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("fmt_name,nibble", LAYOUTS)
@pytest.mark.parametrize("k,n", QWEN3_8B_KN)
def test_auto_dispatch_compiles_at_qwen3_8b(one_chip, on_tpu, k, n, fmt_name, nibble, m):
    """``impl="auto"`` on a TPU, as the serve step calls it:
    the tile rule's blocks fit the v5e's VMEM, the GEMM is one kernel
    named for ``_quantized_matmul``, and nothing pads or copies the codes."""
    pw = PackedWeight(
        codes=jax.ShapeDtypeStruct((k // 2 if nibble else k, n), jnp.uint8, sharding=one_chip),
        sf=jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=one_chip),
        fmt_name=fmt_name,
        nibble=nibble,
        shape=(k, n),
    )
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda x, pw: quantized_matmul(x, pw, impl="auto", out_dtype=x.dtype),
        x,
        pw,
    )
    assert re.search(r"%_quantized_matmul\S* = \S+ custom-call\(.*tpu_custom_call", text)
    # No op pads or copies the codes in HBM. XLA may stage them into VMEM
    # (memory space S(1)) ahead of the kernel, which then reads them there.
    made = re.findall(r"^\s*(?:ROOT )?%\S+ = (u8\[\S+) ([\w-]+)\(", text, re.M)
    assert all(op == "parameter" or (op != "pad" and "S(1)" in t) for t, op in made), made


def test_kernel_dispatch_reports_tiles_and_operand_dtype(on_tpu):
    """The engine reports each decode GEMM's impl, tiles and operand
    dtype as a TPU resolves them: the fused kernel at the rule's tiles,
    bf16 operands for elp4 codes under bf16 activations."""
    from repro.api_schemes import init_packed_lm_params
    from repro.configs import get_config
    from repro.serve import ServeEngine

    cfg = dataclasses.replace(get_config("qwen3_8b").reduced(), dtype_str="bfloat16")
    params = init_packed_lm_params(cfg, jax.random.PRNGKey(0), "elp4")
    engine = ServeEngine(cfg, params, n_slots=16, max_len=16, mesh=None)
    dispatch = engine.kernel_dispatch()
    assert dispatch
    for key, d in dispatch.items():
        m, k, n = map(int, key.split("|")[0].split("x"))
        assert d["impl"] == "pallas_fused" and d["source"] == "heuristic", key
        assert d["blocks"] == tpu_blocks(m, k, n, nibble=key.endswith("|nib"))[1], key
        assert d["operand"] == "bfloat16", key


# VGG-16's fc6, fc7 and fc8
VGG16_FC_KN = [(25088, 4096), (4096, 4096), (4096, 1000)]


@pytest.mark.parametrize("m", [1, 64])
@pytest.mark.parametrize("k,n", VGG16_FC_KN)
def test_auto_dispatch_compiles_at_vgg16_fc(one_chip, on_tpu, k, n, m):
    """VGG-16's fc layers as ``models/cnn.forward`` calls them on a TPU:
    float32 activations at batch 1 and 64 through ``impl="auto"``. Each
    is one kernel, and no float array the size of the weight is made
    (XLA's decode wrote fc6 out as float32 [25088, 4096] and relaid it)."""
    pw = PackedWeight(
        codes=jax.ShapeDtypeStruct((k // 2, n), jnp.uint8, sharding=one_chip),
        sf=jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=one_chip),
        fmt_name="elp_bsd_a4",
        nibble=True,
        shape=(k, n),
    )
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x, pw: quantized_matmul(x, pw, impl="auto", out_dtype=jnp.float32), x, pw
    )
    kernels = re.findall(r"%_quantized_matmul\S* = \S+ custom-call\(.*tpu_custom_call", text)
    assert len(kernels) == 1, kernels
    big = _tensors_of_at_least(text, k * n)
    assert not [t for t in big if t[0].startswith(("f", "bf"))], big


@pytest.mark.parametrize("m", [32, 1024], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", YI_34B_LOCAL_KN)
def test_auto_dispatch_compiles_at_yi_34b_shard(one_chip, on_tpu, k, n, m):
    """One chip's share of each yi-34b GEMM on a 1x4 model mesh, through
    ``impl="auto"`` as a TPU resolves the local shape: 32 slots decoding,
    a 1024-token prefill."""
    pw = PackedWeight(
        codes=jax.ShapeDtypeStruct((k // 2, n), jnp.uint8, sharding=one_chip),
        sf=jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=one_chip),
        fmt_name="elp_bsd_a4",
        nibble=True,
        shape=(k, n),
    )
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda x, pw: quantized_matmul(x, pw, impl="auto", out_dtype=x.dtype),
        x,
        pw,
    )
    assert re.search(r"%_quantized_matmul\S* = \S+ custom-call\(.*tpu_custom_call", text)


@pytest.mark.parametrize("name,k,n", [("wq", 7168, 7168), ("wo", 7168, 7168)])
def test_sharded_gemm_compiles_on_four_chips(topo, on_tpu, name, k, n):
    """The per-shard path on a described 1x4 mesh: a column-parallel
    weight runs the kernel on each chip's columns; a row-parallel one on
    each chip's rows, then one all-reduce of the partial sums."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.runtime import sharding as shr

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    apw = PackedWeight(
        codes=jax.ShapeDtypeStruct((k // 2, n), jnp.uint8),
        sf=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        fmt_name="elp_bsd_a4",
        nibble=True,
        shape=(k, n),
    )
    specs = shr.param_specs({name: apw}, mesh)
    placed = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
        {name: apw}, specs,
    )
    pw = shr.mark_splits(placed, specs, mesh)[name]
    x = jax.ShapeDtypeStruct((32, k), jnp.bfloat16, sharding=NamedSharding(mesh, P()))
    text = _compiled_text(
        lambda x, pw: quantized_matmul(x, pw, impl="auto", out_dtype=x.dtype),
        x,
        pw,
    )
    assert "tpu_custom_call" in text
    reduces = re.findall(r"= \S+ all-reduce(?:-start)?\(", text)
    assert len(reduces) == (1 if name == "wo" else 0), reduces


# the LM cells' decode attention: slots, max_len, query heads, KV heads,
# and the mesh's model-axis width (yi-34b: 14 query, 2 KV heads a chip)
ATTEND_DECODE = {"qwen3-8b": (16, 1024, 32, 8, 1), "yi-34b": (32, 1280, 56, 8, 4)}


def _tensors_of_at_least(text: str, n: int) -> set[tuple[str, str]]:
    """``(dtype[shape], opcode)`` of every value of ``n`` or more
    elements in compiled HLO text, fused computations included."""
    found = set()
    for line in text.splitlines():
        op = re.search(r"= \S+ ([\w-]+)\(", line)
        for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]+)\]", line):
            if math.prod(int(d) for d in dims.split(",")) >= n:
                found.add((f"{dt}[{dims}]", op.group(1) if op else None))
    return found


@pytest.mark.parametrize("model,bound_gb", [("qwen3-8b", 0.25), ("yi-34b", 0.16)])
def test_gqa_decode_attention_reads_the_cache_as_stored(topo, model, bound_gb):
    """``_attend``'s decode call (write this step's k/v, attend at per-row
    depths) over the dense bf16 cache: qwen3-8b on one chip, yi-34b as
    each chip of a 1x4 model mesh runs it, heads on ``model``. No f32
    value the size of a chip's cache or larger is made, nothing that
    size is broadcast, and no collective is added. The attention over the
    written cache reads under ``bound_gb`` per layer per chip by the
    compiler's count (repeating K and V to every head in f32 read 1.28 GB
    and 1.46 GB)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.models import transformer as T
    from repro.models.layers import attention_dot

    b, s, h, kv, tp = ATTEND_DECODE[model]
    mesh = Mesh(np.asarray(topo.devices[:tp]).reshape(1, tp), ("data", "model"))
    heads = NamedSharding(mesh, P(None, None, "model", None))
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((b, 1, h, 128), bf16, sharding=heads)
    new = jax.ShapeDtypeStruct((b, 1, kv, 128), bf16, sharding=heads)
    cache = jax.ShapeDtypeStruct((b, s, kv, 128), bf16, sharding=heads)
    pos = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=NamedSharding(mesh, P()))

    def attend(q, k, v, ck, cv, pos):
        return T._attend(
            q, k, v, bf16, causal=True, window=0, kv_cache=(ck, cv), kv_layout="dense",
            cache_pos=pos, pctx=None,
        )

    text = _compiled_text(attend, q, new, new, cache, cache, pos)
    big = _tensors_of_at_least(text, b * s * (kv // tp) * 128)
    assert not [t for t in big if t[0].startswith("f32") or t[1] == "broadcast"], big
    assert not re.search(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all", text)

    cost = (
        jax.jit(lambda q, ck, cv, pos: attention_dot(q, ck, cv, q_offset=pos))
        .lower(q, cache, cache, pos)
        .compile()
        .cost_analysis()
    )
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < bound_gb * 1e9, cost["bytes accessed"]
