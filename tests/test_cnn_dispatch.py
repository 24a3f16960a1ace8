"""How a packed CNN's forward dispatches its layers by default.

``models/cnn.forward`` defaults to ``impl="auto"``: each packed layer is
resolved by its op's own rule. On a TPU the fc layers take the fused
decode kernel at the tiles ``kernels/ops.select_impl`` derives from the
shape, and the convs keep XLA's conv on decoded codes. These tests take
the TPU branch on the CPU (the backend check patched, the kernel in
interpret mode) and hold the logits to the XLA datapath's.
"""
import inspect
import re

import jax
import numpy as np
import pytest

from repro import api
from repro.api_schemes import CnnAdapter, pack_cnn_params
from repro.kernels import ops
from repro.models import cnn

SPEC = cnn.VGG_MINI
N_CONV = sum(isinstance(l, cnn.Conv) for l in SPEC.layers)


@pytest.fixture(scope="module")
def params():
    return cnn.init_params(SPEC, jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def packed(params):
    return pack_cnn_params(params, "elp_bsd_a4")


@pytest.fixture
def on_tpu(monkeypatch):
    """The backend checks read a TPU. The packed matmul's jit cache is
    emptied around the test, so that it is traced as on a TPU and a later
    test does not meet that trace."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops._quantized_matmul.clear_cache()
    yield
    ops._quantized_matmul.clear_cache()


def _images(batch: int):
    return jax.random.normal(
        jax.random.PRNGKey(batch), (batch, SPEC.input_hw, SPEC.input_hw, SPEC.input_ch)
    )


def _fc_shapes(packed) -> list[tuple[int, int]]:
    return [w.shape for name, w in packed.items() if name.startswith("fc") and name.endswith("_w")]


def test_default_sends_every_fc_layer_through_the_fused_kernel(on_tpu, packed, monkeypatch):
    seen = []
    rule = ops.select_impl

    def spy(m, k, n, **kw):
        out = rule(m, k, n, **kw)
        seen.append(((k, n), out[0], out[1]))
        return out

    monkeypatch.setattr(ops, "select_impl", spy)
    cnn.forward(packed, SPEC, _images(1), interpret=True)
    assert sorted(s for s, _, _ in seen) == sorted(_fc_shapes(packed))
    for (k, n), impl, blocks in seen:
        assert impl == "pallas_fused", (k, n)
        assert blocks == ops.tpu_blocks(1, k, n, nibble=True)[1], (k, n)


def test_default_keeps_convs_on_xla_and_decodes_no_fc_outside_the_kernel(on_tpu, packed):
    compiled = (
        jax.jit(lambda p, x: cnn.forward(p, SPEC, x, interpret=True))
        .lower(packed, _images(1))
        .compile()
        .as_text()
    )
    assert len(re.findall(r"= \S+ convolution\(", compiled)) == N_CONV
    names = set(re.findall(r'op_name="([^"]*)"', compiled))
    decode = [n for n in names if "elp_bsd_decode" in n.split("/")]
    assert any(re.search(r"\bconv\d+/elp_bsd_conv/", n) for n in decode), sorted(names)
    assert not [n for n in decode if re.search(r"\bfc\d+/", n)]


@pytest.mark.parametrize("batch", [1, 4])
def test_default_matches_xla_logits(on_tpu, packed, batch):
    """VGG_MINI's fc layers are 2048x128 and 128x10; the last one's N
    pads to a 128 tile inside the kernel's wrapper."""
    x = _images(batch)
    want = np.asarray(cnn.forward(packed, SPEC, x, impl="xla"))
    got = np.asarray(cnn.forward(packed, SPEC, x, interpret=True))
    assert got.shape == (batch, SPEC.layers[-1].out)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) <= 1e-5


def test_quantized_model_forward_takes_the_cnn_default(params, monkeypatch):
    default = inspect.signature(cnn.forward).parameters["impl"].default
    assert inspect.signature(CnnAdapter.forward).parameters["impl"].default == default
    qm = api.quantize(SPEC, params, api.QuantScheme(fmt="elp_bsd_a4"))
    seen = []
    forward = cnn.forward

    def spy(*args, impl, **kw):
        seen.append(impl)
        return forward(*args, impl=impl, **kw)

    monkeypatch.setattr(cnn, "forward", spy)
    qm.forward(_images(1))
    qm.forward(_images(1), impl="xla")
    assert seen == [default, "xla"]
