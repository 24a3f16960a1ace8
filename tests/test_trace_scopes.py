"""Names the program puts into the profiler's trace.

The compiled programs carry each layer's ``jax.named_scope`` in the
``op_name`` metadata of their instructions, which a device trace
attributes its op time by: ``attn`` (with ``attn_core`` inside it),
``mlp``, ``lm_head`` and ``elp_bsd_matmul`` in the engine's decode step;
``conv{i}``/``fc{i}``, ``elp_bsd_conv`` and ``elp_bsd_decode`` in the
CNN forward. ``ServeEngine.step`` opens ``serve.step``, ``serve.admit``
and ``serve.decode`` host spans, and its ``TraceLog`` events keep their
names and fields.
"""
import re

import jax
import numpy as np
import pytest

from repro.api_schemes import pack_cnn_params, pack_lm_params
from repro.configs.base import ArchConfig
from repro.models import cnn, get_model
from repro.obs.trace import TraceLog
from repro.serve import ServeEngine
from repro.serve import engine as engine_mod

CFG = ArchConfig(
    name="scopes-t", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=128, head_dim=16, dtype_str="float32", qk_norm=True,
)


def _op_names(compiled) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _paths_with(names: set[str], *components: str) -> list[str]:
    """The op names holding each of ``components`` as a path component,
    in that order."""
    out = []
    for n in names:
        parts = n.split("/")
        idx = [parts.index(c) if c in parts else -1 for c in components]
        if min(idx) >= 0 and idx == sorted(idx):
            out.append(n)
    return out


@pytest.fixture(scope="module")
def packed_lm():
    params = get_model(CFG).init_params(CFG, jax.random.PRNGKey(0))
    return pack_lm_params(params, CFG, "elp4")


def test_decode_step_names_its_layers(packed_lm):
    eng = ServeEngine(CFG, packed_lm, n_slots=2, max_len=32, mesh=None)
    names = _op_names(eng.compiled_decode())
    core = _paths_with(names, "attn", "attn_core")
    assert core, sorted(names)
    # the cache write and the scores/softmax/PV run inside attn_core
    assert any("dynamic_update_slice" in n or "scatter" in n for n in core), core
    assert any(n.endswith(("dot_general", "exp", "reduce_max")) for n in core), core
    # the projections are outside it, each a packed matmul
    assert _paths_with(names, "attn", "elp_bsd_matmul")
    assert not _paths_with(names, "attn_core", "elp_bsd_matmul")
    assert _paths_with(names, "mlp", "elp_bsd_matmul")
    assert _paths_with(names, "lm_head")
    # the XLA datapath of a packed matmul decodes under elp_bsd_decode
    assert _paths_with(names, "elp_bsd_matmul", "elp_bsd_decode")


def test_cnn_forward_names_its_layers():
    spec = cnn.VGG_MINI
    params = pack_cnn_params(cnn.init_params(spec, jax.random.PRNGKey(1)), "elp_bsd_a4")
    x = jax.ShapeDtypeStruct((2, spec.input_hw, spec.input_hw, spec.input_ch), np.float32)
    names = _op_names(jax.jit(lambda p, x: cnn.forward(p, spec, x)).lower(params, x).compile())
    n_fc = sum(isinstance(l, cnn.Fc) for l in spec.layers)
    n_layers = sum(isinstance(l, (cnn.Conv, cnn.Fc)) for l in spec.layers)
    last_fc = f"fc{n_layers - 1}"
    assert n_fc >= 2 and f"{last_fc}_w" in params
    assert _paths_with(names, "conv0", "elp_bsd_conv", "elp_bsd_decode")
    assert _paths_with(names, "conv0", "elp_bsd_conv")
    assert _paths_with(names, last_fc, "elp_bsd_matmul", "elp_bsd_decode")
    assert not _paths_with(names, last_fc, "elp_bsd_conv")


class _Spy:
    """Stands in for ``obs.trace.span``: records each span opened, with
    its attributes and what was open around it."""

    def __init__(self):
        self.opened: list[tuple[str, dict, tuple[str, ...]]] = []
        self.stack: list[str] = []

    def __call__(self, name, **attrs):
        spy = self

        class _Cm:
            def __enter__(self):
                spy.opened.append((name, attrs, tuple(spy.stack)))
                spy.stack.append(name)

            def __exit__(self, *exc):
                spy.stack.pop()

        return _Cm()


def test_engine_step_opens_its_spans_and_keeps_its_trace_log(monkeypatch, packed_lm):
    spy = _Spy()
    monkeypatch.setattr(engine_mod, "span", spy)
    log = TraceLog()
    eng = ServeEngine(CFG, packed_lm, n_slots=2, max_len=32, mesh=None, trace=log)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, CFG.vocab, n).astype(np.int32), 3) for n in (5, 7, 6)]
    eng.run()
    assert all(len(eng.result(r)) == 3 for r in rids)

    steps = [o for o in spy.opened if o[0] == "serve.step"]
    admits = [o for o in spy.opened if o[0] == "serve.admit"]
    decodes = [o for o in spy.opened if o[0] == "serve.decode"]
    assert len(steps) == eng.steps and all(o[2] == () for o in steps)
    assert sorted(o[1]["rid"] for o in admits) == sorted(rids)
    assert {o[1]["prompt_len"] for o in admits} == {5, 6, 7}
    assert {o[1]["slot"] for o in admits} <= {0, 1}
    assert all(o[2] == ("serve.step",) for o in admits + decodes)
    assert len(decodes) == eng.stats()["decode_steps"]
    assert all(1 <= o[1]["live"] <= 2 for o in decodes)

    fields = {
        "submit": {"t", "event", "rid", "prompt_len", "max_new"},
        "admit": {"t", "event", "rid", "slot", "prompt_len", "prefill_s", "ttft_s"},
        "decode": {"t", "event", "rid", "live", "dt_s"},
        "finish": {"t", "event", "rid", "slot", "tokens", "truncated", "total_s"},
    }
    seen = {}
    for ev in log.events:
        seen.setdefault(ev["event"], set(ev))
    for name, keys in fields.items():
        assert seen[name] == keys, (name, seen[name])
    assert [ev["rid"] for ev in log.events if ev["event"] == "admit"] == [
        o[1]["rid"] for o in admits
    ]
