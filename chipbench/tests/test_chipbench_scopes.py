"""Device time by the program's named scopes, against hand counts.

The recorded trace below is one device and one host thread, in
nanoseconds, with op names as a TPU trace gives them: the HLO
instruction's text without its metadata. The scopes come from the
compiled program's text (``COMPILED``), whose instructions carry
``op_name``.

host:   bench.window [0, 100000); bench.step [10000, 60000) holding
        serve.step [11000, 59000), which holds serve.admit [12000, 30000)
        and serve.decode [31000, 58000); bench.drain [60000, 100000)
device: programs jit_decode_greedy [5000, 45000) and [50000, 90000),
        jit_prefill_slot [91000, 99000);
        in each decode step: the packed GEMM %_quantized_matmul.3 (mlp,
        elp_bsd_matmul) 15000, %fusion.7 (attn/attn_core) 10000,
        %fusion.8 (attn, outside attn_core) 5000, %copy.9 (no scope) 5000;
        in the prefill %fusion.7 (attn/attn_core) 4000

Per decode step: attn_core 10 us, attn 15 us, elp_bsd_matmul 15 us,
mlp 15 us; the prefill's attention does not count in the decode step.
Busy is 2 x 35 + 4 = 74 us of 100; the gaps are [0, 5000) under the
window, [40000, 50000) in bench.step, [85000, 91000) and [95000,
100000) in bench.drain.
"""
import types

import jax
import pytest

from harness import scopes, spec, tracing

MM = (
    '%_quantized_matmul.3 = bf16[128,256]{1,0:T(8,128)(2,1)} custom-call(bf16[128,64]{1,0} '
    '%fusion.2), custom_call_target="tpu_custom_call"'
)
CORE = "%fusion.7 = f32[16,32]{1,0:T(8,128)} fusion(f32[16,64]{1,0} %p.1), kind=kLoop, calls=%fc.7"
ATTN = "%fusion.8 = bf16[16,64]{1,0:T(8,128)(2,1)} fusion(f32[16,32]{1,0} %fusion.7), kind=kLoop"
COPY = "%copy.9 = bf16[16,64]{1,0:T(8,128)(2,1)} copy(bf16[16,64]{1,0} %fusion.8)"
PREFILL_CORE = "%fusion.7 = f32[512,32]{1,0:T(8,128)} fusion(f32[512,64]{1,0} %p.1), kind=kLoop"

COMPILED = """
HloModule jit_decode_greedy, entry_computation_layout={()->()}

%fc.7 (p.1: f32[16,64]) -> f32[16,32] {
  ROOT %exp.1 = f32[16,32]{1,0} exponential(f32[16,32]{1,0} %p.2), metadata={op_name="jit(decode_greedy)/while/body/closed_call/attn/attn_core/exp"}
}

ENTRY %main {
  %_quantized_matmul.3 = bf16[128,256]{1,0:T(8,128)(2,1)} custom-call(bf16[128,64]{1,0} %fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_greedy)/while/body/closed_call/mlp/elp_bsd_matmul/jit(_quantized_matmul)/pallas_call" source_file="layers.py" source_line=9}
  %fusion.7 = f32[16,32]{1,0:T(8,128)} fusion(f32[16,64]{1,0} %p.1), kind=kLoop, calls=%fc.7, metadata={op_name="jit(decode_greedy)/while/body/closed_call/attn/attn_core/exp"}
  %fusion.8 = bf16[16,64]{1,0:T(8,128)(2,1)} fusion(f32[16,32]{1,0} %fusion.7), kind=kLoop, metadata={op_name="jit(decode_greedy)/while/body/closed_call/attn/add"}
  ROOT %copy.9 = bf16[16,64]{1,0:T(8,128)(2,1)} copy(bf16[16,64]{1,0} %fusion.8), metadata={op_name="jit(decode_greedy)/while/body/squeeze"}
}
"""


def _quote(s):
    return s.replace("\\", "\\\\").replace('"', '\\"')


def _trace_text():
    names = {1: MM, 2: CORE, 3: ATTN, 4: COPY, 5: PREFILL_CORE}
    meta = "\n".join(
        f'  event_metadata {{ key: {k} value {{ id: {k} name: "{_quote(v)}" }} }}'
        for k, v in names.items()
    )
    ops = []
    for base in (5000, 50000):
        t = base
        for mid, dur in ((1, 15000), (2, 10000), (3, 5000), (4, 5000)):
            ops.append(f"events {{ metadata_id: {mid} offset_ps: {t * 1000} duration_ps: {dur * 1000} }}")
            t += dur
    ops.append("events { metadata_id: 5 offset_ps: 91000000 duration_ps: 4000000 }")
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 10 offset_ps: 5000000 duration_ps: 40000000 }}
    events {{ metadata_id: 10 offset_ps: 50000000 duration_ps: 40000000 }}
    events {{ metadata_id: 11 offset_ps: 91000000 duration_ps: 8000000 }}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {chr(10).join(ops)}
  }}
{meta}
  event_metadata {{ key: 10 value {{ id: 10 name: "jit_decode_greedy(7)" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_prefill_slot(8)" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }}
    events {{ metadata_id: 2 offset_ps: 10000000 duration_ps: 50000000 }}
    events {{ metadata_id: 3 offset_ps: 11000000 duration_ps: 48000000 }}
    events {{ metadata_id: 4 offset_ps: 12000000 duration_ps: 18000000 }}
    events {{ metadata_id: 5 offset_ps: 31000000 duration_ps: 27000000 }}
    events {{ metadata_id: 6 offset_ps: 60000000 duration_ps: 40000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.step" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "serve.step" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "serve.admit" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "serve.decode" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "bench.drain" }} }}
}}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return tracing.reduce(tracing.events_from_profile(ProfileData.from_text_proto(_trace_text())))


def test_compiled_text_maps_each_instruction_to_its_scopes():
    got = scopes.hlo_scopes(COMPILED)
    assert got["%fusion.7"] == (
        "f32[16,32]{1,0:T(8,128)}",
        ("jit(decode_greedy)", "while", "body", "closed_call", "attn", "attn_core", "exp"),
    )
    assert "elp_bsd_matmul" in got["%_quantized_matmul.3"][1]
    assert got["%copy.9"][1][-1] == "squeeze" and "%exp.1" in got


@pytest.mark.parametrize(
    "scope,per_step_us",
    [("attn_core", 10), ("attn", 15), ("elp_bsd_matmul", 15), ("mlp", 15), ("lm_head", 0),
     ("attn_co", 0)],
)
def test_scope_time_in_the_decode_step(summary, scope, per_step_us):
    runs, _ = summary.module_time("decode_greedy")
    assert runs == 2
    t = scopes.scope_time_in(summary, "decode_greedy", scope, scopes.hlo_scopes(COMPILED))
    assert t == pytest.approx(2 * per_step_us * 1e-6)


def test_a_compiled_text_that_is_not_the_traced_program_is_refused(summary):
    sc = scopes.hlo_scopes(COMPILED)
    assert scopes.scope_time_in(summary, "decode_greedy", "attn", {**sc, "%copy.9": ("f32[1]", ())}) is None
    del sc["%fusion.8"]
    assert scopes.scope_time_in(summary, "decode_greedy", "attn", sc) is None


def test_the_host_spans_of_the_engine_leave_the_reduction_as_it_was(summary):
    assert summary.window_s == pytest.approx(100e-6)
    assert summary.busy_s == pytest.approx(74e-6)
    # the reduction names a gap by the innermost bench.* span alone: the
    # gap [40000, 50000) inside serve.decode is named bench.step
    assert sorted(summary.gaps, key=lambda g: (-g[1], g[0])) == [
        ("bench.step", pytest.approx(10e-6)),
        ("bench.drain", pytest.approx(6e-6)),
        ("bench.drain", pytest.approx(5e-6)),
        ("bench.window", pytest.approx(5e-6)),
    ]


@pytest.mark.parametrize(
    "reader,ms", [("attention_ms.lm_offline", 0.010), ("packed_gemm_ms.lm_offline", 0.015)]
)
def test_readers_on_the_recorded_trace(monkeypatch, summary, reader, ms):
    monkeypatch.setattr(scopes, "compiled_scopes", lambda *a: scopes.hlo_scopes(COMPILED))
    run = types.SimpleNamespace(trace=summary, config={"driver": "lm_serve"}, cell={})
    assert spec.load_reader(reader).read(run) == pytest.approx(ms)
    assert spec.load_reader(reader).read(types.SimpleNamespace(trace=None)) is None


def test_readers_read_nothing_from_a_program_without_scopes(monkeypatch, summary):
    bare = scopes.hlo_scopes(COMPILED.replace("/attn_core", "").replace("/elp_bsd_matmul", ""))
    monkeypatch.setattr(scopes, "compiled_scopes", lambda *a: bare)
    run = types.SimpleNamespace(trace=summary, config={"driver": "lm_serve"}, cell={})
    assert spec.load_reader("attention_ms.lm_offline").read(run) is None
    assert spec.load_reader("packed_gemm_ms.lm_offline").read(run) is None


# The program itself, compiled here at the tiny sizes the check tests use.
@pytest.fixture
def tiny_arch(monkeypatch):
    from harness import lm_serve

    arch = lm_serve.program_arch
    monkeypatch.setattr(lm_serve, "program_arch", lambda name: arch(name).reduced())


def _tiny(name):
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", name)) as f:
        return json.load(f)


def _fake_trace(sc, module, scope_times):
    """A reduced trace of one run of ``module`` whose leaf ops are every
    instruction of ``sc``, 1 us each, named as a TPU trace names them."""
    ops = {f"{name} = {typ} op()": 1e-6 for name, (typ, _) in sc.items()}
    expect = {s: sum(1e-6 for _, path in sc.values() if s in path) for s in scope_times}
    summary = tracing.Summary(
        window_s=1.0, busy_s=1.0, devices=1, op_s=ops, gaps=[],
        module_runs={f"{module}(1)": [1.0]}, ops_in_modules={f"{module}(1)": ops},
    )
    return summary, expect


def test_the_decode_step_of_the_program_reads_its_scopes(tiny_arch):
    lm = _tiny("tiny-lm.json")
    cell = {"n_slots": 4, "max_len": 64}
    fn, args = scopes.lm_decode_program(lm, cell)
    sc = scopes.hlo_scopes(fn.lower(*args).compile().as_text())
    summary, expect = _fake_trace(sc, "jit_decode_greedy", ("attn_core", "elp_bsd_matmul"))
    assert expect["attn_core"] > 0 and expect["elp_bsd_matmul"] > 0
    run = types.SimpleNamespace(trace=summary, config=lm, cell=cell)
    scopes.compiled_scopes.cache_clear()
    assert spec.load_reader("attention_ms.lm_offline").read(run) == pytest.approx(
        1e3 * expect["attn_core"]
    )
    assert spec.load_reader("packed_gemm_ms.lm_offline").read(run) == pytest.approx(
        1e3 * expect["elp_bsd_matmul"]
    )
    assert jax.config.jax_enable_compilation_cache  # restored after the compile


def test_the_cnn_forward_of_the_program_reads_its_decode():
    cnn = _tiny("tiny-cnn.json")
    cell = {"batch": 4}
    fn, args = scopes.cnn_forward_program(cnn, cell)
    sc = scopes.hlo_scopes(fn.lower(*args).compile().as_text())
    assert any("conv0" in path and "elp_bsd_conv" in path for _, path in sc.values())
    summary, expect = _fake_trace(sc, "jit_cnn_forward", ("elp_bsd_decode",))
    assert expect["elp_bsd_decode"] > 0
    run = types.SimpleNamespace(trace=summary, config=cnn, cell=cell)
    scopes.compiled_scopes.cache_clear()
    assert spec.load_reader("weight_decode_ms.cnn").read(run) == pytest.approx(
        1e3 * expect["elp_bsd_decode"]
    )
