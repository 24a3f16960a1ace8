"""Continuous-batching serve engine (DESIGN.md §9).

Covers: the slot scheduler's invariants, token parity of continuous
batching against per-request static generation (staggered mixed-length
traces), slot reuse / eviction hygiene, packed-weight serving (the
decode step consumes uint8 codes, not a dequantized tree), the
deprecation wrappers in ``runtime/serve_loop``, elastic mesh selection
+ resharding, and the straggler monitor wiring. Multi-device parity
runs in a subprocess on a fake 4-device CPU mesh.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.serve import (
    Request,
    ServeEngine,
    ServeSetup,
    SlotScheduler,
    build_serve_fns,
    static_generate,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = ArchConfig(
    name="engine-t", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=128, head_dim=16, dtype_str="float32",
)


@pytest.fixture(scope="module")
def params():
    from repro.models import get_model

    return get_model(CFG).init_params(CFG, jax.random.PRNGKey(0))


def _prompts(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab, size=s).astype(np.int32) for s in sizes]


def _static_ref(p, prompt, max_new):
    setup = ServeSetup(cfg=CFG, mesh=None, max_len=prompt.size + max_new, batch=1)
    return np.asarray(
        static_generate(setup, p, {"tokens": jnp.asarray(prompt[None])}, max_new)
    )[0]


# ---------------------------------------------------------------------------
# Scheduler invariants
# ---------------------------------------------------------------------------
class TestSlotScheduler:
    def test_fifo_lowest_slot_first(self):
        s = SlotScheduler(2)
        reqs = [Request(rid=i, prompt=np.zeros(1, np.int32), max_new_tokens=1) for i in range(3)]
        for r in reqs:
            s.submit(r)
        admitted = list(s.ready())
        assert [(slot, r.rid) for slot, r in admitted] == [(0, 0), (1, 1)]
        assert s.queued == 1 and s.busy

    def test_finish_makes_slot_immediately_reusable(self):
        s = SlotScheduler(1)
        a = Request(rid=0, prompt=np.zeros(1, np.int32), max_new_tokens=1)
        b = Request(rid=1, prompt=np.zeros(1, np.int32), max_new_tokens=1)
        s.submit(a), s.submit(b)
        assert [r.rid for _, r in s.ready()] == [0]
        assert list(s.ready()) == []  # no free slot
        s.finish(0)
        assert a.done and [(sl, r.rid) for sl, r in s.ready()] == [(0, 1)]

    def test_cancel_queued(self):
        s = SlotScheduler(1)
        a = Request(rid=0, prompt=np.zeros(1, np.int32), max_new_tokens=1)
        s.submit(a)
        s.cancel(a)
        assert a.done and not s.busy


# ---------------------------------------------------------------------------
# Token parity: continuous batching == per-request static generation
# ---------------------------------------------------------------------------
def test_continuous_matches_per_request_static(params):
    """Mixed-length prompts (8/32/96), staggered arrivals, slot count
    below the request count: every request's tokens must be identical to
    generating it alone through the static loop."""
    prompts = _prompts((8, 32, 96, 16))
    max_new = (12, 8, 5, 9)
    refs = [_static_ref(params, p, n) for p, n in zip(prompts, max_new)]

    eng = ServeEngine(CFG, params, n_slots=2, max_len=128, mesh=None)
    r0 = eng.submit(prompts[0], max_new[0])
    r1 = eng.submit(prompts[1], max_new[1])
    for _ in range(3):
        eng.step()
    r2 = eng.submit(prompts[2], max_new[2])  # arrives mid-flight
    r3 = eng.submit(prompts[3], max_new[3])  # queues until a slot frees
    eng.run()

    for rid, ref in zip((r0, r1, r2, r3), refs):
        np.testing.assert_array_equal(eng.result(rid), ref)
    st = eng.stats()
    assert st["requests_completed"] == 4 and st["tokens_generated"] == sum(max_new)
    # continuous batching must beat one-at-a-time decode-step counts:
    # 4 requests decoded (34 tokens total) in fewer steps than serial
    assert st["decode_steps"] < sum(max_new) - 3


def test_serve_trace_with_arrivals(params):
    prompts = _prompts((8, 24, 8))
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, mesh=None)
    outs = eng.serve(list(zip(prompts, (6, 4, 5))), arrivals=[0, 0, 4])
    refs = [_static_ref(params, p, n) for p, n in zip(prompts, (6, 4, 5))]
    for got, want in zip(outs, refs):
        np.testing.assert_array_equal(got, want)
    # arrivals are relative to the call: a second run behaves identically
    outs2 = eng.serve(list(zip(prompts, (6, 4, 5))), arrivals=[0, 0, 4])
    for a, b in zip(outs, outs2):
        np.testing.assert_array_equal(a, b)
    # serve() retires its requests — no unbounded growth across runs
    assert not eng._requests
    with pytest.raises(ValueError, match="entries for"):
        eng.serve([(prompts[0], 2)], arrivals=[0, 1])


def test_slot_reuse_after_finish_and_evict_is_clean(params):
    """A reused slot must produce logits untainted by the previous
    occupant's cache rows (mask-past-pos contract)."""
    prompts = _prompts((24, 16), seed=3)
    fresh = ServeEngine(CFG, params, n_slots=1, max_len=64, mesh=None)
    want = fresh.serve([(prompts[1], 7)])[0]

    # natural finish then reuse of the same slot
    eng = ServeEngine(CFG, params, n_slots=1, max_len=64, mesh=None)
    outs = eng.serve([(prompts[0], 9), (prompts[1], 7)])
    np.testing.assert_array_equal(outs[1], want)

    # eviction mid-flight, then reuse
    eng2 = ServeEngine(CFG, params, n_slots=1, max_len=64, mesh=None)
    rid = eng2.submit(prompts[0], 30)
    for _ in range(4):
        eng2.step()
    partial = eng2.evict(rid)
    assert 0 < partial.size < 30 and eng2._requests[rid].truncated
    rid2 = eng2.submit(prompts[1], 7)
    eng2.run()
    np.testing.assert_array_equal(eng2.result(rid2), want)


def test_capacity_overflow_rejected_at_submit(params):
    # A request that cannot fit prompt + max_new in the slot cache is a
    # caller error, rejected up front (it used to truncate silently).
    eng = ServeEngine(CFG, params, n_slots=1, max_len=16, mesh=None)
    with pytest.raises(ValueError, match="per-slot capacity"):
        eng.submit(_prompts((12,))[0], 50)
    with pytest.raises(ValueError, match="per-slot capacity"):
        eng.submit(np.zeros(17, np.int32), 1)
    # exactly filling the slot is fine and is not counted as truncation
    (out,) = eng.serve([(_prompts((12,))[0], 4)])
    assert out.size == 4
    assert eng.stats()["requests_truncated"] == 0


# ---------------------------------------------------------------------------
# Packed serving: the decode step consumes codes, not a dequantized tree
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def packed_params(params):
    from repro import api

    return api.quantize(CFG, params, api.QuantScheme(fmt="elp4")).params


def test_packed_engine_matches_packed_static(params, packed_params):
    prompts = _prompts((8, 20), seed=5)
    eng = ServeEngine(CFG, packed_params, n_slots=2, max_len=64, mesh=None)
    outs = eng.serve(list(zip(prompts, (8, 6))))
    for got, (p, n) in zip(outs, zip(prompts, (8, 6))):
        np.testing.assert_array_equal(got, _static_ref(packed_params, p, n))


def test_packed_decode_consumes_codes_not_dequant(params, packed_params):
    from repro.kernels.ops import PackedWeight

    eng_p = ServeEngine(CFG, packed_params, n_slots=2, max_len=32, mesh=None)
    eng_f = ServeEngine(CFG, params, n_slots=2, max_len=32, mesh=None)
    # the engine serves the packed tree as-is: uint8 code leaves in, no
    # float twin materialized outside the per-layer in-graph decode
    packed_leaves = [
        l for l in jax.tree.leaves(
            eng_p.params, is_leaf=lambda x: isinstance(x, PackedWeight)
        )
        if isinstance(l, PackedWeight)
    ]
    assert packed_leaves and all(l.codes.dtype == jnp.uint8 for l in packed_leaves)
    # and the compiled decode graph moves fewer bytes than the float one
    # (codes are 1/4 the weight bytes; the per-layer dequant temp is
    # counted once for the scanned body)
    bp = eng_p.decode_cost()["bytes_accessed"]
    bf = eng_f.decode_cost()["bytes_accessed"]
    assert bp < bf, (bp, bf)
    # dispatch observability: every packed [K, N] shape reports how the
    # decode-step matmul impl was resolved (stats()["kernel_dispatch"])
    disp = eng_p.kernel_dispatch()
    keys = {"impl", "source", "blocks", "operand", "count"}
    assert disp and all(set(d) == keys for d in disp.values())
    assert all(d["source"] in ("structural", "heuristic") for d in disp.values())
    assert not eng_f.kernel_dispatch()  # float params: nothing packed to dispatch


def test_packed_decode_logits_within_quant_tolerance(params):
    """One decode step, float vs 8-bit packed weights, same cache/token:
    logits agree to quantization tolerance."""
    from repro import api
    from repro.models import get_model

    packed8 = api.quantize(CFG, params, api.QuantScheme(fmt="elp8")).params
    model = get_model(CFG)
    setup = ServeSetup(cfg=CFG, mesh=None, max_len=32, batch=2)
    aparams = jax.eval_shape(lambda: params)
    prefill_f, decode_f = build_serve_fns(setup, model, aparams=aparams)
    prefill_q, decode_q = build_serve_fns(
        setup, model, aparams=jax.eval_shape(lambda: packed8)
    )
    toks = jnp.asarray(np.stack(_prompts((16, 16), seed=7)))
    cache_f = model.init_cache(CFG, 2, 32)
    cache_q = model.init_cache(CFG, 2, 32)
    lf, cache_f = prefill_f(params, {"tokens": toks}, cache_f)
    lq, cache_q = prefill_q(packed8, {"tokens": toks}, cache_q)
    tok = jnp.argmax(lf[:, -1:], axis=-1).astype(jnp.int32)
    pos = jnp.asarray(np.array([16, 16], np.int32))  # vector positions
    lf2, _ = decode_f(params, tok, cache_f, pos)
    lq2, _ = decode_q(packed8, tok, cache_q, pos)
    scale = float(jnp.mean(jnp.square(lf2)))
    mse = float(jnp.mean(jnp.square(lf2 - lq2)))
    assert mse < 0.1 * scale, (mse, scale)


# ---------------------------------------------------------------------------
# Deprecation wrappers (PR 4 pattern: warn + bit-exact delegation)
# ---------------------------------------------------------------------------
def test_serve_loop_generate_warns_and_matches_engine(params):
    from repro.runtime import serve_loop

    toks = jnp.asarray(np.stack(_prompts((12, 12), seed=9)))
    setup = ServeSetup(cfg=CFG, mesh=None, max_len=20, batch=2)
    with pytest.warns(DeprecationWarning, match="serve_loop.generate is deprecated"):
        legacy = serve_loop.generate(setup, params, {"tokens": toks}, 6)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=20, mesh=None)
    outs = eng.serve([(np.asarray(toks[i]), 6) for i in range(2)])
    np.testing.assert_array_equal(np.asarray(legacy), np.stack(outs))


def test_serve_loop_generate_sampled_uses_static_path(params):
    """Sampled generation keeps the legacy whole-batch PRNG semantics."""
    from repro.runtime import serve_loop

    toks = jnp.asarray(np.stack(_prompts((10, 10), seed=11)))
    setup = ServeSetup(cfg=CFG, mesh=None, max_len=16, batch=2)
    key = jax.random.PRNGKey(4)
    with pytest.warns(DeprecationWarning):
        legacy = serve_loop.generate(setup, params, {"tokens": toks}, 4, greedy=False, key=key)
    direct = static_generate(setup, params, {"tokens": toks}, 4, greedy=False, key=key)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(direct))


def test_make_serve_fns_warns_and_matches_builder(params):
    from repro.models import get_model
    from repro.runtime import serve_loop

    model = get_model(CFG)
    setup = ServeSetup(cfg=CFG, mesh=None, max_len=16, batch=1)
    with pytest.warns(DeprecationWarning, match="make_serve_fns is deprecated"):
        pj, dj = serve_loop.make_serve_fns(setup, model)
    pj2, dj2 = build_serve_fns(setup, model)
    toks = jnp.asarray(_prompts((8,), seed=13)[0][None])
    l1, c1 = pj(params, {"tokens": toks}, model.init_cache(CFG, 1, 16))
    l2, c2 = pj2(params, {"tokens": toks}, model.init_cache(CFG, 1, 16))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    d1, _ = dj(params, jnp.zeros((1, 1), jnp.int32), c1, jnp.int32(8))
    d2, _ = dj2(params, jnp.zeros((1, 1), jnp.int32), c2, jnp.int32(8))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


# ---------------------------------------------------------------------------
# Guard rails + monitor + elastic
# ---------------------------------------------------------------------------
def test_engine_rejects_unsupported_families(params):
    ssm = ArchConfig(name="s", family="ssm", n_layers=1, d_model=32, n_heads=2,
                     n_kv_heads=2, d_ff=64, vocab=64, head_dim=16, dtype_str="float32")
    with pytest.raises(ValueError, match="static_generate"):
        ServeEngine(ssm, {}, mesh=None)
    vlm = ArchConfig(name="v", family="vlm", n_layers=1, d_model=32, n_heads=2,
                     n_kv_heads=2, d_ff=64, vocab=64, head_dim=16, dtype_str="float32",
                     frontend_tokens=4)
    with pytest.raises(ValueError, match="token-only"):
        ServeEngine(vlm, {}, mesh=None)


def test_cnn_adapter_serve_raises():
    from repro.api_schemes import CnnAdapter
    from repro.models import cnn

    with pytest.raises(NotImplementedError, match="continuous-batching"):
        CnnAdapter(cnn.ALEXNET_MINI).serve({}, [(np.zeros(2, np.int32), 1)])


def test_quantized_model_serve_facade(params):
    from repro import api

    qm = api.quantize(CFG, params, api.QuantScheme(fmt="elp4"))
    prompts = _prompts((8, 14), seed=15)
    outs = qm.serve(list(zip(prompts, (5, 4))), n_slots=2)
    for got, (p, n) in zip(outs, zip(prompts, (5, 4))):
        np.testing.assert_array_equal(got, _static_ref(qm.params, p, n))


def test_straggler_monitor_wired_into_decode_loop(params):
    from repro.runtime.straggler import StragglerMonitor

    mon = StragglerMonitor(threshold=2.0)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=32, mesh=None, monitor=mon)
    eng.serve([(p, 6) for p in _prompts((8, 8), seed=17)])
    st = eng.stats()
    assert st["straggler"]["steps"] == st["decode_steps"] > 0
    assert mon.report()["steps"] == st["decode_steps"]
    assert {"median_s", "straggle_events", "worst_ratio"} <= set(st["straggler"])


def test_choose_mesh_shape_policy():
    from repro.runtime.elastic import choose_mesh_shape

    # engine-startup cases: small hosts keep the model axis maximal
    assert choose_mesh_shape(4, 16) == ((1, 4), ("data", "model"))
    assert choose_mesh_shape(8, 4) == ((2, 4), ("data", "model"))
    assert choose_mesh_shape(6, 16) == ((3, 2), ("data", "model"))
    assert choose_mesh_shape(1, 16) == ((1, 1), ("data", "model"))
    # multi-pod split
    assert choose_mesh_shape(512, 16) == ((2, 16, 16), ("pod", "data", "model"))


def test_reshard_applies_spec_tree():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.runtime import sharding as shr
    from repro.runtime.elastic import reshard

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    tree = {"wq": jnp.ones((8, 16)), "ln1": jnp.zeros((8,))}
    specs = shr.param_specs(jax.eval_shape(lambda: tree), mesh)
    out = reshard(tree, mesh, specs)
    assert out["wq"].sharding == NamedSharding(mesh, P(None, "model"))
    assert out["ln1"].sharding == NamedSharding(mesh, P())
    np.testing.assert_array_equal(np.asarray(out["wq"]), np.ones((8, 16)))


# ---------------------------------------------------------------------------
# Multi-device: fake 4-device CPU mesh (subprocess; jax pins the device
# count at first init, the main process must keep seeing 1 device)
# ---------------------------------------------------------------------------
def run_in_subprocess(body: str) -> str:
    script = (
        "import os\n"
        'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"\n'
        + textwrap.dedent(body)
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        timeout=560,
        cwd=REPO,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_multi_device_engine_parity():
    """On a fake 4-device mesh the engine (auto elastic mesh, sharded
    packed weights, flash-decode variant) is token-identical to
    single-device per-request static generation, and the decode step
    consumes sharded uint8 code leaves and makes no dequantized f32 tree."""
    run_in_subprocess(
        """
        import math, re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs.base import ArchConfig
        from repro import api as front
        from repro.runtime import sharding as shr
        from repro.runtime.elastic import reshard
        from repro.serve import ServeEngine, ServeSetup, static_generate
        from repro.models import get_model

        CFG = ArchConfig(name="eng", family="dense", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                         head_dim=16, dtype_str="float32")
        params = get_model(CFG).init_params(CFG, jax.random.PRNGKey(0))
        packed = front.quantize(CFG, params, front.QuantScheme(fmt="elp4")).params
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128, size=s).astype(np.int32) for s in (8, 16, 24)]
        news = (8, 6, 4)

        def ref(pp, p, n):
            setup = ServeSetup(cfg=CFG, mesh=None, max_len=p.size + n, batch=1)
            return np.asarray(static_generate(
                setup, pp, {"tokens": jnp.asarray(p[None])}, n))[0]

        assert jax.device_count() == 4
        for tag, pp, flash in (("float", params, False), ("packed", packed, False),
                               ("packed+flash", packed, True)):
            eng = ServeEngine(CFG, pp, n_slots=2, max_len=64, mesh="auto",
                              flash_decode=flash)
            assert eng.stats()["mesh"] == {"data": 1, "model": 4}
            outs = eng.serve(list(zip(prompts, news)), arrivals=[0, 0, 2])
            for got, (p, n) in zip(outs, zip(prompts, news)):
                want = ref(pp, p, n)
                assert np.array_equal(got, want), (tag, got, want)
            print(tag, "parity OK")

        # decode consumes SHARDED uint8 codes (no dequantized tree)
        # (a short cache, so no cache value is as large as an FFN weight)
        eng = ServeEngine(CFG, packed, n_slots=2, max_len=16, mesh="auto")
        wq = eng.params["blocks"]["wq"]
        assert wq.codes.dtype == jnp.uint8
        assert "model" in tuple(wq.codes.sharding.spec)
        engf = ServeEngine(CFG, params, n_slots=2, max_len=16, mesh="auto")
        # ... and the packed decode program materialises no f32 value as
        # large as a chip's share of a stacked FFN weight, which the float
        # program takes in whole
        w1 = engf.params["blocks"]["w1"]
        n = math.prod(w1.sharding.shard_shape(w1.shape))

        def f32_values_of_at_least(e):
            dims = re.findall(r"\\bf32\\[([\\d,]+)\\]", e.compiled_decode().as_text())
            return {d for d in dims if math.prod(map(int, d.split(","))) >= n}

        assert not f32_values_of_at_least(eng), f32_values_of_at_least(eng)
        assert f32_values_of_at_least(engf)

        # elastic reshard onto a different mesh layout
        mesh22 = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("data", "model"))
        specs = shr.param_specs(jax.eval_shape(lambda: packed), mesh22)
        moved = reshard(packed, mesh22, specs)
        got = moved["blocks"]["wq"].codes.sharding
        from jax.sharding import NamedSharding
        assert got == NamedSharding(mesh22, specs["blocks"]["wq"].codes)
        print("reshard OK")
        """
    )
