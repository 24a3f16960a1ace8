"""Packed GEMMs run per shard on a model mesh, and a Yi-shaped decoder
served on one, against one device and the plain reference.

The multi-device cases run in child processes on 4 host CPU devices
(JAX fixes the device count when it starts; this process keeps one),
the Pallas kernels in interpret mode. Each child reports JSON, read by
a module fixture, so one child serves several tests.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model, transformer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# bf16 outputs: one rounding is 2^-8 of the value; a sharded sum and the
# one-device kernel differ in f32 summation order only, so by at most
# one bf16 step of the largest output (x2 for the step of the reference)
BF16_STEP = 2.0 ** -7
# float32 end to end, as the reference computes: only the order of the
# sums differs. A lost partial sum or a wrong split is off by O(1).
LOGIT_TOL = 1e-3


def _child(body: str) -> dict:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)], capture_output=True,
                         text=True, env=env, timeout=600, cwd=REPO)
    lines = [l for l in out.stdout.splitlines() if l.startswith("JSON ")]
    assert out.returncode == 0 and lines, out.stderr[-3000:]
    return json.loads(lines[-1][5:])


GEMM_CASES = {  # name: (weight name, K, N, M)
    "col-m8": ("wq", 256, 512, 8),
    "col-m32": ("wq", 256, 512, 32),
    "row-m8": ("wo", 512, 256, 8),
    "row-m32": ("wo", 512, 256, 32),
    "row-odd-k": ("w2", 255, 128, 32),
}


@pytest.fixture(scope="module")
def gemms():
    return _child(
        f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.kernels.ops import dequantize, pack_weight, quantized_matmul
        from repro.runtime import sharding as shr
        from repro.runtime.elastic import reshard

        mesh = Mesh(np.asarray(jax.devices()).reshape(1, 4), ("data", "model"))
        out = {{}}
        for case, (name, k, n, m) in {GEMM_CASES!r}.items():
            pw, _ = pack_weight(jax.random.normal(jax.random.PRNGKey(k + n), (k, n)), "elp_bsd_a4")
            specs = shr.param_specs(jax.eval_shape(lambda: {{name: pw}}), mesh)
            placed = shr.mark_splits(reshard({{name: pw}}, mesh, specs), specs, mesh)[name]
            x = jax.random.normal(jax.random.PRNGKey(m), (m, k)).astype(jnp.bfloat16)
            mm = lambda x, w: quantized_matmul(x, w, impl="pallas", interpret=True,
                                               out_dtype=jnp.bfloat16)
            step = jax.jit(mm)
            got = np.asarray(step(x, placed), np.float32)
            one = np.asarray(mm(x, pw), np.float32)
            ref = np.asarray(jnp.dot(x.astype(jnp.float32), dequantize(pw),
                                     precision=jax.lax.Precision.HIGHEST))
            jaxpr = str(jax.make_jaxpr(mm)(x, placed))
            out[case] = {{
                "split": placed.split, "vs_one": float(np.max(np.abs(got - one))),
                "vs_ref": float(np.max(np.abs(got - ref))), "scale": float(np.max(np.abs(ref))),
                "kernels": jaxpr.count("pallas_call"), "shard_maps": jaxpr.count("shard_map"),
                "psums": jaxpr.count("psum"),
            }}
        print("JSON " + json.dumps(out))
        """
    )


@pytest.mark.parametrize("case", sorted(GEMM_CASES))
def test_sharded_packed_gemm_matches_one_device_and_reference(gemms, case):
    """Column-parallel output stays split; row-parallel partial sums add
    up in a psum; either equals the one-device kernel and the
    dequantize-then-dot within the output's bf16 rounding."""
    r = gemms[case]
    print(case, r)
    row = GEMM_CASES[case][0] in ("wo", "w2")
    assert tuple(r["split"]) == (("model", None) if row else (None, "model"))
    assert r["vs_one"] <= BF16_STEP * r["scale"]
    assert r["vs_ref"] <= BF16_STEP * r["scale"]
    assert r["kernels"] == r["shard_maps"] == 1 and r["psums"] == int(row)


YI_TINY = dict(n_layers=2, d_model=224, n_heads=28, n_kv_heads=4, head_dim=8, d_ff=256,
               vocab=256, dtype_str="float32")


@pytest.fixture(scope="module")
def yi_served():
    """A Yi-shaped decoder (7:1 GQA, heads divisible by 4, eps 1e-5, no
    qk_norm) on a 1x4 mesh from seeded codes, through the Pallas kernels
    per shard: the engine's prefill and decode logits, its greedy tokens
    through the public API, and the plain reference's full forward over
    the same tokens."""
    return _child(
        f"""
        import dataclasses, json, sys
        import jax, jax.numpy as jnp, numpy as np
        sys.path.insert(0, "chipbench")
        from harness import lm_serve, weights as wts
        from reference import decoder_lm as ref
        from repro.configs import get_config
        from repro.kernels.ops import force_impl
        from repro.runtime.elastic import make_mesh
        from repro.serve import ServeEngine

        cfg = dataclasses.replace(get_config("yi_34b"), **{YI_TINY!r})
        assert cfg.n_heads == 7 * cfg.n_kv_heads and cfg.norm_eps == 1e-5 and not cfg.qk_norm
        config = {{
            "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_hidden_layers": cfg.n_layers, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.hd, "vocab_size": cfg.vocab,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps, "qk_norm": False,
            "torch_dtype": "float32", "weights": {{"format": "elp_bsd_a4"}},
        }}
        neutral = wts.lm_weights(config, wts.seed_key(2**31 + 5))
        params = lm_serve.program_params(cfg, neutral, "elp_bsd_a4")
        mesh = make_mesh()
        prompt = np.random.default_rng(1).integers(0, cfg.vocab, 12).astype(np.int32)
        with force_impl("pallas"):
            eng = ServeEngine(cfg, params, n_slots=2, max_len=24, mesh=mesh)
            logits, cache = eng._prefill(eng.params, jnp.asarray(prompt[None]), eng._cache,
                                         jnp.int32(0))
            steps, tok = [np.asarray(logits[0])], int(jnp.argmax(logits[0]))
            seq, pos = [tok], np.array([prompt.size, 0], np.int32)
            for _ in range(2):
                toks = jnp.asarray([[tok], [0]], jnp.int32)
                out, cache = eng._decode(eng.params, toks, cache, jnp.asarray(pos))
                steps.append(np.asarray(out[0, -1]))
                tok = int(jnp.argmax(out[0, -1]))
                seq.append(tok)
                pos[0] += 1
            eng = ServeEngine(cfg, params, n_slots=2, max_len=24, mesh=mesh)
            rid = eng.submit(prompt, 6)
            eng.run()
            served = np.asarray(eng.result(rid)).tolist()
            disp = eng.kernel_dispatch()
        full = np.concatenate([prompt, np.asarray(seq[:-1], np.int32)])
        with jax.default_matmul_precision("highest"):
            h = ref.hidden(neutral, config, jnp.asarray(full[None]))
            want = np.asarray(ref._row_logits(h[0], neutral["head"], mode="highest"))
            served_full = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            hs = ref.hidden(neutral, config, jnp.asarray(served_full[None]))
            ws = np.asarray(ref._row_logits(hs[0], neutral["head"], mode="highest"))
        at = [prompt.size - 1 + i for i in range(len(steps))]
        print("JSON " + json.dumps({{
            "errs": [float(np.max(np.abs(s - want[p]))) for s, p in zip(steps, at)],
            "scale": float(np.max(np.abs(want))),
            "gaps": [float(ws[prompt.size - 1 + i].max() - ws[prompt.size - 1 + i][t])
                     for i, t in enumerate(served)],
            "sources": sorted({{d["source"] for d in disp.values()}}),
            "splits": sorted({{d["split"] for d in disp.values()}}),
        }}))
        """
    )


def test_yi_shaped_engine_on_a_mesh_matches_reference_logits(yi_served):
    """Prefill, then decode through the sharded cache: the engine's logits
    are the reference's full forward at each position."""
    r = yi_served
    print(r)
    assert len(r["errs"]) == 3
    assert max(r["errs"]) <= LOGIT_TOL * r["scale"]
    assert r["sources"] == ["sharded"] and r["splits"] == ["col", "row"]


def test_yi_shaped_engine_serves_the_reference_argmax(yi_served):
    """Through the public API (submit, run, result) every served token is
    the reference's best at its position, to within the logit tolerance."""
    gaps = yi_served["gaps"]
    assert len(gaps) == 6 and max(gaps) <= LOGIT_TOL * yi_served["scale"]


# the engine's jitted steps: the function that builds it, which of its jits, its jitted name
SERVE_STEPS = {
    "serve_prefill": ("build_serve_fns", 0, "prefill_fn"),
    "serve_decode": ("build_serve_fns", 1, "decode_fn"),
    "slot_prefill": ("build_slot_prefill", None, "prefill_slot"),
    "paged_prefill": ("build_paged_prefill", None, "prefill_slot"),
    "greedy_decode": ("build_greedy_decode", None, "decode_greedy"),
    "draft_run": ("build_draft_run", None, "draft"),
    "verify_step": ("build_verify_step", None, "verify"),
}


@pytest.fixture(scope="module")
def placements():
    """Each serve step of a Yi-shaped decoder lowered on one device and
    on a 1x4 mesh: the arguments it donates and its jitted name."""
    return _child(
        f"""
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.models import get_model
        from repro.serve import engine

        cfg = dataclasses.replace(get_config("yi_34b"), **{YI_TINY!r})
        api = get_model(cfg)
        aparams = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        meshes = {{"one_chip": None,
                   "mesh": Mesh(np.asarray(jax.devices()).reshape(1, 4), ("data", "model"))}}
        out = {{}}
        for where, mesh in meshes.items():
            for step, (build, which, _) in {SERVE_STEPS!r}.items():
                paged = step == "paged_prefill"
                setup = engine.ServeSetup(cfg=cfg, mesh=mesh, max_len=16, batch=2,
                                          page_size=4 if paged else 0)
                fn = getattr(engine, build)(setup, api, aparams)
                fn = fn if which is None else fn[which]
                cache = engine._abstract_cache(setup, api)
                if paged:  # the batch-1 page-table row the engine injects
                    cache = {{**cache, "pages": i32(1, cache["pages"].shape[1])}}
                args = {{
                    "serve_prefill": (aparams, {{"tokens": i32(2, 8)}}, cache),
                    "serve_decode": (aparams, i32(2, 1), cache, i32(2)),
                    "slot_prefill": (aparams, i32(1, 8), cache, i32()),
                    "paged_prefill": (aparams, i32(1, 4), cache, i32(1)),
                    "greedy_decode": (aparams, i32(2, 1), cache, i32(2)),
                    "draft_run": (aparams, i32(2, 1), cache, i32(2), 3),
                    "verify_step": (aparams, i32(2, 3), cache, i32(2)),
                }}[step]
                low = fn.lower(*args)
                donated = [i for i, a in enumerate(low.args_info[0])
                           if any(x.donated for x in jax.tree.leaves(a))]
                name = low.as_text().split("module @", 1)[1].split(" ", 1)[0]
                out[where + "/" + step] = {{"donated": donated, "name": name}}
        print("JSON " + json.dumps(out))
        """
    )


@pytest.mark.parametrize("where", ["one_chip", "mesh"])
@pytest.mark.parametrize("step", sorted(SERVE_STEPS))
def test_serve_step_donation_and_name(placements, step, where):
    """On a mesh every step donates its cache (argument 2); on one device
    only the draft loop does. Each jit keeps its function's name, which
    the device-trace readers key on."""
    r = placements[f"{where}/{step}"]
    donates = where == "mesh" or step == "draft_run"
    assert r["donated"] == ([2] if donates else [])
    assert r["name"] == "jit_" + SERVE_STEPS[step][2]


def _eps_seen(cfg, monkeypatch, *, drop_eps=False):
    """The eps of every RMSNorm the decoder forward calls, and its logits;
    with ``drop_eps`` each call takes ``rms_norm``'s own default."""
    seen = []
    orig = transformer.rms_norm

    def spy(x, scale, eps=1e-6):
        seen.append(eps)
        return orig(x, scale) if drop_eps else orig(x, scale, eps)

    monkeypatch.setattr(transformer, "rms_norm", spy)
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(8, dtype=jnp.int32)[None] % cfg.vocab
    logits = transformer.forward(params, cfg, tokens)
    monkeypatch.undo()
    return set(seen), np.asarray(logits)


def test_norm_eps_is_the_configs_and_qwen3_8b_is_unchanged(monkeypatch):
    """qwen3-8b keeps eps 1e-6, the value every RMSNorm took before the
    field existed: its forward is bit-identical to one whose norms take
    ``rms_norm``'s default. yi-34b states 1e-5, and every norm of its
    decoder uses it."""
    qwen = get_config("qwen3_8b")
    assert qwen.norm_eps == 1e-6 and get_config("yi_34b").norm_eps == 1e-5
    small = qwen.reduced()
    eps, logits = _eps_seen(small, monkeypatch)
    _, default = _eps_seen(small, monkeypatch, drop_eps=True)
    assert eps == {1e-6}
    np.testing.assert_array_equal(logits, default)
    yi = dataclasses.replace(get_config("yi_34b").reduced(), norm_eps=1e-5)
    eps, _ = _eps_seen(yi, monkeypatch)
    assert eps == {1e-5}
