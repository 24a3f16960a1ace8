"""The tensor-parallel driver, driven through whole runs at a small size
on four host CPU devices (the look for a chip skipped), and one chip's
share of the GEMMs against the whole.

The sound program passes; the float8 control, put in the program's
place, and a token altered as the meshed decode step produces it read
``correct`` false through the same comparison. The runs need four
devices, which JAX fixes when it starts, so they run in one child
process and report back as JSON.
"""
import json
import os
import subprocess
import sys

import pytest

from harness import counts, counts_tp

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# The program reads 0 on the CPU (float32 end to end, like the
# reference); the float8 control reads well above.
LM_GAP_LIMIT = 0.05

RUNS = """
import dataclasses, json, os, sys
import jax
sys.path.insert(0, BENCH)
import run as bench_run
from harness import lm_serve
from repro.configs import get_config
from repro.serve import engine

with open(os.path.join(BENCH, "tests", "data", "tiny-lm-tp.json")) as f:
    config = json.load(f)
tiny = dataclasses.replace(
    get_config("yi_34b"), n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
    n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
    head_dim=config["head_dim"], d_ff=config["intermediate_size"], vocab=config["vocab_size"],
    dtype_str="float32",
)
lm_serve.program_arch = lambda name: tiny
cell = {"traffic": "chat4", "mode": "closed", "mesh": {"data": 1, "model": 4}, "n_slots": 4,
        "max_len": 48, "prompt_len": 16, "max_new": 24,
        "check": {"sample_requests": 3, "max_logit_gap": LIMIT}}
bench = {"end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}], "per_layer": []}


def measure(control=False):
    run, result, _ = bench_run.measure(
        "tp", 2**31 + 91, 0.5, False, bench=bench, work=({"name": "tp", "chips": 4}, config, cell),
        chip=False, control=control)
    return {"correct": result["correct"], "check": run.check_result,
            "metrics": result["metrics"], "count": result["device"]["count"]}


out = {"sound": measure(), "control": measure(control=True)}
orig = engine.build_greedy_decode


def altered(setup, api=None, aparams=None):
    step = orig(setup, api, aparams)

    def token_altered(p, t, c, pos):
        nxt, cache = step(p, t, c, pos)
        return (nxt + 1) % config["vocab_size"], cache

    return jax.jit(token_altered)


from harness import lm_serve_tp, scopes, scopes_tp
from harness.record import Run

served = lm_serve_tp.setup(Run(config, cell, {}, 7, 0.1, False))
text = served.engine.compiled_decode().as_text()
ran = scopes.hlo_scopes(text)
rebuilt = scopes_tp.compiled_scopes(json.dumps(config, sort_keys=True),
                                    json.dumps(cell, sort_keys=True))
out["scopes"] = {
    "ops": len(ran),
    "found": sum(rebuilt.get(k, ("",))[0] == t for k, (t, _) in ran.items()),
    "in_matmul": sum("elp_bsd_matmul" in s for _, s in ran.values()),
    "in_collective": sum("tp_collective" in s for _, s in ran.values()),
    "collectives": [l.strip()[:60] for l in text.splitlines() if scopes_tp.is_collective(l)],
}

engine.build_greedy_decode = altered
out["token_altered"] = measure()
print("RUNS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", f"BENCH, LIMIT = {BENCH_DIR!r}, {LM_GAP_LIMIT!r}\n" + RUNS],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT,
    )
    lines = [l for l in proc.stdout.splitlines() if l.startswith("RUNS ")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    return json.loads(lines[-1][5:])


def test_sound_tp_run_is_correct(runs):
    r = runs["sound"]
    print("sound", r["check"])
    assert r["correct"] and r["count"] == 4
    assert r["metrics"]["tokens_per_s"]["value"] > 0
    assert r["check"]["sampled_in_window"] >= 1 and r["check"]["served_tokens"] >= 24


def test_tp_control_is_not_correct(runs):
    res = runs["control"]["check"]
    print("control", res)
    assert not runs["control"]["correct"]
    assert res["max_logit_gap"] <= LM_GAP_LIMIT < res["control_max_logit_gap"]


def test_tp_altered_token_is_not_correct(runs):
    print("token_altered", runs["token_altered"]["check"])
    assert not runs["token_altered"]["correct"]


def test_tp_scopes_rebuild_the_served_decode_step(runs):
    """The meshed decode step rebuilt from shapes alone is the one the
    engine serves, instruction for instruction; its packed GEMMs sit
    under ``elp_bsd_matmul``, the row-parallel sums under
    ``tp_collective``, and the collectives are found by opcode."""
    sc = runs["scopes"]
    print(sc)
    assert sc["ops"] > 50 and sc["found"] == sc["ops"]
    assert sc["in_matmul"] > 0 and sc["in_collective"] >= 2
    assert any(" all-reduce(" in c for c in sc["collectives"])


def test_collective_opcodes_from_op_text():
    from harness import scopes_tp

    ar = "%psum.3 = bf16[32,7168]{1,0:T(8,128)(2,1)S(1)} all-reduce(%x.1), channel_id=1"
    start = ("%all-gather-start.2 = (f32[1,32]{1,0:T(1,128)}, f32[4,32]{1,0}) "
             "all-gather-start(%y), dimensions={0}")
    fused = "%all-reduce.7 = f32[8]{0} fusion(%z), kind=kLoop, calls=%fc.1"
    dot = "%fusion.3 = bf16[32,7168]{1,0:T(8,128)(2,1)} fusion(%all-reduce.7), kind=kOutput"
    assert scopes_tp.opcode(ar) == "all-reduce"
    assert scopes_tp.opcode(start) == "all-gather-start"
    assert [scopes_tp.is_collective(o) for o in (ar, start, fused, dot)] == [True] * 3 + [False]


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name)) as f:
        return json.load(f)


def test_chip_shares_add_up_to_the_whole_gemm():
    """yi-34b over 4 chips: each GEMM's 4 local shares hold its whole
    operations and its whole code bytes; only activations and the scale
    are counted on every chip."""
    cfg = _config("yi-34b.json")
    whole = {name: (k, n) for name, k, n in counts.decoder_layer_gemms(cfg)}
    local = counts_tp.local_layer_gemms(cfg, 4)
    assert {name for name, *_ in local} == set(whole)
    for name, split, k, n in local:
        kk, nn = whole[name]
        assert (split == "row") == (name in ("wo", "w2"))
        assert 4 * counts.gemm_ops(32, k, n) == counts.gemm_ops(32, kk, nn)
        codes = counts.packed_gemm_bytes(32, k, n) - 4 - 2 * 32 * (k + n)
        assert 4 * codes == counts.packed_gemm_bytes(32, kk, nn) - 4 - 2 * 32 * (kk + nn)
    assert [(n, k, m) for n, _, k, m in local][:2] == [("wq", 7168, 1792), ("wk", 7168, 256)]


def test_chip_share_bound_counts_a_quarter_of_the_codes():
    from harness.peaks import peaks_for

    cfg = _config("yi-34b.json")
    peaks = peaks_for("TPU v5 lite")
    one = counts_tp.decode_step_gemm_bounds(cfg, 32, peaks, 4)
    whole = counts.decode_step_gemm_bounds(cfg, 32, peaks)
    assert one["least_s"] == pytest.approx(one["bytes_s"])  # bound by bytes at 32 rows
    codes = 60 * sum(k * n // 2 for _, k, n in counts.decoder_layer_gemms(cfg))
    assert codes == 16_735_272_960
    moved = 60 * sum(k * n // 2 + 4 + 2 * 32 * (k + n)
                     for _, _, k, n in counts_tp.local_layer_gemms(cfg, 4))
    assert moved == codes // 4 + 60 * (7 * 4 + 2 * 32 * (5 * 7168 + 1792 + 2 * 256 + 2 * 5120
                                                          + 1792 + 5120 + 2 * 7168))
    assert one["bytes_s"] == pytest.approx(moved / peaks["hbm_bytes_per_s"])
    # the activations are not split, so a chip moves more than a quarter
    assert whole["bytes_s"] / 4 < one["bytes_s"] < whole["bytes_s"] / 3.8
    assert counts_tp.mesh_chips({"mesh": {"data": 1, "model": 4}}) == 4
