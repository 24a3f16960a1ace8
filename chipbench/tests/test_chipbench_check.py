"""The check that decides ``correct``, driven through a whole run at a
small size on the CPU (the look for a chip skipped).

The sound program passes. The control, the plain reference computed
with float8 operands and put in the program's place, fails through the
same comparison. So does every fault the cells can have,
planted where the timed path produces it: a decode step that returns
its cache unchanged, a token altered as it is produced, an answer
altered, half of a batch left out and the other half repeated.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import run as bench_run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# Limits at this size, from the readings the tests below print: the
# program reads 0 on the CPU (float32 end to end, like the reference);
# the float8 control reads well above.
LM_GAP_LIMIT = 0.05
CNN_ERR_LIMIT = 0.01
SEED = 2**31 + 77


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


LM = _load("tiny-lm.json")
CNN = _load("tiny-cnn.json")
LM_CELL = {
    "traffic": "offline", "mode": "closed", "n_slots": 4, "max_len": 64, "prompt_len": 16,
    "max_new": 24, "check": {"sample_requests": 3, "max_logit_gap": LM_GAP_LIMIT},
}
CHAT_CELL = {
    "traffic": "chat", "mode": "open", "rate_per_s": 40, "period_s": 1, "n_slots": 4,
    "max_len": 64, "prompt_buckets": [[8, 0.5], [32, 0.5]], "output_buckets": [[4, 0.5], [16, 0.5]],
    "grace_s": 30, "check": {"sample_requests": 3, "max_logit_gap": LM_GAP_LIMIT},
}
CNN_CELL = {
    "traffic": "b4", "mode": "closed", "batch": 4,
    "check": {"sample_calls": 2, "block": 4, "max_rel_err": CNN_ERR_LIMIT},
}
BENCH = {
    "end_to_end": [
        {"name": "tokens_per_s", "unit": "tokens/s", "workloads": ["lm"]},
        {"name": "ttft_p95_ms", "unit": "ms", "workloads": ["chat"]},
        {"name": "images_per_s", "unit": "images/s", "workloads": ["cnn"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}


@pytest.fixture(autouse=True)
def _tiny_program_arch(monkeypatch):
    """The program's qwen3 architecture at the tiny sizes of ``tiny-lm.json``."""
    from harness import lm_serve

    arch = lm_serve.program_arch
    monkeypatch.setattr(lm_serve, "program_arch", lambda name: arch(name).reduced())


def _measure(name, config, cell, control=False):
    run, result, _ = bench_run.measure(
        name, SEED, 0.3, False, bench=BENCH, work=({"name": name, "chips": 1}, config, cell),
        chip=False, control=control,
    )
    return run, result


def _break_decode(monkeypatch, how):
    from repro.serve import engine

    orig = engine.build_greedy_decode
    vocab = LM["vocab_size"]

    def broken(setup, api=None, aparams=None):
        step = orig(setup, api, aparams)
        def altered(p, t, c, pos):
            nxt, cache = step(p, t, c, pos)
            return (nxt + 1) % vocab, cache

        def unchanged(p, t, c, pos):
            return step(p, t, c, pos)[0], c

        return jax.jit(altered if how == "token_altered" else unchanged)

    monkeypatch.setattr(engine, "build_greedy_decode", broken)


def _break_forward(monkeypatch, how):
    from repro.models import cnn

    orig = cnn.forward

    def broken(params, spec, x, *a, **kw):
        if how == "answer_altered":
            return orig(params, spec, x, *a, **kw).at[0, 0].add(1.0)
        half = orig(params, spec, x[: x.shape[0] // 2], *a, **kw)
        return jnp.concatenate([half, half])

    monkeypatch.setattr(cnn, "forward", broken)


def test_sound_lm_runs_are_correct_and_the_control_is_not():
    run, result = _measure("lm", LM, LM_CELL)
    assert result["correct"] and result["metrics"]["tokens_per_s"]["value"] > 0
    assert run.check_result["sampled_in_window"] >= 1
    run, result = _measure("lm", LM, LM_CELL, control=True)
    res = run.check_result
    print("tiny lm readings", res)
    assert not result["correct"], result["compared"]
    assert result["compared"]["max_logit_gap"]["value"] == res["control_max_logit_gap"]
    assert res["max_logit_gap"] <= LM_GAP_LIMIT < res["control_max_logit_gap"]
    assert res["served_tokens"] >= 24


def test_sound_chat_run_is_correct():
    run, result = _measure("chat", LM, CHAT_CELL)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 10 and result["failed"] == 0
    assert result["metrics"]["ttft_p95_ms"]["value"] > 0


def test_sound_cnn_runs_are_correct_and_the_control_is_not():
    run, result = _measure("cnn", CNN, CNN_CELL)
    assert result["correct"] and result["metrics"]["images_per_s"]["value"] > 0
    run, result = _measure("cnn", CNN, CNN_CELL, control=True)
    res = run.check_result
    print("tiny cnn readings", res)
    assert not result["correct"], result["compared"]
    assert res["max_rel_err"] <= CNN_ERR_LIMIT < res["control_max_rel_err"]


@pytest.mark.parametrize("how", ["token_altered", "state_unchanged"])
def test_broken_decode_is_not_correct(monkeypatch, how):
    _break_decode(monkeypatch, how)
    run, result = _measure("lm", LM, LM_CELL)
    print(how, run.check_result)
    assert not result["correct"]


@pytest.mark.parametrize("how", ["answer_altered", "half_batch"])
def test_broken_forward_is_not_correct(monkeypatch, how):
    _break_forward(monkeypatch, how)
    run, result = _measure("cnn", CNN, CNN_CELL)
    print(how, run.check_result)
    assert not result["correct"]
