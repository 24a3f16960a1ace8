"""Serve a decoder LM from packed codes through the program's engine.

Set-up draws the neutral weights from the seed on the device, places
them in the program's packed tree, builds a one-device
``repro.serve.ServeEngine`` and warms every prompt length the cell's
traffic sends, plus the decode step. The window then drives the
engine's public API only (``submit``, ``step``, ``result``,
``release``, ``stats``):

* closed loop (``offline``): every slot busy, a backlog always queued,
  no host sync inside the window; output tokens completed over the
  window;
* open loop (``chat``): requests submitted at their due times, a
  streaming client reading every request's tokens after each step; per
  request the time from due to its first token on the host, and the
  gaps between its tokens as the client sees them.

After the window: the peak memory is read, the engine is freed, and a
sample of the served requests drawn from the seed is checked against
the plain reference: the widest gap by which a served token's logit
lies below the reference's best. The sample always holds the longest
finished request and the longest admitted inside the window, whose
tokens are compared as far as they were served when the window closed.
With ``control`` the reference's lower-precision control takes the
program's place in that comparison.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import device, tracing
from harness import traffic as tr
from harness import weights as wts
from harness.record import LmRecord, Run

# The program's config field that holds each size the configuration file states.
STATED = {
    "n_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size",
    "vocab": "vocab_size",
    "hd": "head_dim",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
    "dtype_str": "torch_dtype",
}
TOP_UP_EVERY = 16  # closed loop: steps between looks at the completion count


def program_arch(name: str):
    """The program's registered architecture of that name."""
    from repro.configs import get_config

    return get_config(name)


def program_config(config: dict):
    """The program's own config for the model, held to the file's sizes."""
    cfg = program_arch(config["program_arch"])
    diff = {
        ours: (getattr(cfg, ours), config[theirs])
        for ours, theirs in STATED.items()
        if getattr(cfg, ours) != config[theirs]
    }
    if diff or cfg.mlp_kind != "swiglu" or cfg.tie_embeddings != config["tie_word_embeddings"]:
        raise ValueError(f"the program's {cfg.name} departs from the configuration file: {diff}")
    return cfg


def program_params(cfg, neutral: dict, fmt: str):
    """Place the neutral weights into the program's packed tree: the
    structure, dtypes and ``PackedWeight`` aux that its converter makes,
    with norm gains at 1 (the program stores ``gain - 1``)."""
    from repro.kernels.ops import PackedWeight
    from repro.models import get_model
    from repro.runtime.quantized_params import abstract_quantize_tree

    aparams = jax.eval_shape(lambda: get_model(cfg).init_params(cfg, jax.random.PRNGKey(0)))
    apacked = abstract_quantize_tree(aparams, cfg, fmt)
    top = {"embed": neutral["embed"], "lm_head": neutral["head"]}

    def place(path, leaf):
        name = path[-1].key
        if isinstance(leaf, PackedWeight):
            src = neutral["layers"][name]
            if src["codes"].shape != leaf.codes.shape or leaf.codes.dtype != jnp.uint8:
                raise ValueError(
                    f"{name}: program codes {leaf.codes.shape}, drawn {src['codes'].shape}"
                )
            sf = src["scale"].reshape(leaf.sf.shape).astype(leaf.sf.dtype)
            return PackedWeight(src["codes"], sf, *leaf.tree_flatten()[1])
        if name in top:
            arr = top[name]
            if arr.shape != leaf.shape or arr.dtype != leaf.dtype:
                raise ValueError(
                    f"{name}: program {leaf.shape} {leaf.dtype}, drawn {arr.shape} {arr.dtype}"
                )
            return arr
        if "norm" in name or name in ("ln1", "ln2"):
            return jnp.zeros(leaf.shape, leaf.dtype)
        raise ValueError(f"the program's tree has a leaf {name!r} the benchmark does not draw")

    return jax.tree_util.tree_map_with_path(
        place, apacked, is_leaf=lambda x: isinstance(x, PackedWeight)
    )


def _warm(engine, cell: dict, seed: int, vocab: int) -> None:
    """Compile every prompt length the traffic sends, the decode step and
    the small admission ops, through the engine's own calls."""
    rng = tr.rng_for(seed, 4)
    rids = [
        engine.submit(rng.integers(0, vocab, n, dtype=np.int32), 2)
        for n in tr.prompt_lengths(cell)
    ]
    engine.run()
    for rid in rids:
        engine.release(rid)
    device.sync()


def _closed_window(engine, run: Run, cell: dict, seed: int, vocab: int, spans) -> list:
    n_slots = int(cell["n_slots"])
    it = tr.lm_requests(cell, seed, vocab)
    submitted: dict[int, tr.LmRequest] = {}

    def top_up():
        done = engine.stats()["requests_completed"]
        while len(submitted) - done < 3 * n_slots:
            r = next(it)
            submitted[engine.submit(r.prompt, r.max_new)] = r

    top_up()
    while engine.stats()["live_slots"] < n_slots:
        engine.step()
    for _ in range(8):
        engine.step()
    top_up()
    device.sync()
    admitted_before = {rid for rid in submitted if len(engine.result(rid))}
    st0 = engine.stats()
    dispatch = []
    t0 = time.perf_counter()
    run.setup_s = t0 - run.t_start  # filling every slot is set-up
    t_end = t0 + run.seconds
    with spans("bench.window"):
        while time.perf_counter() < t_end:
            ts = time.perf_counter()
            with spans("bench.step"):
                engine.step()
            dispatch.append(time.perf_counter() - ts)
            if len(dispatch) % TOP_UP_EVERY == 0:
                with spans("bench.submit"):
                    top_up()
        td = time.perf_counter()
        with spans("bench.drain"):
            device.sync()
        t1 = time.perf_counter()
    st1 = engine.stats()
    run.window_s = t1 - t0
    run.tokens = st1["tokens_generated"] - st0["tokens_generated"]
    run.attempted = st1["requests_completed"] - st0["requests_completed"] + st1["live_slots"]
    served = []
    for rid, r in submitted.items():
        out = engine.result(rid)
        if len(out):
            served.append((r.prompt, out, len(out) == r.max_new, rid not in admitted_before))
    slow = sorted(dispatch, reverse=True)[:3]
    run.note(
        f"[lm] closed loop: {len(dispatch)} steps dispatched in the window, "
        f"{st1['requests_completed'] - st0['requests_completed']} requests finished and "
        f"{sum(s[3] for s in served)} admitted in it; longest step calls "
        + ", ".join(f"{1e3 * x:.1f}" for x in slow)
        + f" ms; drain {1e3 * (t1 - td):.1f} ms"
    )
    return served


def _open_window(engine, run: Run, cell: dict, seed: int, vocab: int, spans) -> list:
    it = tr.lm_requests(cell, seed, vocab)
    grace = float(cell["grace_s"])
    active: dict[int, tuple[LmRecord, tr.LmRequest]] = {}
    recs: dict[int, LmRecord] = {}
    served = []  # every request due in the window is admitted in it
    late = []
    nxt = next(it)
    t0 = time.perf_counter()
    run.setup_s = t0 - run.t_start
    t_close = t0 + run.seconds
    deadline = t_close + grace
    with spans("bench.window"):
        while True:
            now = time.perf_counter()
            with spans("bench.submit"):
                while t0 + nxt.due_s <= now:
                    due = t0 + nxt.due_s
                    rid = engine.submit(nxt.prompt, nxt.max_new)
                    rec = LmRecord(due, time.perf_counter(), nxt.max_new, len(nxt.prompt))
                    active[rid] = (rec, nxt)
                    if due < t_close:
                        recs[rid] = rec
                        late.append(rec.submitted - due)
                    nxt = next(it)
            if active:
                with spans("bench.step"):
                    engine.step()
                with spans("bench.poll"):
                    for rid, (rec, req) in list(active.items()):
                        n = len(engine.result(rid))
                        if n > len(rec.token_times):
                            t = time.perf_counter()
                            rec.token_times.extend([t] * (n - len(rec.token_times)))
                        if n == req.max_new:
                            rec.done = True
                            out = engine.release(rid)
                            if rid in recs:
                                served.append((req.prompt, out, True, True))
                            del active[rid]
            else:
                with spans("bench.idle"):
                    time.sleep(max(0.0, min(t0 + nxt.due_s, deadline) - time.perf_counter()))
            now = time.perf_counter()
            if now >= t_close and all(r.done for r in recs.values()):
                break
            if now >= deadline:
                break
    t1 = time.perf_counter()
    for rec in recs.values():
        if not rec.done:
            rec.gave_up = t1
    run.requests = list(recs.values())
    run.window_s = t1 - t0
    run.attempted = len(recs)
    run.failed = sum(not r.done for r in recs.values())
    run.tokens = sum(len(r.token_times) for r in recs.values())
    run.note(
        f"[lm] open loop: {len(recs)} requests due in {run.seconds:.0f} s, "
        f"{run.failed} unfinished; generator late p50 {1e3 * float(np.median(late)):.3f} ms, "
        f"max {1e3 * max(late):.3f} ms"
    )
    run.rids = list(recs)
    return served


def _admit_times(log, base: float, rids: list[int]) -> dict[int, float]:
    """Admission time of each request from the engine's own ``admit``
    event: the event's clock plus ``base``, less the prefill it reports."""
    want = set(rids)
    return {
        ev["rid"]: base + ev["t"] - ev["prefill_s"]
        for ev in log.events
        if ev["event"] == "admit" and ev["rid"] in want
    }


def check(run: Run, config: dict, neutral: dict, samples: list[tuple[np.ndarray, np.ndarray]],
          max_len: int, control: bool = False) -> dict:
    """The reference over each sampled prompt with its served tokens:
    the widest gap of a served token's logit below the reference's best.
    With ``control`` also the same for the token the fp8 control puts
    first at each position, the control in the program's place."""
    from reference import decoder_lm as ref

    b = len(samples)
    tokens = np.zeros((b, max_len), np.int32)
    picks = np.zeros((b, max_len), np.int32)
    valid = np.zeros((b, max_len), bool)
    for i, (prompt, out, *_) in enumerate(samples):
        seq = np.concatenate([prompt, out[:-1]])
        tokens[i, : len(seq)] = seq
        p = len(prompt) - 1
        picks[i, p : p + len(out)] = out
        valid[i, p : p + len(out)] = True
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(neutral, config, jnp.asarray(tokens))
        h_ctl = ref.hidden(neutral, config, jnp.asarray(tokens), mode="fp8") if control else None
        gaps, ctl = ref.logit_gaps(h, neutral["head"], jnp.asarray(picks), h_ctl)
    out = {
        "served_tokens": int(valid.sum()),
        "max_logit_gap": float(np.max(np.asarray(gaps)[valid])),
    }
    if control:
        out["control_max_logit_gap"] = float(np.max(np.asarray(ctl)[valid]))
    return out


@dataclasses.dataclass
class Served:
    cfg: object
    neutral: dict
    engine: object
    log: object  # the engine's TraceLog in traced runs, else None
    base: float  # perf_counter when the TraceLog's clock started


def setup(run: Run) -> Served:
    """Weights from the seed, the engine, and every shape warmed."""
    from repro.obs.trace import TraceLog
    from repro.serve import ServeEngine

    config, cell = run.config, run.cell
    cfg = program_config(config)
    neutral = jax.jit(functools.partial(wts.lm_weights, config))(wts.seed_key(run.seed))
    params = program_params(cfg, neutral, config["weights"]["format"])
    base = time.perf_counter()
    log = TraceLog() if run.traced else None
    engine = ServeEngine(
        cfg,
        params,
        n_slots=int(cell["n_slots"]),
        max_len=int(cell["max_len"]),
        mesh=None,
        trace=log,
    )
    for shape, d in engine.kernel_dispatch().items():
        run.note(f"[lm] kernel_dispatch {shape}: {d['impl']}/{d['source']} x{d['count']}")
    _warm(engine, cell, run.seed, cfg.vocab)
    return Served(cfg, neutral, engine, log, base)


def run(run: Run, *, control: bool = False) -> Run:
    served = setup(run)
    cell = run.cell
    counter = device.CompileCounter()
    spans = tracing.Spans(run.traced)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if run.traced else None
    window = _open_window if cell["mode"] == "open" else _closed_window
    with counter.counting():
        with tracing.capture(trace_dir) if trace_dir else contextlib.nullcontext():
            served_reqs = window(served.engine, run, cell, run.seed, served.cfg.vocab, spans)
    run.note(
        f"[lm] window {run.window_s:.3f} s: {run.tokens} tokens, {run.attempted} requests; "
        f"programs lowered in the window {counter.lowered}, compiled {counter.compiled}"
    )
    run.memory_peak = device.memory_peak_bytes(1)
    if trace_dir:
        run.trace = tracing.reduce(tracing.read_trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if cell["mode"] == "open":
            admits = _admit_times(served.log, served.base, run.rids)
            for rid, rec in zip(run.rids, run.requests):
                rec.admitted = admits.get(rid)
    samples = sample_served(served_reqs, int(cell["check"]["sample_requests"]), run.seed)
    served.engine = None
    gc.collect()
    res = check(run, run.config, served.neutral, samples, int(cell["max_len"]), control=control)
    res["sampled_in_window"] = sum(s[3] for s in samples)
    run.note(
        f"[lm] check: {len(samples)} requests ({res['sampled_in_window']} admitted in the "
        f"window), {res['served_tokens']} served tokens, max logit gap "
        f"{res['max_logit_gap']:.6f}"
        + (f", control {res['control_max_logit_gap']:.6f}" if control else "")
    )
    run.compared["max_logit_gap"] = {
        "value": res["control_max_logit_gap" if control else "max_logit_gap"],
        "limit": float(cell["check"]["max_logit_gap"]),
    }
    run.compared["unfinished_requests"] = {"value": float(run.failed), "limit": 0.0}
    run.check_result = res
    return run


def sample_served(served: list, k: int, seed: int) -> list:
    """``k`` served ``(prompt, tokens, finished, admitted_in_window)``
    entries drawn from the seed, always holding the longest finished one
    and the longest admitted inside the window."""
    sizes = [len(p) + len(o) for p, o, *_ in served]
    must = []
    for i in (2, 3):
        pool = [j for j, s in enumerate(served) if s[i]]
        if not pool:
            what = "finished" if i == 2 else "was admitted"
            raise RuntimeError(f"no request {what} in the window: nothing to check")
        best = max(pool, key=sizes.__getitem__)
        if best not in must:
            must.append(best)
    return [served[i] for i in tr.sample_indices(seed, len(served), k, must)]
