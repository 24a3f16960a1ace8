"""Run the packed ELP_BSD serve and CNN paths once on a TPU and check them.

    python chip_smoke.py            # one chip: VGG-16 (cnn) and qwen3-8b (lm)
    python chip_smoke.py --chips 4  # qwen3-8b on a 4-chip mesh against one chip

Weights are random, made from ``--seed``; nothing is read from disk.
Phases, each printing its own lines and time:

* ``devices`` — platform, device kind and count. Without a TPU the run
  stops here with a non-zero exit: there is no CPU fallback.
* ``cnn`` — VGG-16 at 224x224 (the paper's Sec. VI network) converted by
  ``repro.api.quantize`` to ``elp_bsd_a4`` codes, run at batch 8 with the
  default dispatch and at batch 1 through the packed Pallas conv kernel,
  each compared with a float32 forward of the decoded weights.
* ``lm`` — qwen3-8b at full width (36 layers, d_model 4096) on elp4
  nibble codes, packed one float leaf at a time, served by a one-device
  ``ServeEngine``. Prefill and first decode-step logits are compared
  with the same packed tree on the plain XLA datapath at highest
  precision. Every packed decode GEMM must resolve to a Pallas kernel,
  and the compiled decode step must call one (``tpu_custom_call``).
* ``lm_sharded`` (``--chips 4`` only, and then the only phase) — the
  same model on an engine whose mesh spans 4 chips, whose GEMMs must
  all run a Pallas kernel on each chip's shard (``tpu_custom_call`` in
  the compiled decode step), against the one-chip engine in the same
  process.

The last line of stdout is ``{"ok": true, "device": {...}}``. A failed
check raises, so the script exits non-zero and never prints that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.api_schemes import init_packed_lm_params  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels.ops import dequantize_tree, force_impl  # noqa: E402
from repro.models import cnn  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.runtime.quantized_params import packed_bytes  # noqa: E402
from repro.serve import ServeEngine, ServeSetup, build_serve_fns  # noqa: E402

# Logits error is max|out - ref| / max|ref|. On a TPU the default
# precision runs f32 matmuls and convs as one bf16 pass (8-bit
# mantissa, 2^-9 relative rounding per operand); over VGG-16's 16
# layers that adds up to about 1e-2 of the logit scale. A wrong decode
# (nibble order, scale, level table) is off by O(1).
CNN_TOL = 5e-2
# qwen3-8b runs in bf16: every matmul output is rounded to bf16, so a
# last-bit difference in f32 accumulation order between the kernels and
# the XLA dot can flip one bf16 rounding (2^-8 relative), and 36 layers
# carry such flips into the logits. A wrong decode is off by O(1).
LM_TOL = 5e-2


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(float(np.max(np.abs(ref))), 1e-30))


KERNEL_IMPLS = ("pallas", "pallas_fused")


def check_kernels(tag: str, dispatch: dict, hlo: str) -> None:
    """One device: every packed decode GEMM resolves to a Pallas kernel
    and the compiled decode step calls one. A fall back to XLA fails."""
    _check(bool(dispatch), f"[{tag}] no packed GEMM in the kernel dispatch report")
    xla = {s: d for s, d in dispatch.items() if d["impl"] not in KERNEL_IMPLS}
    _check(not xla, f"[{tag}] GEMMs not on a Pallas kernel: {xla}")
    _check("tpu_custom_call" in hlo, f"[{tag}] compiled decode step has no tpu_custom_call")


def check_sharded(tag: str, dispatch: dict, hlo: str) -> None:
    """A mesh over several devices: every packed GEMM runs a Pallas
    kernel on each device's shard, in the report and in the compiled
    decode step alike."""
    check_kernels(tag, dispatch, hlo)
    odd = {s: d for s, d in dispatch.items() if d["source"] != "sharded"}
    _check(not odd, f"[{tag}] GEMMs not run per shard: {odd}")


def _peak_bytes(device) -> tuple[int | None, int | None]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


def phase_devices(chips: int) -> dict:
    """Name the devices JAX sees; stop unless there are ``chips`` TPUs."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"[devices] platform={info['platform']} kind={info['kind']} count={info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"[devices] no TPU found: JAX sees {info['platform']} devices only")
    if info["count"] < chips:
        raise SystemExit(f"[devices] {chips} chips asked for, {info['count']} found")
    return info


def phase_cnn(spec: cnn.CnnSpec, *, seed: int, batch: int) -> dict:
    """Packed CNN forward at ``batch`` (default dispatch) and at batch 1
    (Pallas conv kernel) against a float32 forward of the decoded codes."""
    t0 = time.perf_counter()
    params = cnn.init_params(spec, jax.random.PRNGKey(seed))
    qm = api.quantize(spec, params, api.QuantScheme(fmt="elp_bsd_a4"))
    del params
    jax.block_until_ready(qm.params)
    print(
        f"[cnn] {spec.name} {spec.input_hw}x{spec.input_hw}: elp_bsd_a4 codes "
        f"{packed_bytes(qm.params)} B, converted in {time.perf_counter() - t0:.1f} s"
    )
    x = jax.random.normal(
        jax.random.PRNGKey(seed + 1), (batch, spec.input_hw, spec.input_hw, spec.input_ch)
    )
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, v: cnn.forward(p, spec, v))(dequantize_tree(qm.params), x)
    out = {}
    # qm.forward's default CNN dispatch: XLA's conv on decoded codes, the
    # fused decode kernel for the fc layers
    for label, xs, impl in (("default", x, None), ("pallas", x[:1], "pallas")):
        t = time.perf_counter()
        logits = jax.block_until_ready(qm.forward(xs, impl=impl))
        err = _rel_err(logits, ref[: xs.shape[0]])
        top1 = float(np.mean(np.argmax(logits, -1) == np.argmax(ref[: xs.shape[0]], -1)))
        print(
            f"[cnn] batch={xs.shape[0]} impl={impl or 'auto (default)'}: max rel err {err:.3e} "
            f"(tol {CNN_TOL}), top-1 agreement {top1:.3f}, "
            f"{time.perf_counter() - t:.1f} s incl. compile"
        )
        _check(bool(np.all(np.isfinite(logits))), f"[cnn] {label}: non-finite logits")
        _check(err <= CNN_TOL, f"[cnn] {label}: rel err {err:.3e} > {CNN_TOL}")
        out[label] = {"rel_err": err, "top1": top1}
    print(f"[cnn] done in {time.perf_counter() - t0:.1f} s")
    return out


def lm_requests(cfg, *, seed: int, prompt_lens, max_new: int) -> list:
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab, n, dtype=np.int32), max_new) for n in prompt_lens]


def first_step_logits(params, setup: ServeSetup, tokens: np.ndarray):
    """Prefill ``tokens`` ``[B, S]`` and decode one greedy step: the two
    logits arrays ``[B, 1, V]``. Traced afresh on every call, so an
    enclosing :func:`~repro.kernels.ops.force_impl` takes effect."""
    from repro.models import get_model

    prefill, decode = build_serve_fns(setup, aparams=jax.eval_shape(lambda: params))
    cache = get_model(setup.cfg).init_cache(setup.cfg, setup.batch, setup.max_len)
    pre, cache = prefill(params, {"tokens": jnp.asarray(tokens)}, cache)
    nxt = jnp.argmax(pre, -1).astype(jnp.int32)
    dec, _ = decode(params, nxt, cache, jnp.full((setup.batch,), tokens.shape[1], jnp.int32))
    return np.asarray(pre, np.float32), np.asarray(dec, np.float32)


def _compare_logits(tag: str, got, want) -> dict:
    out = {}
    for step, a, b in zip(("prefill", "decode"), got, want):
        err = _rel_err(a, b)
        agree = float(np.mean(np.argmax(a, -1) == np.argmax(b, -1)))
        print(
            f"[{tag}] {step} logits: max rel err {err:.3e} (tol {LM_TOL}), "
            f"argmax agreement {agree:.3f}"
        )
        _check(bool(np.all(np.isfinite(a))), f"[{tag}] {step}: non-finite logits")
        _check(err <= LM_TOL, f"[{tag}] {step}: rel err {err:.3e} > {LM_TOL}")
        out[step] = err
    return out


def make_lm(cfg, *, seed: int):
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_packed_lm_params(cfg, jax.random.PRNGKey(seed), "elp4"))
    print(
        f"[lm] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}; elp4 packed tree "
        f"{packed_bytes(params)} B, made leaf by leaf in {time.perf_counter() - t0:.1f} s"
    )
    return params


def serve_timed(tag: str, engine: ServeEngine, requests) -> list:
    t = time.perf_counter()
    outs = engine.serve(requests)
    dt = time.perf_counter() - t
    n = sum(len(o) for o in outs)
    print(
        f"[{tag}] served {len(requests)} requests (prompts {[len(p) for p, _ in requests]}), "
        f"{n} tokens in {dt:.1f} s = {n / dt:.1f} tokens/s "
        "(one unrepeated smoke timing incl. compilation, not a benchmark)"
    )
    return outs


def phase_lm(cfg, *, seed: int, n_slots: int, prompt_lens, max_new: int, check_len: int) -> dict:
    """Serve ``cfg`` from elp4 codes on one device and check its logits."""
    t0 = time.perf_counter()
    params = make_lm(cfg, seed=seed)
    requests = lm_requests(cfg, seed=seed, prompt_lens=prompt_lens, max_new=max_new)
    tokens = np.stack([p[:check_len] for p, _ in requests[:n_slots]])
    setup = ServeSetup(cfg=cfg, mesh=None, max_len=check_len + 1, batch=n_slots)
    got = first_step_logits(params, setup, tokens)
    with force_impl("xla"), jax.default_matmul_precision("highest"):
        want = first_step_logits(params, setup, tokens)
    errs = _compare_logits("lm", got, want)

    engine = ServeEngine(
        cfg, params, n_slots=n_slots, max_len=max(prompt_lens) + max_new, mesh=None
    )
    dispatch = engine.kernel_dispatch()
    for shape, d in dispatch.items():
        print(f"[lm] kernel_dispatch {shape}: {d}")
    hlo = engine.compiled_decode().as_text()
    print(f"[lm] compiled decode step has tpu_custom_call: {'tpu_custom_call' in hlo}")
    outs = serve_timed("lm", engine, requests)
    _check(
        all(len(o) == max_new for o in outs), f"[lm] expected {max_new} tokens per request"
    )
    peak, limit = _peak_bytes(jax.devices()[0])
    print(f"[lm] peak_bytes_in_use {peak} of bytes_limit {limit}")
    if peak is not None and limit is not None:
        _check(peak < limit, f"[lm] peak {peak} B reaches the device limit {limit} B")
    check_kernels("lm", dispatch, hlo)
    print(f"[lm] done in {time.perf_counter() - t0:.1f} s")
    return {"errs": errs, "dispatch": dispatch}


def phase_lm_sharded(
    cfg, *, seed: int, n_slots: int, prompt_lens, max_new: int, check_len: int, chips: int
) -> dict:
    """The engine on a mesh over ``chips`` devices against a one-device
    engine serving the same requests from the same packed tree."""
    from repro.runtime.elastic import make_mesh

    t0 = time.perf_counter()
    params = make_lm(cfg, seed=seed)
    requests = lm_requests(cfg, seed=seed, prompt_lens=prompt_lens, max_new=max_new)
    tokens = np.stack([p[:check_len] for p, _ in requests[:n_slots]])
    max_len = max(prompt_lens) + max_new
    mesh = make_mesh(jax.devices()[:chips])
    sharded = ServeEngine(cfg, params, n_slots=n_slots, max_len=max_len, mesh=mesh)
    print(f"[lm_sharded] mesh {dict(mesh.shape)}")
    dispatch = sharded.kernel_dispatch()
    for shape, d in dispatch.items():
        print(f"[lm_sharded] kernel_dispatch {shape}: {d}")
    hlo = sharded.compiled_decode().as_text()
    print(f"[lm_sharded] compiled decode step has tpu_custom_call: {'tpu_custom_call' in hlo}")
    check_sharded("lm_sharded", dispatch, hlo)
    got = first_step_logits(
        sharded.params, ServeSetup(cfg=cfg, mesh=mesh, max_len=check_len + 1, batch=n_slots), tokens
    )
    want = first_step_logits(
        params, ServeSetup(cfg=cfg, mesh=None, max_len=check_len + 1, batch=n_slots), tokens
    )
    errs = _compare_logits("lm_sharded", got, want)
    outs = serve_timed("lm_sharded", sharded, requests)
    del sharded
    one = ServeEngine(cfg, params, n_slots=n_slots, max_len=max_len, mesh=None)
    ref = serve_timed("lm_one_chip", one, requests)
    match = float(np.mean(np.concatenate([a == b for a, b in zip(outs, ref)])))
    print(f"[lm_sharded] token match against the one-chip engine: {match:.3f}")
    for i, d in enumerate(jax.devices()[:chips]):
        peak, limit = _peak_bytes(d)
        print(f"[lm_sharded] device {i} peak_bytes_in_use {peak} of bytes_limit {limit}")
    print(f"[lm_sharded] done in {time.perf_counter() - t0:.1f} s")
    return {"errs": errs, "token_match": match}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    t0 = time.perf_counter()
    info = phase_devices(args.chips)
    lm = dict(
        seed=args.seed,
        n_slots=8,
        prompt_lens=(128, 1024, 512, 128, 1024, 512, 128, 512),
        max_new=32,
        check_len=128,
    )
    if args.chips == 1:
        phase_cnn(cnn.VGG16, seed=args.seed, batch=8)
        phase_lm(get_config("qwen3_8b"), **lm)
    else:
        phase_lm_sharded(get_config("qwen3_8b"), chips=args.chips, **lm)
    print(f"[total] {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
