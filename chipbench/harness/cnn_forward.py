"""Run a packed CNN's forward pass, one client, one call at a time.

Set-up draws the neutral weights from the seed on the device, places
them in the program's packed tree (the tree ``repro.api.quantize``
makes) and compiles ``models/cnn.forward`` with its default dispatch,
the call ``QuantizedModel.forward`` makes, at the cell's one batch
size. In the window each call makes a new batch of seeded images on the
device, runs the forward and fetches the logits to the host before the
next call goes. After the window a seeded sample of the calls is
checked against the plain reference: the worst image's largest logit
error over its largest reference logit. With ``control`` the
reference's lower-precision control takes the program's place in that
comparison.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import shutil
import tempfile
import time

import jax
import numpy as np

from harness import device, tracing
from harness import traffic as tr
from harness import weights as wts
from harness.record import Run


def program_spec(config: dict):
    """The program's own network description, held to the file's layers."""
    from repro.models import cnn

    spec = getattr(cnn, config["program_spec"])
    layers = []
    for layer in spec.layers:
        if isinstance(layer, cnn.Conv):
            layers.append(["conv", layer.ch, layer.k, layer.stride])
        elif isinstance(layer, cnn.Pool):
            layers.append(["pool", layer.k, layer.stride])
        else:
            layers.append(["fc", layer.out])
    if (
        layers != config["layers"]
        or spec.input_hw != config["input_hw"]
        or spec.input_ch != config["input_channels"]
    ):
        raise ValueError(f"the program's {spec.name} departs from the configuration file")
    return spec


def program_params(spec, neutral: dict, fmt: str) -> dict:
    """Place the neutral weights into the tree the program's converter
    makes (``api_schemes.pack_cnn_params``): ``conv{i}_w``/``fc{i}_w``
    as ``PackedWeight`` leaves, ``conv{i}_b``/``fc{i}_b`` as float32."""
    from repro.api_schemes import pack_cnn_params
    from repro.kernels.ops import PackedWeight
    from repro.models import cnn

    apacked = jax.eval_shape(
        lambda: pack_cnn_params(cnn.init_params(spec, jax.random.PRNGKey(0)), fmt)
    )
    names = sorted({k[:-2] for k in apacked}, key=lambda n: int(n.lstrip("convfc")))
    if len(names) != len(neutral["layers"]):
        raise ValueError(
            f"the program has {len(names)} weight layers, "
            f"the benchmark draws {len(neutral['layers'])}"
        )
    out = {}
    for name, src in zip(names, neutral["layers"]):
        w, b = apacked[name + "_w"], apacked[name + "_b"]
        if not isinstance(w, PackedWeight) or w.codes.shape != src["codes"].shape:
            raise ValueError(f"{name}: the program's codes are not the drawn {src['codes'].shape}")
        out[name + "_w"] = PackedWeight(
            src["codes"], src["scale"].reshape(w.sf.shape), *w.tree_flatten()[1]
        )
        if b.shape != src["bias"].shape:
            raise ValueError(f"{name}: bias {b.shape}, drawn {src['bias'].shape}")
        out[name + "_b"] = src["bias"].astype(b.dtype)
    return out


def check(config: dict, neutral: dict, image_fn, picks: list[tuple[int, np.ndarray]],
          block: int, control: bool = False) -> dict:
    """Worst image's ``max |out - ref| / max |ref|`` over the sampled
    calls, the reference computed ``block`` images at a time. With
    ``control`` also the same for the fp8 control in the program's
    place."""
    from reference import cnn as ref

    worst, worst_ctl = 0.0, 0.0
    for index, got in picks:
        x = image_fn(index)
        for s in range(0, x.shape[0], block):
            want = np.asarray(ref.forward(neutral, config, x[s : s + block]))
            err = np.max(np.abs(got[s : s + block] - want), axis=1) / np.max(np.abs(want), axis=1)
            worst = max(worst, float(np.max(err)))
            if control:
                c = np.asarray(ref.forward(neutral, config, x[s : s + block], mode="fp8"))
                e = np.max(np.abs(c - want), axis=1) / np.max(np.abs(want), axis=1)
                worst_ctl = max(worst_ctl, float(np.max(e)))
    out = {"images": sum(g.shape[0] for _, g in picks), "max_rel_err": worst}
    if control:
        out["control_max_rel_err"] = worst_ctl
    return out


def run(run: Run, *, control: bool = False) -> Run:
    from repro.models import cnn

    config, cell, seed = run.config, run.cell, run.seed
    spec = program_spec(config)
    key = wts.seed_key(seed)
    neutral = jax.jit(functools.partial(wts.cnn_weights, config))(key)
    params = program_params(spec, neutral, config["weights"]["format"])
    batch = int(cell["batch"])
    image_key = jax.random.fold_in(key, 1)
    image_fn = jax.jit(
        lambda i: wts.images(image_key, i, batch, config["input_hw"], config["input_channels"])
    )

    def cnn_forward(p, x):
        return cnn.forward(p, spec, x)

    fwd = jax.jit(cnn_forward)
    np.asarray(fwd(params, image_fn(0)))
    device.sync()
    counter = device.CompileCounter()
    spans = tracing.Spans(run.traced)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if run.traced else None
    outputs = []
    call_s = []
    run.setup_s = time.perf_counter() - run.t_start

    def window():
        t0 = time.perf_counter()
        t_end = t0 + run.seconds
        i = 0
        with spans("bench.window"):
            while time.perf_counter() < t_end:
                i += 1
                tc = time.perf_counter()
                with spans("bench.call"):
                    y = fwd(params, image_fn(i))
                with spans("bench.fetch"):
                    outputs.append(np.asarray(y))
                call_s.append(time.perf_counter() - tc)
        t1 = time.perf_counter()
        run.window_s = t1 - t0
        run.calls = i
        run.images = i * batch

    with counter.counting():
        with tracing.capture(trace_dir) if trace_dir else contextlib.nullcontext():
            window()
    run.attempted = run.calls
    run.note(
        f"[cnn] window {run.window_s:.3f} s: {run.calls} calls of batch {batch}; "
        f"programs lowered in the window {counter.lowered}, compiled {counter.compiled}; "
        f"median call {1e3 * float(np.median(call_s)):.2f} ms, longest "
        + ", ".join(f"{1e3 * x:.1f}" for x in sorted(call_s, reverse=True)[:3])
        + " ms"
    )
    run.memory_peak = device.memory_peak_bytes(1)
    if trace_dir:
        run.trace = tracing.reduce(tracing.read_trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    k = int(cell["check"]["sample_calls"])
    idx = tr.sample_indices(seed, run.calls, k, [run.calls - 1])
    picks = [(i + 1, outputs[i]) for i in idx]
    del outputs, fwd, params
    gc.collect()
    with jax.default_matmul_precision("highest"):
        res = check(config, neutral, image_fn, picks, int(cell["check"]["block"]), control)
    run.note(
        f"[cnn] check: {res['images']} images, max rel err {res['max_rel_err']:.6e}"
        + (f", control {res['control_max_rel_err']:.6e}" if control else "")
    )
    run.compared["max_rel_err"] = {
        "value": res["control_max_rel_err" if control else "max_rel_err"],
        "limit": float(cell["check"]["max_rel_err"]),
    }
    run.check_result = res
    return run
