"""Serve a decoder LM from packed codes tensor-parallel over a model mesh.

The many-chip form of :mod:`harness.lm_serve`, whose weight placement,
warm-up, windows, check and sample it reuses. What it adds:

* a mesh over the cell's chips (``"mesh"`` in the cell file, ``data`` by
  ``model``);
* the neutral weights drawn from the seed straight into the shardings the
  engine gives the program's tree, so no chip ever holds the whole tree
  and no weight is held twice (the reference reads the same arrays);
* a ``repro.serve.ServeEngine`` on that mesh, driven through its public
  API only, as in :mod:`harness.lm_serve`;
* the largest peak memory of the mesh's chips;
* a check that the program's RMSNorm epsilon is the configuration's
  ``rms_norm_eps``.

The plain reference runs on the same mesh: its arrays are sharded, so
XLA partitions each of its layers over the chips.
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
from jax.sharding import Mesh, PartitionSpec as P

from harness import device, lm_serve, tracing
from harness import weights as wts
from harness.counts_tp import mesh_chips
from harness.record import Run

MESH_AXES = ("data", "model")


def program_config(config: dict):
    """The program's config, held to the file's sizes and its norm epsilon."""
    cfg = lm_serve.program_config(config)
    if cfg.norm_eps != config["rms_norm_eps"]:
        raise ValueError(
            f"the program's {cfg.name} normalises with eps {cfg.norm_eps}, "
            f"the configuration file states {config['rms_norm_eps']}"
        )
    return cfg


def mesh_for(cell: dict) -> Mesh:
    """The cell's ``data`` x ``model`` mesh over the first devices."""
    shape = tuple(int(cell["mesh"][a]) for a in MESH_AXES)
    devs = jax.devices()
    if len(devs) < mesh_chips(cell):
        raise RuntimeError(f"a {shape} mesh needs {mesh_chips(cell)} devices, found {len(devs)}")
    return Mesh(np.asarray(devs[: mesh_chips(cell)]).reshape(shape), MESH_AXES)


def neutral_specs(config: dict, cfg, mesh: Mesh) -> dict:
    """Specs of the neutral weights: each array where the engine puts the
    program's leaf that holds it (codes, embedding, head), the per-layer
    scales on every chip."""
    from repro.runtime import sharding as shr

    fmt = config["weights"]["format"]
    draw = functools.partial(wts.lm_weights, config)
    aneutral = jax.eval_shape(draw, wts.seed_key(0))
    apacked = jax.eval_shape(lambda n: lm_serve.program_params(cfg, n, fmt), aneutral)
    specs = shr.param_specs(apacked, mesh)
    return {
        "embed": specs["embed"],
        "head": specs["lm_head"],
        "layers": {
            name: {"codes": specs["blocks"][name].codes, "scale": P()}
            for name in aneutral["layers"]
        },
    }


def setup(run: Run) -> lm_serve.Served:
    """Weights from the seed on the mesh, the engine, and every shape warmed."""
    from repro.obs.trace import TraceLog
    from repro.runtime import sharding as shr
    from repro.serve import ServeEngine

    config, cell = run.config, run.cell
    cfg = program_config(config)
    mesh = mesh_for(cell)
    draw = jax.jit(
        functools.partial(wts.lm_weights, config),
        out_shardings=shr.named(mesh, neutral_specs(config, cfg, mesh)),
    )
    neutral = draw(wts.seed_key(run.seed))
    params = lm_serve.program_params(cfg, neutral, config["weights"]["format"])
    base = time.perf_counter()
    log = TraceLog() if run.traced else None
    engine = ServeEngine(
        cfg,
        params,
        n_slots=int(cell["n_slots"]),
        max_len=int(cell["max_len"]),
        mesh=mesh,
        trace=log,
    )
    run.note(f"[lm_tp] mesh {dict(mesh.shape)}")
    for shape, d in engine.kernel_dispatch().items():
        run.note(
            f"[lm_tp] kernel_dispatch {shape}: {d['impl']}/{d['source']} x{d['count']} "
            f"split {d.get('split')} local {d.get('local')} blocks {d['blocks']} "
            f"operand {d['operand']}"
        )
    lm_serve._warm(engine, cell, run.seed, cfg.vocab)
    return lm_serve.Served(cfg, neutral, engine, log, base)


def run(run: Run, *, control: bool = False) -> Run:
    served = setup(run)
    cell = run.cell
    counter = device.CompileCounter()
    spans = tracing.Spans(run.traced)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if run.traced else None
    window = lm_serve._open_window if cell["mode"] == "open" else lm_serve._closed_window
    with counter.counting():
        with tracing.capture(trace_dir) if trace_dir else contextlib.nullcontext():
            served_reqs = window(served.engine, run, cell, run.seed, served.cfg.vocab, spans)
    run.note(
        f"[lm_tp] window {run.window_s:.3f} s: {run.tokens} tokens, {run.attempted} requests; "
        f"programs lowered in the window {counter.lowered}, compiled {counter.compiled}"
    )
    run.memory_peak = device.memory_peak_bytes(mesh_chips(cell))
    if trace_dir:
        run.trace = tracing.reduce(tracing.read_trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if cell["mode"] == "open":
            admits = lm_serve._admit_times(served.log, served.base, run.rids)
            for rid, rec in zip(run.rids, run.requests):
                rec.admitted = admits.get(rid)
    samples = lm_serve.sample_served(served_reqs, int(cell["check"]["sample_requests"]), run.seed)
    served.engine = None
    gc.collect()
    res = lm_serve.check(
        run, run.config, served.neutral, samples, int(cell["max_len"]), control=control
    )
    res["sampled_in_window"] = sum(s[3] for s in samples)
    run.note(
        f"[lm_tp] check: {len(samples)} requests ({res['sampled_in_window']} admitted in the "
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
