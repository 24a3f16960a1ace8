"""Device time by the program's named scopes, in a traced run.

The program opens a ``jax.named_scope`` around each layer (``attn``,
``attn_core``, ``mlp``, ``lm_head``, ``elp_bsd_matmul``,
``elp_bsd_conv``, ``elp_bsd_decode``, ``conv{i}``, ``fc{i}``). XLA
keeps the stack of scopes an instruction was traced under in its
metadata, ``op_name="jit(f)/while/body/attn/attn_core/dot_general"``.
On a TPU the profiler shows it as the ``tf_op`` stat of each op's event
metadata; the trace reduction (``tracing.py``) keeps only the op's
name, the text of its HLO instruction without the metadata.

So the scopes are taken from the compiled program's own text: the
program the run traced is lowered and compiled once more, from its
shapes alone, after the window (seconds), and each instruction's
``%name`` is mapped to its ``op_name``. XLA names instructions the same
way in every compile of one program, on this host or another. Every
op of the trace must be found there by name with the same result
type; where one is not, the reading is refused rather than guessed.

A fusion carries the metadata of its root as a rule, so an op counts
under the scope of its fusion's root: a decode that XLA fuses into a
dot's operand counts under the dot's scope.
"""
from __future__ import annotations

import functools
import json
import re
import sys

import jax
import jax.numpy as jnp

from harness import weights as wts
from harness.tracing import Summary

# ``%name = type`` of an instruction: a line of a program's text, or the
# name of an op event in a TPU trace
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(text: str) -> dict[str, tuple[str, tuple[str, ...]]]:
    """``%name -> (result type, op_name components)`` of every
    instruction in a compiled program's text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line)
            out[m.group(1)] = (m.group(2), tuple(n.group(1).split("/")) if n else ())
    return out


def scope_time_in(summary: Summary, module_needle: str, scope: str,
                  scopes: dict[str, tuple[str, tuple[str, ...]]]) -> float | None:
    """Device seconds of the ops inside programs whose name holds
    ``module_needle`` whose ``op_name`` has ``scope`` as a component;
    ops are attributed to programs as ``Summary.ops_in_modules`` does.
    ``None`` when an op of those programs is not in ``scopes`` (by
    instruction name and result type): then ``scopes`` is not the
    program the trace ran."""
    total = 0.0
    for mod, ops in summary.ops_in_modules.items():
        if module_needle not in mod:
            continue
        for op, t in ops.items():
            m = _INSTR.match(op)
            got = scopes.get(m.group(1)) if m else None
            if got is None or got[0] != m.group(2):
                return None
            if scope in got[1]:
                total += t
    return total


def per_run_ms(run, program: str, scope: str) -> float | None:
    """Milliseconds that one run of ``program`` (the traced run's
    decode step or forward) spends under ``scope``; ``None`` where the
    trace holds no such run, none of its ops is under the scope, or the
    compiled program's text does not match the trace."""
    summary = run.trace
    if summary is None:
        return None
    runs, _ = summary.module_time(program)
    if not runs:
        return None
    try:
        scopes = compiled_scopes(
            run.config["driver"], json.dumps(run.config, sort_keys=True),
            json.dumps(run.cell, sort_keys=True),
        )
    except Exception as e:  # a program without the pieces rebuilt here
        print(f"[scopes] {program}: cannot rebuild the program: {e!r}", file=sys.stderr)
        return None
    t = scope_time_in(summary, program, scope, scopes)
    if t is None:
        print(f"[scopes] {program}: the compiled text does not match the trace",
              file=sys.stderr)
        return None
    return 1e3 * t / runs if t > 0 else None


@functools.lru_cache(maxsize=2)
def compiled_scopes(driver: str, config_json: str, cell_json: str) -> dict:
    """``hlo_scopes`` of the program a cell's driver runs, compiled anew.

    The persistent compilation cache is off for this compile: its key
    leaves metadata out, so it could hand back the executable of
    another tree with that tree's scopes."""
    from jax.experimental.compilation_cache import compilation_cache

    build = {"lm_serve": lm_decode_program, "cnn_forward": cnn_forward_program}[driver]
    fn, args = build(json.loads(config_json), json.loads(cell_json))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return hlo_scopes(fn.lower(*args).compile().as_text())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def lm_decode_program(config: dict, cell: dict):
    """The engine's ``decode_greedy`` jit and its arguments' shapes, as
    a one-device ``ServeEngine`` with the cell's slots and length builds
    and calls it (``ServeEngine.compiled_decode``)."""
    from harness import lm_serve
    from repro.models import get_model
    from repro.serve.engine import ServeSetup, build_greedy_decode

    cfg = lm_serve.program_config(config)
    n_slots, max_len = int(cell["n_slots"]), int(cell["max_len"])
    fmt = config["weights"]["format"]
    aparams = jax.eval_shape(
        lambda k: lm_serve.program_params(cfg, wts.lm_weights(config, k), fmt), wts.seed_key(0)
    )
    api = get_model(cfg)
    setup = ServeSetup(cfg=cfg, mesh=None, max_len=max_len, batch=n_slots, moe_impl="dense")
    args = (
        aparams,
        jax.ShapeDtypeStruct((n_slots, 1), jnp.int32),
        jax.eval_shape(lambda: api.init_cache(cfg, n_slots, max_len)),
        jax.ShapeDtypeStruct((n_slots,), jnp.int32),
    )
    return build_greedy_decode(setup, api, aparams=aparams), args


def cnn_forward_program(config: dict, cell: dict):
    """The harness's ``cnn_forward`` jit of ``models/cnn.forward`` and its
    arguments' shapes at the cell's batch."""
    from harness import cnn_forward as cf
    from repro.models import cnn

    spec = cf.program_spec(config)
    fmt = config["weights"]["format"]
    aparams = jax.eval_shape(
        lambda k: cf.program_params(spec, wts.cnn_weights(config, k), fmt), wts.seed_key(0)
    )
    hw, ch = config["input_hw"], config["input_channels"]
    x = jax.ShapeDtypeStruct((int(cell["batch"]), hw, hw, ch), jnp.float32)

    def cnn_forward(p, x):
        return cnn.forward(p, spec, x)

    return jax.jit(cnn_forward), (aparams, x)
