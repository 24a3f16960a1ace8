"""Device time by named scope and by collective in the meshed decode step.

The tensor-parallel form of :mod:`harness.scopes`: the program the run
traced is the engine's ``decode_greedy`` on the cell's mesh, rebuilt here
from its shapes after the window and compiled once more for the same
devices (persistent cache off), so that each instruction of the trace is
found by name with its ``op_name``. :func:`harness.scopes.hlo_scopes` and
:func:`harness.scopes.scope_time_in` do the reading.

The trace holds every chip's ops and every chip's runs of the program, so
a time summed over ops and divided by the runs is one chip's time per
run.
"""
from __future__ import annotations

import functools
import json
import re
import sys

import jax
import jax.numpy as jnp

from harness import scopes
from harness import weights as wts

# Opcodes of XLA's collectives; each may also come as an async
# ``-start`` / ``-done`` pair.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")
_HEAD = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = ")


def opcode(op: str) -> str | None:
    """The opcode of an HLO instruction's text (``%name = type opcode(...)``);
    the result type may be a tuple with parentheses inside it."""
    m = _HEAD.match(op)
    if not m:
        return None
    rest = op[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    return rest.lstrip().partition("(")[0]


def is_collective(op: str) -> bool:
    """Whether an op of the trace is a collective: by its opcode, or by
    its instruction's name (a fusion XLA names after the collective it
    holds)."""
    m = _HEAD.match(op)
    if not m:
        return False
    names = (opcode(op) or "", m.group(1).lstrip("%"))
    return any(n == c or n.startswith((c + "-", c + ".")) for n in names for c in COLLECTIVES)


def collective_time_in(summary, module_needle: str) -> tuple[float, dict[str, float]]:
    """Device seconds of the collectives inside programs whose name holds
    ``module_needle``, summed over devices, and the same by op."""
    by_op: dict[str, float] = {}
    for mod, ops in summary.ops_in_modules.items():
        if module_needle in mod:
            for op, t in ops.items():
                if is_collective(op):
                    by_op[op] = by_op.get(op, 0.0) + t
    return sum(by_op.values()), by_op


def per_run_ms(run, program: str, scope: str) -> float | None:
    """Milliseconds one chip spends under ``scope`` in one run of
    ``program``; ``None`` where the trace holds no run, no op of it is
    under the scope, or the program rebuilt here does not match the trace."""
    summary = run.trace
    if summary is None:
        return None
    runs, _ = summary.module_time(program)
    if not runs:
        return None
    try:
        found = compiled_scopes(json.dumps(run.config, sort_keys=True),
                                json.dumps(run.cell, sort_keys=True))
    except Exception as e:  # a program without the pieces rebuilt here
        print(f"[scopes_tp] {program}: cannot rebuild the program: {e!r}", file=sys.stderr)
        return None
    t = scopes.scope_time_in(summary, program, scope, found)
    if t is None:
        print(f"[scopes_tp] {program}: the compiled text does not match the trace",
              file=sys.stderr)
        return None
    return 1e3 * t / runs if t > 0 else None


@functools.lru_cache(maxsize=2)
def compiled_scopes(config_json: str, cell_json: str) -> dict:
    """``hlo_scopes`` of the meshed ``decode_greedy``, compiled anew with
    the persistent compilation cache off (its key leaves metadata out)."""
    from jax.experimental.compilation_cache import compilation_cache

    fn, args = lm_decode_program(json.loads(config_json), json.loads(cell_json))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return scopes.hlo_scopes(fn.lower(*args).compile().as_text())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def lm_decode_program(config: dict, cell: dict):
    """The engine's ``decode_greedy`` jit on the cell's mesh and its
    arguments' shapes, as ``ServeEngine(mesh=...)`` builds and calls it:
    the packed tree laid out by the sharding rules, each weight marked
    with its split."""
    from harness import lm_serve, lm_serve_tp
    from repro.models import get_model
    from repro.runtime import sharding as shr
    from repro.serve.engine import ServeSetup, build_greedy_decode

    cfg = lm_serve_tp.program_config(config)
    mesh = lm_serve_tp.mesh_for(cell)
    n_slots, max_len = int(cell["n_slots"]), int(cell["max_len"])
    fmt = config["weights"]["format"]
    aparams = jax.eval_shape(
        lambda k: lm_serve.program_params(cfg, wts.lm_weights(config, k), fmt), wts.seed_key(0)
    )
    aparams = shr.mark_splits(aparams, shr.param_specs(aparams, mesh), mesh)
    api = get_model(cfg)
    setup = ServeSetup(cfg=cfg, mesh=mesh, max_len=max_len, batch=n_slots, moe_impl="ep")
    args = (
        aparams,
        jax.ShapeDtypeStruct((n_slots, 1), jnp.int32),
        jax.eval_shape(lambda: api.init_cache(cfg, n_slots, max_len)),
        jax.ShapeDtypeStruct((n_slots,), jnp.int32),
    )
    return build_greedy_decode(setup, api, aparams=aparams), args
