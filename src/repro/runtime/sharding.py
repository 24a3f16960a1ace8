"""Divisibility-aware sharding rules.

One mechanism makes all ten architectures compile on the same
production mesh: a logical dimension is mapped to a mesh axis only if
its size divides the axis size; otherwise the rule falls through to the
next candidate dimension (or replication). This is what absorbs the
awkward configs — yi-34b's 56 heads, seamless' 256206 vocab, olmoe's
odd expert widths — without per-arch special cases.

Baseline layout (the paper-faithful starting point; §Perf iterates):
  * column-parallel (out-feature) sharding for up-projections / QKV,
  * row-parallel (in-feature) sharding for down-projections,
  * expert sharding for MoE,
  * vocab-parallel embedding / LM head when the vocab divides,
  * batch over ("pod", "data"), KV cache heads→model (falling back to
    head_dim→model, then seq→model),
  * ZeRO-1: optimizer state additionally sharded over the data axes.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

# Weight-name → candidate sharded dim, counted from the END of the shape
# (robust to the [L, ...] scan-stacking axis).
_COL = {"wq", "wk", "wv", "w1", "w3", "xq", "xk", "xv", "in_proj", "w_gate",
        "w_rec", "wa", "wx", "frontend_proj", "router"}
_ROW = {"wo", "w2", "out_proj", "w_out", "xo"}
_EXPERT = {"we1", "we2", "we3"}
_VOCAB = {"embed", "lm_head"}
_REPL = {"ln", "ln1", "ln2", "ln_x", "final_norm", "enc_norm", "gnorm", "conv_b",
         "A_log", "D", "dt_bias", "ba", "bx", "lam", "qnorm", "knorm"}


# PackedWeight aux-array leaves: scale factors (and any stamped
# activation-scale arrays) ride next to the codes under the weight's
# name. Their sharding follows the WEIGHT's rule applied to their own
# (keepdims-broadcastable) shape — see _scale_spec.
_SCALE_LEAVES = {"sf", "scale", "act_scale"}


def _leaf_name(path) -> tuple[str, str | None]:
    """(leaf name, owning-weight name for PackedWeight aux leaves).

    ``codes`` inherits the weight's own name outright (the code array
    mirrors the weight layout); scale leaves keep their name plus the
    parent so :func:`_scale_spec` can pick the matching rule.
    """
    names = []
    for entry in path:
        if hasattr(entry, "key"):
            names.append(str(entry.key))
        elif hasattr(entry, "name"):
            names.append(str(entry.name))
    if not names:
        return "", None
    if names[-1] == "codes" and len(names) >= 2:
        return names[-2], None
    if names[-1] in _SCALE_LEAVES:
        return names[-1], names[-2] if len(names) >= 2 else None
    return names[-1], None


def _try(shape: tuple[int, ...], dim: int, axis: str, size: int) -> P | None:
    """Spec sharding ``dim`` (negative ok) over ``axis`` if it divides."""
    d = dim % len(shape)
    if shape[d] % size == 0 and shape[d] > 0:
        spec = [None] * len(shape)
        spec[d] = axis
        return P(*spec)
    return None


def _scale_spec(
    parent: str | None, shape: tuple[int, ...], mesh: Mesh, model_axis: str
) -> P:
    """Spec for a PackedWeight scale leaf (``sf`` / ``act_scale``).

    Per-channel scales are keepdims-shaped ``[..., 1, N]`` against the
    weight's ``[..., K, N]``: when the weight is column-parallel (out
    dim sharded over model), the scales shard the SAME out dim — each
    shard's codes dequantize against exactly its own scale columns, no
    replication, no gather. Expert stacks shard the expert dim with the
    weight. Everything else (per-tensor/per-slice size-1 dims,
    row-parallel weights whose shards each need every out-channel
    scale) replicates — the size-1 dims fail the divisibility test
    naturally, so a per-slice ``[..., 1, 1]`` falls through to ``P()``.
    """
    msize = mesh.shape[model_axis]
    if parent in _EXPERT and len(shape) >= 3:
        s = _try(shape, len(shape) - 3, model_axis, msize)
        if s is not None:
            return s
    if parent in _COL or parent in _VOCAB:
        s = _try(shape, -1, model_axis, msize)
        if s is not None:
            return s
    return P()


def param_spec(path, shape: tuple[int, ...], mesh: Mesh, model_axis: str = "model") -> P:
    """Baseline tensor-parallel spec for one parameter."""
    name, scale_parent = _leaf_name(path)
    if len(shape) == 0 or min(shape) == 0:
        return P()
    if name in _SCALE_LEAVES:
        return _scale_spec(scale_parent, shape, mesh, model_axis)
    msize = mesh.shape[model_axis]
    if name in _REPL:
        return P()
    if name in _VOCAB:
        # embed [V, D] / lm_head [D, V]: prefer the vocab dim
        vdim = 0 if name == "embed" else len(shape) - 1
        for d in (vdim, 1 - vdim if len(shape) == 2 else vdim):
            s = _try(shape, d, model_axis, msize)
            if s is not None:
                return s
        return P()
    if name in _EXPERT and len(shape) >= 3:
        # [L, E, a, b] (or [E, a, b] unstacked): expert dim
        s = _try(shape, len(shape) - 3, model_axis, msize)
        if s is not None:
            return s
    if name in _COL:
        for d in (-1, -2):
            s = _try(shape, d, model_axis, msize)
            if s is not None:
                return s
        return P()
    if name in _ROW:
        for d in (-2, -1):
            s = _try(shape, d, model_axis, msize)
            if s is not None:
                return s
        return P()
    if name == "conv_w" and len(shape) >= 2:
        s = _try(shape, -1, model_axis, msize)
        if s is not None:
            return s
    return P()


def param_specs(abstract_params: Any, mesh: Mesh, model_axis: str = "model") -> Any:
    """Specs for a whole parameter pytree (from ``jax.eval_shape``)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(path, leaf.shape, mesh, model_axis), abstract_params
    )


def mark_splits(tree: Any, spec_tree: Any, mesh: Mesh) -> Any:
    """Record on each ``PackedWeight`` of a tree placed on ``mesh`` where it is.

    ``spec_tree`` is the tree's :func:`param_specs`. Each packed weight's
    ``mesh`` becomes ``mesh`` and its ``split`` its codes' spec over the
    trailing ``(K-row, N)`` dims — what
    :func:`repro.kernels.ops.sharded_matmul` runs per shard. Stack dims
    in front drop out, so the per-layer slices a scan hands the matmul
    carry it too. Apply it to the placed tree and to its abstract twin
    alike: the mark is static data, so it is part of the tree's
    structure.
    """
    from repro.kernels.ops import PackedWeight

    def mark(leaf, spec):
        if not isinstance(leaf, PackedWeight):
            return leaf
        entries = tuple(spec.codes) + (None,) * (leaf.codes.ndim - len(spec.codes))
        return dataclasses.replace(leaf, split=entries[-2:], mesh=mesh)

    return jax.tree.map(mark, tree, spec_tree, is_leaf=lambda x: isinstance(x, PackedWeight))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes carrying the global batch: ("pod","data") when pod exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _axis_entry(axes: tuple[str, ...]) -> str | tuple[str, ...]:
    """PartitionSpec entry for a set of axes: bare name when singleton.

    ``P("data")`` and ``P(("data",))`` shard identically but compare
    unequal, so downstream spec comparisons want the canonical form.
    """
    return axes[0] if len(axes) == 1 else axes


def input_spec(shape: tuple[int, ...], mesh: Mesh) -> P:
    """Batch-shard inputs over the data axes when the batch divides."""
    ba = batch_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in ba]))
    if len(shape) >= 1 and shape[0] % n == 0 and shape[0] > 0:
        return P(ba, *([None] * (len(shape) - 1)))
    # try data only (pod replicated)
    if "data" in mesh.shape and shape[0] % mesh.shape["data"] == 0:
        return P(("data",), *([None] * (len(shape) - 1)))
    return P(*([None] * len(shape)))


def input_specs_tree(abstract_inputs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda l: input_spec(l.shape, mesh), abstract_inputs)


def cache_spec(
    path,
    shape: tuple[int, ...],
    mesh: Mesh,
    model_axis: str = "model",
    prefer_seq: bool = False,
    paged: bool = False,
) -> P:
    """KV / recurrent-state cache layout.

    [L, B, S, KV, hd]-style tensors: batch→data axes, then heads→model
    if they divide, else head_dim→model, else seq→model. With
    ``prefer_seq`` (flash-decoding layout, DESIGN.md §7) the SEQ dim
    takes the model axis directly. Recurrent states [L, B, ...]:
    batch→data, widest trailing dim→model.

    Paged caches (DESIGN.md §12) are dispatched by leaf name: the
    ``pages`` table and the static ``k_scale``/``v_scale`` tensors are
    replicated (host-refreshed / tiny), and the pool's
    [L, n_pages, page, KV, hd] leaves shard only heads→model (else
    head_dim→model) — never the page dims, which every row's gather
    indexes freely, and never batch, which the pool doesn't have.
    """
    name, _ = _leaf_name(path)
    if name in ("pages", "k_scale", "v_scale"):
        return P(*([None] * len(shape)))
    msize_ = mesh.shape[model_axis]
    if paged:
        spec: list[Any] = [None] * len(shape)
        for d in (len(shape) - 2, len(shape) - 1):  # KV heads, then head_dim
            if d > 1 and shape[d] % msize_ == 0 and shape[d] >= msize_:
                spec[d] = model_axis
                break
        return P(*spec)
    ba = batch_axes(mesh)
    nb = int(np.prod([mesh.shape[a] for a in ba]))
    msize = mesh.shape[model_axis]
    spec: list[Any] = [None] * len(shape)
    if len(shape) >= 2:
        # batch dim is dim 1 for stacked caches, dim 0 for unstacked
        bdim = 1 if len(shape) >= 3 else 0
        if shape[bdim] % nb == 0:
            spec[bdim] = _axis_entry(ba)
        elif "data" in mesh.shape and shape[bdim] % mesh.shape["data"] == 0:
            spec[bdim] = "data"
    if prefer_seq and len(shape) >= 4:
        sdim = len(shape) - 3  # seq dim of [.., B, S, KV, hd]
        if shape[sdim] % msize == 0:
            spec[sdim] = model_axis
            return P(*spec)
    # model axis: later dims first (features, then seq); a KV leaf
    # [L, B, S, KV, hd] takes its heads before head_dim, so each device
    # attends with whole heads, the ones its column-parallel k/v make
    order = list(range(len(shape) - 1, 1, -1))
    if name in ("k", "v", "ks", "vs") and len(shape) == 5:
        order = [3, 4, 2]
    for d in order:
        if spec[d] is None and shape[d] % msize == 0 and shape[d] >= msize:
            spec[d] = model_axis
            break
    return P(*spec)


def cache_specs_tree(
    abstract_cache: Any, mesh: Mesh, model_axis: str = "model", prefer_seq: bool = False
) -> Any:
    paths = jax.tree_util.tree_flatten_with_path(abstract_cache)[0]
    paged = any(_leaf_name(p)[0] == "pages" for p, _ in paths)
    return jax.tree_util.tree_map_with_path(
        lambda path, l: cache_spec(path, l.shape, mesh, model_axis, prefer_seq, paged),
        abstract_cache,
    )


def zero1_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Extend a param spec with data-axis sharding for optimizer state
    (ZeRO-1): the largest yet-unsharded dim divisible by the data axes."""
    ba = batch_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in ba]))
    entries = list(spec) + [None] * (len(shape) - len(spec))
    cands = [d for d in range(len(shape)) if entries[d] is None and shape[d] % n == 0 and shape[d] >= n]
    if not cands:
        return P(*entries)
    d = max(cands, key=lambda i: shape[i])
    entries[d] = _axis_entry(ba)
    return P(*entries)


def zero1_specs_tree(param_spec_tree: Any, abstract_params: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s, l: zero1_spec(s, l.shape, mesh), param_spec_tree, abstract_params
    )


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)
