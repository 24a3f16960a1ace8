"""Algorithm 1 — convert-time error compensation, fully vectorized.

The paper's pseudocode walks each filter channel, sorts flip candidates
by cost, and greedily flips weights from their nearest quantization
level to the level on the *other* side of the raw value, as long as the
channel's absolute mean quantization error keeps decreasing.

Here the greedy loop collapses into a closed form: every candidate flip
moves the channel mean in the *same* direction (toward zero), so the
prefix of cost-sorted flips that the paper's loop accepts is exactly the
prefix minimizing ``|mean error|``. That reduces Algorithm 1 to
sort + cumsum + argmin per group, which vmaps over all groups of a
tensor at once — no Python loops, jit-friendly, and it is what lets the
conversion run over billion-parameter LMs in seconds.

Sign conventions: we use ``e = q - w`` (quantization error of the
quantized value). A flip changes the group-mean by ``(q_flip - q)/N``;
candidates are flips whose delta opposes the current mean.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.elp_bsd import ElpBsdFormat
from repro.core.quantize import (
    QuantizedTensor,
    nn_quantize_idx,
    quantize_tensor,
    second_neighbor_idx,
)

Array = jax.Array


def _compensate_one_group(w: Array, nn_idx: Array, levels_j: Array) -> Array:
    """Algorithm 1 for a single group (1-D ``w``). Returns new level idx."""
    n = w.shape[0]
    q = levels_j[nn_idx]
    wf = w.astype(levels_j.dtype)
    mean_err = jnp.mean(q - wf)

    # Flip target: the neighbouring level on the other side of w (edge
    # elements get flip_idx == nn_idx, which zeroes their delta below).
    flip_idx = second_neighbor_idx(wf, levels_j, nn_idx).astype(nn_idx.dtype)
    q_flip = levels_j[flip_idx]
    delta = q_flip - q  # change in group error-sum if flipped

    # Candidates: flips that move the mean toward zero (and are real
    # flips — levels are unique, so delta == 0 iff flip_idx == nn_idx).
    opposes = jnp.sign(delta) == -jnp.sign(mean_err)
    candidate = opposes & (delta != 0.0)

    # Cost (paper: |S - SO|): distance from the raw value to the flip level.
    cost = jnp.where(candidate, jnp.abs(wf - q_flip), jnp.inf)
    # One stable sort carries the deltas along with their costs: a
    # permutation gather and the scatter that inverts it cost more on a
    # TPU than the sort itself.
    cost_sorted, delta_sorted = jax.lax.sort(
        (cost, jnp.where(candidate, delta, 0.0)), num_keys=1, is_stable=True
    )
    prefix_mean = mean_err + jnp.cumsum(delta_sorted) / n
    # |mean| trajectory including "no flips" at position 0
    traj = jnp.abs(jnp.concatenate([mean_err[None], prefix_mean]))
    k_star = jnp.argmin(traj)  # number of accepted flips (first minimum)

    # The first k_star of the stable cost order: every cost below the
    # k_star-th smallest, then the ties at it in index order.
    thr = cost_sorted[jnp.maximum(k_star - 1, 0)]
    below = cost < thr
    tie = cost == thr
    tie_rank = jnp.cumsum(tie) - 1
    first_k = below | (tie & (tie_rank < k_star - jnp.sum(below)))
    accept = candidate & (k_star > 0) & first_k
    return jnp.where(accept, flip_idx, nn_idx)


def compensate_groups(w: Array, nn_idx: Array, levels: np.ndarray) -> Array:
    """Vectorized Algorithm 1 over ``w[G, N]`` groups. Returns idx ``[G, N]``."""
    lv = jnp.asarray(levels, dtype=jnp.float32)
    return jax.vmap(_compensate_one_group, in_axes=(0, 0, None))(w, nn_idx, lv)


def _to_groups(w: Array, group_axes: Sequence[int]) -> tuple[Array, tuple[int, ...], tuple[int, ...]]:
    """Reshape ``w`` to [G, N] where N spans ``group_axes`` (the mean dims)."""
    nd = w.ndim
    group_axes = tuple(a % nd for a in group_axes)
    keep_axes = tuple(a for a in range(nd) if a not in group_axes)
    perm = keep_axes + group_axes
    wt = jnp.transpose(w, perm)
    keep_shape = tuple(w.shape[a] for a in keep_axes)
    grp_shape = tuple(w.shape[a] for a in group_axes)
    g = int(np.prod(keep_shape)) if keep_shape else 1
    n = int(np.prod(grp_shape)) if grp_shape else 1
    return wt.reshape(g, n), perm, wt.shape


def _from_groups(x: Array, perm: tuple[int, ...], t_shape: tuple[int, ...]) -> Array:
    inv = np.argsort(perm)
    return jnp.transpose(x.reshape(t_shape), inv)


def compensate_tensor(
    w: Array,
    qt: QuantizedTensor,
    group_axes: Sequence[int],
) -> QuantizedTensor:
    """Apply Algorithm 1 to a quantized tensor.

    Args:
      w: the raw (unquantized) weights.
      qt: result of nearest-neighbour quantization (same shape).
      group_axes: axes over which the mean error is compensated. For a
        conv ``[H, W, Cin, Cout]`` the paper's intra-channel case is
        ``(0, 1)``; for an LM matmul ``[din, dout]`` use ``(0,)`` to
        compensate each output column's contracting row.

    Returns a new :class:`QuantizedTensor` with flipped levels.
    """
    wg, perm, t_shape = _to_groups(w, group_axes)
    ig, _, _ = _to_groups(qt.level_idx, group_axes)
    new_idx_g = compensate_groups(wg, ig, qt.levels)
    new_idx = _from_groups(new_idx_g, perm, t_shape)
    # repro: noqa[R001] the level table is write-once after quantization
    lv = jnp.asarray(qt.levels)
    return QuantizedTensor(
        values=lv[new_idx].astype(qt.values.dtype),
        level_idx=new_idx.astype(jnp.int32),
        sf=qt.sf,
        levels=qt.levels,
        fmt=qt.fmt,
    )


def compensated_quantize(
    w: Array, fmt: ElpBsdFormat, group_axes: Sequence[int]
) -> QuantizedTensor:
    """Sec. V steps 2-4 in one call: SF → TQL → NN quant → Algorithm 1."""
    qt = quantize_tensor(w, fmt)
    return compensate_tensor(w, qt, group_axes)


def mean_error_report(
    w: Array, qt_before: QuantizedTensor, qt_after: QuantizedTensor, group_axes: Sequence[int]
) -> dict[str, float]:
    """Mean |group-mean error| before/after compensation (benchmark metric)."""
    out = {}
    for tag, qt in (("before", qt_before), ("after", qt_after)):
        eg, _, _ = _to_groups(qt.values - w, group_axes)
        out[tag] = float(jnp.mean(jnp.abs(jnp.mean(eg, axis=1))))
    out["reduction"] = 1.0 - out["after"] / max(out["before"], 1e-30)
    return out
