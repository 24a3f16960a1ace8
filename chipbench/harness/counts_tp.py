"""Operations and bytes of one chip's share of a tensor-parallel decode step.

On a model mesh of ``chips`` devices each chip computes a slice of every
packed GEMM of a decoder layer, as the Megatron layout splits it:

* column-parallel ``wq``, ``wk``, ``wv``, ``w1``, ``w3``: all of K, a
  ``1/chips`` slice of N (its heads, its FFN columns);
* row-parallel ``wo``, ``w2``: a ``1/chips`` slice of K, all of N, and a
  sum of the partial outputs across the chips after.

Each chip moves its own codes, the scale, its activations in (``[m,
K_local]``) and its output (``[m, N_local]``). These are the yardstick's
own counts, from the configuration's sizes; the shards of one GEMM add up
to :mod:`harness.counts`' whole GEMM in operations and in code bytes.
"""
from __future__ import annotations

from harness.counts import BITS_PER_WEIGHT, decoder_layer_gemms, gemm_ops, packed_gemm_bytes

ROW_PARALLEL = ("wo", "w2")


def local_layer_gemms(cfg: dict, chips: int) -> list[tuple[str, str, int, int]]:
    """``(name, split, K_local, N_local)`` of each packed GEMM of one
    decoder layer on one chip of ``chips``; ``split`` is ``"row"`` or
    ``"col"``."""
    out = []
    for name, k, n in decoder_layer_gemms(cfg):
        split = "row" if name in ROW_PARALLEL else "col"
        whole = k if split == "row" else n
        if whole % chips:
            raise ValueError(f"{name}: {whole} does not split over {chips} chips")
        if split == "row":
            out.append((name, split, k // chips, n))
        else:
            out.append((name, split, k, n // chips))
    return out


def decode_step_gemm_bounds(cfg: dict, m: int, peaks: dict, chips: int) -> dict:
    """Least time of one chip's share of a decode step's packed GEMMs at
    ``m`` live rows: per local GEMM the larger of its operations over the
    chip's bf16 peak and its bytes over the chip's HBM bandwidth, summed
    over every layer. Returns the sum and its two parts."""
    bits = BITS_PER_WEIGHT[cfg["weights"]["format"]]
    t_total = t_ops = t_bytes = 0.0
    for _, _, k, n in local_layer_gemms(cfg, chips):
        t_o = gemm_ops(m, k, n) / peaks["bf16_flops_per_s"]
        t_b = packed_gemm_bytes(m, k, n, bits=bits) / peaks["hbm_bytes_per_s"]
        t_total += max(t_o, t_b)
        t_ops += t_o
        t_bytes += t_b
    layers = cfg["num_hidden_layers"]
    return {"least_s": layers * t_total, "ops_s": layers * t_ops, "bytes_s": layers * t_bytes}


def mesh_chips(cell: dict) -> int:
    """Chips of the cell's mesh."""
    n = 1
    for size in cell["mesh"].values():
        n *= int(size)
    return n
