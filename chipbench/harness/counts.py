"""Operations and bytes the algorithms need, counted from shapes.

These are the yardstick's own counts: the same whatever kernel or impl
the program runs, so a later change to the program cannot make them
stale. Each takes the plain sizes of a configuration file.
"""
from __future__ import annotations

BITS_PER_WEIGHT = {"elp_bsd_a4": 4, "elp_bsd_c6": 6, "elp_bsd_b7": 7, "elp_bsd_d6": 6}


def gemm_ops(m: int, k: int, n: int) -> int:
    """Multiply-adds of ``[m, k] @ [k, n]``, counted as two operations."""
    return 2 * m * k * n


def packed_gemm_bytes(
    m: int, k: int, n: int, *, bits: int = 4, n_scales: int = 1, act_bytes: int = 2
) -> int:
    """HBM bytes one packed GEMM must move at least: the codes of the
    ``[k, n]`` weight at ``bits`` a weight, its float32 scales, the
    activations in (``[m, k]``) and out (``[m, n]``)."""
    return (k * n * bits + 7) // 8 + 4 * n_scales + act_bytes * m * (k + n)


def decoder_layer_gemms(cfg: dict) -> list[tuple[str, int, int]]:
    """``(name, K, N)`` of each packed GEMM of one decoder layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv, ff = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["intermediate_size"]
    return [
        ("wq", d, h * hd),
        ("wk", d, kv * hd),
        ("wv", d, kv * hd),
        ("wo", h * hd, d),
        ("w1", d, ff),
        ("w3", d, ff),
        ("w2", ff, d),
    ]


def decoder_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through: every layer's projections and
    the output head (the embedding is a lookup, not a matmul)."""
    per_layer = sum(k * n for _, k, n in decoder_layer_gemms(cfg))
    return cfg["num_hidden_layers"] * per_layer + cfg["hidden_size"] * cfg["vocab_size"]


def decoder_flops_per_token(cfg: dict) -> int:
    return 2 * decoder_matmul_params(cfg)


def decode_step_gemm_bounds(cfg: dict, m: int, peaks: dict) -> dict:
    """Least time of one decode step's packed GEMMs at ``m`` live rows.

    Per GEMM the least time is the larger of its operations over the
    bf16 peak and its bytes over HBM bandwidth; the step's is the sum
    over every layer's GEMMs. Returns the sum and its two parts.
    """
    bits = BITS_PER_WEIGHT[cfg["weights"]["format"]]
    t_total = t_ops = t_bytes = 0.0
    for _, k, n in decoder_layer_gemms(cfg):
        t_o = gemm_ops(m, k, n) / peaks["bf16_flops_per_s"]
        t_b = packed_gemm_bytes(m, k, n, bits=bits) / peaks["hbm_bytes_per_s"]
        t_total += max(t_o, t_b)
        t_ops += t_o
        t_bytes += t_b
    layers = cfg["num_hidden_layers"]
    return {"least_s": layers * t_total, "ops_s": layers * t_ops, "bytes_s": layers * t_bytes}


def cnn_layers(cfg: dict):
    """Walk a CNN configuration: yields ``("conv", ho, wo, k, cin, cout)``
    and ``("fc", fan_in, out)``; pools only shrink the map."""
    hw, ch = cfg["input_hw"], cfg["input_channels"]
    flat = None
    for layer in cfg["layers"]:
        kind = layer[0]
        if kind == "conv":
            _, cout, k, stride = layer
            hw = -(-hw // stride)
            yield ("conv", hw, hw, k, ch, cout)
            ch = cout
        elif kind == "pool":
            _, k, stride = layer
            hw = (hw - k) // stride + 1
        elif kind == "fc":
            _, out = layer
            fan_in = flat if flat is not None else hw * hw * ch
            yield ("fc", fan_in, out)
            flat = out
        else:
            raise ValueError(f"unknown layer kind {kind!r}")


def cnn_forward_flops(cfg: dict) -> int:
    """Operations of one image's forward pass: two per multiply-add of
    every conv (SAME padding, so ``ho*wo*k*k*cin*cout``) and fc layer."""
    total = 0
    for layer in cnn_layers(cfg):
        if layer[0] == "conv":
            _, ho, wo, k, cin, cout = layer
            total += ho * wo * k * k * cin * cout
        else:
            _, fan_in, out = layer
            total += fan_in * out
    return 2 * total


def cnn_weight_count(cfg: dict) -> int:
    total = 0
    for layer in cnn_layers(cfg):
        if layer[0] == "conv":
            _, _, _, k, cin, cout = layer
            total += k * k * cin * cout
        else:
            total += layer[1] * layer[2]
    return total
