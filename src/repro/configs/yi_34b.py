"""yi-34b — llama-architecture GQA decoder. [arXiv:2403.04652; hf]

60L d_model=7168 56H (GQA kv=8) d_ff=20480 vocab=64000. SwiGLU,
rms_norm_eps 1e-5, no qk_norm, untied head.
Note 56 heads do NOT divide the 16-way model axis — the sharding rules
fall back to contracting-dim sharding for attention internals
(DESIGN.md §5); this makes yi-34b a hillclimb candidate.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="yi-34b",
    family="dense",
    n_layers=60,
    d_model=7168,
    n_heads=56,
    n_kv_heads=8,
    d_ff=20480,
    vocab=64000,
    head_dim=128,
    mlp_kind="swiglu",
    rope_theta=5e6,
    norm_eps=1e-5,
)
