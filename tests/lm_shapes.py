"""GEMM shapes ``(K, N)`` of the benchmark's LM cells, shared by the tests."""

# qwen3-8b on one chip: wq/wo, wk/wv, w1/w3, w2
QWEN3_8B_KN = [(4096, 4096), (4096, 1024), (4096, 12288), (12288, 4096)]
# yi-34b's per-chip GEMMs on a 1x4 model mesh: wq, wk/wv, wo, w1/w3, w2
YI_34B_LOCAL_KN = [(7168, 1792), (7168, 256), (1792, 7168), (7168, 5120), (5120, 7168)]
