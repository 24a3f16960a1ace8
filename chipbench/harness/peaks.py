"""Published peaks of each chip the benchmark may run on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB HBM at 819 GB/s per
chip. JAX reports a v5e chip's ``device_kind`` as ``"TPU v5 lite"``.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 394e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"the table has {sorted(PEAKS)}"
        ) from None
