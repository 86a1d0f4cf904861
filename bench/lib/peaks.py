"""Published peaks of the chips the benchmark measures, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect per chip. A kind that is not in the table is an
error, never a default.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s
        "int8_ops": 393e12,        # OP/s
        "hbm_bytes": 16e9,         # B
        "hbm_bw": 819e9,           # B/s
        "ici_bw": 1600e9 / 8,      # B/s per chip, all links
    },
}


def peaks(device_kind: str) -> dict:
    """The row for ``device_kind``; raises ``KeyError`` for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)}; source: {SOURCE})"
                       ) from None
