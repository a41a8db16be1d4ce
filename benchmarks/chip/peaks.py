"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2 at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect.  JAX names the v5e
"TPU v5 lite".  A device that is not in the table is an error, never a
default.
"""

from __future__ import annotations

_V5E = {
    "bf16_flops": 197e12,      # FLOP/s
    "int8_ops": 393e12,        # OP/s
    "hbm_bytes_s": 819e9,      # bytes/s
    "hbm_bytes": 16e9,         # bytes
    "ici_bits_s": 1600e9,      # bits/s
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
