"""Peak rates of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at
819 GB/s.  A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def require_chip(devices, chips: int) -> dict:
    """Check that JAX found at least ``chips`` TPU chips of a kind in the
    table and return that kind's peaks.  Raises ``SystemExit`` otherwise,
    so that no result is printed."""
    if not devices:
        raise SystemExit("bench: JAX found no device")
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found platform "
                         f"{platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    try:
        return peaks(devices[0].device_kind)
    except KeyError as e:
        raise SystemExit(f"bench: {e.args[0]}") from None
