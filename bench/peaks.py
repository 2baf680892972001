"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect (four links of 50 GB/s). The same numbers as
``repro.launch.hlo_analysis.DEVICE_PEAKS``, copied here so that no change
to the program can move the yardstick.

A device kind missing from the table is an error, never a default.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} has no entry in the peaks table "
            f"(bench/peaks.py); known: {sorted(DEVICE_PEAKS)}") from None
