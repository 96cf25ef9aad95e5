"""Peaks of the chips the benchmark knows, keyed by ``device_kind`` as jax
reports it. A device that is not here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
(200 GB/s) of inter-chip interconnect. Values copied from the program's
``telemetry/goodput.PEAKS`` (PR 21), which the benchmark does not read.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add it to "
            "benchmark/lib/peaks.py with its source")
    return PEAKS[device_kind]


def share_pct(achieved: float, peak: float, what: str) -> float:
    """achieved / peak in percent; over 100 the count or the time is wrong."""
    pct = 100.0 * achieved / peak
    if pct > 100.0:
        raise ValueError(
            f"{what}: {pct:.2f}% of peak - the operations or bytes are counted "
            "too high, or the time leaves out part of the work")
    return pct
