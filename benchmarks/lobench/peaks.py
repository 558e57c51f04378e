"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` jax reports.  A device that is not here is an error,
never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s.  jax reports the v5e as "TPU v5 lite".
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"device kind {device_kind!r} is not in the benchmark's "
            f"peaks table ({sorted(PEAKS)}): add it with its source, "
            "do not default it"
        ) from None
