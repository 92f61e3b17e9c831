"""The chips the benchmark knows, keyed by JAX's ``device_kind``, and the
readings it takes from them.  A device missing from the table is an
error, not a default."""

from __future__ import annotations

#: Published peaks of one chip.  ``flops`` is the matrix unit's bf16 rate,
#: the most any fp32 product (three or six bf16 passes) could reach, so a
#: share of a roofline built on it is never overstated.
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": ("Google Cloud TPU v5e documentation: 197 TFLOP/s bf16, "
                   "16 GB HBM at 819 GB/s per chip"),
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/harness/device.py with its source")
    return PEAKS[device_kind]


def peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where the backend
    reports it."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            out.append(int(stats["peak_bytes_in_use"]))
    return max(out) if out else None


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
