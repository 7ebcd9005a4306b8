"""Peak rates of each accelerator the benchmark may run on, keyed by the
``device_kind`` that JAX reports.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e",
per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.

A float32 matmul at JAX's default precision runs as one bf16 pass on the
MXU, so the bf16 peak is the compute roof for the bf16 and the fp32-default
configurations alike.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(platform: str, kind: str) -> dict:
    """The peak table of one device; raises for a non-TPU platform or a
    device kind the table does not hold. There is no default."""
    if platform != "tpu":
        raise UnknownDevice(f"no TPU: jax found {platform} devices "
                            f"({kind!r})")
    if kind not in PEAKS:
        raise UnknownDevice(f"device kind {kind!r} has no entry in the "
                            f"peaks table ({sorted(PEAKS)})")
    return PEAKS[kind]


def roof_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    tf, tb = flops / peaks["flops"], nbytes / peaks["bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
