"""Published peaks of each accelerator the benchmark may run on, keyed by
the `device_kind` JAX reports. A device that is not listed is an error,
never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add its row to chipbench/peaks.py"
                       ) from None


def roofline(ops: float, nbytes: float, seconds: float, device_kind: str,
             op_peak: str) -> tuple:
    """(share of the roofline in %, the bound that applies): the least
    time the chip could take for `ops` at its `op_peak` and `nbytes` at
    its memory bandwidth, over the measured `seconds`."""
    p = peaks_for(device_kind)
    t_ops = ops / p[op_peak]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
