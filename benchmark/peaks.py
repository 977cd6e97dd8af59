"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error, never a
default: a utilization against the wrong peak is worse than none.

Source: Google Cloud TPU documentation, system architecture, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
(The program has its own table, ``kubeflow_tpu/runtime/topology.py::CHIPS``;
this copy is the yardstick's, so that no later PR can move a peak.)
"""

from __future__ import annotations

V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

PEAKS = {
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in benchmark/peaks.py "
            f"(known: {sorted(PEAKS)}); the benchmark has no CPU fallback "
            "and no default peak") from None
