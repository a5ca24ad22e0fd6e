"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error.

Source: Google Cloud documentation, "TPU v5e" system architecture page: one
chip does 197 TFLOP/s in bf16 and has 16 GB of HBM at 819 GB/s, with
1,600 Gbit/s of chip-to-chip interconnect. (Copied from the v5e row of
``kubeflow_tpu/tpu/topology.py``'s catalog; the benchmark keeps its own so
that no PR of the program can move the yardstick.)
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"device kind {device_kind!r} is not in the benchmark's peaks "
            f"table ({sorted(PEAKS)}); add its published peaks with their "
            "source before measuring on it") from None
