"""The chip the benchmark runs on: refusal of anything but a TPU, the table
of published peaks, and the device readings every result line carries."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def require_tpu(devices, chips: int):
    """The first ``chips`` TPU devices, or ``NoAccelerator``."""
    tpus = [d for d in devices if d.platform == "tpu"]
    if not tpus:
        kinds = sorted({f"{d.platform}:{d.device_kind}" for d in devices})
        raise NoAccelerator(f"no TPU among JAX's devices {kinds}; the "
                            "benchmark measures the chip and has no CPU mode")
    if len(tpus) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX finds "
                            f"{len(tpus)}")
    return tpus[:chips]


def memory_readings(device) -> dict:
    """The runtime's high-water marks: buffers (``peak_bytes_in_use``) and
    the region the TPU runtime reserves for a program's temporaries
    (``peak_bytes_reserved``), which the first leaves out."""
    st = device.memory_stats()
    return {"peak_bytes_in_use": int(st["peak_bytes_in_use"]),
            "peak_bytes_reserved": int(st.get("peak_bytes_reserved", 0))}


def peak_bytes(devices) -> int:
    """Device memory held at its peak, on the fullest of ``devices``."""
    return max(sum(memory_readings(d).values()) for d in devices)


def describe(devices, memory_peak_bytes: int) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": int(memory_peak_bytes)}
