"""The device a run measures: refuse anything but the chips a cell asks
for, and look its peaks up by ``device_kind``."""
from __future__ import annotations

from pathlib import Path

from harness.spec import load_json

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class DeviceError(Exception):
    """No accelerator, too few chips, or a chip the peaks table lacks."""


def peaks_for(kind: str) -> dict:
    table = load_json(PEAKS_FILE)["devices"]
    if kind not in table:
        raise DeviceError(f"no peaks for device_kind {kind!r}; the table "
                          f"has {sorted(table)}")
    return table[kind]


def require_chips(devices, chips: int) -> dict:
    """Device record for the result line; raises unless ``devices`` holds
    at least ``chips`` TPU chips whose kind the peaks table knows."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise DeviceError(f"needs a TPU; JAX found {found!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell asks for {chips} chips; JAX sees "
                          f"{len(devices)}")
    kind = devices[0].device_kind
    peaks_for(kind)
    return {"platform": devices[0].platform, "kind": kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0
