"""The chips a run uses, their published peaks, and peak memory."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind missing from
    ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


def memory_note(devices) -> str:
    """``, HBM in use <bytes>, peak <bytes>`` of the fullest chip, or
    nothing where the backend keeps no such counts."""
    best = None
    for d in devices:
        stats = d.memory_stats() or {}
        if "bytes_in_use" in stats:
            pair = (int(stats["bytes_in_use"]),
                    int(stats.get("peak_bytes_in_use", 0)))
            best = pair if best is None else max(best, pair)
    if best is None:
        return ""
    return f", HBM in use {best[0]}, peak {best[1]}"
