"""The device a run finds, and the table of its published peaks."""

from __future__ import annotations

import json

from lib import cell


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    table = json.loads((cell.ROOT / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json; add its "
                       "published peaks with their source")
    return table[kind]


def devices(chips: int, allow_cpu: bool = False) -> list:
    """The first ``chips`` devices; raises unless they are TPUs."""
    import jax

    found = jax.devices()
    if found[0].platform != "tpu" and not allow_cpu:
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{found[0].platform!r}); the benchmark runs on "
                            "the chip only")
    if len(found) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(found)}")
    return found[:chips]


def describe(devs: list) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs: list) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where not reported)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
