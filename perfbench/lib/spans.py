"""The program's own host spans in a traced window: the engine's phases
(``engine.*``), garbage collections (``host.gc``), traces of jitted
functions (``jax.trace``) and the packer (``data.pack``).  A program that
writes none of them reads as nothing (``None``), not as zero."""

from __future__ import annotations

import numpy as np

# A program whose engine writes this span also writes host.gc and
# jax.trace wherever they happen, so their absence there reads as zero.
MARKER = "engine.wait"


def durations_s(run: dict, name: str) -> list:
    """Seconds of each span called ``name`` in the traced window."""
    tr = run["trace"]
    if not tr:
        return []
    return [(e - s) * 1e-9 for s, e, n in tr["spans"] if n == name]


def median_ms(run: dict, name: str):
    d = durations_s(run, name)
    return float(np.median(d) * 1e3) if d else None


def total_ms(run: dict, name: str):
    """Summed ms of the ``name`` spans, where the engine marks its wait."""
    if not durations_s(run, MARKER):
        return None
    return float(sum(durations_s(run, name)) * 1e3)


def count(run: dict, name: str):
    """How many ``name`` spans, where the engine marks its wait."""
    if not durations_s(run, MARKER):
        return None
    return float(len(durations_s(run, name)))
