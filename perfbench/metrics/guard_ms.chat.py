"""Median ms a tick spends in the engine's logits guard (``engine.guard``):
the host copy of the (slots, 1, vocab) logits, the isfinite check and the
quarantine."""

from lib.spans import median_ms


def read(run):
    return median_ms(run, "engine.guard")
