"""Median ms a tick spends in the engine's guard (``engine.guard``):
reading the finite flags that the jitted step returns with its tokens,
and the quarantine of a slot whose logits were not finite."""

from lib.spans import median_ms


def read(run):
    return median_ms(run, "engine.guard")
