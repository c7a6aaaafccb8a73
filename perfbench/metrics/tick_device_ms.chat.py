"""Median device-busy ms per engine tick (between starts of engine.step)."""

from lib.readers import device_ms_per_span


def read(run):
    return device_ms_per_span(run, "engine.step")
