"""Median ms the packer takes for one batch (``data.pack``)."""

from lib.spans import median_ms


def read(run):
    return median_ms(run, "data.pack")
