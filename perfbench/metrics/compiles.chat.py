"""Traces of jitted functions in the traced window (``jax.trace`` spans);
0 in steady serving."""

from lib.spans import count


def read(run):
    return count(run, "jax.trace")
