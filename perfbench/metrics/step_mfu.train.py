"""6 N non-padding tokens per second over chips x bf16 peak (%)."""

from lib.readers import mfu


def read(run):
    return mfu(run, "train")
