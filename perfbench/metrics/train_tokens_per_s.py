"""Non-padding tokens of the steps finished in the window over its length."""

from lib.readers import rate


def read(run):
    return rate(run)
