"""Summed ms of the traced window spent in garbage collection (``host.gc``);
0 where no collection fell in it."""

from lib.spans import total_ms


def read(run):
    return total_ms(run, "host.gc")
