"""Roofline share of the Aaren forward scan kernel (%)."""

from lib import flops
from lib.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "aaren_scan", flops.aaren_scan_fwd)
