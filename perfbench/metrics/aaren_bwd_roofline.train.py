"""Roofline share of the Aaren backward scan kernel (%)."""

from lib import flops
from lib.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "aaren_scan_bwd", flops.aaren_scan_bwd)
