"""Median device ms a tick in the grouped expert kernel: the summed
device time of the ``_expert_gmm`` calls between starts of consecutive
``engine.step`` spans."""

import re

import numpy as np

from lib.profile import op_name

KERNEL = re.compile(r"^%_expert_gmm\.\d+$")


def read(run):
    tr = run["trace"]
    if not tr or not tr["devices"]:
        return None
    starts = sorted(s for s, _, n in tr["spans"] if n == "engine.step")
    ops = tr["devices"][sorted(tr["devices"])[0]]
    calls = sorted((s, e) for s, e, name in ops
                   if KERNEL.match(op_name(name)))
    if len(starts) < 2 or not calls:
        return None
    begin = np.array([s for s, _ in calls])
    dur = np.array([e - s for s, e in calls], np.float64)
    per = [dur[(begin >= a) & (begin < b)].sum() * 1e-6
           for a, b in zip(starts, starts[1:])]
    return float(np.median(per))
