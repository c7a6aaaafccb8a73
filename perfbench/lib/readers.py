"""Arithmetic shared by the metric readers in ``metrics/``."""

from __future__ import annotations

import re

import numpy as np

from lib import flops
from lib.profile import op_name


def percentile_ms(values, q: float):
    values = np.asarray(values, np.float64)
    if values.size == 0:
        return None
    return float(np.percentile(values, q) * 1e3)


def rate(run: dict) -> float:
    res = run["result"]
    return res["tokens"] / res["window_s"]


def idle_share(run: dict):
    """Per cent of the traced window with no operation on the device."""
    tr = run["trace"]
    if not tr or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu(run: dict, kind: str):
    """Model FLOPs of the window's tokens per second over the chips' peak."""
    if run["peaks"] is None:
        return None
    n = flops.matmul_params(run["cell"]["config"])
    achieved = flops.model_flops(n, rate(run), kind)
    return 100.0 * achieved / (run["chips"] * run["peaks"]["bf16_flops_per_s"])


def kernel_roofline(run: dict, kernel: str, cost):
    """Least time of the kernel's calls over their summed device time (%).

    The kernel's events are the device operations named after the Pallas
    kernel function (``%aaren_scan.4``); every call in a cell has the
    cell's shape: rows x heads scan rows of ``seq_len`` positions.
    """
    tr = run["trace"]
    if not tr or run["peaks"] is None:
        return None
    cfg, wl = run["cell"]["config"], run["cell"]["workload"]
    rows = wl["rows"] * cfg["num_attention_heads"]
    f, b = cost(rows, wl["seq_len"], cfg["head_dim"])
    least, _ = flops.roofline_time(f, b, run["peaks"])
    pattern = re.compile(rf"^%{kernel}\.\d+$")
    durs = [(e - s) * 1e-9 for ops in tr["devices"].values()
            for s, e, name in ops if pattern.match(op_name(name))]
    if not durs:
        return None
    return 100.0 * least * len(durs) / sum(durs)


def device_ms_per_span(run: dict, span: str):
    """Median device-busy time between starts of consecutive host spans."""
    tr = run["trace"]
    if not tr or not tr["busy"]:
        return None
    starts = sorted(s for s, _, n in tr["spans"] if n == span)
    if len(starts) < 2:
        return None
    iv = tr["busy"][sorted(tr["busy"])[0]]
    per = []
    k = 0
    for a, b in zip(starts, starts[1:]):
        while k < len(iv) and iv[k][1] <= a:
            k += 1
        busy, j = 0, k
        while j < len(iv) and iv[j][0] < b:
            busy += min(iv[j][1], b) - max(iv[j][0], a)
            j += 1
        per.append(busy * 1e-9)
    return float(np.median(per) * 1e3)
