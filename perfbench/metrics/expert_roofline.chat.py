"""Roofline share of the grouped expert kernel (%): least time over the
summed device time of the calls of ``_expert_gmm``.

A call multiplies the rows of each held expert by that expert's weights,
so the least it must do is read the held experts' weight matrix of its
layer from HBM once: ``num_experts x hidden_size x moe_intermediate_size``
in the served type (16 x 2304 x 896 x 2 B = 66.06 MB in the Mellum cell),
at the chip's HBM peak.  The fused gate and up call reads two matrices,
the down call one; a call is told apart by its result's width (the
expert width for gate and up, the hidden size for down).  Three matrices
a layer, 28 layers: 5.55 GB, 6.78 ms a tick at 819 GB/s.
"""

import re

from lib.profile import op_name

KERNEL = re.compile(r"^%_expert_gmm\.\d+$")
RESULT = re.compile(r"^[^=]*=\s*\w+\[\d+,(\d+)\]")
ITEM = {"bfloat16": 2, "float32": 4}


def matrix_bytes(cfg: dict) -> int:
    """Bytes of one held-expert weight matrix of one layer."""
    return (cfg["num_experts"] * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * ITEM[cfg["dtype"]])


def tick_seconds(cfg: dict, peaks: dict) -> float:
    """Least time of a tick's calls: every layer's three matrices."""
    return 3 * cfg["num_hidden_layers"] * matrix_bytes(cfg) / (
        peaks["hbm_bytes_per_s"])


def matrices(event_name: str, cfg: dict):
    """Weight matrices a call reads, from its result's width (or None)."""
    m = RESULT.match(event_name)
    if m is None:
        return None
    return 2 if int(m.group(1)) == cfg["moe_intermediate_size"] else 1


def read(run):
    tr = run["trace"]
    if not tr or run["peaks"] is None:
        return None
    cfg = run["cell"]["config"]
    least = busy = 0.0
    for ops in tr["devices"].values():
        for s, e, name in ops:
            if KERNEL.match(op_name(name)):
                n = matrices(name, cfg)
                if n is None:
                    return None
                least += n * matrix_bytes(cfg) / run["peaks"][
                    "hbm_bytes_per_s"]
                busy += (e - s) * 1e-9
    if busy <= 0:
        return None
    return 100.0 * least / busy
