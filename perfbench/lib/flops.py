"""Operations and bytes, computed from shapes.

Model FLOPs follow the usual convention (Kaplan et al., arXiv:2001.08361;
the same arithmetic as the program's ``roofline/analysis.model_flops``):
2 N per token for a forward pass and 6 N for a training step, N the
parameters that multiply every token, which the configuration's reference
module counts (``matmul_params``).

The Aaren scan kernels are counted at the least work the algorithm needs:
what they must read and write in HBM (float32), and the floating-point
operations of the (max, denominator, numerator) recurrence.  Residuals a
kernel writes for the backward pass are not counted, so the roofline share
is, if anything, low.
"""

from __future__ import annotations

F32 = 4


def model_flops(n_params: int, n_tokens: float, kind: str) -> float:
    """6·N·D for a training step, 2·N·D for inference."""
    return (6.0 if kind == "train" else 2.0) * n_params * n_tokens


def aaren_scan_fwd(rows: int, n: int, d: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one forward scan over ``rows`` rows of ``n``
    positions with ``d``-wide values.

    Per row and position: new max (1), two rescaling exponents (4), the
    denominator (2), the numerator (3 d: rescale, weight, add) and the
    read-out (d).  Bytes: score and value in, output out.
    """
    flops = rows * n * (4 * d + 7)
    nbytes = rows * n * (1 + 2 * d) * F32 + rows * 2 * (2 + d) * F32
    return float(flops), float(nbytes)


def aaren_scan_bwd(rows: int, n: int, d: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one backward scan.

    Per row and position: g·v and g·o (4 d), the suffix sums of the
    weighted cotangents (4 d), the probability (3) and ds (4).  Bytes:
    score, value, output, prefix max and denominator, output cotangent in;
    score and value cotangents out.
    """
    flops = rows * n * (8 * d + 7)
    nbytes = rows * n * ((3 + 3 * d) + (1 + d)) * F32
    return float(flops), float(nbytes)


def roofline_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(least seconds, what bounds it) on a chip with the given peaks."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "bytes")
