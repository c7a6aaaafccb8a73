"""One general generator for every traffic mix, driven by its data file.

Every seed gets the same multiset of sizes and gaps: a size drawn from a
distribution is its quantile at (i + 1/2) / n for i = 0..n-1.  The seed
permutes them and draws the token ids, so two seeds do the same amount of
work in another order.

Distributions (the ``dist`` key of a length spec):
  ``lognormal``  ``median`` (or the unclipped ``mean``), ``sigma``,
                 clipped to [``min``, ``max``];
  ``uniform``    integers in [``min``, ``max``].
Arrivals (the ``arrivals`` object of a serving mix):
  ``{"gap_cv": c}``      a renewal process at the cell's ``rate`` (req/s)
                         whose gaps are gamma with coefficient of
                         variation ``c``: 1 is Poisson, above 1 bursty;
  ``{"backlog": true}``  every request due when the window opens.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec`` (sorted)."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        median = spec.get("median") or spec["mean"] * math.exp(
            -spec["sigma"] ** 2 / 2)
        vals = np.exp(math.log(median) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def quantile_gaps(arrivals: dict, n: int, rate: float | None) -> np.ndarray:
    """``n`` gaps between arrivals (seconds) at evenly spaced quantiles."""
    if arrivals.get("backlog"):
        return np.zeros(n)
    from scipy.stats import gamma

    shape = 1.0 / arrivals["gap_cv"] ** 2
    u = (np.arange(n) + 0.5) / n
    return gamma.ppf(u, shape, scale=1.0 / (rate * shape))


def rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed)


def serve_requests(mix: dict, vocab: int, seed: int, n: int,
                   rate: float | None = None) -> list[dict]:
    """``n`` requests: {"due": seconds after the window opens, "prompt",
    "max_new"}, in due order."""
    g = rng(seed)
    prompts = g.permutation(quantile_lengths(mix["prompt_len"], n))
    outputs = g.permutation(quantile_lengths(mix["output_len"], n))
    gaps = g.permutation(quantile_gaps(mix["arrivals"], n, rate))
    due = np.cumsum(gaps) - gaps[0]
    return [{"due": float(due[i]),
             "prompt": g.integers(0, vocab, int(prompts[i]), dtype=np.int32),
             "max_new": int(outputs[i])} for i in range(n)]


class DocumentStream:
    """Documents of a training mix: lengths from a fixed pool of quantiles,
    reshuffled by the seed every time the pool runs out; random token ids."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.pool = quantile_lengths(mix["doc_len"], mix["pool"])
        self.vocab = vocab
        self.g = rng(seed)
        self.order: list[int] = []

    def next(self) -> np.ndarray:
        if not self.order:
            self.order = list(self.g.permutation(self.pool))
        n = int(self.order.pop())
        return self.g.integers(1, self.vocab, n, dtype=np.int32)


def packed_batches(mix: dict, vocab: int, seed: int, rows: int,
                   seq_len: int, pack_documents):
    """Endless ((rows, seq_len) packed batch, documents drawn for it).

    Each batch packs documents, first fit, with the program's
    ``pack_documents`` from a draw of 3x the batch's tokens, and keeps the
    first ``rows`` rows: later documents fill the gaps the earlier ones
    leave, as a streaming packer's buffer of three batches would.
    """
    stream = DocumentStream(mix, vocab, seed)
    want = 3 * rows * seq_len
    while True:
        docs, total = [], 0
        while total < want:
            d = stream.next()
            docs.append(d)
            total += d.size
        packed = pack_documents(docs, seq_len, "first_fit")
        yield {k: v[:rows] for k, v in packed.items()}, docs


class PackingError(ValueError):
    """A packed batch that is not a layout of the documents drawn for it."""


def reference_batch(batch: dict, docs: list) -> dict:
    """The reference's own batch, rebuilt from the documents drawn.

    Reads where the packed batch placed each document, checks that every
    run of one segment id is exactly one drawn document, whole, with
    positions from 0 and the loss mask on its tokens alone, and that no
    document is placed twice; then lays the documents out again with
    segment ids and a loss mask of its own.  Raises :class:`PackingError`
    where the packed batch is not such a layout.
    """
    tokens = np.asarray(batch["tokens"], np.int64)
    seg = np.asarray(batch["segment_ids"])
    pos = np.asarray(batch["positions"])
    mask = np.asarray(batch["loss_mask"])
    index = {}
    for i, d in enumerate(docs):
        index.setdefault((d.size, d.astype(np.int64).tobytes()),
                         []).append(i)
    out_tok = np.zeros(tokens.shape, np.int32)
    out_seg = np.zeros(tokens.shape, np.int32)
    used = set()
    for r in range(tokens.shape[0]):
        a, sid = 0, 1
        n = tokens.shape[1]
        while a < n and seg[r, a] != 0:
            b = a
            while b < n and seg[r, b] == seg[r, a]:
                b += 1
            free = [i for i in index.get((b - a, tokens[r, a:b].tobytes()),
                                         []) if i not in used]
            if not free:
                raise PackingError(f"row {r} [{a}, {b}) is no whole document "
                                   "drawn for the batch")
            used.add(free[0])
            if (pos[r, a:b] != np.arange(b - a)).any():
                raise PackingError(f"row {r} [{a}, {b}): positions do not "
                                   "count from 0")
            out_tok[r, a:b] = docs[free[0]]
            out_seg[r, a:b] = sid
            a, sid = b, sid + 1
        if (seg[r, a:] != 0).any() or (tokens[r, a:] != 0).any():
            raise PackingError(f"row {r}: tokens after its padding starts")
        if ((mask[r] > 0) != (out_seg[r] != 0)).any():
            raise PackingError(f"row {r}: loss mask is not on the documents' "
                               "tokens alone")
    return {"tokens": out_tok, "segment_ids": out_seg,
            "loss_mask": (out_seg != 0).astype(np.float32)}
