"""The control of each cell's comparison, at a size a test run can hold:
the plain reference computed in float8 in the program's place comes out
not correct against the cell's limits."""

import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from chipbench_tiny import TINY  # noqa: E402
from lib import train, traffic  # noqa: E402
from lib.cell import load_json, reference  # noqa: E402


def tiny(name):
    cfg = load_json("configs", name)
    cfg.update(TINY)
    return cfg


def limits(cell):
    return load_json("workloads", cell)["limits"]


def test_serving_control_fails_the_limit():
    cfg = tiny("phi3-mini-3.8b")
    ref = reference(cfg)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg["vocab_size"], (2, 256)).astype(np.int32)
    seg = np.ones_like(tokens)
    rows, cols = np.divmod(np.arange(2 * 256), 256)
    want = ref.served_logits(cfg, 3, tokens, seg, rows, cols, "f32")
    ctrl = ref.served_logits(cfg, 3, tokens, seg, rows, cols, "fp8")
    gap = (want.max(1) - want[np.arange(len(rows)), ctrl.argmax(1)]).max()
    assert gap > limits("phi3-serve-chat")["served_gap_max"]


def test_training_control_fails_a_limit():
    from repro.data.packing import pack_documents

    cfg = tiny("phi3-mini-3.8b-4l")
    mix = json.loads((BENCH / "traffic" / "packed-docs.json").read_text())
    mix["doc_len"].update(mean=100, min=8, max=256)
    feed = traffic.packed_batches(mix, cfg["vocab_size"], 4, 2, 256,
                                  pack_documents)
    batches = [traffic.reference_batch(*next(feed))
               for _ in range(train.CHECK_STEPS)]
    ref = reference(cfg)
    want = ref.train(cfg, 4, batches, "f32")
    got = ref.train(cfg, 4, batches, "fp8")
    numbers = train.numbers(got, want)
    lim = limits("phi3-train-packed")
    assert any(numbers[k] > lim[k] for k in lim), numbers
