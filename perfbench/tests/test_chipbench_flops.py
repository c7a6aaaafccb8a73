"""Operations and bytes from shapes, against counts made by hand at
phi3-mini-3.8b's shapes; the table of peaks."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH)]

from lib import device, flops  # noqa: E402
from lib.cell import reference  # noqa: E402


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_matmul_params_at_phi3_shapes():
    # per layer: wk, wv 3072 x 32 x 96 each, wo 32 x 96 x 3072, MLP 3 x
    # 3072 x 8192 = 28,311,552 + 75,497,472 = 103,809,024; unembedding
    # 3072 x 32064 = 98,500,608.  The configuration's reference counts N.
    for name, layers in (("phi3-mini-3.8b", 32), ("phi3-mini-3.8b-4l", 4)):
        cfg = config(name)
        assert reference(cfg).matmul_params(cfg) == (
            layers * 103_809_024 + 98_500_608)


def test_model_flops():
    assert flops.model_flops(10, 3, "train") == 180
    assert flops.model_flops(10, 3, "serve") == 60


def test_aaren_scan_counts_at_the_train_shape():
    rows, n, d = 2 * 32, 8192, 96
    f, b = flops.aaren_scan_fwd(rows, n, d)
    assert f == rows * n * (4 * 96 + 7)
    # s in (4 B) + v in and o out (2 x 96 x 4 B) per row and position,
    # plus the carry in and out (2 x (2 + 96) x 4 B) per row.
    assert b == rows * n * 772 + rows * 784
    f, b = flops.aaren_scan_bwd(rows, n, d)
    assert b == rows * n * (3 + 288 + 1 + 96) * 4


def test_roofline_names_its_bound():
    peak = device.peaks("TPU v5 lite")
    t, bound = flops.roofline_time(*flops.aaren_scan_fwd(64, 8192, 96), peak)
    assert bound == "bytes" and t == pytest.approx(
        flops.aaren_scan_fwd(64, 8192, 96)[1] / 819e9)
    t, bound = flops.roofline_time(197e12, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
