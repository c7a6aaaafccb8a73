"""The reduction from a device trace to per-layer numbers, on two small
traces recorded on one TPU v5e: four ticks of the serving engine (the
4-layer configuration, 16 slots, chunk 16) and one training step of the
4-layer configuration at 1 x 2048 tokens."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH)]

from lib import device, flops, readers  # noqa: E402
from lib.profile import _union, breakdown, reduce_file  # noqa: E402


@pytest.fixture(scope="module")
def serve_trace():
    return reduce_file(str(DATA / "serve_ticks.xplane.pb"))


@pytest.fixture(scope="module")
def train_trace():
    return reduce_file(str(DATA / "train_step.xplane.pb"))


def test_union_merges_overlaps_and_nesting():
    assert _union([(5, 9), (0, 3), (2, 4), (6, 7)]) == [[0, 4], [5, 9]]


def test_serve_trace_has_the_device_and_the_engine_spans(serve_trace):
    assert list(serve_trace["devices"]) == ["/device:TPU:0"]
    names = [n for _, _, n in serve_trace["spans"]]
    assert names.count("engine.step") == 4
    assert names.count("engine.schedule") == 4
    assert names.count("engine.sample") == 4
    busy = serve_trace["busy_s"]
    span = (max(e for _, e, _ in serve_trace["spans"])
            - min(s for s, _, _ in serve_trace["spans"])) * 1e-9
    assert 0 < busy < span


def test_tick_device_time_is_one_step_program(serve_trace):
    run = {"trace": serve_trace}
    ms = readers.device_ms_per_span(run, "engine.step")
    steps = [s for s, _, n in serve_trace["spans"] if n == "engine.step"]
    tick_ms = (max(steps) - min(steps)) * 1e-6 / (len(steps) - 1)
    assert 0 < ms < tick_ms


def test_breakdown_leaves_out_loops_and_labels_gaps(serve_trace):
    bd = breakdown(serve_trace)
    assert 0 < len(bd["device_ops"]) <= 10
    assert not any(n.startswith("%while") for n, _ in bd["device_ops"])
    assert all(v > 0 for _, v in bd["device_ops"])
    assert 0 < len(bd["idle_gaps"]) <= 10
    assert all(lab.startswith(("engine.", "bench.", "after "))
               for lab, _ in bd["idle_gaps"])


def test_kernel_rooflines_from_the_train_step(train_trace):
    cfg = {"num_attention_heads": 32, "head_dim": 96}
    run = {"trace": train_trace, "peaks": device.peaks("TPU v5 lite"),
           "cell": {"config": cfg, "workload": {"rows": 1, "seq_len": 2048}}}
    fwd = readers.kernel_roofline(run, "aaren_scan", flops.aaren_scan_fwd)
    bwd = readers.kernel_roofline(run, "aaren_scan_bwd", flops.aaren_scan_bwd)
    assert 0 < fwd < 100 and 0 < bwd < 100
    run["trace"] = dict(train_trace, devices={})
    assert readers.kernel_roofline(run, "aaren_scan",
                                   flops.aaren_scan_fwd) is None


def test_idle_share_and_mfu_need_the_chip(train_trace):
    run = {"trace": dict(train_trace, window_s=2 * train_trace["busy_s"])}
    assert readers.idle_share(run) == pytest.approx(50.0)
    run = {"peaks": None}
    assert readers.mfu(run, "train") is None
