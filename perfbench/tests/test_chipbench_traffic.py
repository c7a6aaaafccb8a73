"""The traffic generator: deterministic per seed, the same sizes for every
seed, and distributed as the mix files state."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from lib import traffic  # noqa: E402


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_requests_repeat_per_seed(seed):
    a = traffic.serve_requests(mix("chat"), 32064, seed, 50, rate=3.0)
    b = traffic.serve_requests(mix("chat"), 32064, seed, 50, rate=3.0)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))


def test_every_seed_gets_the_same_sizes_in_another_order():
    runs = [traffic.serve_requests(mix("chat"), 32064, s, 120, rate=3.0)
            for s in (1, 2)]
    sizes = [sorted(r["prompt"].size for r in run) for run in runs]
    outs = [sorted(r["max_new"] for r in run) for run in runs]
    gaps = [sorted(np.diff([0.0] + [r["due"] for r in run])) for run in runs]
    assert sizes[0] == sizes[1] and outs[0] == outs[1]
    assert np.allclose(gaps[0][1:], gaps[1][1:], atol=0.2)
    assert [r["prompt"].size for r in runs[0]] != [r["prompt"].size
                                                   for r in runs[1]]
    assert [r["due"] for r in runs[0]] != [r["due"] for r in runs[1]]


def test_chat_lengths_follow_the_mix():
    spec = mix("chat")
    lens = traffic.quantile_lengths(spec["prompt_len"], 1001)
    assert lens.min() >= 32 and lens.max() <= 3072
    assert abs(np.median(lens) - 1020) <= 1
    # lognormal sigma 1: the 16th percentile is the median over e
    assert abs(np.percentile(lens, 15.87) * np.e / 1020 - 1) < 0.02
    outs = traffic.quantile_lengths(spec["output_len"], 1001)
    assert abs(np.median(outs) - 129) <= 1 and outs.min() >= 16
    assert lens.max() + outs.max() <= 4096       # phi3-mini-4k's context


def test_a_lognormal_given_by_its_mean_has_that_mean():
    spec = mix("packed-docs")["doc_len"]
    lens = traffic.quantile_lengths(spec, 20001)
    assert abs(lens.mean() / spec["mean"] - 1) < 0.01
    assert lens.min() >= spec["min"] and lens.max() <= spec["max"]


def test_poisson_gaps_have_the_rate():
    reqs = traffic.serve_requests(mix("chat"), 100, 3, 2000, rate=4.0)
    gaps = np.diff([r["due"] for r in reqs])
    assert abs(gaps.mean() - 0.25) < 0.01
    assert abs(np.std(gaps) / gaps.mean() - 1.0) < 0.1   # exponential


@pytest.mark.parametrize("cv", [0.5, 2.0, 4.0])
def test_bursty_gaps_have_the_rate_and_their_spread(cv):
    bursty = dict(mix("chat"), arrivals={"gap_cv": cv})
    reqs = traffic.serve_requests(bursty, 100, 3, 4000, rate=2.0)
    gaps = np.diff([r["due"] for r in reqs])
    assert abs(gaps.mean() / 0.5 - 1) < 0.03
    assert abs(np.std(gaps) / gaps.mean() / cv - 1) < 0.1


def test_backlog_is_due_at_once_and_uniform_prompts():
    backlog = {"arrivals": {"backlog": True},
               "prompt_len": {"dist": "uniform", "min": 16, "max": 128},
               "output_len": {"dist": "lognormal", "median": 1024,
                              "sigma": 0.6, "min": 256, "max": 4096}}
    reqs = traffic.serve_requests(backlog, 100, 3, 64)
    assert all(r["due"] == 0 for r in reqs)
    sizes = np.array([r["prompt"].size for r in reqs])
    assert sizes.min() >= 16 and sizes.max() <= 128
    outs = np.array([r["max_new"] for r in reqs])
    assert outs.min() >= 256 and outs.max() <= 4096


def feed(pack=None):
    from repro.data.packing import pack_documents

    return traffic.packed_batches(mix("packed-docs"), 32064, 9, 2, 8192,
                                  pack or pack_documents)


def test_packed_batches_are_full_rows_of_whole_documents():
    fill = []
    stream = feed()
    for _ in range(4):
        b, docs = next(stream)
        assert b["tokens"].shape == (2, 8192)
        seg = b["segment_ids"]
        for row in seg:
            ids = row[row != 0]
            assert (np.diff(ids) >= 0).all()          # contiguous runs
            assert (row[len(ids):] == 0).all()         # padding at the end
        assert ((b["tokens"] != 0) == (seg != 0)).all()
        mine = traffic.reference_batch(b, docs)
        assert (mine["tokens"] == b["tokens"]).all()
        assert (mine["loss_mask"] == b["loss_mask"]).all()
        assert ((mine["segment_ids"] != 0) == (seg != 0)).all()
        fill.append((seg != 0).mean())
    assert min(fill) > 0.85 and np.mean(fill) > 0.95


def _merge_first_two(packed):
    packed["segment_ids"][0][packed["segment_ids"][0] == 2] = 1
    return packed


def _shift_segments(packed):
    seg = packed["segment_ids"][0]
    cut = int(np.argmax(seg == 2))
    seg[cut] = 1
    return packed


def _drop_a_token(packed):
    seg = packed["segment_ids"][0]
    cut = int(np.argmax(seg == 2))
    packed["tokens"][0, cut - 1] = packed["tokens"][0, cut - 2]
    return packed


def _unmask_a_token(packed):
    packed["loss_mask"][0, 3] = 0.0
    return packed


def _restart_positions(packed):
    packed["positions"][0, 5] = 0
    return packed


@pytest.mark.parametrize("fault", [_merge_first_two, _shift_segments,
                                   _drop_a_token, _unmask_a_token,
                                   _restart_positions],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_packing_is_refused(fault):
    def broken(real):
        return lambda docs, n, how: fault(real(docs, n, how))

    from repro.data.packing import pack_documents

    b, docs = next(feed(broken(pack_documents)))
    with pytest.raises(traffic.PackingError):
        traffic.reference_batch(b, docs)
