"""A whole serving run of the benchmark at a tiny size on the CPU: sound,
it is correct and names its device; with a token altered where the engine
produces it, ``correct`` comes out false."""

import pytest

from chipbench_tiny import make_checkout, run_cell


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("serve"))


def test_sound_run_is_correct_and_names_its_device(checkout):
    rc, line, err = run_cell(checkout, "tiny-chat")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {"served_gap_max"}
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert err.rstrip().splitlines()[-1].startswith("compared served_gap_max")


ALTERED = """
def engine(eng):
    sample = eng._batched_sample

    def altered(logits, key, rids, steps):
        return (sample(logits, key, rids, steps) + 1) % logits.shape[-1]

    eng._batched_sample = altered

HOOKS = {"engine": engine}
"""


def test_token_altered_where_produced_is_not_correct(checkout):
    rc, line, err = run_cell(checkout, "tiny-chat", hooks=ALTERED)
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
