"""A whole training run of the benchmark at a tiny size on the CPU: sound,
it is correct; with a step that returns its state unchanged, that leaves
out half of the batch, or fed by a packer that merges two documents into
one segment, ``correct`` comes out false."""

import pytest

from chipbench_tiny import make_checkout, run_cell


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("train"))


def test_sound_run_is_correct(checkout):
    rc, line, err = run_cell(checkout, "tiny-train")
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert set(line["compared"]) == {"grad1_diff", "grad1_gap", "delta3_gap"}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


UNCHANGED = """
import jax
import jax.numpy as jnp

def step(real):
    def unchanged(state, batch, key):
        keep = jax.tree.map(jnp.copy, state)
        _, metrics = real(state, batch, key)
        return keep, metrics
    return unchanged

HOOKS = {"step": step}
"""

HALF_BATCH = """
def step(real):
    def half(state, batch, key):
        rows = batch["tokens"].shape[0] // 2
        return real(state, {k: v[:rows] for k, v in batch.items()}, key)
    return half

HOOKS = {"step": step}
"""


SEGMENTS_MERGED = """
def pack(real):
    def merged(docs, seq_len, how):
        out = real(docs, seq_len, how)
        seg = out["segment_ids"]
        seg[seg == 2] = 1
        return out
    return merged

HOOKS = {"pack": pack}
"""


@pytest.mark.parametrize("hooks", [UNCHANGED, HALF_BATCH, SEGMENTS_MERGED],
                         ids=["state_unchanged", "half_batch",
                              "segments_merged"])
def test_broken_step_is_not_correct(checkout, hooks):
    rc, line, err = run_cell(checkout, "tiny-train", hooks=hooks)
    assert rc == 0, err[-3000:]
    assert line["correct"] is False, err[-2000:]
