"""The seeded weights and the plain reference's prefix softmax."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from chipbench_tiny import TINY  # noqa: E402
from lib import weights as W  # noqa: E402
from lib.cell import reference  # noqa: E402

CFG = dict(TINY, dtype="bfloat16", rms_norm_eps=1e-6,
           reference="aaren_lm_reference", program_config="phi3-mini-3.8b",
           attn_mode="aaren")


def test_program_tree_matches_the_reference_draws():
    from lib.model import arch_config
    from repro.models.factory import build

    api = build(arch_config(CFG))
    key = W.base_key(2 ** 40 + 3)
    tree = jax.jit(lambda k: W.program_tree(api.abstract(), CFG, k))(key)
    for layer in range(CFG["num_hidden_layers"]):
        want = W.layer_weights(CFG, key, layer)
        got = tree["periods"][0]
        assert np.array_equal(
            np.asarray(got["mlp"]["wo"][layer], np.float32),
            np.asarray(want["mlp.wo"]))
        assert np.array_equal(
            np.asarray(got["mixer"]["query"][layer], np.float32),
            np.asarray(want["mixer.query"]))
    top = W.top_weights(CFG, key)
    assert np.array_equal(np.asarray(tree["unembed"]["kernel"], np.float32),
                          np.asarray(top["unembed.kernel"]))


def test_seeds_past_32_bits_differ():
    a = W.draw(W.base_key(5), "x", 0, (4,), 1.0)
    b = W.draw(W.base_key(5 + 2 ** 32), "x", 0, (4,), 1.0)
    assert not np.allclose(a, b)
    with pytest.raises(ValueError):
        W.base_key(-1)


def dense_prefix_softmax(s, v, seg):
    b, h, n = s.shape
    out = np.zeros(v.shape)
    for bi in range(b):
        for i in range(n):
            if seg[bi, i] == 0:
                continue
            js = [j for j in range(i + 1) if seg[bi, j] == seg[bi, i]]
            for hi in range(h):
                w = np.exp(s[bi, hi, js] - s[bi, hi, js].max())
                out[bi, hi, i] = (w[:, None] * v[bi, hi, js]).sum(0) / w.sum()
    return out


def test_reference_prefix_softmax_is_exact_across_blocks_and_documents():
    ref = reference(CFG)
    rng = np.random.default_rng(0)
    n = 300                     # more than two blocks of 128
    s = rng.normal(size=(2, 3, n)) * 3
    v = rng.normal(size=(2, 3, n, 4))
    seg = np.zeros((2, n), np.int32)
    seg[0, :100], seg[0, 100:290] = 1, 2          # a document across blocks
    seg[1, :7], seg[1, 7:129], seg[1, 129:] = 1, 2, 3
    got = ref.prefix_softmax(jnp.asarray(s, jnp.float32),
                             jnp.asarray(v, jnp.float32), jnp.asarray(seg))
    np.testing.assert_allclose(np.asarray(got), dense_prefix_softmax(s, v, seg),
                               rtol=1e-5, atol=1e-5)
