"""Where a configuration's leaves, layer indices, program fields and
parameter count come from: its reference module's table, the position of
a program leaf in its tree, the file's keys and ``"program"`` object."""

import hashlib
import importlib.util
import json
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from chipbench_tiny import TINY  # noqa: E402
from lib import weights as W  # noqa: E402
from lib.cell import load_json, reference  # noqa: E402
from lib.model import arch_config  # noqa: E402

SEED = 2 ** 40 + 3


def frozen_layer_leaves(cfg):
    """The dense Aaren + SwiGLU table as the harness held it before it
    moved into the reference module."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, g, k = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    return {
        "norm1.scale": ((d,), "ones"),
        "mixer.query": ((d,), 1.0),
        "mixer.wq": ((d, h, k), d ** -0.5),
        "mixer.wk": ((d, g, k), d ** -0.5),
        "mixer.wv": ((d, g, k), d ** -0.5),
        "mixer.wo": ((h, k, d), (h * k) ** -0.5),
        "norm2.scale": ((d,), "ones"),
        "mlp.wi_gate": ((d, f), d ** -0.5),
        "mlp.wi_up": ((d, f), d ** -0.5),
        "mlp.wo": ((f, d), f ** -0.5),
    }


def frozen_top_leaves(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed.table": ((v, d), 0.02),
        "final_norm.scale": ((d,), "ones"),
        "unembed.kernel": ((d, v), d ** -0.5),
    }


# sha256 (first 16 hex digits) of the tiny phi3 configurations' draws at
# SEED, as the harness drew them before the table moved: the program's
# tree in its flattening order, and the reference's layers then its top
# leaves, each by sorted name.
DIGESTS = {"program": "f1afae4ec0d78886", "reference": "f79c82a4fd65adc2"}


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()[:16]


def drawn(cfg, layer):
    """The reference's draws of one layer, in one jitted call as the
    reference makes them (an eager draw may round an element otherwise)."""
    return jax.jit(lambda k: W.layer_weights(cfg, k, layer))(
        W.base_key(SEED))


def tiny(name, **over):
    cfg = load_json("configs", name)
    cfg.update(TINY, **over)
    return cfg


def program(cfg):
    from repro.models.factory import build

    api = build(arch_config(cfg))
    return jax.jit(lambda k: W.program_tree(api.abstract(), cfg, k))(
        W.base_key(SEED))


@pytest.mark.parametrize("name", ["phi3-mini-3.8b", "phi3-mini-3.8b-4l"])
def test_phi3_draws_are_the_parents(name):
    cfg = tiny(name)
    ref = reference(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        assert ref.layer_leaves(cfg, layer) == frozen_layer_leaves(cfg)
    assert ref.top_leaves(cfg) == frozen_top_leaves(cfg)
    tree = program(cfg)
    assert digest(jax.tree.leaves(tree)) == DIGESTS["program"]
    key = W.base_key(SEED)
    drawn = [v for layer in range(cfg["num_hidden_layers"])
             for _, v in sorted(W.layer_weights(cfg, key, layer).items())]
    drawn += [v for _, v in sorted(W.top_weights(cfg, key).items())]
    assert digest(drawn) == DIGESTS["reference"]


def test_matmul_params_of_the_4_layer_configuration():
    cfg = load_json("configs", "phi3-mini-3.8b-4l")
    assert reference(cfg).matmul_params(cfg) == 513_736_704


# Two positions a period, two periods, and one remainder layer.
PERIOD_2 = {"num_hidden_layers": 5,
            "program": {"pattern": ["attn", "attn"],
                        "mlp_pattern": ["swiglu", "swiglu"]}}


def layer_of(tree, layer, period=2, n_periods=2):
    """The program's leaves of one layer, by name, in float32."""
    if layer < period * n_periods:
        i, p = divmod(layer, period)
        part = jax.tree.map(lambda a: a[i], tree["periods"][p])
    else:
        part = tree["rest"][layer - period * n_periods]
    return {".".join(k.key for k in path): np.asarray(x, np.float32)
            for path, x in jax.tree_util.tree_flatten_with_path(part)[0]}


def test_two_position_period_and_remainder_get_their_own_layers():
    cfg = tiny("phi3-mini-3.8b-4l", **PERIOD_2)
    assert arch_config(cfg).layer_plan() == (2, 1)
    tree = program(cfg)
    assert len(tree["periods"]) == 2 and len(tree["rest"]) == 1
    layers = [layer_of(tree, layer) for layer in range(5)]
    for layer, got in enumerate(layers):
        want = drawn(cfg, layer)
        assert set(got) == set(want)
        for name in want:
            assert np.array_equal(got[name], np.asarray(want[name])), (
                layer, name)
    for a in range(5):
        for b in range(a):
            assert not np.array_equal(layers[a]["mixer.wq"],
                                      layers[b]["mixer.wq"]), (a, b)
    norms = W.leaf_norms(tree, cfg)
    arrays = W.leaf_arrays(tree)
    for layer in range(5):
        assert np.array_equal(arrays[("mlp.wo", layer)],
                              layers[layer]["mlp.wo"])
        assert float(norms[("mlp.wo", layer)]) == pytest.approx(
            float(np.linalg.norm(layers[layer]["mlp.wo"])), rel=1e-6)
    assert set(norms) == set(arrays)
    assert {layer for _, layer in norms} == set(range(5))


@pytest.mark.parametrize("table, edit, name", [
    ("layer_leaves", "drop", "mlp.wo"),
    ("layer_leaves", "add", "mlp.bias"),
    ("top_leaves", "drop", "unembed.kernel"),
])
def test_a_leaf_on_one_side_only_is_named(monkeypatch, table, edit, name):
    cfg = tiny("phi3-mini-3.8b")
    ref = reference(cfg)
    original = getattr(ref, table)

    def edited(*args):
        leaves = dict(original(*args))
        if edit == "drop":
            del leaves[name]
        else:
            leaves[name] = ((8,), 0.1)
        return leaves

    monkeypatch.setattr(ref, table, edited)
    with pytest.raises(ValueError, match=re.escape(name)):
        program(cfg)


def test_published_expert_keys_and_program_fields():
    cfg = json.loads((DATA / "tiny-moe.json").read_text())
    arch = arch_config(cfg)
    assert (arch.n_experts, arch.n_experts_per_tok, arch.moe_d_ff) == (8, 2,
                                                                       64)
    assert arch.mlp_pattern == ("moe",) and arch.capacity_factor == 4.0
    for field in ("n_experts", "d_model", "compute_dtype"):
        with pytest.raises(ValueError, match=field):
            arch_config(dict(cfg, program=dict(cfg["program"], **{field: 1})))


def test_expert_leaves_are_drawn_as_served(monkeypatch):
    path = DATA / "tiny_moe_reference.py"
    spec = importlib.util.spec_from_file_location("tiny_moe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(W, "reference", lambda cfg: mod)
    cfg = json.loads((DATA / "tiny-moe.json").read_text())
    cfg["dtype"] = "bfloat16"
    tree = program(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        got = layer_of(tree, layer, period=1, n_periods=2)
        want = drawn(cfg, layer)
        assert set(got) == set(want) and "mlp.router" in got
        for name in want:
            assert np.array_equal(got[name], np.asarray(want[name])), name
    assert mod.matmul_params(cfg) == 2 * (
        2 * 128 * 2 * 32 + 4 * 32 * 128 + 128 * 8 + 2 * 3 * 128 * 64
    ) + 128 * 512
