"""Compiles of the main path's kernels at real widths for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with libtpu
compiles for a ``v5e:2x2`` topology that is described, not attached, and
refuses what the chip would refuse (scoped-VMEM overflow, block shapes that
break the (8, 128) tiling rule).  Shapes are phi3-mini-3.8b's (head_dim 96,
32 heads): serving chunks (R = 8 slots x 32 heads, N = 16) and training
rows (R = 4 x 32, N = 4096).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around these
compiles, since an entry compiled for a described chip cannot be read back
without one.

The compile-cache helper's tests sit at the end of the file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.aaren_scan import aaren_scan
from repro.kernels.aaren_scan_bwd import aaren_scan_bwd
from repro.kernels.flash_attention import flash_attention, flash_attention_bwd
from repro.launch import compile_cache
from repro.models.factory import build
from repro.models.lm import lm_prefill_chunk, lm_state_specs
from repro.serving import StreamingEngine
from repro.serving.sampler import greedy_sampler, temperature_sampler

D = 96                          # phi3-mini head_dim
SERVE = (8 * 32, 16)            # (rows, tokens): 8 slots x 32 heads, C = 16
TRAIN = (4 * 32, 4096)          # batch 4 x 32 heads, N = 4096
B, H, N = 4, 32, 4096           # flash at phi3 training shape


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    """Compile for the described chip; returns the program text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _fwd_args(sh, r, n, segmented):
    args = [_sds(sh, (r, n)), _sds(sh, (r, n, D)), _sds(sh, (r, 1)),
            _sds(sh, (r, 1)), _sds(sh, (r, D))]
    if segmented:
        args.append(_sds(sh, (r, n)))
    return args


@pytest.mark.parametrize("shape", [SERVE, TRAIN], ids=["serve", "train"])
@pytest.mark.parametrize("variant", ["plain", "residuals", "segmented"])
def test_aaren_forward_compiles(one_chip, shape, variant):
    r, n = shape
    residuals = variant != "plain"
    _compile(lambda *a: aaren_scan(*a, return_residuals=residuals),
             *_fwd_args(one_chip, r, n, variant == "segmented"))


@pytest.mark.parametrize("segmented", [False, True],
                         ids=["plain", "segmented"])
def test_aaren_backward_compiles(one_chip, segmented):
    r, n = TRAIN
    sh = one_chip
    args = [_sds(sh, (r, n)), _sds(sh, (r, n, D)), _sds(sh, (r, n, D)),
            _sds(sh, (r, n)), _sds(sh, (r, n)), _sds(sh, (r, n, D)),
            _sds(sh, (r, 1)), _sds(sh, (r, D)), _sds(sh, (r, 1))]
    if segmented:
        args.append(_sds(sh, (r, n)))
    _compile(aaren_scan_bwd, *args)


def _flash_io(sh):
    qkv = [_sds(sh, (B, H, N, D), jnp.bfloat16) for _ in range(3)]
    extra = [_sds(sh, (B,), jnp.int32), _sds(sh, (B, N), jnp.int32)]
    return qkv, extra


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "lens+seg"])
def test_flash_forward_compiles(one_chip, masked):
    qkv, (lens, seg) = _flash_io(one_chip)
    if masked:
        _compile(lambda q, k, v, ln, sg: flash_attention(
            q, k, v, q_lens=ln, kv_lens=ln, q_segment_ids=sg,
            kv_segment_ids=sg, return_residuals=True), *qkv, lens, seg)
    else:
        _compile(lambda q, k, v: flash_attention(q, k, v,
                                                 return_residuals=True), *qkv)


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "lens+seg"])
def test_flash_backward_compiles(one_chip, masked):
    qkv, (lens, seg) = _flash_io(one_chip)
    o_do = [_sds(one_chip, (B, H, N, D), jnp.bfloat16) for _ in range(2)]
    lse = _sds(one_chip, (B, H, N))
    args = [*qkv, o_do[0], lse, o_do[1]]
    if masked:
        text = _compile(lambda q, k, v, o, l, do, ln, sg: flash_attention_bwd(
            q, k, v, o, l, do, q_lens=ln, kv_lens=ln, q_segment_ids=sg,
            kv_segment_ids=sg), *args, lens, seg)
    else:
        text = _compile(flash_attention_bwd, *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 2  # dq, dkv


def test_phi3_prefill_chunk_compiles(one_chip, monkeypatch):
    """The serving step at full phi3 width and depth: S = 8, C = 16."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    jax.clear_caches()  # no trace from another kernel mode is reused
    cfg = get_config("phi3-mini-3.8b").replace(attn_mode="aaren")
    api = build(cfg)
    place = lambda tree: jax.tree.map(
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    params = place(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    states = place(lm_state_specs(cfg, 8, 1))
    tokens = _sds(one_chip, (8, 16), jnp.int32)
    mask = _sds(one_chip, (8, 16), jnp.bool_)
    text = _compile(lambda p, t, m, s: lm_prefill_chunk(
        cfg, p, t, s, length_mask=m), params, tokens, mask, states)
    jax.clear_caches()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sampler", [greedy_sampler,
                                     temperature_sampler(0.7, top_k=8)],
                         ids=["greedy", "temperature"])
def test_phi3_engine_step_compiles(one_chip, monkeypatch, sampler):
    """The engine's whole tick program at the chat cell's shape, S = 16,
    C = 16: the chunk step, the finite guard and the sampler."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    jax.clear_caches()
    cfg = get_config("phi3-mini-3.8b").replace(attn_mode="aaren")
    api = build(cfg)
    place = lambda tree: jax.tree.map(
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    params = place(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    eng = StreamingEngine(api, params, n_slots=16, chunk=16, sampler=sampler)
    text = _compile(eng._step_fn, params,
                    _sds(one_chip, (16, 16 + 3), jnp.int32),
                    place(lm_state_specs(cfg, 16, 1)),
                    place(jax.eval_shape(lambda: eng.key)))
    jax.clear_caches()
    assert "tpu_custom_call" in text


def test_mellum_engine_step_compiles(one_chip, monkeypatch):
    """The Mellum cell's tick program: 28 layers at the published widths,
    16 of 64 experts held, S = 64, C = 16; the grouped expert kernel twice
    a layer (gate and up fused, then down), in the scanned period of four
    layers."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    jax.clear_caches()
    cfg = get_config("mellum2-12b-a2.5b").replace(
        n_experts=16, n_routed_experts=64)
    api = build(cfg)
    place = lambda tree: jax.tree.map(
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    params = place(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    eng = StreamingEngine(api, params, n_slots=64, chunk=16)
    text = _compile(eng._step_fn, params,
                    _sds(one_chip, (64, 16 + 3), jnp.int32),
                    place(lm_state_specs(cfg, 64, 1)),
                    place(jax.eval_shape(lambda: eng.key)))
    jax.clear_caches()
    assert len(set(re.findall(r"%_expert_gmm\.\d+ = ", text))) == 8


# ------------------------------------------------------- compile cache


def test_compile_cache_uses_the_variable(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory of its own.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(root, ".jax_cache")
