"""Training-stack tests: optimizers, microbatching, compression, the
fault-tolerant loop (crash/resume, preemption, straggler detection)."""

import os
import signal
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.data.synthetic import CopyTaskIterator, SyntheticLMIterator
from repro.distributed.grad import (
    compress_gradients,
    microbatch_grads,
    quantize_int8_stochastic,
)
from repro.models.factory import build
from repro.train.loop import LoopConfig, run_train_loop
from repro.train.optim import (
    adafactor,
    adamw,
    clip_by_global_norm,
    make_optimizer,
    opt_param_specs,
    warmup_cosine,
)
from repro.train.state import (
    abstract_train_state,
    build_train_state,
    init_train_state,
    make_train_step,
)


def _tiny():
    cfg = smoke_config("phi3-mini-3.8b", n_layers=2, d_model=64, d_ff=128,
                       vocab=64)
    return cfg, build(cfg)


def test_microbatch_equals_full_batch(rng):
    """Grad accumulation over k microbatches == one full-batch grad."""
    cfg, api = _tiny()
    params = api.init(rng)
    it = CopyTaskIterator(vocab=64, seq_len=17, batch=8)
    batch = next(it)
    g1, l1, _ = microbatch_grads(api.loss, params, batch, 1)
    g4, l4, _ = microbatch_grads(api.loss, params, batch, 4)
    np.testing.assert_allclose(float(l1), float(l4), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_int8_quantization_unbiased(rng):
    """Stochastic rounding must be unbiased: E[dequant(quant(g))] == g."""
    g = jax.random.normal(rng, (256,)) * 0.1
    total = jnp.zeros_like(g)
    n = 200
    for i in range(n):
        q, s = quantize_int8_stochastic(g, jax.random.fold_in(rng, i))
        total = total + q.astype(jnp.float32) * s
    mean = total / n
    scale = float(jnp.max(jnp.abs(g))) / 127
    np.testing.assert_allclose(np.asarray(mean), np.asarray(g),
                               atol=scale * 0.35)


def test_int8_quantization_roundtrip_property():
    """Property test over shapes/scales: quantize→dequantize round-trips
    shape and dtype, every error is below one quantization step, the codes
    are genuine int8, and repeated draws average back toward g (unbiased —
    momentum must not accumulate quantization bias, DESIGN.md §6)."""
    hypothesis = pytest.importorskip("hypothesis")
    given, settings, st = (hypothesis.given, hypothesis.settings,
                           hypothesis.strategies)

    @settings(max_examples=30, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        shape=st.sampled_from([(7,), (4, 5), (2, 3, 4), (1,), (128,)]),
        log_scale=st.floats(-6.0, 4.0),
    )
    def check(seed, shape, log_scale):
        rng = np.random.default_rng(seed)
        g = jnp.asarray(
            rng.standard_normal(shape) * 10.0 ** log_scale, jnp.float32)
        key = jax.random.PRNGKey(seed)
        q, scale = quantize_int8_stochastic(g, key)
        assert q.shape == g.shape and q.dtype == jnp.int8
        assert np.ndim(scale) == 0 and float(scale) > 0
        back = q.astype(jnp.float32) * scale
        assert back.shape == g.shape and back.dtype == g.dtype
        # one stochastic-rounding step of error, never more
        assert float(jnp.max(jnp.abs(back - g))) <= float(scale) * (1 + 1e-6)
        # unbiasedness: the mean over independent keys approaches g
        n = 64
        acc = jnp.zeros_like(g)
        for i in range(n):
            qi, si = quantize_int8_stochastic(g, jax.random.fold_in(key, i))
            acc = acc + qi.astype(jnp.float32) * si
        # SE of a U(-.5,.5) rounding residual is scale/sqrt(12 n); 6 sigma
        tol = float(scale) * 6.0 / np.sqrt(12 * n)
        np.testing.assert_allclose(np.asarray(acc / n), np.asarray(g),
                                   atol=tol)

    check()


def test_int8_quantization_zero_gradient():
    """All-zero g must survive the scale floor: finite scale, zero codes."""
    q, scale = quantize_int8_stochastic(jnp.zeros((16,)), jax.random.PRNGKey(0))
    assert np.isfinite(float(scale))
    np.testing.assert_array_equal(np.asarray(q), np.zeros((16,), np.int8))


def test_compression_modes(rng):
    g = {"a": jax.random.normal(rng, (32, 32)),
         "b": jax.random.normal(jax.random.fold_in(rng, 1), (8,))}
    for mode in ("none", "bf16", "int8"):
        out = compress_gradients(g, mode, key=rng)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(out)):
            assert a.shape == b.shape
            rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))
            assert rel < {"none": 1e-9, "bf16": 0.01, "int8": 0.02}[mode]


def test_clip_by_global_norm(rng):
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 20.0, rtol=1e-6)
    total = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(clipped)))
    np.testing.assert_allclose(float(total), 1.0, rtol=1e-5)


@pytest.mark.parametrize("name", ["adamw", "adamw_bf16", "adafactor"])
def test_optimizer_reduces_loss(name, rng):
    cfg, api = _tiny()
    params = api.init(rng)
    opt = make_optimizer(name, warmup_cosine(2e-3, 5, 60))
    state = init_train_state(params, opt)
    step = jax.jit(make_train_step(api.loss, opt))
    it = CopyTaskIterator(vocab=64, seq_len=17, batch=8)
    losses = []
    for i in range(40):
        state, m = step(state, next(it), jax.random.fold_in(rng, i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, f"{name}: {losses[0]} -> {losses[-1]}"


def test_opt_param_specs_structure_matches():
    """opt_param_specs must mirror jax.eval_shape(opt.init) exactly — the
    dry-run depends on this to shard optimizer state."""
    cfg, api = _tiny()
    for name in ("adamw", "adafactor"):
        opt = make_optimizer(name, warmup_cosine(1e-3, 5, 50))
        astate = jax.eval_shape(opt.init, api.abstract())
        from repro.models.param import abstract_params

        spec_tree = abstract_params(opt_param_specs(name, api.specs()))
        assert jax.tree.structure(astate) == jax.tree.structure(spec_tree)
        for a, b in zip(jax.tree.leaves(astate), jax.tree.leaves(spec_tree)):
            assert a.shape == b.shape, (name, a.shape, b.shape)
            assert a.dtype == b.dtype


def test_warmup_cosine_schedule():
    s = warmup_cosine(1.0, 10, 110)
    assert float(s(0)) == 0.0
    np.testing.assert_allclose(float(s(10)), 1.0, rtol=1e-6)
    assert float(s(5)) == 0.5
    np.testing.assert_allclose(float(s(110)), 0.1, rtol=1e-5)  # final_frac


def test_loop_crash_resume_bit_identical(rng):
    """Kill the loop mid-run; resume must continue to the same final state as
    an uninterrupted run (fault-tolerance acceptance test)."""
    cfg, api = _tiny()
    params = api.init(rng)
    opt = make_optimizer("adamw", warmup_cosine(1e-3, 5, 40))
    step = jax.jit(make_train_step(api.loss, opt))

    def fresh_iter():
        return CopyTaskIterator(vocab=64, seq_len=17, batch=8)

    # uninterrupted reference
    res_ref = run_train_loop(
        step, init_train_state(params, opt), fresh_iter(),
        LoopConfig(total_steps=20, install_signal_handlers=False))

    with tempfile.TemporaryDirectory() as d:
        lc = LoopConfig(total_steps=20, ckpt_dir=d, save_every=5,
                        install_signal_handlers=False)
        with pytest.raises(KeyboardInterrupt):
            run_train_loop(step, init_train_state(params, opt), fresh_iter(),
                           lc, _test_hooks={"crash_at": 10})
        res = run_train_loop(step, init_train_state(params, opt),
                             fresh_iter(), lc)
        assert res.resumed_from == 10
        assert int(res.state.step) == 20
        for a, b in zip(jax.tree.leaves(res.state.params),
                        jax.tree.leaves(res_ref.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loop_straggler_detection(rng):
    cfg, api = _tiny()
    params = api.init(rng)
    opt = make_optimizer("adamw", warmup_cosine(1e-3, 2, 30))
    step = jax.jit(make_train_step(api.loss, opt))
    res = run_train_loop(
        step, init_train_state(params, opt),
        CopyTaskIterator(vocab=64, seq_len=17, batch=8),
        LoopConfig(total_steps=30, install_signal_handlers=False),
        _test_hooks={"sleep": {20: 10.0}})  # inject one 10s straggler
    assert any(s[0] == 20 for s in res.stragglers), res.stragglers


def test_data_iterator_determinism_and_restore():
    it1 = SyntheticLMIterator(vocab=128, seq_len=16, batch=4, seed=7)
    batches = [next(it1) for _ in range(5)]
    it2 = SyntheticLMIterator(vocab=128, seq_len=16, batch=4, seed=7)
    it2.restore({"count": 3})
    np.testing.assert_array_equal(next(it2)["tokens"], batches[3]["tokens"])
    # per-host sharding draws disjoint deterministic streams
    h0 = SyntheticLMIterator(vocab=128, seq_len=16, batch=4, seed=7,
                             host_id=0, num_hosts=2)
    h1 = SyntheticLMIterator(vocab=128, seq_len=16, batch=4, seed=7,
                             host_id=1, num_hosts=2)
    assert not np.array_equal(next(h0)["tokens"], next(h1)["tokens"])


def test_data_iterator_host_slices_union_is_global_batch():
    """Concatenating every host's slice must reproduce the single-host
    global batch exactly, batch after batch — the property that makes the
    stream invariant to host-count changes (and lets the multi-host loop
    resume on a different topology)."""
    kw = dict(vocab=128, seq_len=24, batch=8, seed=11)
    global_it = SyntheticLMIterator(**kw)
    hosts = [SyntheticLMIterator(**kw, host_id=h, num_hosts=4)
             for h in range(4)]
    for _ in range(3):
        ref = next(global_it)["tokens"]
        union = np.concatenate([next(h)["tokens"] for h in hosts], axis=0)
        np.testing.assert_array_equal(union, ref)


def test_data_iterator_state_roundtrip_mid_epoch():
    """state()/restore() round-trips mid-stream on every host: the restored
    iterator replays the exact remaining batches."""
    kw = dict(vocab=64, seq_len=12, batch=6, seed=3)
    for host_id, num_hosts in ((0, 1), (1, 3)):
        it = SyntheticLMIterator(**kw, host_id=host_id, num_hosts=num_hosts)
        next(it), next(it)
        snap = it.state()
        tail = [next(it)["tokens"] for _ in range(3)]
        it2 = SyntheticLMIterator(**kw, host_id=host_id,
                                  num_hosts=num_hosts)
        it2.restore(snap)
        assert it2.state() == snap
        for want in tail:
            np.testing.assert_array_equal(next(it2)["tokens"], want)


def test_build_train_state_places_leaves_at_init():
    """One jitted init: the same values as the eager path, and on a mesh
    every leaf is created with its rule-derived NamedSharding."""
    from jax.sharding import NamedSharding

    from repro.sharding import MeshPlan

    cfg, api = _tiny()
    opt = make_optimizer(cfg.optimizer, warmup_cosine(1e-3, 1, 10))
    key = jax.random.PRNGKey(3)
    want = init_train_state(api.init(key), opt)
    got = build_train_state(api, opt, key)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mesh = MeshPlan(devices=jax.devices()[:1]).build_mesh()
    placed = build_train_state(api, opt, key, mesh=mesh)
    for leaf in jax.tree.leaves((placed.params, placed.opt_state)):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.mesh == mesh
