"""Serving tests: wave generation, chunked-prefill continuous batching, the
paper's constant-memory / linear-time claims measured literally."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from repro.configs import smoke_config
from repro.core.scan_attention import combine, make_empty_state, make_leaf_state, readout
from repro.models.factory import build
from repro.models.lm import lm_state_batch_axes
from repro.serving import StreamingEngine, decode_state_bytes, generate
from repro.serving.sampler import greedy_sampler, temperature_sampler


@pytest.fixture(scope="module")
def aaren_model():
    cfg = smoke_config("phi3-mini-3.8b", n_layers=2, d_model=64, d_ff=128,
                       vocab=64)
    api = build(cfg)
    return api, api.init(jax.random.PRNGKey(0))


def test_generate_shapes(aaren_model, rng):
    api, params = aaren_model
    prompts = jax.random.randint(rng, (3, 5), 0, 64)
    toks, states = generate(api, params, prompts, 7)
    assert toks.shape == (3, 7)
    assert toks.dtype == jnp.int32


def test_streaming_matches_wave(aaren_model, rng):
    """Continuous-batching engine (greedy) == wave generation (greedy)."""
    api, params = aaren_model
    prompts = jax.random.randint(rng, (2, 5), 0, 64)
    toks, _ = generate(api, params, prompts, 6)
    eng = StreamingEngine(api, params, n_slots=2)
    r0 = eng.submit(prompts[0], 6)
    r1 = eng.submit(prompts[1], 6)
    out = eng.run()
    assert out[r0] == [int(x) for x in toks[0]]
    assert out[r1] == [int(x) for x in toks[1]]


def test_slot_reuse_correctness(aaren_model, rng):
    """More requests than slots: recycled slots must produce the same output
    as a dedicated run (state fully reset — no leakage between requests)."""
    api, params = aaren_model
    prompts = jax.random.randint(rng, (5, 4), 0, 64)
    solo = {}
    for i in range(5):
        t, _ = generate(api, params, prompts[i:i + 1], 5)
        solo[i] = [int(x) for x in t[0]]
    eng = StreamingEngine(api, params, n_slots=2)
    rids = [eng.submit(prompts[i], 5) for i in range(5)]
    out = eng.run()
    for i, rid in enumerate(rids):
        assert out[rid] == solo[i], f"request {i} diverged after slot reuse"


def test_fill_slots_split_keys(aaren_model, rng):
    """Every slot fill must sample its first token with a freshly split key
    (the un-split ``self.key`` would give every refilled request the same
    first-token randomness)."""
    api, params = aaren_model
    seen = []

    def recording_sampler(logits, key):
        seen.append(tuple(np.asarray(key).tolist()))
        return greedy_sampler(logits, key)

    eng = StreamingEngine(api, params, n_slots=2, sampler=recording_sampler)
    for i in range(4):
        prompt = jax.random.randint(jax.random.fold_in(rng, i), (4,), 0, 64)
        eng.submit(prompt, 3)
    eng.run()
    assert len(seen) == len(set(seen)), "PRNG key reused across samples"


def test_engine_rejects_kv_models(rng):
    cfg = smoke_config("phi3-mini-3.8b", attn_mode="softmax")
    api = build(cfg)
    with pytest.raises(ValueError, match="position-free"):
        StreamingEngine(api, api.init(rng))


@pytest.mark.parametrize("attn_mode", ["aaren", "softmax"])
def test_generate_ragged_prefill_matches_unpadded(attn_mode):
    """Ragged wave prefill (right-pad + true lengths) == per-prompt runs.

    The legacy path left-pads prompts to one length and attends the pad
    tokens as real context — approximate by construction.  With
    ``prompt_lengths=`` the padding is masked in-kernel (``flash_mha``
    q_lens/kv_lens for softmax archs, ⊕-identity leaves for Aaren), the
    first sample reads each row's true last-token logits, and decode
    continues from exact per-row states (KV caches mask the padded gap and
    use true absolute positions).  Greedy tokens must match running each
    prompt alone, exactly — for the O(1)-state arch AND the KV-cache
    baseline (the ROADMAP PR-4 follow-up this closes).
    """
    cfg = smoke_config("phi3-mini-3.8b", attn_mode=attn_mode, n_layers=2,
                       d_model=64, d_ff=128, vocab=64)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng_np = np.random.default_rng(0)
    lens = [3, 7, 5, 1]
    max_p = max(lens)
    raw = [rng_np.integers(1, 64, size=L).astype(np.int32) for L in lens]
    prompts = np.zeros((len(lens), max_p), np.int32)
    for i, r in enumerate(raw):
        prompts[i, :len(r)] = r
    cache_len = max_p + 6
    toks, _ = generate(api, params, jnp.asarray(prompts), 6,
                       prompt_lengths=jnp.asarray(lens),
                       cache_len=cache_len)
    for i, r in enumerate(raw):
        solo, _ = generate(api, params, jnp.asarray(r)[None], 6,
                           cache_len=cache_len)
        np.testing.assert_array_equal(
            np.asarray(toks[i]), np.asarray(solo[0]),
            err_msg=f"row {i} (len {lens[i]}) diverged from its solo run")
    # A wrapping KV ring would overwrite prompt slots the ragged decode
    # mask still reads as prompt — must be rejected, not silently wrong.
    with pytest.raises(ValueError, match="non-wrapping"):
        generate(api, params, jnp.asarray(prompts), 6,
                 prompt_lengths=jnp.asarray(lens), cache_len=max_p + 3)


def test_constant_memory_claim(aaren_model):
    """Paper Fig. 5-left: Aaren decode state does not grow with tokens;
    KV-cache state grows linearly."""
    api, params = aaren_model
    p1 = jnp.zeros((1, 4), jnp.int32)
    _, s_short = generate(api, params, p1, 4)
    _, s_long = generate(api, params, p1, 32)
    assert decode_state_bytes(s_short) == decode_state_bytes(s_long)

    cfg_kv = smoke_config("phi3-mini-3.8b", n_layers=2, d_model=64, d_ff=128,
                          vocab=64, attn_mode="softmax")
    api_kv = build(cfg_kv)
    params_kv = api_kv.init(jax.random.PRNGKey(0))
    _, kv_short = generate(api_kv, params_kv, p1, 4)
    _, kv_long = generate(api_kv, params_kv, p1, 32)
    assert decode_state_bytes(kv_long) > decode_state_bytes(kv_short)


def test_streaming_matches_wave_temperature(aaren_model, rng):
    """Seeded temperature sampling: streaming == wave, token for token.

    Sampling keys are derived per (request, step), never from engine
    scheduling, so the two engines must agree exactly."""
    api, params = aaren_model
    sampler = temperature_sampler(0.8, top_k=8)
    prompts = jax.random.randint(rng, (3, 6), 0, 64)
    key = jax.random.PRNGKey(7)
    toks, _ = generate(api, params, prompts, 6, sampler=sampler, key=key)
    eng = StreamingEngine(api, params, n_slots=3, sampler=sampler, key=key)
    rids = [eng.submit(prompts[i], 6) for i in range(3)]
    out = eng.run()
    for i, rid in enumerate(rids):
        assert out[rid] == [int(x) for x in toks[i]], f"request {i} diverged"


def test_midflight_refill_unequal_max_new(aaren_model, rng):
    """Mixed prompt lengths AND unequal max_new_tokens: slots free at
    different ticks, refills prefill over multiple chunks while other slots
    keep decoding — every request must still match its dedicated run."""
    api, params = aaren_model
    plens = [3, 9, 17, 4, 33]
    news = [2, 7, 3, 5, 4]
    prompts = [jax.random.randint(jax.random.fold_in(rng, i), (l,), 0, 64)
               for i, l in enumerate(plens)]
    solo = []
    for p, n in zip(prompts, news):
        t, _ = generate(api, params, p[None], n)
        solo.append([int(x) for x in t[0]])
    eng = StreamingEngine(api, params, n_slots=2, chunk=4)
    rids = [eng.submit(p, n) for p, n in zip(prompts, news)]
    out = eng.run()
    for i, rid in enumerate(rids):
        assert out[rid] == solo[i], f"request {i} diverged after refill"


def test_one_trace_per_entry_point(aaren_model, rng, monkeypatch):
    """The recompile-storm regression: serving mixed prompt lengths
    (1..11 tokens, chunk 4) traces each jitted engine function exactly once.
    The old engine re-traced its prefill for every distinct prompt length."""
    api, params = aaren_model
    counts = {}
    real_jit = jax.jit

    def counting_jit(fn):
        counts[fn.__name__] = 0

        def wrapped(*a, **k):
            counts[fn.__name__] += 1
            return fn(*a, **k)

        wrapped.__name__ = fn.__name__
        return real_jit(wrapped)

    monkeypatch.setattr(engine_mod, "_jit", counting_jit)
    eng = StreamingEngine(api, params, n_slots=2, chunk=4)
    eng.warmup()
    for i, plen in enumerate([1, 3, 4, 7, 11, 2]):
        eng.submit(jax.random.randint(
            jax.random.fold_in(rng, i), (plen,), 0, 64), 5)
    eng.run()
    assert counts == {"step": 1, "reset": 1}, counts


def test_state_reset_batch_axis_at_nslots_eq_nheads(rng):
    """Slot addressing must come from explicit batch-axis metadata: with
    n_slots == n_heads every (B, H) state leaf is square and a shape-matching
    heuristic can zero a *head* instead of a *slot*."""
    cfg = smoke_config("phi3-mini-3.8b", n_layers=2, d_model=64, d_ff=128,
                       vocab=64, n_heads=4, n_kv_heads=4, head_dim=16)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = StreamingEngine(api, params, n_slots=cfg.n_heads)
    poisoned = jax.tree.map(lambda a: jnp.full_like(a, 7.0), eng.states)
    mask = jnp.asarray([False, False, True, False])
    out = eng._reset_fn(poisoned, mask)
    axes = jax.tree.leaves(lm_state_batch_axes(cfg))
    fresh = jax.tree.leaves(eng._init_states)
    for leaf, init, ax in zip(jax.tree.leaves(out), fresh, axes):
        got = jnp.moveaxis(leaf, ax, 0)
        want_fresh = jnp.moveaxis(init, ax, 0)
        np.testing.assert_array_equal(got[2], want_fresh[2])  # slot 2 reset
        for s in (0, 1, 3):                                   # others intact
            np.testing.assert_array_equal(got[s], jnp.full_like(got[s], 7.0))


def test_mixed_pattern_engine_chunk1(rng):
    """rglru + aaren pattern (recurrentgemma): carries advance token-by-token,
    so the engine runs at chunk=1 — and must still match wave generation."""
    cfg = smoke_config("recurrentgemma-9b", d_model=64, d_ff=128, vocab=64,
                       rnn_width=64)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="all-aaren"):
        StreamingEngine(api, params, chunk=4)
    eng = StreamingEngine(api, params, n_slots=2)
    assert eng.chunk == 1
    prompts = jax.random.randint(rng, (3, 4), 0, 64)
    toks, _ = generate(api, params, prompts, 4)
    rids = [eng.submit(prompts[i], 4) for i in range(3)]
    out = eng.run()
    for i, rid in enumerate(rids):
        assert out[rid] == [int(x) for x in toks[i]]


def test_readout_empty_state_is_defined():
    """readout(empty) used to be 0/0 = nan with the default eps=0; the empty
    index set attends to nothing, so its readout is 0 — and folding in one
    real token afterwards must behave exactly as if the nan never lurked."""
    empty = make_empty_state((2, 3), 4)
    out = readout(empty)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out), 0.0)

    s = jnp.ones((2, 3)) * 0.5
    v = jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 4)
    one = combine(empty, make_leaf_state(s, v))
    np.testing.assert_allclose(np.asarray(readout(one)), np.asarray(v),
                               rtol=1e-6)


def _batch(chunk, lengths, rids=None, steps=None, tokens=None):
    """The step's packed (S, chunk + 3) input: the tokens (0 by default),
    then each row's valid length, request id and sample index."""
    n = len(lengths)
    batch = np.zeros((n, chunk + 3), np.int32)
    if tokens is not None:
        batch[:, :chunk] = tokens
    batch[:, chunk] = lengths
    batch[:, chunk + 1] = rids if rids is not None else 0
    batch[:, chunk + 2] = steps if steps is not None else 0
    return jnp.asarray(batch)


def test_all_padding_step_is_safe(aaren_model, rng):
    """A slot scheduled with ``lengths == 0`` (all-padding row) used to
    gather last-valid logits at index ``lengths - 1 == -1`` — silently
    reading some other position's logits.  The guarded step must (a) return
    finite logits for every slot and (b) leave the padded slot's carries
    exactly untouched (the whole row enters the scan as ⊕-identity
    leaves)."""
    api, params = aaren_model
    eng = StreamingEngine(api, params, n_slots=2, chunk=4,
                          key=jax.random.PRNGKey(1))
    # Give slot carries non-trivial values first: serve one real request.
    eng.submit(np.asarray([3, 5, 7], np.int32), 2)
    eng.step()
    before = jax.tree.map(np.asarray, eng.states)

    def step(lengths):
        return eng._step_fn(eng.params, _batch(4, lengths), eng.states,
                            eng.key)

    picked, last, after = step([0, 0])           # every slot all-padding
    assert np.all(np.isfinite(np.asarray(last)))
    np.testing.assert_array_equal(np.asarray(picked)[:, 1], [1, 1])
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_allclose(np.asarray(b), a, rtol=0, atol=0)

    # Mixed tick: one real decode row next to an all-padding row must give
    # the real row exactly the logits (and token) it gets when every slot
    # is live — the padded row must not leak into it through any cross-row
    # path.
    picked_live, last_live, _ = step([1, 1])
    picked_mixed, last_mixed, _ = step([1, 0])
    assert np.all(np.isfinite(np.asarray(last_mixed)))
    np.testing.assert_allclose(np.asarray(last_mixed[0]),
                               np.asarray(last_live[0]), rtol=0, atol=0)
    assert int(picked_mixed[0, 0]) == int(picked_live[0, 0])


@pytest.mark.parametrize("sampler", [
    greedy_sampler, temperature_sampler(0.7, top_k=8)],
    ids=["greedy", "temperature"])
def test_step_samples_like_sample_rows(aaren_model, rng, sampler):
    """The step's tokens are, bit for bit, what ``_sample_rows`` (the wave
    path) draws from the logits the step returns, per (request, step)."""
    api, params = aaren_model
    key = jax.random.PRNGKey(3)
    eng = StreamingEngine(api, params, n_slots=3, chunk=4, sampler=sampler,
                          key=key)
    batch = _batch(4, [4, 1, 2], rids=[5, 9, 2], steps=[0, 3, 7],
                   tokens=jax.random.randint(rng, (3, 4), 0, 64))
    picked, last, _ = eng._step_fn(params, batch, eng.states, key)
    want = engine_mod._sample_rows(sampler, last, key, [5, 9, 2], [0, 3, 7])
    np.testing.assert_array_equal(np.asarray(picked)[:, 0],
                                  np.asarray(want)[:, 0])
    np.testing.assert_array_equal(np.asarray(picked)[:, 1], [1, 1, 1])


def test_step_guard_flags_only_the_poisoned_row(aaren_model, rng):
    """A NaN carry in slot 1 clears slot 1's finite flag alone; the other
    rows' flags and tokens are those of the unpoisoned step."""
    from repro.testing.faults import poison_engine_slot

    api, params = aaren_model
    eng = StreamingEngine(api, params, n_slots=3, chunk=4)
    for i in range(3):
        eng.submit(jax.random.randint(jax.random.fold_in(rng, i), (5,),
                                      0, 64), 6)
    eng.step()
    batch = _batch(4, [1, 1, 1], rids=[0, 1, 2], steps=[1, 1, 1])
    clean, _, _ = eng._step_fn(params, batch, eng.states, eng.key)
    poison_engine_slot(eng, 1)
    picked, last, _ = eng._step_fn(params, batch, eng.states, eng.key)
    picked, clean = np.asarray(picked), np.asarray(clean)
    np.testing.assert_array_equal(picked[:, 1], [1, 0, 1])
    assert not np.isfinite(np.asarray(last[1])).all()
    np.testing.assert_array_equal(picked[[0, 2]], clean[[0, 2]])


def _recording(seen):
    def sampler(logits, key):
        seen.append(1)
        return greedy_sampler(logits, key)
    return sampler


@pytest.mark.parametrize("jit_safe", [True, False], ids=["in_step", "eager"])
def test_sampled_in_step_counter(aaren_model, rng, jit_safe):
    """``serve_sampled_in_step_total`` counts every token a jit-safe sampler
    drew inside the step, and none where a recording sampler (not
    ``jit_safe``) samples on the host."""
    from repro.obs.metrics import MetricsRegistry, use_metrics

    api, params = aaren_model
    seen = []
    sampler = greedy_sampler if jit_safe else _recording(seen)
    with use_metrics(MetricsRegistry()) as reg:
        eng = StreamingEngine(api, params, n_slots=2, chunk=4,
                              sampler=sampler)
        for i, n in enumerate([3, 5, 2]):
            eng.submit(jax.random.randint(jax.random.fold_in(rng, i), (6,),
                                          0, 64), n)
        out = eng.run()
    emitted = sum(map(len, out.values()))
    assert emitted == 10
    if jit_safe:
        assert reg.peek("serve_sampled_in_step_total") == emitted
    else:
        assert reg.peek("serve_sampled_in_step_total") is None
        assert len(seen) == emitted


class _CountedRead:
    """Stands in for the step's small output: counts host reads."""

    def __init__(self, x, reads):
        self.x, self.reads = x, reads

    def __array__(self, dtype=None, copy=None):
        self.reads.append(1)
        return np.asarray(self.x, dtype)


class _Unreadable:
    """Stands in for the step's logits: any use fails the test."""

    def __getattr__(self, name):
        raise AssertionError(f"the host touched the logits ({name})")

    def __array__(self, dtype=None, copy=None):
        raise AssertionError("the host copied the logits")

    def __getitem__(self, idx):
        raise AssertionError("the host indexed the logits")


def test_decode_tick_one_upload_one_read(aaren_model, rng, capfd):
    """With a jit-safe sampler, a tick that frees no slot uploads one array
    (logged by JAX's transfer guard) and reads one back; the logits never
    reach the host."""
    api, params = aaren_model
    eng = StreamingEngine(api, params, n_slots=2, chunk=4)
    eng.warmup()
    for i in range(2):
        eng.submit(jax.random.randint(jax.random.fold_in(rng, i), (3,),
                                      0, 64), 8)
    assert eng.step() == 2                       # prefill + first tokens
    reads = []
    real = eng._step_fn

    def spy(*args):
        picked, _, states = real(*args)
        return _CountedRead(picked, reads), _Unreadable(), states

    eng._step_fn = spy
    capfd.readouterr()
    with jax.transfer_guard_host_to_device("log_explicit"):
        assert eng.step() == 2                   # decode, no slot freed
    assert capfd.readouterr().err.count("host-to-device transfer") == 1
    assert reads == [1]
    assert all(len(s.tokens) == 2 for s in eng.active)


def test_masked_chunk_matches_sliced(rng):
    """⊕-identity masking: a fixed-shape chunk with a ragged valid prefix
    must equal the same chunk sliced to the prefix, on both the layer-level
    reference (aaren_attention_chunked) and the core carry path
    (attention_many_to_many_with_state)."""
    from repro.core.aaren import aaren_attention_chunked, empty_carry
    from repro.core.scan_attention import attention_many_to_many_with_state

    b, n, valid, h, g, d = 2, 6, 4, 4, 2, 8
    q = jax.random.normal(rng, (h, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, n, g, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, n, g, d))
    carry = empty_carry(b, h, d)
    mask = jnp.broadcast_to(jnp.arange(n)[None, :] < valid, (b, n))
    out_m, fin_m = aaren_attention_chunked(q, k, v, carry, 0.5, mask=mask)
    out_s, fin_s = aaren_attention_chunked(
        q, k[:, :valid], v[:, :valid], carry, 0.5)
    np.testing.assert_allclose(np.asarray(out_m[:, :valid]),
                               np.asarray(out_s), rtol=1e-6)
    for a, c in zip(jax.tree.leaves(fin_m), jax.tree.leaves(fin_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-6)

    qv = jax.random.normal(jax.random.fold_in(rng, 3), (b, d))
    kv = k[:, :, 0]
    vv = v[:, :, 0]
    out_m, fin_m = attention_many_to_many_with_state(
        qv, kv, vv, mask=mask)
    out_s, fin_s = attention_many_to_many_with_state(
        qv, kv[:, :valid], vv[:, :valid])
    np.testing.assert_allclose(np.asarray(out_m[:, :valid]),
                               np.asarray(out_s), rtol=1e-6)
    for a, c in zip(jax.tree.leaves(fin_m), jax.tree.leaves(fin_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-6)


def test_temperature_sampler_topk(rng):
    logits = jnp.asarray([[[0.0, 1.0, 2.0, 3.0]]])
    s = temperature_sampler(1.0, top_k=2)
    for i in range(20):
        tok = s(logits, jax.random.fold_in(rng, i))
        assert int(tok[0, 0]) in (2, 3)  # only top-2 survive


# ---------------------------------------------------------------------------
# Input validation at the API boundary (DESIGN.md §Fault-tolerance)
# ---------------------------------------------------------------------------


def test_generate_rejects_bad_inputs(aaren_model, rng):
    api, params = aaren_model
    good = jax.random.randint(rng, (2, 5), 0, 64)
    with pytest.raises(ValueError, match="empty"):
        generate(api, params, jnp.zeros((0, 5), jnp.int32), 4)
    with pytest.raises(ValueError, match="empty"):
        generate(api, params, jnp.zeros((2, 0), jnp.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(api, params, good, 0)
    with pytest.raises(ValueError, match="prompt_lengths"):
        generate(api, params, good, 4, prompt_lengths=jnp.asarray([3]),
                 cache_len=32)
    with pytest.raises(ValueError, match=r"\[1, 5\]"):
        generate(api, params, good, 4, prompt_lengths=jnp.asarray([0, 9]),
                 cache_len=32)


def test_generate_rejects_wrapping_kv_cache():
    """A global-attention KV ring that wraps silently drops the earliest
    context — must be a loud error, not a quietly wrong answer."""
    cfg = smoke_config("phi3-mini-3.8b", attn_mode="softmax", n_layers=2,
                      d_model=64, d_ff=128, vocab=64)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    prompts = jnp.ones((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="wrap"):
        generate(api, params, prompts, 8, cache_len=10)
    toks, _ = generate(api, params, prompts, 8, cache_len=16)
    assert toks.shape == (1, 8)


def test_generate_ragged_attn_local_window_raises_at_entry():
    """Ragged prefill with an attn_local window shorter than the padded
    prompt needs per-row ring indices (unimplemented): the error must name
    the config at the generate() boundary, not surface mid-trace."""
    cfg = smoke_config("phi3-mini-3.8b", attn_mode="softmax", n_layers=2,
                      d_model=64, d_ff=128, vocab=64, window=4,
                      pattern=("attn_local",))
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    prompts = jnp.ones((2, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="window"):
        generate(api, params, prompts, 4,
                 prompt_lengths=jnp.asarray([3, 8]), cache_len=32)
    # window >= padded prompt length stays supported
    cfg2 = smoke_config("phi3-mini-3.8b", attn_mode="softmax", n_layers=2,
                       d_model=64, d_ff=128, vocab=64, window=8,
                       pattern=("attn_local",))
    api2 = build(cfg2)
    toks, _ = generate(api2, api2.init(jax.random.PRNGKey(0)), prompts, 4,
                       prompt_lengths=jnp.asarray([3, 8]), cache_len=32)
    assert toks.shape == (2, 4)


def test_submit_rejects_bad_inputs(aaren_model):
    api, params = aaren_model
    eng = StreamingEngine(api, params, n_slots=2)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.asarray([], np.int32), 4)
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(np.zeros((2, 3), np.int32), 4)
    with pytest.raises(ValueError, match="integer"):
        eng.submit(np.asarray([1.5, 2.5]), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.asarray([1, 2], np.int32), 0)
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(np.asarray([1, 2], np.int32), 4, deadline_s=-1.0)
    assert eng.queue == [] and eng._next_id == 0   # nothing half-admitted

# ---------------------------------------------------------------------------
# Slot-carry lifecycle invariant (DESIGN.md §Serving): free slots ALWAYS hold
# the ⊕-identity init carry, the latency maps track only in-flight requests,
# and the scheduler gauges match scheduler state — after EVERY exit path
# (completion, deadline expiry, quarantine, restore).
# ---------------------------------------------------------------------------


def _assert_free_slots_fresh(eng):
    """Every free slot's rows of eng.states equal the ⊕-identity init."""
    axes = jax.tree.leaves(lm_state_batch_axes(eng.api.cfg))
    free = [i for i, s in enumerate(eng.active) if s is None]
    assert free, "test needs at least one free slot"
    for leaf, init, ax in zip(jax.tree.leaves(eng.states),
                              jax.tree.leaves(eng._init_states), axes):
        got = np.moveaxis(np.asarray(leaf), ax, 0)
        want = np.moveaxis(np.asarray(init), ax, 0)
        for i in free:
            np.testing.assert_array_equal(got[i], want[i])


def _assert_departed(eng, rids):
    for rid in rids:
        assert rid not in eng.submitted_at, rid
        assert rid not in eng.first_token_at, rid


def test_lifecycle_completion_resets_carry_eagerly(aaren_model, rng):
    """A completed request's carry returns to init in the same tick — not
    lazily at the next admit."""
    api, params = aaren_model
    eng = StreamingEngine(api, params, n_slots=2, chunk=4)
    rid = eng.submit(jax.random.randint(rng, (6,), 0, 64), 3)
    eng.run()
    assert eng.active == [None, None]
    _assert_free_slots_fresh(eng)
    _assert_departed(eng, [rid])


def test_lifecycle_active_deadline_resets_carry_eagerly(aaren_model, rng):
    """The stale-carry regression: an active slot freed by deadline expiry
    used to keep the dead request's carry in eng.states until the next
    admit refilled the slot — a snapshot (or cache gather) taken in the gap
    saw another tenant's state in a 'free' slot."""
    import time as _time
    api, params = aaren_model
    eng = StreamingEngine(api, params, n_slots=1, chunk=4)
    rid = eng.submit(jax.random.randint(rng, (8,), 0, 64), 1000,
                     deadline_s=0.03)
    eng.step()          # prefill a chunk: carry now non-trivial
    _time.sleep(0.05)
    eng.step()          # expiry tick — queue is empty, nothing re-admits
    assert eng.errors[rid] == engine_mod.ERR_DEADLINE
    assert eng.active == [None]
    _assert_free_slots_fresh(eng)
    _assert_departed(eng, [rid])


def test_lifecycle_quarantine_resets_carry_eagerly(aaren_model, rng):
    from repro.testing.faults import poison_engine_slot
    api, params = aaren_model
    eng = StreamingEngine(api, params, n_slots=2, chunk=4)
    rid = eng.submit(jax.random.randint(rng, (4,), 0, 64), 100)
    eng.step()
    poison_engine_slot(eng, 0)
    eng.step()
    assert eng.errors[rid] == engine_mod.ERR_POISONED
    _assert_free_slots_fresh(eng)
    _assert_departed(eng, [rid])


def test_lifecycle_restore_reseeds_latency_and_gauges(aaren_model, rng):
    """restore() used to wipe submitted_at outright: every restored
    request's terminal event then dropped total_s and its first token never
    reached the TTFT histogram.  Restored requests are re-seeded at restore
    time (post-restore latencies exclude pre-crash time by design) and the
    scheduler gauges reflect the restored state immediately."""
    from repro.obs.events import EventLog, use_events
    from repro.obs.metrics import MetricsRegistry, use_metrics

    api, params = aaren_model
    a = StreamingEngine(api, params, n_slots=2, chunk=4)
    prompts = jax.random.randint(rng, (3, 9), 0, 64)
    rids = [a.submit(prompts[i], 6) for i in range(3)]   # 2 active + 1 queued
    a.step()
    snap = a.snapshot()

    b = StreamingEngine(api, params, n_slots=2, chunk=4)
    with use_metrics(MetricsRegistry()) as reg, \
            use_events(EventLog(path=None)) as log:
        b.restore(snap)
        assert set(b.submitted_at) == set(rids)
        assert b.first_token_at == {}
        assert reg.gauge("serve_queue_depth").value == len(b.queue) == 1
        assert reg.gauge("serve_slot_occupancy").value == 1.0
        out = b.run()
        done = [r for r in log.records if r["kind"] == "request_completed"]
        assert {r["data"]["rid"] for r in done} == set(rids)
        for r in done:
            assert r["data"]["total_s"] >= 0           # present again
        # every restored request's first token reached the TTFT histogram
        assert reg.histogram("serve_ttft_s").count == len(rids)
    assert len(out) == 3
    _assert_free_slots_fresh(b)
    _assert_departed(b, rids)


def test_lifecycle_restore_enforces_free_slot_invariant(aaren_model, rng):
    """A snapshot whose free-slot rows hold garbage (taken by a pre-fix
    build) is sanitised at restore: free slots come back as ⊕-identity."""
    api, params = aaren_model
    a = StreamingEngine(api, params, n_slots=2, chunk=4)
    rid = a.submit(jax.random.randint(rng, (5,), 0, 64), 4)
    a.step()
    snap = a.snapshot()                      # slot 1 is free
    assert snap["meta"]["active"][1] is None
    snap["tree"]["states"] = jax.tree.map(
        lambda x: np.full_like(x, 7.0), snap["tree"]["states"])
    # keep slot 0's rows meaningless too — only the free slot is asserted
    b = StreamingEngine(api, params, n_slots=2, chunk=4)
    b.restore(snap)
    axes = jax.tree.leaves(lm_state_batch_axes(api.cfg))
    for leaf, init, ax in zip(jax.tree.leaves(b.states),
                              jax.tree.leaves(b._init_states), axes):
        got = np.moveaxis(np.asarray(leaf), ax, 0)
        want = np.moveaxis(np.asarray(init), ax, 0)
        np.testing.assert_array_equal(got[1], want[1])
    assert rid in b.submitted_at
