"""The dropless expert layer of the serving paths, against plain loops.

A tiny Mellum-shaped model (8 experts, top-2, the 3:1 pattern of window
and full layers, every layer Aaren) in float32: the layer against a
per-token loop, a chip's shares against the uncut layer, the engine's
logits at several chunk sizes against one full forward pass, the Pallas
kernel in interpret mode against the jnp path, and the registry's
parameter counts at the published widths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import expert_gmm as gmm_kernel
from repro.kernels import ops as kops
from repro.models import moe
from repro.models.factory import build
from repro.models.lm import lm_prefill_chunk, lm_state_init
from repro.models.param import count_params, init_params
from repro.obs.metrics import MetricsRegistry, use_metrics
from repro.serving import StreamingEngine
from repro.serving.engine import request_key

E, K, SHARES = 8, 2, 4


def tiny(**over):
    cfg = get_config("mellum2-12b-a2.5b").replace(
        n_layers=5, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        vocab=128, n_experts=E, n_experts_per_tok=K, moe_d_ff=32,
        param_dtype="float32", compute_dtype="float32")
    return cfg.replace(**over)


def layer_params(cfg, seed=0):
    return init_params(moe.moe_specs(cfg), jax.random.PRNGKey(seed))


def per_token(p, x, cfg):
    """The layer as a loop over tokens and their chosen experts: only the
    experts held here add, each times its renormalised gate."""
    gates, ids, _ = moe.route(p["router"], x, cfg.n_experts_per_tok)
    x, gates, ids = (np.asarray(a, np.float64) for a in (x, gates, ids))
    w = {n: np.asarray(v, np.float64) for n, v in p.items()}
    out = np.zeros_like(x)
    for idx in np.ndindex(x.shape[:-1]):
        for j in range(cfg.n_experts_per_tok):
            e = int(ids[idx + (j,)]) - cfg.expert_offset
            if not 0 <= e < cfg.n_experts:
                continue
            g = x[idx] @ w["wi_gate"][e]
            h = g / (1 + np.exp(-g)) * (x[idx] @ w["wi_up"][e])
            out[idx] += gates[idx + (j,)] * (h @ w["wo"][e])
    return out


@pytest.fixture(params=["jnp", "interpret"])
def kernel_mode(request, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", request.param)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


@pytest.mark.parametrize("routing", ["random", "one_expert"])
def test_dropless_layer_equals_per_token_loop(kernel_mode, routing):
    cfg = tiny()
    p = layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, cfg.d_model))
    if routing == "one_expert":
        # Every token's first choice is expert 5: one group of 48 rows
        # spans tiles, seven others share the second choices.
        x = jnp.abs(x)
        p = dict(p, router=p["router"].at[:, 5].set(1.0))
    y, counts = moe.apply_moe_dropless(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), per_token(p, x, cfg),
                               rtol=1e-4, atol=1e-5)
    _, ids, _ = moe.route(p["router"], x, K)
    load = np.bincount(np.asarray(ids).ravel(), minlength=E)
    assert counts.tolist() == [3 * 16 * K, load.max()]
    if routing == "one_expert":
        assert load[5] == 48


def test_padded_positions_add_nothing_and_are_not_counted():
    cfg = tiny()
    p = layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
    mask = jnp.arange(8)[None, :] < jnp.array([[3], [8]])
    y, counts = moe.apply_moe_dropless(p, x, cfg, mask)
    want = per_token(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y)[np.asarray(mask)],
                               want[np.asarray(mask)], rtol=1e-4, atol=1e-5)
    assert int(counts[0]) == 11 * K


def test_shares_add_up_to_the_uncut_layer():
    cfg = tiny()
    p = layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    whole, counts = moe.apply_moe_dropless(p, x, cfg)
    held = E // SHARES
    parts, pairs = [], 0
    for s in range(SHARES):
        share = cfg.replace(n_experts=held, n_routed_experts=E,
                            expert_offset=s * held)
        sl = slice(s * held, (s + 1) * held)
        ps = dict(p, **{n: p[n][sl] for n in ("wi_gate", "wi_up", "wo")})
        y, c = moe.apply_moe_dropless(ps, x, share)
        np.testing.assert_allclose(np.asarray(y), per_token(ps, x, share),
                                   rtol=1e-4, atol=1e-5)
        parts.append(np.asarray(y))
        pairs += int(c[0])
    np.testing.assert_allclose(sum(parts), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert pairs == int(counts[0]) == 2 * 16 * K


def test_a_share_has_the_routers_width():
    share = tiny(n_experts=2, n_routed_experts=E, expert_offset=6)
    specs = moe.moe_specs(share)
    assert specs["router"].shape == (share.d_model, E)
    assert specs["wi_gate"].shape[0] == 2
    with pytest.raises(ValueError, match="router scores"):
        tiny(n_experts=2, n_routed_experts=E, expert_offset=7)
    with pytest.raises(ValueError, match="holds every expert"):
        moe.apply_moe(layer_params(share), jnp.zeros((1, 4, 64)), share)


def _serve(cfg, params, prompts, max_new, chunk):
    """Engine logits per (request, step), through a recording sampler."""
    seen = {}
    key = jax.random.PRNGKey(0)
    names = {tuple(np.asarray(jax.random.key_data(
        request_key(key, r, s))).ravel()): (r, s)
        for r in range(len(prompts)) for s in range(max_new)}

    def record(logits, k):
        seen[names[tuple(np.asarray(jax.random.key_data(k)).ravel())]] = (
            np.asarray(logits[0, 0]))
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    eng = StreamingEngine(build(cfg), params, n_slots=3, chunk=chunk,
                          sampler=record, key=key)
    for p in prompts:
        eng.submit(p, max_new)
    out = eng.run()
    return out, seen


def test_engine_logits_do_not_depend_on_chunk():
    """Prefill at chunk 1, 4 and 16, then decode, against one forward pass
    over each whole sequence: no token is dropped at any chunk size."""
    cfg = tiny(n_experts=2, n_routed_experts=E, expert_offset=2)
    params = build(cfg).init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (21, 5, 13, 30)]
    max_new = 6
    for chunk in (1, 4, 16):
        out, seen = _serve(cfg, params, prompts, max_new, chunk)
        for r, prompt in enumerate(prompts):
            seq = np.concatenate([prompt, out[r][:-1]])
            n = seq.size
            logits = lm_prefill_chunk(
                cfg, params, jnp.asarray(seq)[None], lm_state_init(cfg, 1, 1),
                length_mask=jnp.ones((1, n), bool))[0]
            want = np.asarray(logits[0, prompt.size - 1:])
            got = np.stack([seen[(r, s)] for s in range(max_new)])
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4,
                                       err_msg=f"chunk {chunk}")


def test_engine_counts_the_experts_pairs():
    cfg = tiny()                    # every expert held: k pairs a token
    params = build(cfg).init(jax.random.PRNGKey(5))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (9, 4)]
    reg = MetricsRegistry()
    with use_metrics(reg):
        eng = StreamingEngine(build(cfg), params, n_slots=2, chunk=4)
        for p in prompts:
            eng.submit(p, 3)
        eng.run()
    snap = reg.snapshot()
    # Every prompt token and every fed-back sample but the last of each.
    tokens = sum(p.size for p in prompts) + 2 * (3 - 1)
    assert snap["counters"]["serve_moe_pairs_total"]["value"] == (
        tokens * K * cfg.n_layers)
    assert 1 <= snap["gauges"]["serve_moe_expert_load_max"]["value"] <= 2


@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stack"])
@pytest.mark.parametrize("swiglu", [False, True], ids=["plain", "swiglu"])
def test_kernel_interpret_equals_jnp_path(swiglu, stacked):
    """Held groups 3..6 of 10, two of them empty, rows past the groups, a
    group longer than a tile and tiles shared by groups; the weights of
    one layer, or a stack of three read at layer 2."""
    sizes = jnp.array([5, 0, 7, 0, 150, 9, 0, 4, 1, 2], jnp.int32)
    m, k, n, held, off = 200, 64, 32, 4, 3
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    lhs = jax.random.normal(keys[0], (m, k))
    rhs = jax.random.normal(keys[1], (3, held, k, n)) / 8
    up = jax.random.normal(keys[2], (3, held, k, n)) / 8 if swiglu else None
    want = kops._expert_gmm_jnp(lhs, rhs[2], sizes, jnp.int32(off),
                                None if up is None else up[2])
    if stacked:
        args = (rhs, sizes, jnp.int32(off), up, jnp.int32(2))
    else:
        args = (rhs[2], sizes, jnp.int32(off), None if up is None else up[2])
    got = gmm_kernel.expert_gmm(lhs, *args, tm=16, interpret=True)
    ends = np.cumsum(np.asarray(sizes))
    rows = slice(int(ends[off - 1]), int(ends[off + held - 1]))
    assert rows.stop - rows.start == 159
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(want)[rows.stop:].any()


def test_kernel_schedule_visits_each_held_tile():
    sizes = jnp.array([5, 0, 7, 0, 150, 9, 0, 4, 1, 2], jnp.int32)
    (group, tile, lo, hi), count = gmm_kernel.visits(
        sizes, jnp.int32(3), 4, 208, 16)
    count = int(count)
    # Held rows 12..170: group 1 (150 rows) tiles 0..10, group 2 (9 rows)
    # tile 10 again; groups 0 and 3 are empty and never visited.
    assert count == 12
    assert np.asarray(group)[:count].tolist() == [1] * 11 + [2]
    assert np.asarray(tile)[:count].tolist() == list(range(11)) + [10]
    assert np.asarray(lo)[:count].tolist() == [12] * 11 + [162]
    assert np.asarray(hi)[:count].tolist() == [162] * 11 + [171]
    assert np.asarray(hi)[count:].tolist() == [0] * (len(hi) - count)


def test_registry_counts_the_published_parameters():
    cfg = get_config("mellum2-12b-a2.5b")
    total = count_params(build(cfg).specs())
    experts = count_params({n: s for n, s in moe.moe_specs(cfg).items()
                            if n != "router"})
    active = total - experts * cfg.n_layers * (1 - 8 / 64)
    assert cfg.router_width == cfg.n_experts == 64
    assert round(total / 1e9, 2) == 12.15
    assert round(active / 1e9, 2) == 2.44
    assert cfg.effective_pattern() == ("aaren",) * 4
