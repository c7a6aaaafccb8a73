"""MeshPlan + composed-mesh tests (DESIGN.md §Parallelism).

The plan arithmetic / derivation tests run on 1 CPU device (tier-1).  The
2x2x2 (data x seq x model) parity suite needs 8 emulated devices and runs in
CI's composed-mesh job with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``: loss and parameter
gradients through ``mesh_plan_session`` must match the single-device run to
1e-5 for both mixers, packed and unpacked — FSDP, context parallelism, and
tensor parallelism live *simultaneously*, so this is the test that the three
collectives (grad psum on ``data``, carry ppermute on ``seq``, TP psum on
``model``) compose without corrupting each other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.data.packing import pack_documents
from repro.distributed.context import (
    ContextParallel,
    current_cp,
    mesh_plan_session,
)
from repro.models.factory import build
from repro.sharding import MeshPlan, current_rules, plan_from_mesh

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 (emulated) devices: "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ---------------------------------------------------------------------------
# Plan arithmetic (1 device, tier-1)
# ---------------------------------------------------------------------------


def test_plan_shape_and_axis_names():
    p = MeshPlan(data=4, seq=2, model=8)
    assert p.shape == (4, 2, 8)
    assert p.axis_names == ("data", "seq", "model")
    assert p.total == 64
    assert not p.is_trivial
    # pod stays out of the mesh at size 1, in at > 1
    q = MeshPlan(data=4, seq=2, model=8, pod=2)
    assert q.shape == (2, 4, 2, 8)
    assert q.axis_names == ("pod", "data", "seq", "model")
    assert q.describe() == "2x4x2x8 (pod x data x seq x model)"
    assert MeshPlan().is_trivial


def test_plan_validation():
    with pytest.raises(ValueError, match="must be an int >= 1"):
        MeshPlan(data=0)
    with pytest.raises(ValueError, match="must be an int >= 1"):
        MeshPlan(seq=-2)
    with pytest.raises(ValueError, match="must be an int >= 1"):
        MeshPlan(model=2.0)        # floats rejected, not coerced
    with pytest.raises(ValueError, match="needs 4 devices"):
        MeshPlan(data=2, seq=2, devices=("d0", "d1"))


def test_plan_host_derivation():
    p = MeshPlan.host(seq=2, model=2, n_devices=8)
    assert p.shape == (2, 2, 2)    # data soaks up the remainder
    assert MeshPlan.host(seq=8, n_devices=8).shape == (1, 8, 1)
    with pytest.raises(ValueError, match="not divisible"):
        MeshPlan.host(seq=3, n_devices=8)
    with pytest.raises(ValueError, match="needs 16 devices"):
        MeshPlan.host(data=4, seq=2, model=2, n_devices=8)


def test_plan_production_derivation():
    """The dry-run cells' historical shapes, derived instead of hard-coded."""
    assert MeshPlan.production().shape == (16, 1, 16)
    assert MeshPlan.production(multi_pod=True).shape == (2, 16, 1, 16)
    p = MeshPlan.production(multi_pod=True, context_parallel=4)
    assert p.shape == (2, 4, 4, 16)
    assert p.total == 512
    with pytest.raises(ValueError, match="must divide"):
        MeshPlan.production(context_parallel=3)


def test_plan_exchange_rounds():
    """1 shift + ceil(log2 P) doubling rounds; 0 when seq is trivial."""
    assert MeshPlan().exchange_rounds() == 0
    assert MeshPlan(seq=2).exchange_rounds() == 2
    assert MeshPlan(seq=4).exchange_rounds() == 3
    assert MeshPlan(seq=8).exchange_rounds() == 4
    assert MeshPlan(seq=6).exchange_rounds() == 4   # non-power-of-two


def test_plan_from_mesh_roundtrip():
    mesh = jax.make_mesh((1, 1, 1), ("data", "seq", "model"),
                         devices=jax.devices()[:1])
    p = plan_from_mesh(mesh)
    assert (p.data, p.seq, p.model, p.pod) == (1, 1, 1, 1)
    assert len(p.devices) == 1
    bad = jax.make_mesh((1,), ("stage",), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="non-plan axes"):
        plan_from_mesh(bad)


def test_predict_axis_exchange_shape():
    """The roofline predictor reports one entry per non-trivial plan axis."""
    from repro.roofline.analysis import predict_axis_exchange

    pred = predict_axis_exchange(
        MeshPlan(data=2, seq=2, model=2), batch=2, seq_len=64, n_heads=4,
        head_dim=16, d_model=64, n_layers=2, param_bytes=1 << 20)
    assert set(pred) == {"data", "seq", "model"}
    assert all(v > 0 for v in pred.values())
    # trivial plan: nothing crosses any wire
    assert predict_axis_exchange(
        MeshPlan(), batch=2, seq_len=64, n_heads=4, head_dim=16,
        d_model=64, n_layers=2, param_bytes=1 << 20) == {}


def test_session_noop_for_trivial_plan():
    with mesh_plan_session(None) as cp:
        assert cp is None and current_cp() is None
    with mesh_plan_session(MeshPlan()) as cp:
        assert cp is None and current_cp() is None


# ---------------------------------------------------------------------------
# Composed 2x2x2 parity (8 emulated devices; CI composed-mesh job)
# ---------------------------------------------------------------------------


def _tiny_cfg(mode: str) -> ArchConfig:
    # every shardable dim divisible by its plan axis: heads 4 / kv 2 on
    # model=2, d_ff 128 on model=2, batch 2 on data=2, N 64 on seq=2
    return ArchConfig(
        name=f"plan-{mode}", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab=128, pattern=("attn",),
        mlp_pattern=("swiglu",), attn_mode=mode, param_dtype="float32",
        compute_dtype="float32", remat="none")


def _grad_err(g_a, g_b) -> float:
    from jax.tree_util import tree_leaves_with_path

    ref = dict(tree_leaves_with_path(g_b))
    return max(float(jnp.max(jnp.abs(a - ref[path])))
               for path, a in tree_leaves_with_path(g_a))


def _packed_batch(vocab: int):
    # lengths 40+24 and 30+20 first-fit into exactly two 64-token rows, so
    # documents straddle the seq=2 shard boundary (32-token shards)
    rng_np = np.random.default_rng(11)
    docs = [rng_np.integers(0, vocab, size=L).astype(np.int32)
            for L in (40, 24, 30, 20)]
    packed = pack_documents(docs, 64)
    assert packed["tokens"].shape == (2, 64)
    return {k: jnp.asarray(v) for k, v in packed.items()}


@needs8
@pytest.mark.parametrize("mode", ["aaren", "softmax"])
@pytest.mark.parametrize("packed", [False, True])
def test_composed_mesh_loss_and_grads_match(rng, mode, packed):
    """2x2x2 (data x seq x model) loss + grads == single device, <= 1e-5."""
    cfg = _tiny_cfg(mode)
    api = build(cfg)
    params = api.init(rng)
    if packed:
        batch = _packed_batch(cfg.vocab)
    else:
        toks = jax.random.randint(jax.random.fold_in(rng, 1), (2, 64), 0,
                                  cfg.vocab)
        batch = {"tokens": toks}
    loss_ref, _ = api.loss(params, batch)
    g_ref = jax.grad(lambda p: api.loss(p, batch)[0])(params)
    with mesh_plan_session(MeshPlan(data=2, seq=2, model=2)) as cp:
        assert cp is not None and cp.size == 2
        assert current_rules() is not None
        loss_pl = jax.jit(lambda p: api.loss(p, batch)[0])(params)
        g_pl = jax.jit(jax.grad(lambda p: api.loss(p, batch)[0]))(params)
    assert abs(float(loss_pl) - float(loss_ref)) <= 1e-5
    assert _grad_err(g_pl, g_ref) <= 1e-5


@needs8
def test_session_installs_rules_and_cp():
    plan = MeshPlan(data=2, seq=2, model=2)
    with mesh_plan_session(plan) as cp:
        sr = current_rules()
        assert sr is not None and sr.mesh is cp.mesh
        assert dict(cp.mesh.shape) == {"data": 2, "seq": 2, "model": 2}
        rt = plan_from_mesh(cp.mesh)
        assert (rt.data, rt.seq, rt.model) == (2, 2, 2)
    assert current_rules() is None and current_cp() is None


@needs8
def test_batch_axis_resolves_through_rules():
    """Satellite: ContextParallel.batch_axis consults the batch rule —
    joint ("pod", "data") on pod-carrying meshes, divisibility fallback,
    never the seq axis — instead of the old hard-coded "data" lookup."""
    pod_plan = MeshPlan(pod=2, data=2, seq=2)
    with mesh_plan_session(pod_plan) as cp:
        assert cp.batch_axis(4) == ("pod", "data")   # joint entry wins
        assert cp.batch_axis(2) == "data"            # 2 % (pod*data) != 0
        assert cp.batch_axis(3) is None              # nothing divides
    flat = MeshPlan(data=4, seq=2)
    with mesh_plan_session(flat) as cp:
        assert cp.batch_axis(4) == "data"
        assert cp.batch_axis(5) is None
    # outside any rules context the handle builds its own rules view
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "seq"),
                         devices=jax.devices()[:8])
    cp = ContextParallel(mesh)
    assert cp.batch_axis(4) == ("pod", "data")


def test_plan_mesh_axes_are_auto():
    """Sharded arrays on a plan mesh must not carry their sharding in their
    type: the rules steer GSPMD, and Explicit axes make plain ops such as
    the embedding gather refuse sharded operands."""
    from jax.sharding import AxisType

    mesh = MeshPlan(devices=jax.devices()[:1]).build_mesh()
    assert set(mesh.axis_types) == {AxisType.Auto}


@pytest.mark.parametrize("mixer", ["aaren", "softmax"])
def test_kernel_island_matches_plain_call(mixer, monkeypatch, rng):
    """On a mesh whose seq axis is 1, the kernel path runs in a batch
    shard_map island (GSPMD cannot partition a Mosaic kernel); it must
    equal the plain call."""
    from repro.distributed.context import cp_aaren_prefix_attention, cp_flash_mha
    from repro.kernels import ops as kops

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cp = ContextParallel(MeshPlan(devices=jax.devices()[:1]).build_mesh())
    b, h, n, d = 2, 2, 24, 16
    seg = jnp.array([[1] * 10 + [2] * 14, [1] * 20 + [0] * 4], jnp.int32)
    if mixer == "aaren":
        s = jax.random.normal(rng, (b, h, n))
        v = jax.random.normal(jax.random.fold_in(rng, 1), (b, h, n, d))
        got, fin = cp_aaren_prefix_attention(s, v, segment_ids=seg, cp=cp)
        want, fin_w = kops.aaren_prefix_attention(s, v, segment_ids=seg)
        np.testing.assert_allclose(fin.w, fin_w.w, rtol=1e-6, atol=1e-6)
    else:
        q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (b, n, h, d))
                   for i in range(3))
        lens = jnp.array([24, 17], jnp.int32)
        got = cp_flash_mha(q, k, v, lengths=lens, segment_ids=seg, cp=cp)
        want = kops.flash_mha(q, k, v, q_lens=lens, kv_lens=lens,
                              q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
