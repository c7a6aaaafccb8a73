"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes (assignment requirement).  The gradient-parity
suite drives ``jax.grad`` through the analytic kernel VJPs (interpret mode)
and checks them against autodiff of the jnp reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scan_attention import NEG_INF
from repro.kernels.aaren_scan import aaren_scan
from repro.kernels.aaren_scan_bwd import aaren_scan_bwd
from repro.kernels.flash_attention import flash_attention, flash_attention_bwd
from repro.kernels.ref import (
    aaren_scan_reference,
    aaren_scan_vjp_reference,
    flash_reference,
    flash_vjp_reference,
)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("r,n,d", [
    (1, 128, 32), (4, 256, 64), (2, 512, 128), (3, 384, 16),
    (2, 250, 32), (3, 97, 16),   # non-power-of-two N -> padded, not bn//=2
])
@pytest.mark.parametrize("block_n", [64, 128])
def test_aaren_scan_shapes(r, n, d, block_n, rng):
    s = jax.random.normal(rng, (r, n)) * 3.0
    v = jax.random.normal(jax.random.fold_in(rng, 1), (r, n, d))
    m0 = jnp.full((r, 1), NEG_INF)
    u0 = jnp.zeros((r, 1))
    w0 = jnp.zeros((r, d))
    o_k, mf, uf, wf, *_ = aaren_scan(s, v, m0, u0, w0, block_n=block_n,
                                     interpret=True)
    o_r, mr, ur, wr = aaren_scan_reference(s, v)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(mf), np.asarray(mr), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(uf), np.asarray(ur), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(wf), np.asarray(wr),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aaren_scan_dtypes(dtype, rng):
    r, n, d = 2, 256, 64
    s = (jax.random.normal(rng, (r, n)) * 2).astype(jnp.float32)
    v = jax.random.normal(jax.random.fold_in(rng, 1), (r, n, d)).astype(dtype)
    m0 = jnp.full((r, 1), NEG_INF)
    u0 = jnp.zeros((r, 1))
    w0 = jnp.zeros((r, d), jnp.float32)
    o_k, *_ = aaren_scan(s, v.astype(jnp.float32), m0, u0, w0,
                         block_n=128, interpret=True)
    o_r, *_ = aaren_scan_reference(s, v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), **_tol(dtype))


def test_aaren_scan_carry_chaining(rng):
    """Two chained half-sequence kernel calls == one full-sequence call
    (the Appendix-A block property at the kernel-API level)."""
    r, n, d = 2, 256, 32
    s = jax.random.normal(rng, (r, n)) * 2
    v = jax.random.normal(jax.random.fold_in(rng, 1), (r, n, d))
    m0 = jnp.full((r, 1), NEG_INF)
    u0 = jnp.zeros((r, 1))
    w0 = jnp.zeros((r, d))
    o_full, mf, uf, wf, *_ = aaren_scan(s, v, m0, u0, w0, block_n=64,
                                        interpret=True)
    h = n // 2
    o1, m1, u1, w1, *_ = aaren_scan(s[:, :h], v[:, :h], m0, u0, w0,
                                    block_n=64, interpret=True)
    o2, m2, u2, w2, *_ = aaren_scan(s[:, h:], v[:, h:], m1, u1, w1,
                                    block_n=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o_full),
                               np.asarray(jnp.concatenate([o1, o2], 1)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(mf), np.asarray(m2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(wf), np.asarray(w2),
                               rtol=1e-4, atol=1e-4)


def test_aaren_scan_extreme_scores():
    """f32 stability across blocks with adversarial score ranges."""
    s = jnp.asarray([[-80.0, 85.0] * 64])  # alternate extremes, N=128
    v = jnp.ones((1, 128, 8))
    o, *_ = aaren_scan(s, v, jnp.full((1, 1), NEG_INF), jnp.zeros((1, 1)),
                       jnp.zeros((1, 8)), block_n=32, interpret=True)
    assert not bool(jnp.isnan(o).any())
    np.testing.assert_allclose(np.asarray(o), 1.0, rtol=1e-5)


@pytest.mark.parametrize("b,h,g,nq,nk,d", [
    (1, 4, 4, 128, 128, 32),    # MHA
    (2, 8, 2, 256, 256, 64),    # GQA 4:1
    (1, 4, 1, 128, 128, 128),   # MQA
    (1, 2, 2, 64, 256, 32),     # cross-shape (nq != nk)
])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_sweep(b, h, g, nq, nk, d, window, rng):
    q = jax.random.normal(rng, (b, h, nq, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, g, nk, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, g, nk, d))
    o_k = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    o_r = flash_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype, rng):
    b, h, g, n, d = 1, 4, 2, 128, 64
    q = jax.random.normal(rng, (b, h, n, d)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, g, n, d)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, g, n, d)).astype(dtype)
    o_k = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    o_r = flash_reference(q, k, v, causal=True)
    assert o_k.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
        **_tol(dtype))


def test_flash_noncausal(rng):
    b, h, g, n, d = 1, 4, 4, 128, 32
    q = jax.random.normal(rng, (b, h, n, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, g, n, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, g, n, d))
    o_k = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    o_r = flash_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)


def test_ops_grad_paths(rng):
    """custom_vjp gradients of the dispatched ops match pure-jnp autodiff."""
    import os

    from repro.kernels.ops import aaren_prefix_attention, flash_mha

    s = jax.random.normal(rng, (2, 3, 64)) * 2          # (B, H, N)
    v = jax.random.normal(jax.random.fold_in(rng, 1), (2, 3, 64, 16))

    def loss_ops(s, v):
        o, fin = aaren_prefix_attention(s, v)
        return jnp.sum(o ** 2) + jnp.sum(fin.w ** 2)

    def loss_ref(s, v):
        from repro.core.scan_attention import prefix_scan_states, readout

        states = prefix_scan_states(s, v)
        o = readout(states)
        return jnp.sum(o ** 2) + jnp.sum(states.w[..., -1, :] ** 2)

    g_ops = jax.grad(loss_ops, argnums=(0, 1))(s, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1))(s, v)
    for a, b in zip(g_ops, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Gradient parity: analytic kernel VJPs (interpret mode) vs jnp autodiff
# ---------------------------------------------------------------------------


def _grad_close(g_kernel, g_jnp, rtol=1e-4):
    for a, b in zip(g_kernel, g_jnp):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(np.abs(b).max(), 1e-6)
        np.testing.assert_allclose(a / scale, b / scale, rtol=rtol,
                                   atol=rtol)


@pytest.mark.parametrize("with_carry", [False, True])
@pytest.mark.parametrize("n", [128, 250])          # pow-2 and padded odd N
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aaren_grad_parity(with_carry, n, dtype, rng, monkeypatch):
    """jax.grad through the fused analytic backward (interpret mode) ==
    autodiff of the lax.associative_scan reference, across the parity
    matrix: carry/no-carry, non-power-of-two N, bf16 inputs."""
    from repro.core.scan_attention import ScanState
    from repro.kernels.ops import aaren_prefix_attention

    b, h, d = 2, 3, 16
    s = (jax.random.normal(rng, (b, h, n)) * 2).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 1), (b, h, n, d)).astype(dtype)
    if with_carry:
        # m0 above most scores so the m_f subgradient path gets exercised.
        carry = ScanState(
            m=jax.random.normal(jax.random.fold_in(rng, 2), (b, h)) + 6.0,
            u=jnp.abs(jax.random.normal(jax.random.fold_in(rng, 3), (b, h))) + 1.0,
            w=jax.random.normal(jax.random.fold_in(rng, 4), (b, h, d)))
    else:
        carry = None

    def loss(s, v):
        o, fin = aaren_prefix_attention(s, v, carry)
        return (jnp.sum(o ** 2) + jnp.sum(fin.w ** 2) + jnp.sum(fin.u ** 2)
                + 0.1 * jnp.sum(fin.m))

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    g_kernel = jax.grad(loss, argnums=(0, 1))(s, v)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "jnp")
    g_jnp = jax.grad(loss, argnums=(0, 1))(s, v)
    _grad_close(g_kernel, g_jnp, rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_aaren_bwd_kernel_vs_reference(rng):
    """The fused reverse-scan kernel == the dense analytic formulas,
    including the final reverse carry used for (dm0, du0, dw0)."""
    r, n, d = 3, 250, 16
    s = jax.random.normal(rng, (r, n)) * 3.0
    v = jax.random.normal(jax.random.fold_in(rng, 1), (r, n, d))
    m0 = jax.random.normal(jax.random.fold_in(rng, 2), (r, 1)) + 4.0
    u0 = jnp.abs(jax.random.normal(jax.random.fold_in(rng, 3), (r, 1))) + 1.0
    w0 = jax.random.normal(jax.random.fold_in(rng, 4), (r, d))
    g_o = jax.random.normal(jax.random.fold_in(rng, 5), (r, n, d))
    g_m = jax.random.normal(jax.random.fold_in(rng, 6), (r, 1))
    g_u = jax.random.normal(jax.random.fold_in(rng, 7), (r, 1))
    g_w = jax.random.normal(jax.random.fold_in(rng, 8), (r, d))

    from repro.kernels.ops import aaren_bwd_epilogue

    o, m_f, u_f, w_f, m_all, u_all = aaren_scan(
        s, v, m0, u0, w0, block_n=64, return_residuals=True, interpret=True)
    ds, dv, n1, g1, b1 = aaren_scan_bwd(
        s, v, o, m_all, u_all, g_o, -m_f, g_w, -g_u,
        block_n=64, interpret=True)
    ds, dm0, du0, dw0 = aaren_bwd_epilogue(
        s, m0, u0, w0, m_f, u_f, w_f, g_m, g_u, g_w, ds, n1, g1, b1)

    ref = aaren_scan_vjp_reference(s, v, m0, u0, w0, g_o, g_m, g_u, g_w)
    _grad_close((ds, dv, dm0, du0, dw0), ref)


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("g", [4, 2])              # MHA and GQA 2:1
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_grad_parity(window, g, dtype, rng, monkeypatch):
    """jax.grad through the two-pass flash backward (interpret mode) ==
    autodiff of the masked-softmax reference: windowed + causal, GQA, bf16."""
    from repro.kernels.ops import flash_mha

    b, h, n, d = 1, 4, 128, 32
    q = jax.random.normal(rng, (b, n, h, d)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, n, g, d)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, n, g, d)).astype(dtype)

    def loss(q, k, v):
        return jnp.sum(flash_mha(q, k, v, causal=True, window=window) ** 2)

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    g_kernel = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "jnp")
    g_jnp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    _grad_close(g_kernel, g_jnp, rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_flash_bwd_kernel_vs_reference(rng):
    """flash_attention_bwd == the dense analytic formulas (cross-shape GQA)."""
    b, h, g, nq, nk, d = 1, 4, 2, 64, 128, 32
    q = jax.random.normal(rng, (b, h, nq, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, g, nk, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, g, nk, d))
    do = jax.random.normal(jax.random.fold_in(rng, 3), (b, h, nq, d))
    o, lse = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             return_residuals=True, interpret=True)
    got = flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                              block_q=64, block_k=64, interpret=True)
    ref = flash_vjp_reference(q, k, v, do, causal=True)
    _grad_close(got, ref)


def test_kernel_mode_refuses_interpret_on_tpu(monkeypatch):
    """Interpret mode is for CPU tests: on a TPU it would hide the chip."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    with pytest.raises(RuntimeError, match="interpret"):
        ops.kernel_mode()
    monkeypatch.delenv("REPRO_KERNEL_MODE")
    assert ops.kernel_mode() == "pallas"
    monkeypatch.setenv("REPRO_KERNEL_MODE", "jnp")
    assert ops.kernel_mode() == "jnp"   # the on-chip reference path
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallsa")
    with pytest.raises(ValueError, match="pallsa"):
        ops.kernel_mode()
