"""Jit'd public wrappers for the Pallas kernels, with backend dispatch + VJPs.

Dispatch policy (DESIGN.md §Dispatch)
-------------------------------------
* On TPU, ``aaren_prefix_attention`` / ``flash_mha`` run the Pallas kernels.
* Everywhere else (CPU tests, the 512-host-device dry-run) they run the
  pure-jnp paths: ``lax.associative_scan`` for Aaren (XLA lowers it to a
  work-efficient tree) and masked softmax for flash.  Pallas-TPU kernels
  cannot lower on the CPU backend, so the dry-run compiles the jnp path —
  its HLO cost analysis is what the roofline reads, and DESIGN.md §Perf
  documents the kernel-vs-jnp delta analytically.
* ``REPRO_KERNEL_MODE`` env: ``auto`` (default) | ``pallas`` | ``interpret``
  (kernels in interpret mode — used by kernel-parity tests on the CPU; it
  raises on a TPU, where it would hide the device) | ``jnp``.

Gradients (DESIGN.md §Backward): both ops carry a ``custom_vjp`` that
dispatches like the forward.  On the kernel path the forward saves compact
residuals — ``(o, m, u)`` for the Aaren scan, ``(o, logsumexp)`` for flash —
and the backward runs the *fused analytic* Pallas kernels
(``aaren_scan_bwd.py`` / ``flash_attention.flash_attention_bwd``), so a
training step never materialises the O(N²) score matrix nor pays the
multi-pass ``associative_scan`` lowering.  On the jnp path the backward
re-runs the jnp forward under ``jax.vjp`` — recompute-style autodiff, kept
both as the any-backend fallback and as the parity oracle the kernel
backwards are tested against (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scan_attention import (
    NEG_INF,
    ScanState,
    combine,
    mask_to_identity,
    prefix_scan_states,
    prefix_scan_states_segmented,
    segment_starts_from_ids,
)
from repro.kernels import aaren_scan as _aaren_kernel
from repro.kernels import aaren_scan_bwd as _aaren_bwd_kernel
from repro.kernels import flash_attention as _flash_kernel
from repro.obs.trace import span as _span


KERNEL_MODES = ("auto", "pallas", "interpret", "jnp")


def kernel_mode() -> str:
    mode = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"REPRO_KERNEL_MODE={mode!r}; expected one of {KERNEL_MODES}")
    on_tpu = jax.default_backend() == "tpu"
    if mode == "auto":
        return "pallas" if on_tpu else "jnp"
    if mode == "interpret" and on_tpu:
        raise RuntimeError(
            "REPRO_KERNEL_MODE=interpret runs the Pallas interpreter on the "
            "host; it is for CPU tests.  Unset it on a TPU.")
    return mode


# ---------------------------------------------------------------------------
# Aaren prefix attention: (s, v, carry) -> (o, final carry)
# ---------------------------------------------------------------------------


def _aaren_jnp(s, v, m0, u0, w0, starts=None):
    """lax.associative_scan path — differentiable, runs on any backend.

    ``starts``: optional (R, N) segment-start flags (packed sequences).  The
    scan then restarts at every flag and the incoming carry folds only into
    positions before a row's first flag — identical semantics to the
    segmented Pallas kernel.
    """
    if starts is None:
        states = prefix_scan_states(s, v)  # m,u: (R, N); w: (R, N, d)
        carry = ScanState(
            m=jnp.broadcast_to(m0, states.m.shape),
            u=jnp.broadcast_to(u0, states.u.shape),
            w=jnp.broadcast_to(w0[:, None, :], states.w.shape),
        )
        total = combine(carry, states)
        o = total.w / total.u[..., None]
        return (o.astype(v.dtype), total.m[:, -1:], total.u[:, -1:],
                total.w[:, -1, :])
    states, seen = prefix_scan_states_segmented(s, v, starts)
    # Gated carry fold: positions at or after the first reset never see it.
    nos = seen == 0.0
    m_tot = jnp.where(nos, jnp.maximum(states.m, m0), states.m)
    alpha = jnp.where(nos, jnp.exp(m0 - m_tot), 0.0)
    beta = jnp.exp(states.m - m_tot)
    u_tot = u0 * alpha + states.u * beta
    w_tot = w0[:, None, :] * alpha[..., None] + states.w * beta[..., None]
    # Empty states (padding) read 0 — the readout() empty-set convention.
    u_safe = jnp.where(u_tot == 0.0, 1.0, u_tot)
    o = w_tot / u_safe[..., None]
    return (o.astype(v.dtype), m_tot[:, -1:], u_tot[:, -1:], w_tot[:, -1, :])


def _segment_ends(starts):
    """Reverse-scan boundary flags: the forward's starts shifted left one.

    Token ``j`` ends its segment iff ``j + 1`` starts one; the last token of
    a row (or of its trailing padding) is *not* flagged, so final-carry
    cotangents flow backwards through padding into the last real segment —
    mirroring the forward, where padding never resets the carry.
    """
    return jnp.pad(starts[:, 1:], ((0, 0), (0, 1)))


def _in_last_segment(starts):
    """(R, N) 1.0 where no segment start occurs strictly after the position.

    The ``m_f`` output of a segmented scan is the *last* segment's max; its
    max-subgradient may only route to scores inside that segment, so the
    epilogue's tie detector is masked with this.
    """
    future = jnp.flip(jax.lax.cummax(jnp.flip(starts, -1), axis=starts.ndim - 1), -1)
    return (_segment_ends(future) == 0).astype(jnp.float32)


def _aaren_dispatch(s, v, m0, u0, w0, starts, block_n):
    mode = kernel_mode()
    with _span(f"aaren_scan_fwd.{mode}"):
        if mode == "jnp":
            return _aaren_jnp(s, v, m0, u0, w0, starts)
        interpret = mode == "interpret"
        seg = None if starts is None else starts.astype(jnp.float32)
        return _aaren_kernel.aaren_scan(
            s, v, m0, u0, w0, seg, block_n=block_n, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _aaren_core(s, v, m0, u0, w0, starts, block_n):
    return _aaren_dispatch(s, v, m0, u0, w0, starts, block_n)


def _aaren_fwd(s, v, m0, u0, w0, starts, block_n):
    mode = kernel_mode()
    with _span(f"aaren_scan_fwd.{mode}"):
        if mode == "jnp":
            # Recompute-style: save inputs, differentiate the jnp forward.
            return (_aaren_jnp(s, v, m0, u0, w0, starts),
                    (s, v, m0, u0, w0, starts))
        interpret = mode == "interpret"
        seg = None if starts is None else starts.astype(jnp.float32)
        o, m_f, u_f, w_f, m_all, u_all = _aaren_kernel.aaren_scan(
            s, v, m0, u0, w0, seg, block_n=block_n, return_residuals=True,
            interpret=interpret)
        res = (s, v, o, m_all, u_all, m_f, u_f, w_f, m0, u0, w0, starts)
        return (o, m_f, u_f, w_f), res


def aaren_bwd_epilogue(s, m0, u0, w0, m_f, u_f, w_f, g_m, g_u, g_w,
                       ds, n1, g1, b1, hit_mask=None):
    """Elementwise epilogue of the fused Aaren backward (DESIGN.md §Backward).

    Turns the kernel's final reverse-carry state ``(n1, g1, b1)`` into the
    incoming-carry cotangents and adds the max-subgradient of the ``m_f``
    output to ``ds``, split across exact ties the way autodiff's
    balanced-eq rule does.  ``hit_mask`` (segmented scans only) restricts
    the tie detector to the last segment — the span ``m_f`` is the max of.
    Shared by ops and the parity tests so the shipped formula is the tested
    one.  Returns (ds, dm0, du0, dw0).
    """
    e01 = jnp.exp(m0 + n1)                       # exp(m0 - M_N-ish), <= 1
    dw0 = e01 * g1
    du0 = -e01 * b1
    c = g_m - g_u * u_f - jnp.sum(g_w * w_f, axis=-1, keepdims=True)
    hit_s = (s == m_f).astype(s.dtype)
    if hit_mask is not None:
        hit_s = hit_s * hit_mask
    hit_0 = (m0 == m_f).astype(s.dtype)
    cnt = jnp.sum(hit_s, axis=-1, keepdims=True) + hit_0
    c = c / jnp.maximum(cnt, 1.0)
    ds = ds + c * hit_s
    dm0 = u0 * du0 + jnp.sum(w0 * dw0, axis=-1, keepdims=True) + c * hit_0
    return ds, dm0, du0, dw0


def _aaren_bwd(block_n, res, g):
    # Residual arity identifies the forward path (pytrees can't carry tags):
    # 6 = jnp-mode raw inputs, 12 = kernel-mode compact residuals.
    if len(res) == 6:
        s, v, m0, u0, w0, starts = res
        with _span("aaren_scan_bwd.jnp"):
            _, vjp = jax.vjp(
                lambda s_, v_, m_, u_, w_: _aaren_jnp(
                    s_, v_, m_, u_, w_, starts),
                s, v, m0, u0, w0)
            return (*vjp(g), _len_cotangent(starts))

    s, v, o, m_all, u_all, m_f, u_f, w_f, m0, u0, w0, starts = res
    g_o, g_m, g_u, g_w = g
    mode = kernel_mode()
    interpret = mode == "interpret"
    with _span(f"aaren_scan_bwd.{mode}"):
        ends = hit_mask = None
        if starts is not None:
            ends = _segment_ends(starts).astype(jnp.float32)
            hit_mask = _in_last_segment(starts)
        # (u_f, w_f) cotangents seed the reverse carry (suffix "past" token
        # N); see aaren_scan_bwd.py for the derivation.
        ds, dv, n1, g1, b1 = _aaren_bwd_kernel.aaren_scan_bwd(
            s, v, o, m_all, u_all, g_o,
            -m_f, g_w, -g_u, ends, block_n=block_n, interpret=interpret)
        ds, dm0, du0, dw0 = aaren_bwd_epilogue(
            s, m0, u0, w0, m_f, u_f, w_f, g_m, g_u, g_w, ds, n1, g1, b1,
            hit_mask=hit_mask)
        return (ds.astype(s.dtype), dv.astype(v.dtype), dm0, du0, dw0,
                _len_cotangent(starts))


_aaren_core.defvjp(_aaren_fwd, _aaren_bwd)


def aaren_prefix_attention(
    s: jax.Array,
    v: jax.Array,
    carry: ScanState | None = None,
    *,
    segment_ids: jax.Array | None = None,
    segment_starts: jax.Array | None = None,
    block_n: int = _aaren_kernel.DEFAULT_BLOCK_N,
):
    """All-prefix Aaren attention over arbitrary leading batch dims.

    s: (..., N) scores; v: (..., N, d) values; carry leaves: m,u (...,),
    w (..., d).  Returns (o: (..., N, d), final carry ScanState).

    Packed sequences (DESIGN.md §Packing): ``segment_ids`` (int, id 0 =
    padding; shape (..., N) or missing one leading dim, e.g. (B, N) against
    (B, H, N) scores — broadcast over heads) makes the scan restart its
    carry at every segment start and turns padding into ⊕-identity leaves.
    Ids must form **contiguous same-id runs** per row (the bin-packer's
    contract): the scan keys on id *transitions*, flash on id *equality* —
    the two agree only for contiguous runs, so a reused id is undefined
    behaviour across mixers, not a wider attention span.
    ``segment_starts`` overrides the locally-computed start flags — sequence
    -sharded callers pass globally-computed flags so a document spanning a
    shard boundary is not re-reset (distributed/context.py).  An incoming
    ``carry`` composes: it reaches exactly the positions before a row's
    first start flag.  The final carry is the last segment's state (padding
    never resets it).
    """
    batch_shape = s.shape[:-1]
    n = s.shape[-1]
    d = v.shape[-1]
    r = int(np.prod(batch_shape)) if batch_shape else 1
    starts2 = None
    pad_mask = None
    if segment_ids is not None or segment_starts is not None:
        if segment_ids is not None:
            seg = jnp.asarray(segment_ids, jnp.int32)
            if seg.ndim == s.ndim - 1:  # e.g. (B, N) vs (B, H, N)
                seg = jnp.broadcast_to(seg[..., None, :], s.shape)
            seg = jnp.broadcast_to(seg, s.shape)
            # Padding (id 0) enters the scan as ⊕-identity leaves; the scan
            # still *carries* the last segment's state through it (so the
            # final carry is the last real segment), but the padding's own
            # outputs are pinned to 0 below — the flash empty-row convention.
            s, v = mask_to_identity(s, v, seg != 0)
            pad_mask = seg != 0
        if segment_starts is None:
            segment_starts = segment_starts_from_ids(seg)
        starts = jnp.asarray(segment_starts, jnp.int32)
        if starts.ndim == s.ndim - 1:
            starts = jnp.broadcast_to(starts[..., None, :], s.shape)
        starts2 = jnp.broadcast_to(starts, s.shape).reshape(r, n)
    s2 = s.reshape(r, n).astype(jnp.float32)
    v2 = v.reshape(r, n, d).astype(jnp.float32)
    if carry is None:
        m0 = jnp.full((r, 1), NEG_INF, jnp.float32)
        u0 = jnp.zeros((r, 1), jnp.float32)
        w0 = jnp.zeros((r, d), jnp.float32)
    else:
        m0 = carry.m.reshape(r, 1).astype(jnp.float32)
        u0 = carry.u.reshape(r, 1).astype(jnp.float32)
        w0 = carry.w.reshape(r, d).astype(jnp.float32)
    o, m_f, u_f, w_f = _aaren_core(s2, v2, m0, u0, w0, starts2, block_n)
    if pad_mask is not None:
        o = jnp.where(pad_mask.reshape(r, n)[..., None], o, 0.0)
    final = ScanState(
        m=m_f.reshape(batch_shape),
        u=u_f.reshape(batch_shape),
        w=w_f.reshape(batch_shape + (d,)),
    )
    return o.reshape(batch_shape + (n, d)).astype(v.dtype), final


# ---------------------------------------------------------------------------
# Flash attention: (q, k, v) -> o
# ---------------------------------------------------------------------------


def _flash_jnp(q, k, v, q_lens, kv_lens, q_seg, kv_seg, causal, window,
               scale):
    from repro.kernels.ref import flash_reference

    return flash_reference(q, k, v, causal=causal, window=window, scale=scale,
                           q_lens=q_lens, kv_lens=kv_lens,
                           q_segment_ids=q_seg, kv_segment_ids=kv_seg)


def _flash_dispatch(q, k, v, q_lens, kv_lens, q_seg, kv_seg, causal, window,
                    scale):
    mode = kernel_mode()
    with _span(f"flash_fwd.{mode}"):
        if mode == "jnp":
            return _flash_jnp(q, k, v, q_lens, kv_lens, q_seg, kv_seg,
                              causal, window, scale)
        interpret = mode == "interpret"
        return _flash_kernel.flash_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            q_lens=q_lens, kv_lens=kv_lens,
            q_segment_ids=q_seg, kv_segment_ids=kv_seg, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _flash_core(q, k, v, q_lens, kv_lens, q_seg, kv_seg, causal, window,
                scale):
    return _flash_dispatch(q, k, v, q_lens, kv_lens, q_seg, kv_seg,
                           causal, window, scale)


def _flash_fwd(q, k, v, q_lens, kv_lens, q_seg, kv_seg, causal, window,
               scale):
    mode = kernel_mode()
    with _span(f"flash_fwd.{mode}"):
        if mode == "jnp":
            out = _flash_jnp(q, k, v, q_lens, kv_lens, q_seg, kv_seg,
                             causal, window, scale)
            return out, (q, k, v, q_lens, kv_lens, q_seg, kv_seg)
        interpret = mode == "interpret"
        o, lse = _flash_kernel.flash_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            q_lens=q_lens, kv_lens=kv_lens,
            q_segment_ids=q_seg, kv_segment_ids=kv_seg,
            return_residuals=True, interpret=interpret)
        return o, (q, k, v, q_lens, kv_lens, q_seg, kv_seg, o, lse)


def _len_cotangent(lens):
    """Symbolic-zero cotangent for an integer lengths array (float0)."""
    if lens is None:
        return None
    return np.zeros(np.shape(lens), jax.dtypes.float0)


def _flash_bwd(causal, window, scale, res, g):
    # 7 residuals = jnp-mode raw inputs; 9 = kernel-mode (+ o, logsumexp).
    if len(res) == 7:
        q, k, v, q_lens, kv_lens, q_seg, kv_seg = res
        with _span("flash_dq_dkv.jnp"):
            _, vjp = jax.vjp(
                lambda q_, k_, v_: _flash_jnp(q_, k_, v_, q_lens, kv_lens,
                                              q_seg, kv_seg, causal, window,
                                              scale),
                q, k, v)
            return (*vjp(g), _len_cotangent(q_lens), _len_cotangent(kv_lens),
                    _len_cotangent(q_seg), _len_cotangent(kv_seg))
    q, k, v, q_lens, kv_lens, q_seg, kv_seg, o, lse = res
    mode = kernel_mode()
    with _span(f"flash_dq_dkv.{mode}"):
        dq, dk, dv = _flash_kernel.flash_attention_bwd(
            q, k, v, o, lse, g, causal=causal, window=window, scale=scale,
            q_lens=q_lens, kv_lens=kv_lens,
            q_segment_ids=q_seg, kv_segment_ids=kv_seg,
            interpret=mode == "interpret")
        return (dq, dk, dv, _len_cotangent(q_lens), _len_cotangent(kv_lens),
                _len_cotangent(q_seg), _len_cotangent(kv_seg))


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_lens: jax.Array | None = None,
    kv_lens: jax.Array | None = None,
    q_segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Flash attention over (B, Nq, H, d) q and (B, Nk, G, d) k/v.

    Framework layout is sequence-major (B, N, H, d); the kernel wants head-
    major (B, H, N, d) — transpose at the boundary.  ``q_lens``/``kv_lens``:
    optional (B,) int32 true lengths; positions at or beyond them are masked
    inside the kernel (and its backward), so ragged batches run the dense
    block grid with no sequence-length divisibility requirement.
    ``q_segment_ids``/``kv_segment_ids``: optional (B, Nq)/(B, Nk) int32
    packed-segment ids (id 0 = padding) — attention never crosses a segment
    boundary, and tiles whose id ranges are disjoint skip compute
    (DESIGN.md §Packing).  For self-attention pass the same array to both.
    Ids must form contiguous same-id runs per row (the bin-packer's
    contract); a reused id would rejoin here by equality but not in the
    Aaren scan's transition-keyed resets — undefined across mixers.
    """
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if q_lens is not None:
        q_lens = jnp.asarray(q_lens, jnp.int32)
    if kv_lens is not None:
        kv_lens = jnp.asarray(kv_lens, jnp.int32)
    if q_segment_ids is not None and kv_segment_ids is None:
        kv_segment_ids = q_segment_ids
    if q_segment_ids is None and kv_segment_ids is not None:
        q_segment_ids = kv_segment_ids
    if q_segment_ids is not None:
        q_segment_ids = jnp.asarray(q_segment_ids, jnp.int32)
        kv_segment_ids = jnp.asarray(kv_segment_ids, jnp.int32)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = _flash_core(qt, kt, vt, q_lens, kv_lens, q_segment_ids,
                    kv_segment_ids, causal, window, float(scale))
    return jnp.swapaxes(o, 1, 2)
