"""Pallas TPU kernel: chunked prefix-scan Aaren attention (paper §3.2 + App. A).

The kernel computes, per (batch·head) row, all causal prefix-softmax outputs

    o_i = ( Σ_{j<=i} exp(s_j - m_i) v_j ) / ( Σ_{j<=i} exp(s_j - m_i) )

from scores ``s`` (the learned-query dot products) and values ``v``, plus the
final ``(m, u, w)`` carry so chunked prefill / streaming decode can continue
where the kernel stopped.

Structure — this is the paper's two algorithms composed for the TPU memory
hierarchy:

* **within a block** (VMEM-resident, ``block_n`` tokens): the paper's
  Algorithm 1 (Hillis–Steele parallel prefix scan) over the associative
  operator ⊕ on ``(m, u, w)`` tuples — ``log2(block_n)`` vectorised
  shift-and-combine steps on the VPU.  O(b log b) work, all on-chip.
* **across blocks** (the grid's sequence dimension, executed sequentially per
  TPU core): the paper's Appendix-A block-by-block recurrence — a single
  ``(m, u, w)`` carry lives in VMEM scratch, so HBM traffic is O(N) reads +
  O(N) writes and on-chip memory is O(block_r · block_n · d).

Compared with materialising the scan in HBM (`lax.associative_scan` lowers to
O(log N) full-array passes), this fuses the whole scan into one pass:
HBM bytes drop from ~2·log2(N)·N·d to ~2·N·d.

Tiling: each grid step processes ``block_r`` rows x ``block_n`` tokens, so
the score tile is a full ``(block_r, block_n)`` VPU lane layout (8 x 128
sublane/lane tiles) rather than one ``(bn, 1)`` lane-starved column per row.
Rows and sequence are both padded to block multiples with ⊕-identity leaves
(``s = NEG_INF``, ``v = 0``) and sliced on the way out, so odd / prime N no
longer collapses the block size toward a fully sequential grid.

With ``return_residuals`` the kernel also writes the per-position normaliser
pair ``(m_i, u_i)`` — the Aaren analogue of flash-attention's logsumexp
residual.  The analytic backward (``aaren_scan_bwd.py``) consumes
``(o, m, u)`` instead of re-running the scan; inference-only forwards leave
the flag off and skip that write.  See DESIGN.md §Backward.

Layout: scores ``s: (R, N)`` and values ``v: (R, N, d)`` with ``R = B·H``
rows; carries are ``(R, 1)`` / ``(R, d)``.  f32 throughout the kernel (the
paper's stability argument needs f32 exponent range; callers cast I/O).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scan_attention import NEG_INF

# Token tile: the lane width.  The in-block scan keeps about log2(block_n)
# live (block_r, block_n, d) f32 temporaries in VMEM, d padded to 128 lanes.
# On a TPU v5e (16 MB of scoped VMEM by default) block_n = 256 needs 20.6 MB
# in the forward and 27.0 MB in the backward at every head dim from 64 to
# 128; block_n = 128 compiles at every head dim the registered configs use
# (32 to 256).  Below 128 the (block_r, block_n) score tile breaks the
# (8, 128) block rule unless it spans the whole (padded) sequence.
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_R = 8


def _shifted(x: jax.Array, off: int, fill: float, axis: int) -> jax.Array:
    """x[..., i, ...] -> x[..., i - off, ...] with ``fill`` for i < off."""
    pad_shape = list(x.shape)
    pad_shape[axis] = off
    pad = jnp.full(pad_shape, fill, x.dtype)
    keep = [slice(None)] * x.ndim
    keep[axis] = slice(0, x.shape[axis] - off)
    return jnp.concatenate([pad, x[tuple(keep)]], axis=axis)


def _block_prefix_scan(m, u, w, f=None):
    """Hillis–Steele scan of the paper's ⊕ over the token axis (axis 1).

    m, u: (br, bn); w: (br, bn, d).  Exactly Algorithm 1 of the paper with
    ``identity = (-inf, 0, 0)`` shifted in at the left edge.

    ``f`` (br, bn) optionally carries segment-start flags (1.0 at the first
    token of each packed segment): the scan then becomes the *segmented*
    scan — a window whose resident half already contains a start drops the
    shifted (older) half entirely, so every position accumulates only its
    own segment's prefix (DESIGN.md §Packing).  Returns (m, u, w[, f]) with
    ``f`` scanned by OR (1 once the window has seen any start).
    """
    bn = m.shape[1]
    off = 1
    while off < bn:
        m_s = _shifted(m, off, NEG_INF, 1)
        u_s = _shifted(u, off, 0.0, 1)
        w_s = _shifted(w, off, 0.0, 1)
        if f is None:
            m_new = jnp.maximum(m, m_s)
            alpha = jnp.exp(m_s - m_new)  # weight of the shifted (older) half
        else:
            f_s = _shifted(f, off, 0.0, 1)
            keep = f == 0.0               # no reset inside the resident half
            m_new = jnp.where(keep, jnp.maximum(m, m_s), m)
            alpha = jnp.where(keep, jnp.exp(m_s - m_new), 0.0)
            f = jnp.maximum(f, f_s)
        beta = jnp.exp(m - m_new)         # weight of the resident half
        u = u_s * alpha + u * beta
        w = w_s * alpha[..., None] + w * beta[..., None]
        m = m_new
        off *= 2
    if f is None:
        return m, u, w
    return m, u, w, f


def _aaren_scan_kernel(
    *args,                                           # see parsing below
    n_blocks: int, save_residuals: bool, has_segments: bool,
):
    s_ref, v_ref, m0_ref, u0_ref, w0_ref = args[:5]
    idx = 5
    if has_segments:
        f_ref = args[idx]
        idx += 1
    o_ref, mf_ref, uf_ref, wf_ref = args[idx:idx + 4]
    idx += 4
    if save_residuals:
        mall_ref, uall_ref = args[idx:idx + 2]
        idx += 2
    cm, cu, cw = args[idx:idx + 3]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cm[...] = m0_ref[...]
        cu[...] = u0_ref[...]
        cw[...] = w0_ref[...]

    s = s_ref[...].astype(jnp.float32)   # (br, bn)
    v = v_ref[...].astype(jnp.float32)   # (br, bn, d)

    cmv = cm[...]            # (br, 1)
    cuv = cu[...]            # (br, 1)
    cwv = cw[...]            # (br, d)
    if has_segments:
        # Segmented scan: each position accumulates its own segment only,
        # and the cross-block carry folds only into positions whose block
        # prefix has not yet hit a segment start (the carry itself then
        # advances past the boundary via the folded last column).
        f = f_ref[...].astype(jnp.float32)
        m, u, w, fseen = _block_prefix_scan(s, jnp.ones_like(s), v, f)
        keep = fseen == 0.0                     # (br, bn)
        m_tot = jnp.where(keep, jnp.maximum(m, cmv), m)
        alpha = jnp.where(keep, jnp.exp(cmv - m_tot), 0.0)
    else:
        # Leaves (s_i, 1, v_i) -> all within-block prefixes via Algorithm 1,
        # then fold in the carry state of all previous blocks (Appendix A):
        # state_i <- carry ⊕ state_i.
        m, u, w = _block_prefix_scan(s, jnp.ones_like(s), v)
        m_tot = jnp.maximum(m, cmv)             # (br, bn)
        alpha = jnp.exp(cmv - m_tot)            # carry weight
    beta = jnp.exp(m - m_tot)                   # block weight
    u_tot = cuv * alpha + u * beta
    w_tot = cwv[:, None, :] * alpha[..., None] + w * beta[..., None]

    # Positions with an empty state (padding inside packed rows, before any
    # real token) have u = w = 0; the guard pins their readout to exactly 0
    # (the empty-set convention of scan_attention.readout) instead of 0/0.
    u_safe = jnp.where(u_tot == 0.0, 1.0, u_tot)
    o_ref[...] = (w_tot / u_safe[..., None]).astype(o_ref.dtype)
    if save_residuals:
        mall_ref[...] = m_tot
        uall_ref[...] = u_tot

    # Advance the carry with this block's final state.
    bn = s.shape[1]
    cm[...] = m_tot[:, bn - 1:bn]
    cu[...] = u_tot[:, bn - 1:bn]
    cw[...] = w_tot[:, bn - 1, :]

    @pl.when(j == n_blocks - 1)
    def _fin():
        mf_ref[...] = cm[...]
        uf_ref[...] = cu[...]
        wf_ref[...] = cw[...]


def pad_to_blocks(n: int, block: int) -> tuple[int, int]:
    """(padded size, block): block clamped to n, n rounded up to a multiple."""
    b = max(1, min(block, n))
    return ((n + b - 1) // b) * b, b


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_r", "return_residuals", "interpret"))
def aaren_scan(
    s: jax.Array,
    v: jax.Array,
    m0: jax.Array,
    u0: jax.Array,
    w0: jax.Array,
    segment_starts: jax.Array | None = None,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_r: int = DEFAULT_BLOCK_R,
    return_residuals: bool = False,
    interpret: bool = False,
):
    """All-prefix Aaren attention outputs + final carry (+ bwd residuals).

    s: (R, N) f32 scores; v: (R, N, d); m0/u0: (R, 1); w0: (R, d) carry
    (use ``NEG_INF``/0/0 for a fresh sequence).  ``segment_starts``:
    optional (R, N) flags (nonzero at the first token of each packed
    segment) — the scan then resets its carry to the ⊕ identity at every
    flagged position, and the incoming carry only reaches positions before
    the row's first flag (DESIGN.md §Packing).
    Returns (o: (R, N, d), m_f: (R, 1), u_f: (R, 1), w_f: (R, d)); with
    ``return_residuals`` also (m: (R, N), u: (R, N)) — the per-position
    running max / softmax denominator the analytic backward consumes.
    Inference-only callers leave the flag off and skip that HBM write.
    """
    r, n = s.shape
    d = v.shape[-1]
    n_pad, bn = pad_to_blocks(n, block_n)
    r_pad, br = pad_to_blocks(r, block_r)
    n_blocks = n_pad // bn

    s = s.astype(jnp.float32)
    v = v.astype(jnp.float32)
    has_segments = segment_starts is not None
    if has_segments:
        segment_starts = segment_starts.astype(jnp.float32)
    if n_pad != n or r_pad != r:
        # Padded tokens are the ⊕ identity (s = -inf, v = 0): they leave the
        # carry untouched, so outputs/finals only need slicing afterwards.
        dr, dn = r_pad - r, n_pad - n
        s = jnp.pad(s, ((0, dr), (0, dn)), constant_values=NEG_INF)
        v = jnp.pad(v, ((0, dr), (0, dn), (0, 0)))
        m0 = jnp.pad(m0, ((0, dr), (0, 0)), constant_values=NEG_INF)
        u0 = jnp.pad(u0, ((0, dr), (0, 0)))
        w0 = jnp.pad(w0, ((0, dr), (0, 0)))
        if has_segments:  # padding never starts a segment
            segment_starts = jnp.pad(segment_starts, ((0, dr), (0, dn)))

    kernel = functools.partial(_aaren_scan_kernel, n_blocks=n_blocks,
                               save_residuals=return_residuals,
                               has_segments=has_segments)
    grid = (r_pad // br, n_blocks)
    out_specs = [
        pl.BlockSpec((br, bn, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((br, d), lambda i, j: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((r_pad, n_pad, d), v.dtype),
        jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct((r_pad, d), jnp.float32),
    ]
    if return_residuals:
        out_specs += [
            pl.BlockSpec((br, bn), lambda i, j: (i, j)),
            pl.BlockSpec((br, bn), lambda i, j: (i, j)),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((r_pad, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((r_pad, n_pad), jnp.float32),
        ]
    in_specs = [
        pl.BlockSpec((br, bn), lambda i, j: (i, j)),
        pl.BlockSpec((br, bn, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((br, d), lambda i, j: (i, 0)),
    ]
    operands = [s, v, m0, u0, w0]
    if has_segments:
        in_specs.append(pl.BlockSpec((br, bn), lambda i, j: (i, j)))
        operands.append(segment_starts)
    o, m_f, u_f, w_f, *res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((br, 1), jnp.float32),
            pltpu.VMEM((br, 1), jnp.float32),
            pltpu.VMEM((br, d), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    if n_pad != n or r_pad != r:
        o = o[:r, :n]
        m_f, u_f, w_f = m_f[:r], u_f[:r], w_f[:r]
        res = [x[:r, :n] for x in res]
    return (o, m_f, u_f, w_f, *res)
