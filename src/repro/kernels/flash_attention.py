"""Pallas TPU kernels: causal (optionally sliding-window) flash attention,
forward and analytic backward, with in-kernel true-length masking.

The softmax-attention baseline the paper compares Aaren against.  The online
softmax recurrence carried across KV blocks is *literally the paper's
(m, c, a) recurrence* (§3.1 / App. A) — the same combine used in
``aaren_scan.py``, here applied per query row instead of per prefix:

    m   <- max(m, rowmax(S_blk))
    l   <- l · exp(m_old - m) + rowsum(exp(S_blk - m))
    acc <- acc · exp(m_old - m) + exp(S_blk - m) @ V_blk

Forward grid: ``(B, H, n_q_blocks, n_kv_blocks)`` — the KV dimension is the
TPU's sequentially-executed minor grid axis, so the (m, l, acc) carry lives
in VMEM scratch across KV steps.  The forward also writes the logsumexp
``L_i = m_i + log l_i`` per query row: the standard flash residual that lets
the backward re-materialise ``p_ij = exp(s_ij - L_i)`` tile-by-tile without
ever holding the N x N matrix in HBM.

True-length masking (DESIGN.md §Masking): every kernel reads its batch
row's ``(q_len, kv_len)`` from the whole ``(B,)`` length arrays in SMEM and
masks score-tile positions at or beyond the true length to ``-inf``
*before* the online-softmax update (and re-applies the mask to the
re-materialised probability tile in the backward).  Zero-padded K/V is **not** an identity under softmax — a padded
key would get weight ``exp((q·0)·scale − m) > 0`` — so the mask is the only
correct way to run a dense block grid at arbitrary N.  The wrappers pad all
sequence dims up to the block multiple and the grid never shrinks its tiles
(the old ``bq //= 2`` fallback, which degenerated to a fully sequential
grid at odd/prime N, is gone).  Rows with no attendable key (beyond their
``q_len``, or ``window == 0`` configs) output 0 with ``lse = NEG_INF`` —
the same empty-set convention as ``scan_attention.readout``.

Backward (standard two-pass flash-bwd, DESIGN.md §Backward): with
``D_i = Σ_d do_id o_id`` precomputed by the caller,

    dS_ij = p_ij (do_i · v_j - D_i)
    dq_i  = scale · Σ_j dS_ij k_j      — kernel A, KV minor, dq in scratch
    dk_j  = scale · Σ_i dS_ij q_i      — kernel B, Q minor, dk/dv in scratch
    dv_j  = Σ_i p_ij do_i

Causal, sliding-window, and true-length block-level relevance gating skips
the *compute* of masked-out blocks in all three kernels (the BlockSpec index
maps are static grid functions, so dead tiles still stream through VMEM —
skipping their HBM traffic would need a scalar-prefetch grid).  GQA is
handled by index arithmetic in the forward and in dq:
query head ``h`` reads KV head ``h // (H // G)`` — KV is never expanded in
HBM.  dk/dv are accumulated per *query* head and group-summed by the wrapper
(a ``(B, H)`` vs ``(B, G)`` HBM round-trip; see DESIGN.md §Backward for why
the in-kernel alternative revisits output blocks non-contiguously).

Validated in interpret mode against ``ref.flash_reference`` /
``ref.flash_vjp_reference`` over shape/dtype sweeps (tests/test_kernels.py)
and over ragged/odd/prime lengths (tests/test_flash_masking.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scan_attention import NEG_INF

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256

# Dense-grid tile quanta for sequences shorter than the requested block:
# the f32 sublane count for query rows, the lane width for key columns.
MIN_BLOCK_Q = 8
MIN_BLOCK_K = 128


def round_up(x: int, m: int) -> int:
    """Ceil ``x`` to a multiple of ``m`` (shared by wrappers and benches)."""
    return -(-x // m) * m


def resolve_blocks(n_q, n_k, block_q, block_k):
    """Dense tiles at any N — the grid never shrinks below the request.

    Sequences at least one block long keep the requested ``(bq, bk)``
    verbatim (the wrapper pads the arrays up to the block multiple; the
    in-kernel true-length mask keeps the padding out of the softmax).
    Shorter sequences get a single tile rounded up to the hardware quantum.
    The invariant tests/test_flash_masking.py pins: prime N launches the
    same tiles as N rounded up to the block multiple.
    """
    bq = block_q if n_q >= block_q else round_up(n_q, MIN_BLOCK_Q)
    bk = block_k if n_k >= block_k else round_up(n_k, MIN_BLOCK_K)
    return bq, bk


def _pad_dim(x: jax.Array, n_to: int, axis: int, value=0.0) -> jax.Array:
    """Pad ``axis`` up to ``n_to`` with ``value`` (no-op when already there)."""
    n = x.shape[axis]
    if n == n_to:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n_to - n)
    return jnp.pad(x, widths, constant_values=value)


def _as_lens(lens, batch: int, n: int) -> jax.Array:
    """Normalise an optional per-row lengths array to (B,) int32 for SMEM.

    Clamped to [0, n]: an oversized length would unmask the zero-padded
    tail (whose keys score ``exp(-m) > 0`` and absorb real probability
    mass), where the dense reference — whose mask index range simply ends
    at n — treats it as a no-op.
    """
    if lens is None:
        lens = jnp.full((batch,), n, jnp.int32)
    return jnp.clip(jnp.asarray(lens, jnp.int32), 0, n).reshape(batch)


def _lens_spec():
    """The whole (B,) lengths array in SMEM; kernels index it by batch row."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _seg_specs(bq, bk, q_minor: bool):
    """Segment-id tiles that meet the TPU (8, 128)-or-full block rule.

    Query ids ride as a (B, Nq, 1) column, so a tile reads as (bq, 1);
    key ids as a (B, 1, Nk) row, read as (1, bk).  Their comparison
    broadcasts straight to the (bq, bk) score tile.  ``q_minor``: the grid
    is (b, h, k-block, q-block), as in the dk/dv kernel.
    """
    if q_minor:
        qmap = lambda ib, ih, jk, jq: (ib, jq, 0)
        kmap = lambda ib, ih, jk, jq: (ib, 0, jk)
    else:
        qmap = lambda ib, ih, jq, jk: (ib, jq, 0)
        kmap = lambda ib, ih, jq, jk: (ib, 0, jk)
    return [pl.BlockSpec((1, bq, 1), qmap), pl.BlockSpec((1, 1, bk), kmap)]


def _seg_operands(q_segment_ids, kv_segment_ids, n_qp, n_kp):
    """(B, Nq_pad, 1) / (B, 1, Nk_pad) ids; padding keeps the padding id 0."""
    segq = _pad_dim(jnp.asarray(q_segment_ids, jnp.int32), n_qp, 1)
    segk = _pad_dim(jnp.asarray(kv_segment_ids, jnp.int32), n_kp, 1)
    return [segq[:, :, None], segk[:, None, :]]


def _block_relevant(q_start, k_start, block_q, block_k, causal, window,
                    q_len, kv_len, seg_q=None, seg_k=None):
    """Does any (q, k) pair in this tile survive the mask?

    Causal/window bounds are static per tile; the true-length bounds come
    from the per-row SMEM scalars, so irrelevant tail blocks of a short row
    skip compute exactly like causally-masked blocks do.  ``seg_q``/``seg_k``
    are this tile's packed-segment ids ((bq, 1) / (1, bk)): a tile whose
    id *ranges* are disjoint cannot contain an equal pair, so cross-document
    tiles of a packed batch skip compute too — exact when ids are monotone
    along the row (the bin-packer emits them in order), conservative but
    still correct otherwise.  Id 0 is padding: an all-padding tile is never
    relevant.
    """
    relevant = jnp.logical_and(q_start < q_len, k_start < kv_len)
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + block_q - 1)
    if window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - window)
    if seg_q is not None:
        q_min, q_max = jnp.min(seg_q), jnp.max(seg_q)
        k_min, k_max = jnp.min(seg_k), jnp.max(seg_k)
        overlap = jnp.logical_and(q_max >= k_min, k_max >= q_min)
        nonpad = jnp.logical_and(q_max > 0, k_max > 0)
        relevant = jnp.logical_and(relevant,
                                   jnp.logical_and(overlap, nonpad))
    return relevant


def _tile_mask(s_shape, q_start, k_start, causal, window, q_len, kv_len,
               seg_q=None, seg_k=None):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    mask = (q_pos < q_len) & (k_pos < kv_len)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    if seg_q is not None:
        mask &= (seg_q == seg_k) & (seg_q != 0)  # (bq, 1) vs (1, bk)
    return mask


def _flash_kernel(
    q_ref, k_ref, v_ref,      # (1, 1, bq, d), (1, 1, bk, d), (1, 1, bk, d)
    qlen_ref, klen_ref,       # SMEM (B,) int32 true lengths
    *rest,                    # [segq, segk,] o, lse + VMEM scratch m, l, acc
    scale: float, block_q: int, block_k: int, n_kv_blocks: int,
    causal: bool, window: int | None, has_segments: bool,
):
    if has_segments:
        segq_ref, segk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    jq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = jq * block_q
    k_start = jk * block_k
    ib = pl.program_id(0)
    q_len = qlen_ref[ib]
    kv_len = klen_ref[ib]
    seg_q = segq_ref[0] if has_segments else None    # (bq, 1) int32
    seg_k = segk_ref[0] if has_segments else None    # (1, bk) int32
    relevant = _block_relevant(q_start, k_start, block_q, block_k,
                               causal, window, q_len, kv_len, seg_q, seg_k)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        mask = _tile_mask(s.shape, q_start, k_start, causal, window,
                          q_len, kv_len, seg_q, seg_k)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # (bq, 1)
        l_prev = l_scr[...]
        acc_prev = acc_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)              # the paper's carry rescale
        p = jnp.exp(s - m_new)                       # (bq, bk)
        # A fully-masked row has m_new == NEG_INF, where exp(s - m_new) is
        # exp(0) = 1 per masked entry — phantom mass.  Re-applying the mask
        # keeps empty rows exactly at the ⊕ identity (l = 0, acc = 0); for
        # rows with any live entry it is a no-op (masked entries underflow).
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_prev * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(jk == n_kv_blocks - 1)
    def _finish():
        # Empty rows (beyond q_len, or window == 0 configs) read out as 0
        # with lse = NEG_INF — the empty-set convention of readout().
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_safe)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "return_residuals", "interpret"))
def flash_attention(
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
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    return_residuals: bool = False,
    interpret: bool = False,
):
    """Flash attention.  q: (B, H, Nq, d); k/v: (B, G, Nk, d), G | H.

    ``q_lens`` / ``kv_lens``: optional (B,) int32 true lengths per batch
    row; positions at or beyond them are masked in-kernel (queries there
    output 0).  ``q_segment_ids`` / ``kv_segment_ids``: optional (B, Nq) /
    (B, Nk) int32 packed-segment ids — score tiles where the ids differ are
    masked to −inf, id 0 is padding (rows there output 0), and tiles whose
    id ranges are disjoint skip compute entirely (DESIGN.md §Packing).  Any
    Nq/Nk launches a dense grid — the wrapper pads to the block multiple
    and the mask keeps the padding out of the softmax.

    Returns (B, H, Nq, d) in q.dtype; with ``return_residuals`` also the
    per-row logsumexp (B, H, Nq) f32 the backward consumes.
    """
    b, h, n_q, d = q.shape
    g, n_k = k.shape[1], k.shape[2]
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("segment ids must be given for both q and kv")
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    bq, bk = resolve_blocks(n_q, n_k, block_q, block_k)
    n_qp, n_kp = round_up(n_q, bq), round_up(n_k, bk)
    ql = _as_lens(q_lens, b, n_q)
    kl = _as_lens(kv_lens, b, n_k)
    q = _pad_dim(q, n_qp, 2)
    k = _pad_dim(k, n_kp, 2)
    v = _pad_dim(v, n_kp, 2)
    has_segments = q_segment_ids is not None
    n_kv_blocks = n_kp // bk
    grid = (b, h, n_qp // bq, n_kv_blocks)
    group = h // g  # queries per kv head

    kernel = functools.partial(
        _flash_kernel, scale=float(scale), block_q=bq, block_k=bk,
        n_kv_blocks=n_kv_blocks, causal=causal, window=window,
        has_segments=has_segments)

    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        pl.BlockSpec(
            (1, 1, bk, d),
            lambda ib, ih, jq, jk: (ib, ih // group, jk, 0)),
        pl.BlockSpec(
            (1, 1, bk, d),
            lambda ib, ih, jq, jk: (ib, ih // group, jk, 0)),
        _lens_spec(),
        _lens_spec(),
    ]
    operands = [q, k, v, ql, kl]
    if has_segments:
        in_specs += _seg_specs(bq, bk, q_minor=False)
        operands += _seg_operands(q_segment_ids, kv_segment_ids, n_qp, n_kp)

    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
            # logsumexp as a (bq, 1) column: a (1, 1, bq) block over
            # (B, H, N) would break the TPU block rule.
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_qp, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, n_qp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    o, lse = o[:, :, :n_q], lse[:, :, :n_q, 0]
    return (o, lse) if return_residuals else o


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _recompute_p_ds(q, k, v, do, lse, delta, *, scale, q_start, k_start,
                    causal, window, q_len, kv_len, seg_q=None, seg_k=None):
    """Re-materialise the probability tile and dS tile from residuals.

    q/do: (bq, d); k/v: (bk, d); lse/delta: (bq, 1).
    Returns p, ds: (bq, bk) f32.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    mask = _tile_mask(s.shape, q_start, k_start, causal, window,
                      q_len, kv_len, seg_q, seg_k)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)                             # (bq, bk)
    # Empty rows carry lse == NEG_INF, where exp(NEG_INF - NEG_INF) = 1;
    # the mask pins them (and their dS) to exactly 0, mirroring the
    # forward's zero output for rows with no attendable key.
    p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # do_i · v_j
    ds = p * (dp - delta)
    return p, ds


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    qlen_ref, klen_ref,
    *rest,                    # [segq, segk,] dq out + dq scratch
    scale: float, block_q: int, block_k: int, n_kv_blocks: int,
    causal: bool, window: int | None, has_segments: bool,
):
    if has_segments:
        segq_ref, segk_ref, dq_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
    jq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = jq * block_q
    k_start = jk * block_k
    ib = pl.program_id(0)
    q_len = qlen_ref[ib]
    kv_len = klen_ref[ib]
    seg_q = segq_ref[0] if has_segments else None
    seg_k = segk_ref[0] if has_segments else None
    relevant = _block_relevant(q_start, k_start, block_q, block_k,
                               causal, window, q_len, kv_len, seg_q, seg_k)

    @pl.when(relevant)
    def _compute():
        _, ds = _recompute_p_ds(
            q_ref[0, 0].astype(jnp.float32), k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32), do_ref[0, 0].astype(jnp.float32),
            lse_ref[0, 0], delta_ref[0, 0], scale=scale,
            q_start=q_start, k_start=k_start, causal=causal, window=window,
            q_len=q_len, kv_len=kv_len, seg_q=seg_q, seg_k=seg_k)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == n_kv_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    qlen_ref, klen_ref,
    *rest,                    # [segq, segk,] dk/dv outs + dk/dv scratch
    scale: float, block_q: int, block_k: int, n_q_blocks: int,
    causal: bool, window: int | None, has_segments: bool,
):
    if has_segments:
        segq_ref, segk_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    jk = pl.program_id(2)
    jq = pl.program_id(3)

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = jq * block_q
    k_start = jk * block_k
    ib = pl.program_id(0)
    q_len = qlen_ref[ib]
    kv_len = klen_ref[ib]
    seg_q = segq_ref[0] if has_segments else None
    seg_k = segk_ref[0] if has_segments else None
    relevant = _block_relevant(q_start, k_start, block_q, block_k,
                               causal, window, q_len, kv_len, seg_q, seg_k)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        p, ds = _recompute_p_ds(
            q, k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32), do,
            lse_ref[0, 0], delta_ref[0, 0], scale=scale,
            q_start=q_start, k_start=k_start, causal=causal, window=window,
            q_len=q_len, kv_len=kv_len, seg_q=seg_q, seg_k=seg_k)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # Σ_i p_ij do_i
        dk_scr[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # Σ_i dS_ij q_i

    @pl.when(jq == n_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...]
        dv_ref[0, 0] = dv_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_bwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o: jax.Array,
    lse: jax.Array,
    do: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_lens: jax.Array | None = None,
    kv_lens: jax.Array | None = None,
    q_segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """Analytic flash backward from forward residuals ``(o, lse)``.

    q/o/do: (B, H, Nq, d); k/v: (B, G, Nk, d); lse: (B, H, Nq) f32.
    ``q_lens`` / ``kv_lens`` / segment ids must match the forward call: the
    probability tiles are re-materialised under the same mask, so masked
    queries get dq = 0 and masked keys get dk = dv = 0 (cross-segment pairs
    of a packed batch contribute no cotangent at all).
    Returns (dq, dk, dv) in the corresponding input dtypes.
    """
    b, h, n_q, d = q.shape
    g, n_k = k.shape[1], k.shape[2]
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("segment ids must be given for both q and kv")
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    bq, bk = resolve_blocks(n_q, n_k, block_q, block_k)
    n_qp, n_kp = round_up(n_q, bq), round_up(n_k, bk)
    ql = _as_lens(q_lens, b, n_q)
    kl = _as_lens(kv_lens, b, n_k)
    q = _pad_dim(q, n_qp, 2)
    o = _pad_dim(o, n_qp, 2)
    do = _pad_dim(do, n_qp, 2)
    # Padded lse rows read NEG_INF (the empty-row residual convention).
    lse = _pad_dim(lse, n_qp, 2, value=NEG_INF)
    k = _pad_dim(k, n_kp, 2)
    v = _pad_dim(v, n_kp, 2)
    group = h // g
    has_segments = q_segment_ids is not None
    # D_i = Σ_d do·o — one elementwise pass, shared by both kernels.  It and
    # the logsumexp enter as (bq, 1) columns (the TPU block rule).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    lse = lse[..., None]

    common = dict(scale=float(scale), block_q=bq, block_k=bk,
                  causal=causal, window=window, has_segments=has_segments)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda ib, ih, jq, jk: (ib, ih // group, jk, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda ib, ih, jq, jk: (ib, ih // group, jk, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        _lens_spec(),
        _lens_spec(),
    ]
    operands = [q, k, v, do, lse, delta, ql, kl]
    if has_segments:
        in_specs += _seg_specs(bq, bk, q_minor=False)
        operands += _seg_operands(q_segment_ids, kv_segment_ids, n_qp, n_kp)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_kv_blocks=n_kp // bk,
                          **common),
        grid=(b, h, n_qp // bq, n_kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, bq, d), lambda ib, ih, jq, jk: (ib, ih, jq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, n_qp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(*operands)

    # dk/dv accumulate over queries: Q is the minor (sequential) grid axis.
    # Accumulated per *query* head — the (b, g) output block for a KV head
    # would be revisited non-contiguously across the h grid axis — then
    # group-summed here (f32) and cast.
    bwd_in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda ib, ih, jk, jq: (ib, ih, jq, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda ib, ih, jk, jq: (ib, ih // group, jk, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda ib, ih, jk, jq: (ib, ih // group, jk, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda ib, ih, jk, jq: (ib, ih, jq, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, jk, jq: (ib, ih, jq, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, jk, jq: (ib, ih, jq, 0)),
        _lens_spec(),
        _lens_spec(),
    ]
    if has_segments:
        bwd_in_specs += _seg_specs(bq, bk, q_minor=True)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_q_blocks=n_qp // bq,
                          **common),
        grid=(b, h, n_kp // bk, n_qp // bq),
        in_specs=bwd_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, jk, jq: (ib, ih, jk, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, jk, jq: (ib, ih, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_kp, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n_kp, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)

    dq = dq[:, :, :n_q]
    dk_h, dv_h = dk_h[:, :, :n_k], dv_h[:, :, :n_k]
    dk = jnp.sum(dk_h.reshape(b, g, group, n_k, d), axis=2).astype(k.dtype)
    dv = jnp.sum(dv_h.reshape(b, g, group, n_k, d), axis=2).astype(v.dtype)
    return dq, dk, dv
