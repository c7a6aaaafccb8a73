"""Context parallelism: cross-device prefix-scan attention over a ``seq`` axis.

The paper's claim (3) — the many-to-many attention output is an associative
parallel prefix scan over ``(m, u, w)`` states — composes across devices
exactly as it composes across Pallas blocks (App. A) and serving chunks
(``lm_prefill_chunk``).  This module is the shards-on-a-mesh instance of
that recurrence (DESIGN.md §Context-parallelism):

* **Aaren scan mode** (:func:`cp_aaren_prefix_attention`): each device runs
  the existing fused scan (``kops.aaren_prefix_attention`` with carry-in /
  carry-out) on its local shard of the sequence.  The shard is *seeded* with
  the ⊕-total of every earlier shard, obtained by an **exclusive cross-device
  scan of the (m, u, w) carries**: a log₂(P)-step ``ppermute`` exchange under
  the same ⊕ from ``scan_attention.combine``.  The per-boundary payload is
  one carry — O(rows·(d+2)) floats — against the O(N·d) activations that
  stay put; that asymmetry is the whole point of the subsystem.
* **Softmax mode** (:func:`cp_flash_mha`): ring flash attention — K/V shards
  rotate around the ``seq`` axis ring while each device folds one partial
  softmax block per step into a running ``(m, u, w)`` accumulator (running
  logsumexp is ``m + log u``), so causal/windowed softmax parity with
  ``kops.flash_mha`` holds shard-by-shard.

Gradients: the scan op carries a ``custom_vjp`` whose backward re-linearises
the saved forward with ``jax.vjp``.  Transposing the forward's *prefix*
``ppermute`` rounds yields exactly the mirrored *suffix* exchange (a
``ppermute`` transpose is the same permutation with every edge reversed), and
the inner ``kops.aaren_prefix_attention`` call hits its own custom VJP — the
fused analytic reverse kernels of ``kernels/aaren_scan_bwd.py`` on the
kernel path, recompute-autodiff on the jnp path.  The ring-flash backward is
plain autodiff: the ring is an unrolled loop of linear ``ppermute`` ops plus
the ⊕ algebra, so its transpose is the reverse-direction ring.

Both entry points fall back to the single-device ``kops`` ops when no
context-parallel session is active (or the ``seq`` axis has size 1), so model
code can call them unconditionally.  On a mesh whose ``seq`` axis has size 1
the kernel path still runs inside a ``shard_map`` island over the batch
(:func:`_batch_island`): GSPMD cannot partition a Mosaic kernel.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.scan_attention import (
    NEG_INF,
    ScanState,
    combine,
    combine_segmented,
    make_empty_state,
    mask_to_identity,
    readout,
)
from repro.kernels import flash_attention as _kflash
from repro.kernels import ops as kops
from repro.obs.trace import span as _span

SEQ_AXIS = "seq"


@dataclasses.dataclass(frozen=True)
class ContextParallel:
    """Handle naming which mesh axis carries the sequence dimension."""

    mesh: Mesh
    axis: str = SEQ_AXIS

    @property
    def size(self) -> int:
        return int(self.mesh.shape[self.axis])

    def batch_axis(self, dim: int):
        """Mesh axes for the leading batch dim inside the shard_map island.

        Resolved through the sharding rules' ``"batch"`` entry — the same
        priority/divisibility/joint-entry logic every other batch spec uses
        — instead of a hard-coded ``"data"`` lookup, so batch sharding over
        joint ``("pod", "data")`` meshes survives into the island and the
        island boundary needs no all-gather on composed meshes.  The
        ``seq`` axis itself is never eligible (it carries the length dim).
        Returns a mesh-axis name, a tuple of names (joint entry), or None
        (replicated).
        """
        from repro.sharding import (
            ShardingRules, current_rules, spec_for_axes)

        sr = current_rules()
        if sr is None or sr.mesh is not self.mesh:
            sr = ShardingRules(self.mesh)
        spec = spec_for_axes(("batch",), (dim,), sr)
        part = spec[0] if len(spec) else None
        if part is None:
            return None
        names = (part,) if isinstance(part, str) else tuple(part)
        if self.axis in names:
            return None
        return names[0] if len(names) == 1 else names


_CTX = threading.local()


def current_cp() -> ContextParallel | None:
    return getattr(_CTX, "cp", None)


@contextlib.contextmanager
def use_context_parallel(cp: ContextParallel):
    """Ambient-context activation, mirroring ``sharding.use_rules``.

    Like ``use_rules`` (and ``REPRO_KERNEL_MODE`` in kernels/ops.py), the
    ambient handle is read at **trace time**: it is not part of any jit
    cache key, so a function jitted outside a session keeps its
    single-device trace if called inside one later (and vice versa).  Build
    the jitted step *inside* the session — the training loop enters the
    session before its first step for exactly this reason.
    """
    prev = getattr(_CTX, "cp", None)
    _CTX.cp = cp
    try:
        yield cp
    finally:
        _CTX.cp = prev


@contextlib.contextmanager
def mesh_plan_session(plan):
    """Activate one composed mesh (rules + attention dispatch) from a plan.

    The one-stop entry point for the training stack: builds the
    ``pod × data × seq × model`` mesh from a :class:`repro.sharding.MeshPlan`,
    installs the logical-axis sharding rules on it (so ``constrain`` shards
    batch dims over ``data``/``pod``, length dims over ``seq``, and TP dims
    over ``model``) and — when the plan carries a non-trivial ``seq`` axis —
    the context-parallel attention dispatch *on that same ambient mesh*:
    the shard_map islands' carry ppermutes ride ``seq`` while GSPMD keeps
    the gradient psum on ``data``/``pod`` and the TP collectives on
    ``model`` around them.  ``plan=None`` or an all-ones plan is a no-op
    scope (no mesh, no dispatch).
    """
    if plan is None or plan.is_trivial:
        yield None
        return
    from repro.sharding import ShardingRules, use_rules

    mesh = plan.build_mesh()
    sr = ShardingRules(mesh)
    cp = ContextParallel(mesh)
    with use_rules(sr), use_context_parallel(cp):
        # cp.size == 1 keeps every cp_* entry point on its single-device
        # fallback; installing the handle anyway keeps the session uniform.
        yield cp


@contextlib.contextmanager
def context_parallel_session(seq: int):
    """Back-compat wrapper: a plan whose only non-trivial axis is ``seq``.

    Builds ``MeshPlan.host(seq=seq)`` (remaining devices soak into
    ``data``) and delegates to :func:`mesh_plan_session`.  ``seq <= 1`` is
    a no-op scope.
    """
    if seq <= 1:
        yield None
        return
    from repro.sharding import MeshPlan

    with mesh_plan_session(MeshPlan.host(seq=seq)) as cp:
        yield cp


def _batch_island(cp: ContextParallel | None, fn, *args):
    """``fn(*args)``, once per batch shard of ``cp.mesh`` on the kernel path.

    GSPMD refuses to partition a Mosaic kernel, so on a mesh the Pallas
    path runs inside ``shard_map``.  Every operand and result leads with
    the batch dim, sharded as the rules shard ``"batch"`` (whole on each
    device where that does not divide); all other dims are whole on each
    device.  ``None`` operands pass through.  With no mesh, or on the jnp
    path, ``fn`` is called as is.
    """
    if cp is None or kops.kernel_mode() == "jnp":
        return fn(*args)
    given = [a is not None for a in args]
    operands = [a for a in args if a is not None]

    def local(*xs):
        it = iter(xs)
        return fn(*(next(it) if g else None for g in given))

    spec = P(cp.batch_axis(operands[0].shape[0]))
    return shard_map(local, mesh=cp.mesh, in_specs=(spec,) * len(operands),
                     out_specs=spec, check_rep=False)(*operands)


# ---------------------------------------------------------------------------
# Cross-device carry algebra (runs *inside* shard_map, per shard)
# ---------------------------------------------------------------------------


def shard_total(s: jax.Array, v: jax.Array) -> ScanState:
    """⊕-total of one shard in a single cheap reduction (no scan).

    ``(m, u, w) = (max s, Σ exp(s - m), Σ exp(s - m) v)`` — O(N·d) elementwise
    work, so seeding the shards costs one reduction + the carry exchange
    rather than a second full scan.  A fully ⊕-identity shard (every position
    masked) must stay the identity: ``exp(NEG_INF - NEG_INF) = 1`` would
    manufacture mass, hence the explicit guard.
    """
    m = jnp.max(s, axis=-1)
    e = jnp.exp(s - m[..., None])
    e = jnp.where((m == NEG_INF)[..., None], 0.0, e)
    u = jnp.sum(e, axis=-1)
    w = jnp.einsum("...n,...nd->...d", e, v)
    return ScanState(m=m, u=u, w=w)


def _shift_states(st: ScanState, shift: int, axis: str, axis_size: int,
                  idx: jax.Array) -> ScanState:
    """Receive the carry from ``shift`` ranks below; ⊕-identity at the edge.

    ``ppermute`` hands devices without a sender *zeros*, which are not the
    ⊕ identity (``m`` needs ``NEG_INF``), so the edge ranks are patched.
    """
    perm = [(i, i + shift) for i in range(axis_size - shift)]
    recv = jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), st)
    has = idx >= shift
    return ScanState(m=jnp.where(has, recv.m, NEG_INF),
                     u=jnp.where(has, recv.u, 0.0),
                     w=jnp.where(has, recv.w, 0.0))


def device_exclusive_scan(total: ScanState, axis: str,
                          axis_size: int) -> ScanState:
    """Exclusive cross-device prefix scan of carries under ⊕.

    One right-shift plus ⌈log₂ P⌉ doubling rounds of ``ppermute`` (the
    Hillis–Steele / Blelloch-style log-step exchange): after the shift,
    rank p holds T_{p-1}; round k folds in the carry from 2^k ranks below,
    so rank p ends with E_p = T_0 ⊕ … ⊕ T_{p-1} (⊕-identity at rank 0).
    Payload per round is one carry state per row — O(rows·(d+2)) floats,
    independent of the shard length.
    """
    with _span("cp.carry_exchange"):
        idx = jax.lax.axis_index(axis)
        acc = _shift_states(total, 1, axis, axis_size, idx)
        shift = 1
        while shift < axis_size:
            acc = combine(
                _shift_states(acc, shift, axis, axis_size, idx), acc)
            shift *= 2
        return acc


def device_allreduce_state(total: ScanState, axis: str,
                           axis_size: int) -> ScanState:
    """⊕-allreduce of per-shard totals: the replicated global final carry.

    ``all_gather`` + an ordered fold instead of ``pmax``/``psum`` trickery —
    every step is differentiable (``pmax`` has no transpose rule), which the
    custom-VJP backward relies on.  P is small (≤ mesh axis size), so the
    O(P) fold is noise next to the local scans.
    """
    g = jax.tree.map(lambda x: jax.lax.all_gather(x, axis), total)
    acc = ScanState(m=g.m[0], u=g.u[0], w=g.w[0])
    for p in range(1, axis_size):
        acc = combine(acc, ScanState(m=g.m[p], u=g.u[p], w=g.w[p]))
    return acc


# ---------------------------------------------------------------------------
# Segmented carry algebra (packed sequences; DESIGN.md §Packing)
# ---------------------------------------------------------------------------


def _seg_combine(lhs: ScanState, f_l, rhs: ScanState, f_r):
    """Segmented ⊕ on (state, has-reset) pairs; ``rhs`` is the later span.

    If the later span contains a segment start, the earlier state is
    dropped (the scan restarted inside ``rhs``); flags compose by OR.
    ScanState-shaped adapter over the one shared operator
    (``scan_attention.combine_segmented`` — also the kernels' formula), so
    the reset/rescale algebra exists in exactly one place.
    """
    m, u, w, f = combine_segmented((lhs.m, lhs.u, lhs.w, f_l),
                                   (rhs.m, rhs.u, rhs.w, f_r))
    return ScanState(m=m, u=u, w=w), f


def shard_total_segmented(s, v, starts):
    """⊕-total of a shard *since its last segment start* + a has-start flag.

    Positions before the shard's last flagged start are masked to the
    ⊕ identity (they belong to documents the running carry must not cross),
    so the pair ``(total, flag)`` is exactly the shard's aggregate under
    the segmented operator: composing shards with :func:`_seg_combine`
    reproduces the sequential segmented fold.
    """
    n = s.shape[-1]
    axis = starts.ndim - 1
    # has a start at a position strictly AFTER t  ⇔  t precedes the last
    # start  ⇒  masked out of the running total.
    at_or_after = jnp.flip(jax.lax.cummax(jnp.flip(starts, -1), axis=axis), -1)
    after = jnp.concatenate(
        [at_or_after[..., 1:], jnp.zeros_like(at_or_after[..., :1])], axis=-1)
    s_m, v_m = mask_to_identity(s, v, after == 0)
    flag = (jnp.max(starts, axis=-1) > 0).astype(jnp.float32)
    return shard_total(s_m, v_m), flag


def segment_starts_sharded(seg, axis: str, axis_size: int):
    """Per-shard segment-start flags with a 1-step ppermute halo.

    The flags must reflect *global* neighbours — a shard-local shifted
    compare would flag a false boundary wherever a document spans a shard
    edge.  But computing them globally *outside* the island and letting
    GSPMD partition the shifted compare is not safe either: on composed
    (seq x model) meshes XLA's SPMD partitioner miscompiles the halo
    exchange for a concatenate-shift feeding a shard_map, yielding garbage
    flags (spurious starts at arbitrary positions).  So the shift is done
    here, inside the island, with an explicit collective we own: each rank
    fetches the left neighbour's last id via ppermute and compares against
    that; rank 0 compares position 0 against itself (position 0 is never a
    start — the incoming carry seeds it, see
    ``segment_starts_from_ids``).
    """
    last = seg[..., -1:]
    perm = [(i, i + 1) for i in range(axis_size - 1)]
    recv = jax.lax.ppermute(last, axis, perm)
    idx = jax.lax.axis_index(axis)
    left = jnp.where(idx == 0, seg[..., :1], recv)
    prev = jnp.concatenate([left, seg[..., :-1]], axis=-1)
    return ((seg != prev) & (seg != 0)).astype(jnp.int32)


def device_exclusive_scan_segmented(total: ScanState, flag, axis: str,
                                    axis_size: int):
    """Exclusive cross-device prefix scan under the *segmented* ⊕.

    Same log-step ppermute ladder as :func:`device_exclusive_scan`, lifted
    to (state, flag) pairs: rank p ends with the segmented fold of shards
    0..p-1 — i.e. the state of the document still open at its left
    boundary, and the ⊕ identity if a start occurred in between.  Returns
    (prefix state, prefix flag); a shard whose prefix flag is set must not
    fold the global incoming carry (a reset separates them).
    """
    idx = jax.lax.axis_index(axis)

    def shift(st, f, k):
        recv = _shift_states(st, k, axis, axis_size, idx)
        perm = [(i, i + k) for i in range(axis_size - k)]
        f_recv = jax.lax.ppermute(f, axis, perm)
        return recv, jnp.where(idx >= k, f_recv, 0.0)

    with _span("cp.carry_exchange_segmented"):
        acc, f_acc = shift(total, flag, 1)
        k = 1
        while k < axis_size:
            older, f_old = shift(acc, f_acc, k)
            acc, f_acc = _seg_combine(older, f_old, acc, f_acc)
            k *= 2
        return acc, f_acc


# ---------------------------------------------------------------------------
# Context-parallel Aaren prefix attention (scan mode)
# ---------------------------------------------------------------------------


def _cp_scan_forward(s, v, m0, u0, w0, axis, axis_size):
    """Per-shard forward: local total → carry exchange → seeded local scan.

    Shapes are *local*: s (..., N/P), v (..., N/P, d); the incoming carry
    (m0, u0, w0) is replicated across the ``seq`` axis.  Returns the local
    output slice plus the replicated global final carry.
    """
    carry0 = ScanState(m=m0, u=u0, w=w0)
    total = shard_total(s, v)
    prefix = device_exclusive_scan(total, axis, axis_size)
    seed = combine(carry0, prefix)
    with _span("cp.local_scan"):
        o, _ = kops.aaren_prefix_attention(s, v, seed)
    fin = combine(carry0, device_allreduce_state(total, axis, axis_size))
    return o, fin.m, fin.u, fin.w


def _cp_scan_forward_segmented(s, v, m0, u0, w0, seg, axis, axis_size):
    """Segmented per-shard forward (packed sequences, DESIGN.md §Packing).

    Resets stay *local to each shard's fused scan* — the only cross-device
    change is that the carry exchange runs under the segmented ⊕: a shard's
    contribution is its ⊕-total since its last internal reset plus a
    has-reset flag, so a document spanning a shard boundary is seeded by
    exactly its own prefix and a boundary inside an earlier shard cuts the
    chain.  ``seg`` holds the (sharded) segment ids; the start flags are
    derived in-island by :func:`segment_starts_sharded`, whose ppermute
    halo gives each shard its true global left neighbour.  The incoming
    carry folds only into shards before the first global reset; the final
    carry is the segmented fold of all shards = the last document's state.
    """
    carry0 = ScanState(m=m0, u=u0, w=w0)
    starts = segment_starts_sharded(seg, axis, axis_size)
    total, flag = shard_total_segmented(s, v, starts)
    prefix, pre_flag = device_exclusive_scan_segmented(
        total, flag, axis, axis_size)
    seed, _ = _seg_combine(carry0, jnp.zeros_like(pre_flag), prefix, pre_flag)
    with _span("cp.local_scan"):
        o, _ = kops.aaren_prefix_attention(s, v, seed,
                                           segment_starts=starts)
    # Final carry: ordered segmented fold of the gathered shard aggregates.
    g = jax.tree.map(lambda x: jax.lax.all_gather(x, axis), (total, flag))
    acc = ScanState(m=g[0].m[0], u=g[0].u[0], w=g[0].w[0])
    f_acc = g[1][0]
    for p in range(1, axis_size):
        acc, f_acc = _seg_combine(
            acc, f_acc, ScanState(m=g[0].m[p], u=g[0].u[p], w=g[0].w[p]),
            g[1][p])
    fin, _ = _seg_combine(carry0, jnp.zeros_like(f_acc), acc, f_acc)
    return o, fin.m, fin.u, fin.w


def _make_cp_scan_core(axis: str, axis_size: int, segmented: bool = False):
    """Build the custom-VJP per-shard op for one (axis, size) pair."""

    if segmented:
        def fwd_fn(s, v, m0, u0, w0, seg):
            return _cp_scan_forward_segmented(s, v, m0, u0, w0, seg,
                                              axis, axis_size)

        @jax.custom_vjp
        def core(s, v, m0, u0, w0, seg):
            return fwd_fn(s, v, m0, u0, w0, seg)

        def core_fwd(s, v, m0, u0, w0, seg):
            return fwd_fn(s, v, m0, u0, w0, seg), (s, v, m0, u0, w0, seg)

        def core_bwd(res, g):
            s, v, m0, u0, w0, seg = res
            _, vjp = jax.vjp(
                lambda s_, v_, m_, u_, w_: fwd_fn(s_, v_, m_, u_, w_, seg),
                s, v, m0, u0, w0)
            return (*vjp(g),
                    np.zeros(np.shape(seg), jax.dtypes.float0))

        core.defvjp(core_fwd, core_bwd)
        return core

    def fwd_fn(s, v, m0, u0, w0):
        return _cp_scan_forward(s, v, m0, u0, w0, axis, axis_size)

    @jax.custom_vjp
    def core(s, v, m0, u0, w0):
        return fwd_fn(s, v, m0, u0, w0)

    def core_fwd(s, v, m0, u0, w0):
        # Save raw inputs (the jnp-path idiom of kernels/ops.py): the
        # backward re-linearises the forward, which (a) transposes the
        # prefix ppermutes into the mirrored suffix exchange and (b) enters
        # the inner op's own custom VJP — the fused analytic reverse
        # kernels on the Pallas path.
        return fwd_fn(s, v, m0, u0, w0), (s, v, m0, u0, w0)

    def core_bwd(res, g):
        _, vjp = jax.vjp(fwd_fn, *res)
        return vjp(g)

    core.defvjp(core_fwd, core_bwd)
    return core


def cp_aaren_prefix_attention(
    s: jax.Array,
    v: jax.Array,
    carry: ScanState | None = None,
    *,
    segment_ids: jax.Array | None = None,
    cp: ContextParallel | None = None,
):
    """Context-parallel drop-in for ``kops.aaren_prefix_attention``.

    s: (..., N) scores; v: (..., N, d) values; carry leaves m,u (...,),
    w (..., d).  Any N: an indivisible tail is padded with ⊕-identity
    leaves (contributing nothing to outputs or the final carry) and sliced
    off.  ``segment_ids`` (packed sequences; shape (..., N) or missing one
    leading dim, broadcast over it): resets are local to each shard's scan
    and the carry exchange runs under the segmented ⊕ — the ids ship into
    the island sharded and start flags are derived there with a ppermute
    halo (:func:`segment_starts_sharded`), so a document spanning a shard
    boundary is never falsely reset and the shifted compare never crosses
    the SPMD partitioner (DESIGN.md §Packing).  Falls
    back to the single-device fused op when no session is active.  Returns
    (o: (..., N, d), replicated global final ScanState).
    """
    cp = cp if cp is not None else current_cp()
    if cp is None or cp.size == 1:
        return _batch_island(
            cp, lambda s_, v_, c_, g_: kops.aaren_prefix_attention(
                s_, v_, c_, segment_ids=g_),
            s, v, carry, segment_ids)
    n = s.shape[-1]
    batch_shape = s.shape[:-1]
    d = v.shape[-1]
    if carry is None:
        carry = make_empty_state(batch_shape, d)
    s32 = s.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    seg = None
    if segment_ids is not None:
        seg = jnp.asarray(segment_ids, jnp.int32)
        if seg.ndim == s32.ndim - 1:  # e.g. (B, N) vs (B, H, N)
            seg = jnp.broadcast_to(seg[..., None, :], s32.shape)
        seg = jnp.broadcast_to(seg, s32.shape)
        # Padding (id 0) -> ⊕-identity leaves; outputs there pinned to 0
        # after the island (the kops empty-row convention).
        s32, v32 = mask_to_identity(s32, v32, seg != 0)
    # Arbitrary N: pad the sequence dim up to the seq-axis multiple with
    # ⊕-identity leaves (s = NEG_INF, v = 0) — they contribute nothing to
    # any prefix or to the global final carry — and slice the tail off
    # after the island.
    n_pad = _kflash.round_up(n, cp.size)
    if n_pad != n:
        widths = [(0, 0)] * s32.ndim
        widths[-1] = (0, n_pad - n)
        s32 = jnp.pad(s32, widths, constant_values=NEG_INF)
        v32 = jnp.pad(v32, [*widths, (0, 0)])
        if seg is not None:
            seg = jnp.pad(seg, widths)  # pad id 0: never a start
    m0 = carry.m.astype(jnp.float32)
    u0 = carry.u.astype(jnp.float32)
    w0 = carry.w.astype(jnp.float32)

    bax = cp.batch_axis(batch_shape[0]) if batch_shape else None
    lead = (bax,) + (None,) * (len(batch_shape) - 1)
    in_specs = (P(*lead, cp.axis),          # s: length dim sharded
                P(*lead, cp.axis, None),    # v
                P(*lead), P(*lead), P(*lead, None))  # carry: replicated
    out_specs = (P(*lead, cp.axis, None),   # o
                 P(*lead), P(*lead), P(*lead, None))
    operands = [s32, v32, m0, u0, w0]
    if seg is not None:
        in_specs = in_specs + (P(*lead, cp.axis),)   # seg ids: sharded like s
        operands.append(seg)
    fn = shard_map(
        _make_cp_scan_core(cp.axis, cp.size, segmented=seg is not None),
        mesh=cp.mesh, in_specs=in_specs, out_specs=out_specs,
        check_rep=False)
    o, m_f, u_f, w_f = fn(*operands)
    o = o[..., :n, :]
    if seg is not None:
        o = jnp.where((seg[..., :n] != 0)[..., None], o, 0.0)
    return o.astype(v.dtype), ScanState(m=m_f, u=u_f, w=w_f)


# ---------------------------------------------------------------------------
# Ring flash attention (softmax mode)
# ---------------------------------------------------------------------------


def _expand_kv(x: jax.Array, n_heads: int) -> jax.Array:
    """(B, N, G, d) -> (B, N, H, d); head h reads kv head h // (H/G)."""
    b, n, g, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, n, g, n_heads // g, d))
    return x.reshape(b, n, n_heads, d)


def _ring_flash_local(q, k, v, lens, axis, axis_size, causal, window, scale,
                      seg=None):
    """Per-shard ring flash: rotate K/V shards, fold blocks under ⊕.

    q: (B, Nl, H, d) local queries; k/v: (B, Nl, G, d) local keys/values;
    lens: (B,) int32 true lengths, replicated across the ring.  Step t folds
    the block attention of the local queries against the K/V shard currently
    held (shard ``idx - t mod P``, masked by *absolute* causal/window
    position AND by the true length — each rank derives its shard's valid
    span from ``lens`` and its absolute offset, so padded global tails and
    ragged batch rows contribute the ⊕ identity) into a running ``(m, u, w)``
    accumulator — the running logsumexp is ``m + log u``.  K/V rotate in
    their compact G-head layout, so the wire payload per step is O(Nl·G·d),
    and only P−1 of the P steps move data.

    ``seg``: optional (B, N_global) packed-segment ids, *replicated* —
    every rank slices its query rows' and the held shard's ids by absolute
    position, so the same-nonzero-id rule masks by absolute segment id
    regardless of which rank currently holds the keys (DESIGN.md §Packing).
    """
    idx = jax.lax.axis_index(axis)
    b, nl, h, d = q.shape
    q32 = q.astype(jnp.float32)
    q_pos = idx * nl + jnp.arange(nl)
    row_ok = (q_pos[None, :] < lens[:, None])[:, None, :, None]  # (B,1,nl,1)
    if seg is not None:
        q_seg = jax.lax.dynamic_slice_in_dim(seg, idx * nl, nl, 1)  # (B, nl)
        row_ok = row_ok & (q_seg != 0)[:, None, :, None]
    acc = ScanState(
        m=jnp.full((b, h, nl), NEG_INF, jnp.float32),
        u=jnp.zeros((b, h, nl), jnp.float32),
        w=jnp.zeros((b, h, nl, d), jnp.float32),
    )
    ring = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    k_cur, v_cur = k, v
    with _span("cp.ring_flash"):
        acc = _ring_flash_steps(q32, k_cur, v_cur, acc, idx, axis, axis_size,
                                ring, nl, h, q_pos, row_ok, lens, seg,
                                q_seg if seg is not None else None,
                                causal, window, scale)
    o = readout(acc)  # (B, H, Nl, d); empty rows (fully masked) read 0
    return jnp.swapaxes(o, 1, 2)


def _ring_flash_steps(q32, k_cur, v_cur, acc, idx, axis, axis_size, ring,
                      nl, h, q_pos, row_ok, lens, seg, q_seg,
                      causal, window, scale):
    """The P-step rotate-and-fold loop of :func:`_ring_flash_local`."""
    for step in range(axis_size):
        src = jnp.mod(idx - step, axis_size)  # shard id currently held
        k_pos = src * nl + jnp.arange(nl)
        kf = _expand_kv(k_cur, h).astype(jnp.float32)
        vf = _expand_kv(v_cur, h).astype(jnp.float32)
        srt = jnp.einsum("bqhd,bkhd->bhqk", q32, kf) * scale
        allowed = jnp.ones((nl, nl), bool)
        if causal:
            allowed = allowed & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            allowed = allowed & (k_pos[None, :] > q_pos[:, None] - window)
        lane_ok = (k_pos[None, :] < lens[:, None])[:, None, None, :]
        ok = allowed[None, None] & row_ok & lane_ok        # (B, 1|H, nl, nl)
        if seg is not None:
            k_seg = jax.lax.dynamic_slice_in_dim(seg, src * nl, nl, 1)
            ok = ok & (q_seg[:, :, None] == k_seg[:, None, :])[:, None]
        srt = jnp.where(ok, srt, NEG_INF)
        blk_m = jnp.max(srt, axis=-1)
        e = jnp.exp(srt - blk_m[..., None])
        e = jnp.where((blk_m == NEG_INF)[..., None], 0.0, e)  # empty block
        blk = ScanState(
            m=blk_m,
            u=jnp.sum(e, axis=-1),
            w=jnp.einsum("bhqk,bkhd->bhqd", e, vf),
        )
        acc = combine(acc, blk)
        if step != axis_size - 1:
            with _span("cp.ring_rotate"):
                k_cur, v_cur = jax.tree.map(
                    lambda x: jax.lax.ppermute(x, axis, ring),
                    (k_cur, v_cur))
    return acc


def cp_flash_mha(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    lengths: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
    cp: ContextParallel | None = None,
) -> jax.Array:
    """Context-parallel drop-in for ``kops.flash_mha`` (self-attention).

    q: (B, N, H, d); k/v: (B, N, G, d) — sequence-major framework layout,
    any N: the wrapper zero-pads the sequence dim up to the ``seq``-axis
    multiple and every rank masks by true length in-kernel (a zero-padded
    K/V is *not* an identity under softmax — the mask is what makes the
    padding free; DESIGN.md §Masking).  ``lengths``: optional (B,) int32
    per-row true lengths for ragged batches; defaults to N.
    ``segment_ids``: optional (B, N) packed-segment ids — replicated around
    the ring, masked by *absolute* position against each held K/V shard
    (id 0 = padding; DESIGN.md §Packing).  Falls back to the single-device
    flash op when no session is active.
    """
    cp = cp if cp is not None else current_cp()
    if cp is None or cp.size == 1:
        return _batch_island(
            cp, lambda q_, k_, v_, len_, seg_: kops.flash_mha(
                q_, k_, v_, causal=causal, window=window, scale=scale,
                q_lens=len_, kv_lens=len_, q_segment_ids=seg_,
                kv_segment_ids=seg_),
            q, k, v, lengths, segment_ids)
    b, n, _, d = q.shape
    if k.shape[1] != n:
        raise ValueError("ring flash is self-attention: Nq must equal Nk")
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    # Clamped to [0, n]: an oversized length would unmask the zero-padded
    # ring tail (same rule as the kernel wrapper's _as_lens).
    lens = (jnp.full((b,), n, jnp.int32) if lengths is None
            else jnp.clip(jnp.asarray(lengths, jnp.int32), 0, n))
    n_pad = _kflash.round_up(n, cp.size)
    seg = None
    if segment_ids is not None:
        # Replicated (B, N_pad) ids; global padding keeps the padding id 0.
        seg = _kflash._pad_dim(jnp.asarray(segment_ids, jnp.int32), n_pad, 1)
    if n_pad != n:
        widths = [(0, 0), (0, n_pad - n), (0, 0), (0, 0)]
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)

    bax = cp.batch_axis(b)
    spec = P(bax, cp.axis, None, None)
    axis, size, scale_f = cp.axis, cp.size, float(scale)

    if seg is None:
        def local(q_, k_, v_, lens_):
            return _ring_flash_local(q_, k_, v_, lens_, axis, size, causal,
                                     window, scale_f)

        fn = shard_map(local, mesh=cp.mesh,
                       in_specs=(spec, spec, spec, P(bax)),
                       out_specs=spec, check_rep=False)
        return fn(q, k, v, lens)[:, :n].astype(v.dtype)

    def local_seg(q_, k_, v_, lens_, seg_):
        return _ring_flash_local(q_, k_, v_, lens_, axis, size, causal,
                                 window, scale_f, seg=seg_)

    fn = shard_map(local_seg, mesh=cp.mesh,
                   in_specs=(spec, spec, spec, P(bax), P(bax, None)),
                   out_specs=spec, check_rep=False)
    return fn(q, k, v, lens, seg)[:, :n].astype(v.dtype)
