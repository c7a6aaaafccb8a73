"""Pallas TPU kernel: grouped matrix product over the experts held here.

The dropless expert layer (``models/moe.py``) sorts its (token, expert)
pairs by expert, so the rows of one expert are contiguous in ``lhs``.  This
kernel multiplies each held expert's rows by that expert's weights, and
nothing else: rows of experts that live on other chips, and rows past the
last group, are never computed.  It is MegaBlocks' grouped GEMM (Gale et
al., arXiv:2211.15841) in the form of JAX's Megablox ``gmm``: ``group_sizes``
counts the rows of every expert of the router, and ``rhs`` holds only the
``H`` experts ``group_offset .. group_offset + H - 1``.

Grid: one step per *visit*, a (held group, ``tm``-row tile) pair, in group
order.  A tile that two groups share is visited once per group, each visit
storing only its own rows, and consecutive visits of one tile keep the
output block in VMEM.  Consecutive visits of one group keep its weight
block, so each held expert's weights are read from HBM once per call — the
least the layer must read.  Blocks span the whole contraction and output
widths (``K``, ``N``), so ``lhs`` is read once too.

The weights may come stacked over layers, ``(L, H, K, N)``, with the
layer's index: the kernel then reads that layer's experts in place.  A
slice of the stack taken outside would be a copy of every expert matrix
(a custom call takes whole buffers), which costs twice what the kernel
reads.

With ``rhs_up`` the call is the fused SwiGLU front half:
``silu(lhs @ rhs) * (lhs @ rhs_up)``, both products accumulated in f32.

Rows outside the held groups are left unwritten: callers mask them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TM = 128          # rows a tile; the MXU's height on a v5e
VMEM_CAP = 100 * 2 ** 20  # v5e has 128 MiB of VMEM; leave the compiler room


def visits(group_sizes: jax.Array, group_offset: jax.Array, n_held: int,
           m: int, tm: int):
    """The grid's schedule: (group, tile, lo, hi) per visit, and the count.

    ``group`` is local to the held experts; ``lo``/``hi`` bound the rows the
    visit stores.  Arrays have the static length ``m // tm + n_held - 1``
    (every tile once, plus one shared tile per group boundary); entries
    past the count repeat a store-nothing visit.  At least one visit is
    scheduled, so the grid is never empty.
    """
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    lo = jax.lax.dynamic_slice(starts, (group_offset,), (n_held,))
    hi = jax.lax.dynamic_slice(ends, (group_offset,), (n_held,))
    first = lo // tm
    n_tiles = jnp.where(hi > lo, (hi - 1) // tm - first + 1, 0)
    cum = jnp.cumsum(n_tiles)
    total = cum[-1]
    n_visits = m // tm + n_held - 1
    v = jnp.arange(n_visits, dtype=jnp.int32)
    group = jnp.sum(v[:, None] >= cum[None, :], axis=1).astype(jnp.int32)
    group = jnp.minimum(group, n_held - 1)
    tile = first[group] + v - (cum[group] - n_tiles[group])
    live = v < total
    group = jnp.where(live, group, 0)
    tile = jnp.where(live, tile, 0)
    vlo = jnp.where(live, lo[group], 0)
    vhi = jnp.where(live, hi[group], 0)
    return (group, tile, vlo, vhi), jnp.maximum(total, 1)


def _expert_gmm(group_ref, tile_ref, lo_ref, hi_ref, layer_ref, x_ref, *refs,
                tm: int, swiglu: bool):
    if swiglu:
        w_ref, w_up_ref, out_ref = refs
    else:
        w_ref, out_ref = refs
    i = pl.program_id(0)
    x = x_ref[...]
    y = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    if swiglu:
        up = jnp.dot(x, w_up_ref[...], preferred_element_type=jnp.float32)
        y = y / (1.0 + jnp.exp(-y)) * up
    rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    keep = (rows >= lo_ref[i]) & (rows < hi_ref[i])
    out_ref[...] = jax.lax.select(
        keep, y, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def expert_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
               group_offset: jax.Array, rhs_up: jax.Array | None = None,
               layer: jax.Array | None = None, *, tm: int = DEFAULT_TM,
               interpret: bool = False) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g - group_offset]`` for each held group.

    lhs: (m, K), rows sorted by group; rhs, rhs_up: (H, K, N), the held
    groups' weights, or (L, H, K, N) stacked over layers with ``layer``
    () int32 naming one; group_sizes: (G,) int32 rows per group of all G
    (rows past their sum belong to no group); group_offset: () int32, the
    first held group.  Returns (m, N) in ``lhs``'s dtype; rows of groups
    not held, and rows past the groups, are unspecified.
    """
    if layer is None:
        rhs = rhs[None]
        rhs_up = None if rhs_up is None else rhs_up[None]
        layer = 0
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    m, k = lhs.shape
    _, n_held, _, n = rhs.shape
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    (group, tile, lo, hi), n_visits = visits(
        group_sizes, jnp.asarray(group_offset, jnp.int32), n_held, m_pad, tm)
    weights = [rhs] if rhs_up is None else [rhs, rhs_up]
    w_spec = pl.BlockSpec((None, None, k, n),
                          lambda i, g, t, lo, hi, ly: (ly[0], g[i], 0, 0))
    row_spec = lambda width: pl.BlockSpec(
        (tm, width), lambda i, g, t, lo, hi, ly: (t[i], 0))
    # Double-buffered blocks, plus the f32 products.
    item = lambda x: jnp.dtype(x.dtype).itemsize
    need = 2 * (tm * k * item(lhs) + tm * n * item(lhs)
                + sum(k * n * item(w) for w in weights)) + 3 * tm * n * 4
    out = pl.pallas_call(
        functools.partial(_expert_gmm, tm=tm, swiglu=rhs_up is not None),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_visits,),
            in_specs=[row_spec(k)] + [w_spec] * len(weights),
            out_specs=row_spec(n),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(max(need + (4 << 20), 16 << 20),
                                     VMEM_CAP))),
        interpret=interpret,
        name="_expert_gmm",
    )(group, tile, lo, hi, layer, lhs, *weights)
    return out[:m]
