"""Mixture-of-Experts MLP: a softmax top-k router and two dispatches.

Both dispatches route alike: router logits in float32, a softmax over the
router's experts, the ``k`` most likely kept and their gates renormalised
to sum to one (``norm_topk_prob``), and each chosen expert a SwiGLU MLP.

* :func:`apply_moe_dropless` — the serving paths (``blocks.block_chunk``,
  ``blocks.block_step``).  No token is ever dropped, so a chunk's output
  does not depend on the chunk size.  The layer may hold a share of the
  router's experts (expert parallelism, ``ArchConfig.expert_offset``): it
  routes over all ``router_width`` of them, computes only the ``n_experts``
  it holds, and adds their gated outputs; pairs routed to experts on other
  chips add nothing here, and no exchange stands in for those chips.  The
  (token, expert) pairs are sorted by expert and each held expert
  multiplies only its own rows (``kernels.ops.expert_gmm``), rows padded
  to the static count of pairs so that one shape compiles once.
* :func:`apply_moe` — the training path (``blocks.block_sequence``):
  GShard's grouped dispatch.  Each batch row dispatches independently
  with its own capacity ``C = ceil(N · k / E · capacity_factor)``; tokens
  enter a ``(B, E·(C+1), d)`` buffer by a scatter over the sharded batch
  axis, overflow lands in a trash slot and is dropped (the residual path
  carries it, Switch semantics).  Expert weights carry the ``experts``
  logical axis (→ the ``model`` mesh axis), so the ``(B, E, C, d) ×
  (E, d, f)`` einsum lowers to the all-to-all + local GEMM pattern, at
  ``capacity_factor ×`` the active-expert FLOPs.  It holds every expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models.param import ParamSpec
from repro.obs.trace import span
from repro.sharding import constrain


def moe_specs(cfg: ArchConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    return {
        "router": ParamSpec((d, cfg.router_width),
                            ("embed", "experts_router")),
        "wi_gate": ParamSpec((e, d, f), ("experts", "embed", "moe_mlp")),
        "wi_up": ParamSpec((e, d, f), ("experts", "embed", "moe_mlp")),
        "wo": ParamSpec((e, f, d), ("experts", "moe_mlp", "embed")),
    }


def route(router: jax.Array, x: jax.Array, k: int):
    """(renormalised gates, expert ids, probabilities) of x (..., d).

    The logits are float32 products at ``Precision.HIGHEST``: the router is
    small, and bfloat16 passes would move near-ties between experts.
    """
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    return gates / jnp.sum(gates, axis=-1, keepdims=True), ids, probs


def apply_moe_dropless(p: dict, x: jax.Array, cfg: ArchConfig,
                       mask: jax.Array | None = None, layer=None):
    """x: (B, N, D) -> (y (B, N, D), counts (2,) int32).

    ``mask`` (B, N): valid positions (None: all).  Pairs of other positions
    are routed but never computed.  ``layer``: the expert weights come
    stacked over the layers of a scan, and this is the layer's index.
    ``counts``: the (token, expert) pairs of valid positions routed to the
    experts held here, and the most that any one held expert took (the
    straggler).
    """
    b, n, d = x.shape
    k, held, off = cfg.n_experts_per_tok, cfg.n_experts, cfg.expert_offset
    width = cfg.router_width
    t = b * n
    with span("moe.route"):
        xf = x.reshape(t, d)
        gates, ids, _ = route(p["router"], xf, k)               # (t, k)
        valid = (jnp.ones((t,), bool) if mask is None
                 else mask.reshape(t))[:, None]
        mine = valid & (ids >= off) & (ids < off + held)
        # Sort key: the expert, or `width` (after every expert) for pairs
        # of padded positions.
        key = jnp.where(valid, ids, width).reshape(t * k)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(width), axis=0,
                        dtype=jnp.int32)
        rows = xf[order // k]                                   # (t·k, d)
    with span("moe.experts"):
        h = kops.expert_gmm(rows, p["wi_gate"], sizes, off, p["wi_up"],
                            layer)
        out = kops.expert_gmm(h, p["wo"], sizes, off, layer=layer)
    with span("moe.combine"):
        where = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        got = out[where].reshape(t, k, d).astype(jnp.float32)
        # `where`, not a product: rows of experts not held are unwritten.
        y = jnp.sum(jnp.where(mine[..., None], got * gates[..., None], 0.0),
                    axis=1)
        load = jax.lax.dynamic_slice(sizes, (off,), (held,))
        counts = jnp.stack([jnp.sum(load), jnp.max(load)])
    return y.astype(x.dtype).reshape(b, n, d), counts


def expert_capacity(n_tokens_per_group: int, cfg: ArchConfig) -> int:
    ideal = n_tokens_per_group * cfg.n_experts_per_tok / cfg.n_experts
    return max(int(np.ceil(ideal * cfg.capacity_factor)), 1)


def apply_moe(p: dict, x: jax.Array, cfg: ArchConfig, *,
              return_aux: bool = False):
    """x: (B, N, D) -> (B, N, D) [+ aux dict with load-balancing loss]."""
    if cfg.router_width != cfg.n_experts:
        raise ValueError(
            "the capacity dispatch holds every expert; a share of "
            f"{cfg.n_experts} of {cfg.router_width} serves through "
            "apply_moe_dropless")
    b, n, d = x.shape
    k = cfg.n_experts_per_tok
    e = cfg.n_experts
    cap = expert_capacity(n, cfg)

    gate_vals, expert_ids, probs = route(p["router"], x, k)   # (b, n, k)

    # --- rank of each routed token within (row, expert) --------------------
    # Sort-based ranking (MegaBlocks-style): O(nk log nk) work on (b, nk)
    # int32 tensors.  The naive one-hot cumsum materialises (b, nk, E) int32
    # — ~17 GB/layer/microbatch at qwen3's E=128.
    flat_ids = expert_ids.reshape(b, n * k)                     # (b, nk)
    nk = n * k
    order = jnp.argsort(flat_ids, axis=1, stable=True)          # (b, nk)
    sorted_ids = jnp.take_along_axis(flat_ids, order, axis=1)
    idx = jnp.arange(nk, dtype=jnp.int32)[None, :]
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool), sorted_ids[:, 1:] != sorted_ids[:, :-1]],
        axis=1)
    seg_start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    rank_sorted = idx - seg_start                               # (b, nk)
    pos = jnp.zeros((b, nk), jnp.int32)
    pos = jax.vmap(lambda pp, oo, rr: pp.at[oo].set(rr))(
        pos, order, rank_sorted)
    keep = pos < cap
    # destination row in the (E, C+1) buffer; C is the trash slot
    dest = flat_ids * (cap + 1) + jnp.where(keep, pos, cap)     # (b, nk)

    # --- batched scatter into per-row expert buffers ------------------------
    xrep = jnp.repeat(x, k, axis=1)                             # (b, nk, d)
    buf = jnp.zeros((b, e * (cap + 1), d), x.dtype)
    buf = jax.vmap(lambda bb, dd, xx: bb.at[dd].set(xx))(buf, dest, xrep)
    buf = buf.reshape(b, e, cap + 1, d)[:, :, :cap, :]          # drop trash
    buf = constrain(buf, ("batch", "act_experts", None, None))

    # --- expert MLPs (SwiGLU), expert axis sharded over `model` ------------
    gate = jnp.einsum("becd,edf->becf", buf, p["wi_gate"].astype(buf.dtype))
    up = jnp.einsum("becd,edf->becf", buf, p["wi_up"].astype(buf.dtype))
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(buf.dtype) * up
    out = jnp.einsum("becf,efd->becd", h, p["wo"].astype(buf.dtype))
    out = constrain(out, ("batch", "act_experts", None, None))

    # --- gather back + weighted combine -------------------------------------
    pad = jnp.zeros((b, e, 1, d), out.dtype)                    # trash slot
    out_flat = jnp.concatenate([out, pad], axis=2).reshape(
        b, e * (cap + 1), d)
    # Replicate the expert axis before the gather: an expert-sharded gather
    # operand lowers to a masked gather + all-reduce of the whole (b, nk, d)
    # result; the explicit all-gather moves only the buffer.
    out_flat = constrain(out_flat, ("batch", None, None))
    yrep = jax.vmap(lambda oo, dd: oo[dd])(out_flat, dest)      # (b, nk, d)
    w = (gate_vals.reshape(b, n * k, 1).astype(out.dtype)
         * keep[..., None].astype(out.dtype))
    y = jnp.sum((yrep * w).reshape(b, n, k, d), axis=2)

    if not return_aux:
        return y
    # Switch-style load-balancing auxiliary loss.  Expert densities via
    # scatter-add (a (b, E) tensor) — not a (b, n, k, E) one-hot.
    counts = jax.vmap(
        lambda ids: jnp.zeros((e,), jnp.float32).at[ids].add(1.0))(flat_ids)
    density = jnp.sum(counts, axis=0) / (b * n * k)
    router_mean = jnp.mean(probs, axis=(0, 1))
    aux = {"load_balance_loss": e * jnp.sum(density * router_mean),
           "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return y, aux
