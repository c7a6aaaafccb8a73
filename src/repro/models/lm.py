"""Decoder-only language model: scan-over-layers, three eval modes.

The model is a repeating *period* of blocks (``cfg.pattern`` × one layer
each); full periods are evaluated with ``lax.scan`` over stacked parameters
(one HLO body regardless of depth — compile-time and HBM-layout win), with
``jax.checkpoint`` per period when ``cfg.remat == 'block'``.  Remainder layers
(n_layers % len(pattern)) are unrolled.

Entry points:

* :func:`lm_apply`      — tokens -> logits (+ optional decode states + aux);
  serves training (``collect_state=False``) and prefill (``True``);
* :func:`lm_decode_step`— one token through all layers against decode states,
  O(1) for Aaren/RG-LRU/SSD layers, O(cache) for softmax layers;
* :func:`lm_loss`       — next-token cross entropy (+ MoE aux losses).

VLM (phi3-vision): ``prefix_embeds`` (stub patch embeddings, already in
d_model) are prepended to the token embeddings; the loss masks them out.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import blocks
from repro.models.layers import (
    apply_embed,
    apply_norm,
    apply_unembed,
    embed_specs,
    norm_specs,
    unembed_specs,
)
from repro.models.param import ParamSpec, stack_specs
from repro.sharding import constrain

# Residual-stream logical axes; under a context-parallel mesh the `seq`
# entry shards the token dim across devices (see distributed/context.py).
ACT_AXES = blocks.RESIDUAL_AXES


def _sigs(cfg: ArchConfig) -> list[tuple[str, str]]:
    """Per-position (mixer, mlp) signatures after the Aaren rewrite."""
    return list(zip(cfg.effective_pattern(), cfg.mlp_pattern))


def lm_specs(cfg: ArchConfig) -> dict:
    """ParamSpec tree of the full LM."""
    n_periods, n_rest = cfg.layer_plan()
    sigs = _sigs(cfg)
    specs: dict[str, Any] = {
        "embed": embed_specs(cfg.vocab, cfg.d_model),
        "final_norm": norm_specs(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = unembed_specs(cfg.vocab, cfg.d_model)
    if n_periods:
        specs["periods"] = tuple(
            stack_specs(blocks.block_specs(sig, cfg), n_periods)
            for sig in sigs
        )
    if n_rest:
        specs["rest"] = tuple(
            blocks.block_specs(sigs[i % len(sigs)], cfg) for i in range(n_rest)
        )
    return specs


def _group_size(n_periods: int) -> int:
    """Largest divisor of n_periods <= sqrt(n_periods) x ~1.3 (sqrt-remat)."""
    best = 1
    for g in range(2, int(np.sqrt(n_periods) * 1.3) + 1):
        if n_periods % g == 0:
            best = g
    return best


def _period_fn(cfg, sigs, cache_len, collect_state, want_aux,
               segment_ids=None, positions=None, lengths=None):
    """One scan step: apply the whole period of blocks to x.

    The packed/ragged arrays are closed over — they become scan constants,
    shared by every period.
    """

    def fn(x, period_params):
        states, auxes = [], []
        for pos, sig in enumerate(sigs):
            x = constrain(x, ACT_AXES)
            x, st, aux = blocks.block_sequence(
                period_params[pos], x, sig, cfg,
                cache_len=cache_len, collect_state=collect_state,
                want_aux=want_aux, segment_ids=segment_ids,
                positions=positions, lengths=lengths)
            states.append(st)
            auxes.append(aux)
        aux_sum = jax.tree.map(lambda *a: sum(a), *auxes)
        return x, (tuple(states) if collect_state else None, aux_sum)

    return fn


def lm_apply(
    cfg: ArchConfig,
    params: dict,
    tokens: jax.Array,
    *,
    prefix_embeds: jax.Array | None = None,
    collect_state: bool = False,
    cache_len: int | None = None,
    want_aux: bool = True,
    segment_ids: jax.Array | None = None,
    positions: jax.Array | None = None,
    lengths: jax.Array | None = None,
):
    """tokens (B, N) -> logits (B, N_total, vocab) [f32].

    Returns (logits, states, aux).  ``states`` is None unless
    ``collect_state``; layout: {"periods": tuple-of-stacked-trees,
    "rest": tuple-of-trees}.  ``aux`` holds MoE load-balance scalars
    (averaged over layers).

    Packed batches (DESIGN.md §Packing): ``segment_ids``/``positions``
    (B, N) keep the packed documents independent in every mixer (segment
    masks / carry resets) and restart RoPE per document.  ``lengths`` (B,)
    instead marks ragged right-padded rows (one document each, true length
    per row) — the serving ragged-prefill path.  Both are incompatible with
    ``prefix_embeds`` (the prefix would shift every position).
    """
    n_periods, n_rest = cfg.layer_plan()
    sigs = _sigs(cfg)
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    if prefix_embeds is not None and (segment_ids is not None
                                      or lengths is not None):
        raise ValueError("prefix_embeds cannot combine with packed/ragged "
                         "batches (positions would shift)")

    x = apply_embed(params["embed"], tokens, compute_dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(compute_dtype), x], axis=1)
    n_total = x.shape[1]
    if cache_len is None:
        cache_len = n_total
    x = constrain(x, ACT_AXES)

    period = _period_fn(cfg, sigs, cache_len, collect_state, want_aux,
                        segment_ids=segment_ids, positions=positions,
                        lengths=lengths)
    use_group = cfg.remat == "group" and cfg.scan_layers and n_periods > 3
    if cfg.remat == "block" or (cfg.remat == "group" and not use_group):
        period = jax.checkpoint(period, prevent_cse=False)

    states: dict[str, Any] = {}
    aux_acc = dict(blocks.ZERO_AUX)
    n_aux_layers = 0
    if n_periods:
        if use_group:
            # sqrt-L two-level remat: outer scan over groups (checkpointed),
            # inner scan over periods within the group.  Backward stores only
            # n_groups group inputs + one group's per-period carries:
            # peak activations ~ (n_groups + g) x per-layer instead of
            # n_periods x per-layer.  Same recompute FLOPs as 'block'
            # (every layer re-run exactly once).  See DESIGN.md SPerf.
            g = _group_size(n_periods)
            ng = n_periods // g
            regrouped = jax.tree.map(
                lambda a: a.reshape((ng, g) + a.shape[1:]),
                params["periods"])

            def group_fn(xx, gp):
                return jax.lax.scan(period, xx, gp)

            group_fn = jax.checkpoint(group_fn, prevent_cse=False)
            x, (per_states, period_aux) = jax.lax.scan(group_fn, x, regrouped)
            flat2 = lambda a: a.reshape((ng * g,) + a.shape[2:])
            if collect_state:
                per_states = jax.tree.map(flat2, per_states)
            period_aux = jax.tree.map(flat2, period_aux)
        elif cfg.scan_layers:
            x, (per_states, period_aux) = jax.lax.scan(
                period, x, params["periods"])
        else:  # unrolled (dry-run cost probe; identical math)
            sts, auxs = [], []
            for i in range(n_periods):
                x, (st, aux) = period(
                    x, jax.tree.map(lambda a: a[i], params["periods"]))
                sts.append(st)
                auxs.append(aux)
            per_states = (jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
                          if collect_state else None)
            period_aux = jax.tree.map(lambda *xs: jnp.stack(xs), *auxs)
        if collect_state:
            states["periods"] = per_states
        aux_acc = jax.tree.map(
            lambda acc, a: acc + jnp.sum(a), aux_acc, period_aux)
        n_aux_layers += n_periods * len(sigs)
    if n_rest:
        rest_states = []
        for i in range(n_rest):
            sig = sigs[i % len(sigs)]
            x = constrain(x, ACT_AXES)
            x, st, aux = blocks.block_sequence(
                params["rest"][i], x, sig, cfg, cache_len=cache_len,
                collect_state=collect_state, want_aux=want_aux,
                segment_ids=segment_ids, positions=positions,
                lengths=lengths)
            rest_states.append(st)
            aux_acc = jax.tree.map(lambda a, b: a + b, aux_acc, aux)
        if collect_state:
            states["rest"] = tuple(rest_states)
        n_aux_layers += n_rest

    x = apply_norm(params["final_norm"], x, cfg.norm)
    x = constrain(x, ACT_AXES)
    logits = apply_unembed(
        params.get("unembed"), params["embed"], x, cfg.logit_softcap)
    logits = constrain(logits, ("batch", "seq", "act_vocab"))
    aux = jax.tree.map(lambda a: a / max(n_aux_layers, 1), aux_acc)
    return logits, (states if collect_state else None), aux


def lm_decode_step(cfg: ArchConfig, params: dict, token_t: jax.Array,
                   states: dict):
    """One-token decode.  token_t: (B, 1) int32 -> (logits (B,1,V), states).

    Aaren layers update in O(1); softmax layers in O(cache).  The state
    layout mirrors :func:`lm_apply(collect_state=True)`.
    """
    n_periods, n_rest = cfg.layer_plan()
    sigs = _sigs(cfg)
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = apply_embed(params["embed"], token_t, compute_dtype)

    new_states: dict[str, Any] = {}
    if n_periods:

        def step_fn(x_t, scanned):
            period_params, period_states = scanned
            outs = []
            for pos, sig in enumerate(sigs):
                x_t, st = blocks.block_step(
                    period_params[pos], x_t, period_states[pos], sig, cfg)
                outs.append(st)
            return x_t, tuple(outs)

        if cfg.scan_layers:
            x, per_states = jax.lax.scan(
                step_fn, x, (params["periods"], states["periods"]))
        else:
            sts = []
            for i in range(n_periods):
                x, st = step_fn(x, jax.tree.map(
                    lambda a: a[i], (params["periods"], states["periods"])))
                sts.append(st)
            per_states = jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
        new_states["periods"] = per_states
    if n_rest:
        rest_states = []
        for i in range(n_rest):
            sig = sigs[i % len(sigs)]
            x, st = blocks.block_step(
                params["rest"][i], x, states["rest"][i], sig, cfg)
            rest_states.append(st)
        new_states["rest"] = tuple(rest_states)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = apply_unembed(
        params.get("unembed"), params["embed"], x, cfg.logit_softcap)
    return logits, new_states


EXPERT_WEIGHTS = ("wi_gate", "wi_up", "wo")


def _hold_experts(period_params: tuple, sigs) -> tuple[tuple, list]:
    """(the period parameters without the expert weights, and per position
    the expert weights stacked over the periods, or None)."""
    moving, held = [], []
    for p, sig in zip(period_params, sigs):
        if sig[1] != "moe":
            moving.append(p)
            held.append(None)
            continue
        mlp = {n: v for n, v in p["mlp"].items() if n not in EXPERT_WEIGHTS}
        held.append({n: p["mlp"][n] for n in EXPERT_WEIGHTS})
        moving.append(dict(p, mlp=mlp))
    return tuple(moving), held


def _merge_counts(counts: list):
    """Expert counts of several layers: pairs summed, the straggler's max.
    ``counts`` holds (2,) or (L, 2) int32 arrays; None when it is empty."""
    if not counts:
        return None
    c = jnp.concatenate([jnp.reshape(x, (-1, 2)) for x in counts])
    return jnp.stack([jnp.sum(c[:, 0]), jnp.max(c[:, 1])])


def lm_prefill_chunk(cfg: ArchConfig, params: dict, tokens: jax.Array,
                     states: dict, *, length_mask: jax.Array | None = None):
    """Advance per-layer carries by one fixed-shape chunk of tokens.

    tokens: (B, C) int32; states: decode-state tree (layout of
    :func:`lm_decode_step`); length_mask: (B, C) bool, True at valid
    positions (a *prefix* per row — row i carries ``lengths[i]`` real tokens,
    the rest is padding).  Returns (logits (B, C, V) f32, new states), and
    for a model with expert layers also (2,) int32: the (token, expert)
    pairs of valid positions computed by the held experts over all
    layers, and the most any held expert took at one layer.

    This is the serving hot path: the chunk shape is static, so the engine
    traces exactly one step function per (B, C) and serves every prompt
    length through it — mid-prefill rows consume up to C prompt tokens,
    decoding rows carry one valid token, padded positions are ⊕-identity in
    the mixer scan (see :func:`repro.models.blocks.block_chunk`).  Logits at
    padded positions are garbage by construction; callers read row i at
    position ``lengths[i] - 1``.  C == 1 reproduces :func:`lm_decode_step`
    bit-for-bit on Aaren layers.
    """
    n_periods, n_rest = cfg.layer_plan()
    sigs = _sigs(cfg)
    expert_counts = "moe" in cfg.mlp_pattern
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = apply_embed(params["embed"], tokens, compute_dtype)

    new_states: dict[str, Any] = {}
    counts: list = []
    if n_periods:
        # Expert weights are not scanned: the grouped kernel reads each
        # layer's experts in place, given the period's index, where a scan
        # slice would copy them every tick.
        moving, held = _hold_experts(params["periods"], sigs)

        def chunk_fn(x_c, scanned):
            period_params, period_states, *index = scanned
            outs, cnts = [], []
            for pos, sig in enumerate(sigs):
                p = period_params[pos]
                if held[pos] is not None:
                    p = dict(p, mlp=dict(p["mlp"], **held[pos]))
                x_c, st, cnt = blocks.block_chunk(
                    p, x_c, period_states[pos], sig, cfg, mask=length_mask,
                    layer=index[0] if held[pos] is not None else None)
                outs.append(st)
                if cnt is not None:
                    cnts.append(cnt)
            if expert_counts:
                return x_c, (tuple(outs), _merge_counts(cnts))
            return x_c, tuple(outs)

        scanned = (moving, states["periods"])
        if any(h is not None for h in held):
            scanned += (jnp.arange(n_periods),)
        if cfg.scan_layers:
            x, ys = jax.lax.scan(chunk_fn, x, scanned)
        else:
            outs = []
            for i in range(n_periods):
                x, y = chunk_fn(x, jax.tree.map(lambda a: a[i], scanned))
                outs.append(y)
            ys = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        if expert_counts:
            ys, per_counts = ys
            counts.append(per_counts)
        new_states["periods"] = ys
    if n_rest:
        rest_states = []
        for i in range(n_rest):
            sig = sigs[i % len(sigs)]
            x, st, cnt = blocks.block_chunk(
                params["rest"][i], x, states["rest"][i], sig, cfg,
                mask=length_mask)
            rest_states.append(st)
            if cnt is not None:
                counts.append(cnt)
        new_states["rest"] = tuple(rest_states)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = apply_unembed(
        params.get("unembed"), params["embed"], x, cfg.logit_softcap)
    if expert_counts:
        return logits, new_states, _merge_counts(counts)
    return logits, new_states


def lm_state_specs(cfg: ArchConfig, batch: int, cache_len: int):
    """ShapeDtypeStruct tree of the decode state (dry-run, no allocation)."""
    n_periods, n_rest = cfg.layer_plan()
    sigs = _sigs(cfg)
    out: dict[str, Any] = {}

    def _stack_sds(tree, n):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)

    if n_periods:
        out["periods"] = tuple(
            _stack_sds(blocks.block_state_specs(sig, cfg, batch, cache_len),
                       n_periods)
            for sig in sigs)
    if n_rest:
        out["rest"] = tuple(
            blocks.block_state_specs(sigs[i % len(sigs)], cfg, batch,
                                     cache_len)
            for i in range(n_rest))
    return out


def lm_state_axes(cfg: ArchConfig):
    """Logical-axis tree mirroring :func:`lm_state_specs` (None = layer dim)."""
    n_periods, n_rest = cfg.layer_plan()
    sigs = _sigs(cfg)
    out: dict[str, Any] = {}

    def _stack_axes(tree):
        return jax.tree.map(lambda axes: [None] + list(axes), tree,
                            is_leaf=blocks.AXES_IS_LEAF)

    if n_periods:
        out["periods"] = tuple(
            _stack_axes(blocks.block_state_axes(sig, cfg)) for sig in sigs)
    if n_rest:
        out["rest"] = tuple(
            blocks.block_state_axes(sigs[i % len(sigs)], cfg)
            for i in range(n_rest))
    return out


def lm_state_batch_axes(cfg: ArchConfig):
    """Tree of ints mirroring the decode-state tree: the batch-axis index of
    every leaf (-1 if the leaf has no batch axis, e.g. a KV ring index).

    This is the *explicit* metadata the serving engine uses to address slot
    ``i`` of a batched state.  Inferring the axis from shapes (matching
    ``1`` vs ``n_slots``) is unsound: any state dimension that happens to
    equal ``n_slots`` — heads, layers, conv taps — is indistinguishable from
    the batch dimension by shape alone.
    """
    axes = lm_state_axes(cfg)
    return jax.tree.map(
        lambda a: a.index("batch") if "batch" in a else -1, axes,
        is_leaf=blocks.AXES_IS_LEAF)


def lm_state_take_slot(cfg: ArchConfig, states: dict, idx: jax.Array):
    """Extract slot ``idx`` of a batched decode-state tree.

    Returns a tree of the same structure whose every batched leaf keeps a
    size-1 batch axis (so the result round-trips through
    :func:`lm_state_put_slot` unchanged) — the serving prefix cache's
    carry-extraction primitive.  Leaves with no batch axis (``-1`` in
    :func:`lm_state_batch_axes`) are passed through untouched.  ``idx`` may
    be traced: the serving engine jits this once and gathers any slot.
    """
    axes = lm_state_batch_axes(cfg)

    def leaf(batched, ax):
        if ax < 0:
            return batched
        return jax.lax.dynamic_index_in_dim(batched, idx, axis=ax,
                                            keepdims=True)

    return jax.tree.map(leaf, states, axes)


def lm_state_put_slot(cfg: ArchConfig, states: dict, carry: dict,
                      mask: jax.Array):
    """Write a size-1-batch ``carry`` into every slot where ``mask`` is True.

    The injection twin of :func:`lm_state_take_slot`: a masked ``where``
    against the batched state, addressed by the same explicit batch-axis
    metadata the engine's ``reset`` uses (shape-matching heuristics break
    when a state dim equals ``n_slots``).  The carry's size-1 batch axis
    broadcasts across the masked slots.
    """
    axes = lm_state_batch_axes(cfg)
    n = mask.shape[0]

    def leaf(batched, one, ax):
        if ax < 0:
            return batched
        sel = mask.reshape((1,) * ax + (n,) + (1,) * (batched.ndim - ax - 1))
        return jnp.where(sel, one, batched)

    return jax.tree.map(leaf, states, carry, axes)


def lm_state_init(cfg: ArchConfig, batch: int, cache_len: int):
    """Concrete zero-initialised decode state (tests + serving)."""
    n_periods, n_rest = cfg.layer_plan()
    sigs = _sigs(cfg)
    out: dict[str, Any] = {}
    if n_periods:
        out["periods"] = tuple(
            jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape).copy(),
                blocks.block_state_init(sig, cfg, batch, cache_len))
            for sig in sigs)
    if n_rest:
        out["rest"] = tuple(
            blocks.block_state_init(sigs[i % len(sigs)], cfg, batch, cache_len)
            for i in range(n_rest))
    return out


def lm_loss(cfg: ArchConfig, params: dict, batch: dict,
            *, aux_weight: float = 0.01):
    """Next-token CE loss.  batch: {"tokens": (B,N), "loss_mask": (B,N)?,
    "prefix_embeds": (B,T,D)?, "segment_ids": (B,N)?, "positions": (B,N)?}.
    Returns (loss, metrics).

    Packed batches (``segment_ids`` present): the attention stack keeps the
    documents independent, and the loss must too — position ``t`` only
    scores its target ``t+1`` when both belong to the same real document
    (``seg[t] == seg[t+1] != 0``).  Without the guard, the last token of
    every document would be trained to predict the *next document's* first
    token, and padding would be scored on garbage logits.  The masked mean
    then sums exactly the per-document next-token terms an unpacked padded
    batch would — the parity tests pin this to ≤1e-5.
    """
    tokens = batch["tokens"]
    prefix = batch.get("prefix_embeds")
    seg = batch.get("segment_ids")
    logits, _, aux = lm_apply(
        cfg, params, tokens, prefix_embeds=prefix, collect_state=False,
        segment_ids=seg, positions=batch.get("positions"))
    if prefix is not None:  # VLM: score text positions only
        logits = logits[:, prefix.shape[1]:]
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    # Keep the per-token loss sequence-sharded under context parallelism
    # (N-1 may not divide the seq axis — the divisibility fallback then
    # replicates, which is still correct, just not free).
    nll = constrain(nll, ("batch", "seq"))
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(nll) if mask is None else mask[:, 1:].astype(nll.dtype)
    if seg is not None:  # cross-segment-safe: target must share the document
        seg = jnp.asarray(seg)
        same_doc = (seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0)
        mask = mask * same_doc.astype(nll.dtype)
    ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce + aux_weight * aux["load_balance_loss"]
    metrics = {"loss": loss, "ce": ce, **aux}
    return loss, metrics
