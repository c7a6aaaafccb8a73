"""Residual blocks: (norm → mixer → +) (norm → mlp → +), three eval modes.

A block is described by a signature ``(mixer, mlp)`` drawn from the config's
pattern.  All mixers share the interface defined in ``models/attention.py``;
decode states are per-mixer pytrees (Aaren ScanState / KV ring cache / RG-LRU
state / SSD state).  ``block_sequence`` optionally returns the decode state
(prefill); in pure training mode callers pass ``collect_state=False`` so the
scan carries no cache tensors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssd as ssd_mod
from repro.models.layers import (
    apply_gelu_mlp,
    apply_norm,
    apply_swiglu,
    gelu_mlp_specs,
    norm_specs,
    swiglu_specs,
)
from repro.sharding import constrain

Sig = tuple[str, str]

# Residual-stream logical axes (shared with models/lm.py).  Under context
# parallelism the `seq` entry maps the length dim to the `seq` mesh axis, so
# re-asserting it at the mixer/MLP seams keeps GSPMD from round-tripping the
# residual stream through a gathered layout between the sharded mixer island
# and the position-wise MLP.
RESIDUAL_AXES = ("batch", "seq", "act_embed")

ZERO_AUX = {"load_balance_loss": 0.0, "dropped_frac": 0.0}


def block_specs(sig: Sig, cfg: ArchConfig) -> dict:
    mixer, mlp = sig
    specs = {"norm1": norm_specs(cfg.d_model, cfg.norm)}
    if mixer in ("attn", "attn_local"):
        specs["mixer"] = attn.attn_proj_specs(cfg, with_query_token=False)
    elif mixer == "aaren":
        specs["mixer"] = attn.attn_proj_specs(cfg, with_query_token=True)
    elif mixer == "rglru":
        specs["mixer"] = rglru_mod.rglru_specs(cfg)
    elif mixer == "ssd":
        specs["mixer"] = ssd_mod.ssd_specs(cfg)
    else:
        raise ValueError(mixer)
    if mlp != "none":
        specs["norm2"] = norm_specs(cfg.d_model, cfg.norm)
        if mlp == "swiglu":
            specs["mlp"] = swiglu_specs(cfg.d_model, cfg.d_ff)
        elif mlp == "gelu":
            specs["mlp"] = gelu_mlp_specs(cfg.d_model, cfg.d_ff)
        elif mlp == "moe":
            specs["mlp"] = moe_mod.moe_specs(cfg)
        else:
            raise ValueError(mlp)
    return specs


def block_state_init(sig: Sig, cfg: ArchConfig, batch: int, cache_len: int):
    mixer = sig[0]
    if mixer == "aaren":
        return attn.aaren_state_init(cfg, batch)
    if mixer == "attn":
        return attn.softmax_state_init(cfg, batch, cache_len)
    if mixer == "attn_local":
        return attn.softmax_state_init(cfg, batch, min(cfg.window, cache_len))
    if mixer == "rglru":
        return rglru_mod.rglru_state_init(cfg, batch)
    if mixer == "ssd":
        return ssd_mod.ssd_state_init(cfg, batch)
    raise ValueError(mixer)


def block_state_specs(sig: Sig, cfg: ArchConfig, batch: int, cache_len: int):
    mixer = sig[0]
    if mixer == "aaren":
        return attn.aaren_state_specs(cfg, batch)
    if mixer == "attn":
        return attn.softmax_state_specs(cfg, batch, cache_len)
    if mixer == "attn_local":
        return attn.softmax_state_specs(cfg, batch, min(cfg.window, cache_len))
    if mixer == "rglru":
        return rglru_mod.rglru_state_specs(cfg, batch)
    if mixer == "ssd":
        return ssd_mod.ssd_state_specs(cfg, batch)
    raise ValueError(mixer)


def block_state_axes(sig: Sig, cfg: ArchConfig):
    """Logical-axis tree mirroring :func:`block_state_specs` (for sharding).

    Leaves are **lists** of logical axis names (lists, so that pytree
    containers like the ScanState NamedTuple are not mistaken for leaves);
    consumed by ``repro.sharding.spec_for_axes`` when the dry-run/serving
    shards decode states across the mesh.
    """
    mixer = sig[0]
    if mixer == "aaren":
        # ScanState(m, u, w): (B, H), (B, H), (B, H, d)
        from repro.core.scan_attention import ScanState

        return ScanState(
            m=["batch", "act_heads"],
            u=["batch", "act_heads"],
            w=["batch", "act_heads", None],
        )
    if mixer in ("attn", "attn_local"):
        return {"k": ["batch", None, "kv_heads", None],
                "v": ["batch", None, "kv_heads", None],
                "index": []}
    if mixer == "rglru":
        return {"h": ["batch", "rnn"], "conv": ["batch", None, "rnn"]}
    if mixer == "ssd":
        return {"s": ["batch", "ssm_heads", None, None],
                "conv": ["batch", None, "ssm_conv"]}
    raise ValueError(mixer)


AXES_IS_LEAF = lambda x: isinstance(x, list)  # noqa: E731


def _apply_mixer_sequence(p, h, sig, cfg, cache_len, segment_ids=None,
                          positions=None, lengths=None):
    mixer = sig[0]
    if mixer == "aaren":
        return attn.aaren_sequence(p, h, cfg, segment_ids=segment_ids,
                                   lengths=lengths)
    if mixer == "attn":
        return attn.softmax_sequence(p, h, cfg, window=None,
                                     cache_len=cache_len,
                                     segment_ids=segment_ids,
                                     positions=positions, lengths=lengths)
    if mixer == "attn_local":
        return attn.softmax_sequence(p, h, cfg, window=cfg.window,
                                     cache_len=min(cfg.window, cache_len),
                                     segment_ids=segment_ids,
                                     positions=positions, lengths=lengths)
    if mixer in ("rglru", "ssd"):
        if segment_ids is not None or lengths is not None:
            raise ValueError(
                f"{mixer} has no packed-segment or ragged-length support: "
                "its recurrence has no maskable identity element")
        if mixer == "rglru":
            return rglru_mod.rglru_sequence(p, h, cfg)
        return ssd_mod.ssd_sequence(p, h, cfg)
    raise ValueError(mixer)


def _apply_mixer_step(p, h_t, state, sig, cfg):
    mixer = sig[0]
    if mixer == "aaren":
        return attn.aaren_step(p, h_t, state, cfg)
    if mixer == "attn":
        return attn.softmax_step(p, h_t, state, cfg, window=None)
    if mixer == "attn_local":
        return attn.softmax_step(p, h_t, state, cfg, window=cfg.window)
    if mixer == "rglru":
        return rglru_mod.rglru_step(p, h_t, state, cfg)
    if mixer == "ssd":
        return ssd_mod.ssd_step(p, h_t, state, cfg)
    raise ValueError(mixer)


def _apply_mixer_chunk(p, h, state, sig, cfg, mask):
    """Advance a mixer's carry state by one fixed-shape (B, C, D) chunk.

    Aaren folds all C positions in one prefix scan (masked positions are
    ⊕-identity).  RG-LRU/SSD carries advance strictly token-by-token — their
    conv windows and decays have no masked identity element — so those
    mixers require C == 1 (the engine enforces chunk == 1 for them).
    """
    mixer = sig[0]
    if mixer == "aaren":
        return attn.aaren_chunk(p, h, state, cfg, mask=mask)
    if mixer in ("rglru", "ssd"):
        if h.shape[1] != 1:
            raise ValueError(
                f"{mixer} carries advance one token at a time; chunked "
                f"prefill needs chunk == 1, got chunk = {h.shape[1]}")
        step = rglru_mod.rglru_step if mixer == "rglru" else ssd_mod.ssd_step
        return step(p, h, state, cfg)
    raise ValueError(
        f"chunked prefill needs a position-free carry; {mixer!r} has none")


def _apply_mlp(p, x, sig, cfg, want_aux: bool):
    """The training path's MLP (expert layers: the capacity dispatch)."""
    mlp = sig[1]
    if mlp == "none":
        return x, dict(ZERO_AUX)
    h = apply_norm(p["norm2"], x, cfg.norm)
    if mlp == "swiglu":
        return x + apply_swiglu(p["mlp"], h), dict(ZERO_AUX)
    if mlp == "gelu":
        return x + apply_gelu_mlp(p["mlp"], h), dict(ZERO_AUX)
    if mlp == "moe":
        y, aux = moe_mod.apply_moe(p["mlp"], h, cfg, return_aux=True)
        if not want_aux:
            aux = dict(ZERO_AUX)
        return x + y, aux
    raise ValueError(mlp)


def _serve_mlp(p, x, sig, cfg, mask=None, layer=None):
    """The serving paths' MLP: (x, expert counts or None).  Expert layers
    take the dropless dispatch, so no token is dropped."""
    if sig[1] == "moe":
        y, counts = moe_mod.apply_moe_dropless(
            p["mlp"], apply_norm(p["norm2"], x, cfg.norm), cfg, mask, layer)
        return x + y, counts
    return _apply_mlp(p, x, sig, cfg, want_aux=False)[0], None


def block_sequence(p: dict, x: jax.Array, sig: Sig, cfg: ArchConfig, *,
                   cache_len: int, collect_state: bool, want_aux: bool = True,
                   segment_ids: jax.Array | None = None,
                   positions: jax.Array | None = None,
                   lengths: jax.Array | None = None):
    """Full-sequence block.  Returns (x, state_or_None, aux).

    ``segment_ids``/``positions``: packed-sequence arrays (only the mixer
    consumes them — norms and MLPs are position-wise, so documents cannot
    leak into each other there); ``lengths``: ragged right-padded rows.
    """
    h = apply_norm(p["norm1"], x, cfg.norm)
    y, state = _apply_mixer_sequence(p["mixer"], h, sig, cfg, cache_len,
                                     segment_ids, positions, lengths)
    x = constrain(x + y, RESIDUAL_AXES)
    x, aux = _apply_mlp(p, x, sig, cfg, want_aux)
    x = constrain(x, RESIDUAL_AXES)
    return x, (state if collect_state else None), aux


def block_step(p: dict, x_t: jax.Array, state, sig: Sig, cfg: ArchConfig):
    """One-token decode.  Returns (x_t, new_state)."""
    h = apply_norm(p["norm1"], x_t, cfg.norm)
    y, new_state = _apply_mixer_step(p["mixer"], h, state, sig, cfg)
    x_t, _ = _serve_mlp(p, x_t + y, sig, cfg)
    return x_t, new_state


def block_chunk(p: dict, x: jax.Array, state, sig: Sig, cfg: ArchConfig, *,
                mask: jax.Array | None = None, layer=None):
    """Fixed-shape chunk through one block's carry.

    x: (B, C, D); mask: (B, C) valid-position flags (None = all valid).
    Norms and MLPs are position-wise, so padded positions cannot leak into
    valid ones; the mixer takes the mask, and an expert layer computes no
    pair of a padded position.  No token is dropped, so the output does
    not depend on C.  ``layer``: an expert layer's weights come stacked
    over the periods, read at this index.  Returns (x, new_state,
    counts): ``counts`` (2,) int32 for an expert layer
    (``moe.apply_moe_dropless``), else None.
    """
    h = apply_norm(p["norm1"], x, cfg.norm)
    y, new_state = _apply_mixer_chunk(p["mixer"], h, state, sig, cfg, mask)
    x, counts = _serve_mlp(p, x + y, sig, cfg, mask, layer)
    return x, new_state, counts
