"""The system under test: the program's model, built as a configuration
file states it, with weights drawn from the seed on the device."""

from __future__ import annotations

import jax

from lib import weights as W

# Configuration-file key -> the program's ArchConfig field.
FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab",
    "attn_mode": "attn_mode",
    "dtype": "param_dtype",
    "num_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "moe_intermediate_size": "moe_d_ff",
}
# Fields that the harness sets from other keys of the file.
SET_BY_FILE = frozenset(FIELDS.values()) | {"compute_dtype", "remat"}


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file.

    The published keys of :data:`FIELDS` set their fields; an optional
    ``"program"`` object sets any other field by its own name (lists are
    taken as tuples), such as ``pattern``, ``mlp_pattern`` or
    ``capacity_factor``.  It may not set a field that the file states
    under another key.
    """
    from repro.configs import get_config

    over = {FIELDS[k]: cfg[k] for k in FIELDS if k in cfg}
    over["compute_dtype"] = cfg["dtype"]
    if "remat" in cfg:
        over["remat"] = cfg["remat"]
    program = cfg.get("program", {})
    both = sorted(SET_BY_FILE & set(program))
    if both:
        raise ValueError(f"'program' sets {both}, which the configuration "
                         "file states under its own keys")
    over.update((k, tuple(v) if isinstance(v, list) else v)
                for k, v in program.items())
    arch = get_config(cfg["program_config"]).replace(**over)
    if cfg.get("tie_word_embeddings", False) != arch.tie_embeddings:
        raise ValueError("tie_word_embeddings differs from the program's")
    return arch


def build(cfg: dict, seed: int):
    """(ModelAPI, params on the device), the params in one jitted call."""
    from repro.models.factory import build as build_api

    api = build_api(arch_config(cfg))
    abstract = api.abstract()
    params = jax.jit(lambda k: W.program_tree(abstract, cfg, k))(
        W.base_key(seed))
    return api, params
