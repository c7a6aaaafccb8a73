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
}


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.configs import get_config

    over = {FIELDS[k]: cfg[k] for k in FIELDS if k in cfg}
    over["compute_dtype"] = cfg["dtype"]
    if "remat" in cfg:
        over["remat"] = cfg["remat"]
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
