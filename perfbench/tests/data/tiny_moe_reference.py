"""Plain float32 reference of a tiny Aaren decoder LM with sparse experts.

Test data: the benchmark's tests add it, with ``tiny-moe.json``, to a copy
of the benchmark as new files, to show that a configuration of another
layer layout needs no edit of the harness.

Each layer is the Aaren mixer of ``aaren_lm_reference`` followed by a
mixture of ``num_experts`` SwiGLU experts of width
``moe_intermediate_size``.  A softmax router over the experts picks the
``num_experts_per_tok`` most likely for each token and renormalises their
probabilities to sum to one (``norm_topk_prob``); the layer adds the
chosen experts' outputs, each times its gate.  No token is dropped: the
configuration gives every expert room for every token.  The training loss
adds the Switch Transformer load-balance term (Fedus et al.,
arXiv:2101.03961) at weight 0.01: ``E * sum_e f_e P_e``, with ``f_e`` the
share of the (token, choice) pairs routed to expert ``e`` and ``P_e`` its
mean router probability, both over every position of the batch, padding
included, and the term averaged over the layers.  Every expert is
computed for every token, which a tiny model affords.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lib.cell import reference

base = reference({"reference": "aaren_lm_reference"})
AUX_WEIGHT = 0.01


def layer_leaves(cfg: dict, layer) -> dict:
    """The dense reference's mixer and norms, and the experts' leaves."""
    d, e, f = (cfg["hidden_size"], cfg["num_experts"],
               cfg["moe_intermediate_size"])
    leaves = {n: v for n, v in base.layer_leaves(cfg, layer).items()
              if not n.startswith("mlp.")}
    leaves.update({
        "mlp.router": ((d, e), d ** -0.5),
        "mlp.wi_gate": ((e, d, f), d ** -0.5),
        "mlp.wi_up": ((e, d, f), d ** -0.5),
        "mlp.wo": ((e, f, d), f ** -0.5),
    })
    return leaves


top_leaves = base.top_leaves


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply every token: keys, values, output, the
    router, the chosen experts, and the unembedding."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, g, k = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    e, top, f = (cfg["num_experts"], cfg["num_experts_per_tok"],
                 cfg["moe_intermediate_size"])
    per_layer = 2 * d * g * k + h * k * d + d * e + top * 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * v


def experts(w, h, cfg, precision):
    """(gated sum of the chosen experts, load-balance term) of h (B, N, d)."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(base.mm("bnd,de->bne", h, w["mlp.router"],
                                   precision), axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    picked = jax.nn.one_hot(chosen, e)                      # (B, N, k, E)
    gates = jnp.einsum("bnk,bnke->bne", top / jnp.sum(top, -1, keepdims=True),
                       picked)
    gate = base.mm("bnd,edf->bnef", h, w["mlp.wi_gate"], precision)
    up = base.mm("bnd,edf->bnef", h, w["mlp.wi_up"], precision)
    out = base.mm("bnef,efd->bned", jax.nn.silu(gate) * up, w["mlp.wo"],
                  precision)
    y = jnp.sum(gates[..., None] * out, axis=2)
    share = jnp.mean(jnp.sum(picked, axis=2), axis=(0, 1)) / k
    return y, e * jnp.sum(share * jnp.mean(probs, axis=(0, 1)))


def block(w, x, seg, cfg, precision):
    eps = cfg["rms_norm_eps"]
    x = x + base.aaren_mixer(w, base.rmsnorm(x, w["norm1.scale"], eps), seg,
                             cfg, precision)
    y, load = experts(w, base.rmsnorm(x, w["norm2.scale"], eps), cfg,
                      precision)
    return x + y, load


def served_logits(cfg, seed, tokens, seg, rows, cols, precision="f32"):
    return base.served_logits(cfg, seed, tokens, seg, rows, cols, precision,
                              block=block)


def train(cfg, seed, batches, precision="f32"):
    return base.train(cfg, seed, batches, precision, block=block,
                      aux_weight=AUX_WEIGHT)
