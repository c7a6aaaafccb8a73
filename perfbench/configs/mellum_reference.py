"""Plain float32 reference of Mellum2-12B-A2.5B under the Aaren rewrite,
as one chip's share of an expert-parallel deployment.

Each of the 28 layers is the Aaren mixer of ``aaren_lm_reference`` (the
published 32 query heads and 4 kv heads of 128; no RoPE, no window, as the
configuration file notes) followed by a sparse MLP: a softmax router over
the published experts (``program.n_routed_experts``, 64) keeps the
``num_experts_per_tok`` most likely for each token and renormalises their
probabilities to sum to one (``norm_topk_prob``).  The chip holds
``num_experts`` of them (16), from ``program.expert_offset`` on; the
layer's output is the sum over the held experts of each one's SwiGLU MLP
times its gate, which is 0 for a token that did not choose it.  What the
experts on other chips would add is left out here as in the program.  The
experts are computed one by one over every token, a plain loop with no
sort and no kernel.

Written from the published config and this description in ``jax.numpy``,
with every product at ``Precision.HIGHEST`` (under
``jax.default_matmul_precision("highest")`` as well); it imports nothing
of the program.  ``precision="fp8"`` is the control of
``aaren_lm_reference``: every matrix product, the router's included,
takes its operands rounded to float8 e4m3.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lib.cell import reference

base = reference({"reference": "aaren_lm_reference"})


def share(cfg: dict) -> tuple[int, int, int]:
    """(experts the router scores, experts held, the first held)."""
    program = cfg.get("program", {})
    held = cfg["num_experts"]
    return (program.get("n_routed_experts", held), held,
            program.get("expert_offset", 0))


def layer_leaves(cfg: dict, layer) -> dict:
    """The dense reference's mixer and norms, the router and the held
    experts' leaves."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    width, held, _ = share(cfg)
    leaves = {n: v for n, v in base.layer_leaves(cfg, layer).items()
              if not n.startswith("mlp.")}
    leaves.update({
        "mlp.router": ((d, width), d ** -0.5),
        "mlp.wi_gate": ((held, d, f), d ** -0.5),
        "mlp.wi_up": ((held, d, f), d ** -0.5),
        "mlp.wo": ((held, f, d), f ** -0.5),
    })
    return leaves


top_leaves = base.top_leaves


def matmul_params(cfg: dict) -> int:
    """Parameters that multiply a token on this chip: keys, values,
    output, the router, the held experts a token is routed to (``k``
    times the held share, on average), and the unembedding."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, g, k = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    width, held, _ = share(cfg)
    f, top = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    per_layer = (2 * d * g * k + h * k * d + d * width
                 + 3 * d * f * top * held // width)
    return cfg["num_hidden_layers"] * per_layer + d * v


def experts(w, h, cfg, precision):
    """The held experts' gated sum for h (B, N, d)."""
    width, held, offset = share(cfg)
    probs = jax.nn.softmax(base.mm("bnd,de->bne", h, w["mlp.router"],
                                   precision), axis=-1)
    top, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(held):
        gate = jnp.sum(jnp.where(chosen == offset + e, top, 0.0), axis=-1)
        a = base.mm("bnd,df->bnf", h, w["mlp.wi_gate"][e], precision)
        b = base.mm("bnd,df->bnf", h, w["mlp.wi_up"][e], precision)
        out = base.mm("bnf,fd->bnd", jax.nn.silu(a) * b, w["mlp.wo"][e],
                      precision)
        y = y + gate[..., None] * out
    return y


def block(w, x, seg, cfg, precision):
    eps = cfg["rms_norm_eps"]
    x = x + base.aaren_mixer(w, base.rmsnorm(x, w["norm1.scale"], eps), seg,
                             cfg, precision)
    return x + experts(w, base.rmsnorm(x, w["norm2.scale"], eps), cfg,
                       precision), None


def served_logits(cfg, seed, tokens, seg, rows, cols, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return base.served_logits(cfg, seed, tokens, seg, rows, cols,
                                  precision, block=block)
