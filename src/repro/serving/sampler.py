"""Token samplers: pure functions (logits, key) -> token ids.

Samplers that are safe to trace (pure jnp/jax.random on their arguments,
no host effects) carry ``jit_safe = True``; the streaming engine then
samples every slot, vmapped, inside its jitted step, and the host reads
back only the token ids.  Custom samplers without the attribute (e.g.
recording samplers in tests) keep an eager per-row call on the step's
logits and see concrete keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def greedy_sampler(logits: jax.Array, key=None) -> jax.Array:
    """logits (B, 1, V) -> (B, 1) int32."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


greedy_sampler.jit_safe = True


def temperature_sampler(temperature: float = 1.0, top_k: int | None = None):
    def sample(logits: jax.Array, key: jax.Array) -> jax.Array:
        x = logits.astype(jnp.float32) / max(temperature, 1e-6)
        if top_k is not None:
            kth = jnp.sort(x, axis=-1)[..., -top_k][..., None]
            x = jnp.where(x < kth, -jnp.inf, x)
        b, n, v = x.shape
        toks = jax.random.categorical(key, x.reshape(b * n, v))
        return toks.reshape(b, n).astype(jnp.int32)

    sample.jit_safe = True
    return sample
