"""Random weights drawn from ``--seed``, leaf by leaf, by name and layer.

Every leaf is ``std * normal(key(seed, name, layer))`` in float32, or ones
for a norm scale, rounded to the type it is served in.  The program's
parameters are built from these draws in one jitted call on the device
(:func:`program_tree`); the plain reference draws the same leaves again,
one layer at a time (:func:`layer_weights`), so it takes nothing that the
program has made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# Init scales (the configuration files list them under "assumed").
EMBED_STD = 0.02
QUERY_STD = 1.0


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any seed in [0, 2**63), not only those 32 bits hold."""
    if not 0 <= seed < 2 ** 63:
        raise ValueError(f"seed {seed} outside [0, 2**63)")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def layer_leaves(cfg: dict) -> dict:
    """name -> (shape, std or "ones") of one Aaren decoder layer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, g, k = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    return {
        "norm1.scale": ((d,), "ones"),
        "mixer.query": ((d,), QUERY_STD),
        "mixer.wq": ((d, h, k), d ** -0.5),
        "mixer.wk": ((d, g, k), d ** -0.5),
        "mixer.wv": ((d, g, k), d ** -0.5),
        "mixer.wo": ((h, k, d), (h * k) ** -0.5),
        "norm2.scale": ((d,), "ones"),
        "mlp.wi_gate": ((d, f), d ** -0.5),
        "mlp.wi_up": ((d, f), d ** -0.5),
        "mlp.wo": ((f, d), f ** -0.5),
    }


def top_leaves(cfg: dict) -> dict:
    """name -> (shape, std or "ones") of the leaves outside the layers."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed.table": ((v, d), EMBED_STD),
        "final_norm.scale": ((d,), "ones"),
        "unembed.kernel": ((d, v), d ** -0.5),
    }


def draw(key: jax.Array, name: str, layer, shape, init) -> jax.Array:
    """One leaf in float32.  ``layer`` may be traced (vmapped over layers)."""
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    return init * jax.random.normal(k, shape, jnp.float32)


def served(x: jax.Array, dtype) -> jax.Array:
    """``x`` rounded to the type it is served in, back in float32."""
    return x.astype(dtype).astype(jnp.float32)


def layer_weights(cfg: dict, key: jax.Array, layer) -> dict:
    """All leaves of one layer, as served, in float32 (for the reference)."""
    dt = jnp.dtype(cfg["dtype"])
    return {n: served(draw(key, n, layer, s, i), dt)
            for n, (s, i) in layer_leaves(cfg).items()}


def top_weights(cfg: dict, key: jax.Array) -> dict:
    dt = jnp.dtype(cfg["dtype"])
    return {n: served(draw(key, n, 0, s, i), dt)
            for n, (s, i) in top_leaves(cfg).items()}


def leaf_name(path) -> tuple[str, bool]:
    """(canonical name, stacked over layers?) of a program parameter path."""
    keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
    if keys and keys[0] == "periods":
        return ".".join(keys[1:]), True
    if keys and keys[0] == "rest":
        raise ValueError("unstacked remainder layers are not supported")
    return ".".join(keys), False


def program_tree(abstract, cfg: dict, key: jax.Array):
    """The program's parameter tree (shapes from ``abstract``), filled from
    the draws.  Traceable: call it inside one ``jax.jit``."""
    per_layer, top = layer_leaves(cfg), top_leaves(cfg)

    def leaf(path, sds):
        name, stacked = leaf_name(path)
        shape, init = (per_layer if stacked else top)[name]
        if stacked:
            if tuple(sds.shape[1:]) != shape:
                raise ValueError(f"{name}: program shape {sds.shape} vs "
                                 f"(layers,) + {shape}")
            val = jax.vmap(lambda l: draw(key, name, l, shape, init))(
                jnp.arange(sds.shape[0]))
        else:
            if tuple(sds.shape) != shape:
                raise ValueError(f"{name}: program shape {sds.shape} vs "
                                 f"{shape}")
            val = draw(key, name, 0, shape, init)
        return val.astype(sds.dtype)

    return jax.tree_util.tree_map_with_path(leaf, abstract)


def leaf_norms(tree, cfg: dict, minus_key: jax.Array | None = None) -> dict:
    """{(name, layer): L2 norm in float32} of a program parameter tree.

    With ``minus_key``, of the tree minus the draws of that key (the change
    of the parameters since they were drawn).  Traceable.
    """
    per_layer, top = layer_leaves(cfg), top_leaves(cfg)
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name, stacked = leaf_name(path)
        shape, init = (per_layer if stacked else top)[name]
        for layer in range(x.shape[0] if stacked else 1):
            v = (x[layer] if stacked else x).astype(jnp.float32)
            if minus_key is not None:
                v = v - draw(minus_key, name, layer, shape, init).astype(
                    x.dtype).astype(jnp.float32)
            out[(name, layer)] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


def leaf_arrays(tree, scale: float = 1.0) -> dict:
    """{(name, layer): float32 array on the host} of a program
    parameter-shaped tree, times ``scale``."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(
            jax.device_get(tree))[0]:
        name, stacked = leaf_name(path)
        x = np.asarray(x, np.float32)
        for layer in range(x.shape[0] if stacked else 1):
            out[(name, layer)] = (x[layer] if stacked else x) * scale
    return out
