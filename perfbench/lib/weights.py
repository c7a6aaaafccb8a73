"""Random weights drawn from ``--seed``, leaf by leaf, by name and layer.

Every leaf is ``std * normal(key(seed, name, layer))`` in float32, or ones
for a norm scale, rounded to the type it is served in.  Which leaves each
layer has, with their shapes and scales, is the table of the
configuration's plain reference module (``layer_leaves(cfg, layer)`` and
``top_leaves(cfg)``), so a configuration of another layout brings its
own.  The program's parameters are built from these draws in one jitted
call on the device (:func:`program_tree`); the plain reference draws the
same leaves again, one layer at a time (:func:`layer_weights`), so it
takes nothing that the program has made.

A program parameter path names its layer by where it sits
(:func:`leaf_layers`): ``periods[p]`` holds position ``p`` of the
repeating layer pattern, stacked over the periods, so its index ``i`` is
layer ``i * P + p``, with ``P`` positions in a period; ``rest[r]`` is
layer ``n_periods * P + r``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from lib.cell import reference

# Init scales (the configuration files list them under "assumed").
EMBED_STD = 0.02
QUERY_STD = 1.0


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any seed in [0, 2**63), not only those 32 bits hold."""
    if not 0 <= seed < 2 ** 63:
        raise ValueError(f"seed {seed} outside [0, 2**63)")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


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
    table = reference(cfg).layer_leaves(cfg, layer)
    return {n: served(draw(key, n, layer, s, i), dt)
            for n, (s, i) in table.items()}


def top_weights(cfg: dict, key: jax.Array) -> dict:
    dt = jnp.dtype(cfg["dtype"])
    return {n: served(draw(key, n, 0, s, i), dt)
            for n, (s, i) in reference(cfg).top_leaves(cfg).items()}


def layout(tree) -> tuple[int, int]:
    """(positions in a period, periods stacked) of a program parameter
    tree, or of any tree of its structure."""
    periods = tree.get("periods", ())
    if not periods:
        return 0, 0
    return len(periods), jax.tree.leaves(periods)[0].shape[0]


def leaf_layers(path, period: int, n_periods: int):
    """(name, layers, stacked) of a program parameter path.

    ``layers`` is None outside the layers; for a leaf stacked over the
    periods, the layer of each index of its leading axis; for a remainder
    layer, its one layer.
    """
    keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
    name = ".".join(keys[1:])
    if keys and keys[0] == "periods":
        p = path[1].idx
        return name, tuple(i * period + p for i in range(n_periods)), True
    if keys and keys[0] == "rest":
        return name, (n_periods * period + path[1].idx,), False
    return ".".join(keys), None, False


def plan(tree, cfg: dict) -> list:
    """[(name, layers, stacked, shape, init)] for each leaf of a program
    parameter tree, in its flattening order, from the reference's table.

    Raises, naming it, on a program leaf that the table lacks, on a table
    entry that the program lacks, and on a shape that differs.
    """
    ref = reference(cfg)
    n_layers = cfg["num_hidden_layers"]
    tables = {None: ref.top_leaves(cfg)}
    tables.update((layer, ref.layer_leaves(cfg, layer))
                  for layer in range(n_layers))
    period, n_periods = layout(tree)
    out, seen = [], set()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name, layers, stacked = leaf_layers(path, period, n_periods)
        entries = set()
        for layer in layers or (None,):
            if name not in tables.get(layer, {}):
                where = ("outside the layers" if layer is None
                         else f"layer {layer}")
                raise ValueError(
                    f"program leaf {jax.tree_util.keystr(path)} ({name}, "
                    f"{where}) is not in the reference's table")
            entries.add(tables[layer][name])
            seen.add((name, layer))
        if len(entries) != 1:
            raise ValueError(f"{name}: the stacked layers {layers} differ "
                             f"in the reference's table: {entries}")
        shape, init = entries.pop()
        want = ((len(layers),) if stacked else ()) + tuple(shape)
        if tuple(x.shape) != want:
            raise ValueError(f"{name}: program shape {tuple(x.shape)} vs "
                             f"{want} from the reference's table")
        out.append((name, layers, stacked, tuple(shape), init))
    lacking = sorted(
        f"{n} ({'outside the layers' if layer is None else layer})"
        for layer, table in tables.items() for n in table
        if (n, layer) not in seen)
    if lacking:
        raise ValueError("reference leaves the program lacks: "
                         + ", ".join(lacking))
    return out


def program_tree(abstract, cfg: dict, key: jax.Array):
    """The program's parameter tree (shapes from ``abstract``), filled from
    the draws.  Traceable: call it inside one ``jax.jit``."""

    def value(name, layers, stacked, shape, init):
        if stacked:
            return jax.vmap(lambda l: draw(key, name, l, shape, init))(
                jnp.asarray(layers))
        return draw(key, name, layers[0] if layers else 0, shape, init)

    flat, treedef = jax.tree_util.tree_flatten(abstract)
    return jax.tree_util.tree_unflatten(treedef, [
        value(*entry).astype(sds.dtype)
        for entry, sds in zip(plan(abstract, cfg), flat)])


def leaf_norms(tree, cfg: dict, minus_key: jax.Array | None = None) -> dict:
    """{(name, layer): L2 norm in float32} of a program parameter tree,
    layer 0 for a leaf outside the layers.

    With ``minus_key``, of the tree minus the draws of that key (the change
    of the parameters since they were drawn).  Traceable.
    """
    out = {}
    for (name, layers, stacked, shape, init), x in zip(
            plan(tree, cfg), jax.tree.leaves(tree)):
        for i, layer in enumerate(layers or (0,)):
            v = (x[i] if stacked else x).astype(jnp.float32)
            if minus_key is not None:
                v = v - draw(minus_key, name, layer, shape, init).astype(
                    x.dtype).astype(jnp.float32)
            out[(name, layer)] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


def leaf_arrays(tree, scale: float = 1.0) -> dict:
    """{(name, layer): float32 array on the host} of a program
    parameter-shaped tree, times ``scale``; layer 0 for a leaf outside
    the layers."""
    period, n_periods = layout(tree)
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(
            jax.device_get(tree))[0]:
        name, layers, stacked = leaf_layers(path, period, n_periods)
        x = np.asarray(x, np.float32)
        for i, layer in enumerate(layers or (0,)):
            out[(name, layer)] = (x[i] if stacked else x) * scale
    return out
