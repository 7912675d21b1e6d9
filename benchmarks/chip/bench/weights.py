"""Weights drawn from the run seed, by leaf name.

Every leaf is named by its path (``stack/blocks/0/attn/wq/w``) and drawn
from a key folded from the seed and that name; a leaf stacked over
layers draws each layer from the layer's own key.  The harness builds
the program's parameter tree with ``make_tree`` in one jitted call on
the device; the reference draws the same leaves, layer by layer, with
``leaf``.  Both round to the served dtype first, so they hold the same
numbers.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

STACK_PREFIX = "stack/blocks/"


def seed_key_data(seed: int) -> np.ndarray:
    """The uint32[2] key data of a whole seed in [0, 2**64)."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def path_name(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
    return "/".join(parts)


def kind_of(name: str) -> str:
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        return "norm"
    if last == "b":
        return "bias"
    return "matrix"


def name_tag(name: str) -> int:
    """The number a leaf's name folds into the seed's key."""
    return zlib.crc32(name.encode())


def leaf(key_data, name: str, shape, std: dict, dtype, layer=None):
    """One leaf (one layer of it where ``layer`` is given), rounded to
    ``dtype``.  ``std`` maps the leaf kind to its standard deviation."""
    return draw(key_data, name_tag(name), kind_of(name), shape, std, dtype,
                layer)


def draw(key_data, tag, kind: str, shape, std: dict, dtype, layer=None):
    """``leaf`` by the name's tag and kind; ``tag`` and ``layer`` may be
    traced, so that one program draws the leaves of many layers."""
    key = jax.random.fold_in(jax.random.wrap_key_data(key_data), tag)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    s = std[kind]
    if s == 0:
        return jnp.zeros(shape, dtype)
    return (s * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_tree(key_data, specs, std: dict):
    """A tree shaped like ``specs`` (ShapeDtypeStructs), drawn by name.
    Leaves under ``stack/blocks/`` are stacked over their leading axis."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    out = []
    for path, s in flat:
        name = path_name(path)
        if name.startswith(STACK_PREFIX):
            layers = jnp.arange(s.shape[0])
            out.append(jax.vmap(
                lambda l, _n=name, _s=s: leaf(key_data, _n, _s.shape[1:],
                                              std, _s.dtype, l))(layers))
        else:
            out.append(leaf(key_data, name, s.shape, std, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def named_leaves(tree) -> dict:
    """``{name: leaf}`` of a tree, by the same naming as ``make_tree``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_name(p): x for p, x in flat}
