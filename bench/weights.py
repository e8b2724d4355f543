"""The benchmark's weights: a parameter tree made on the device from the seed.

The layout, leaf name ('/'-joined tree path) -> shape, is the caller's:
the ``param_shapes(cfg)`` of the plain reference that the configuration
names. This module holds only what every layout shares. The harness hands
the tree to the program, and the reference makes the same tree again from
the same seed: neither takes weights from the other. Leaves are drawn in
the sorted order of their names, each from its own split of the seed's
key: matrices from normal(0, initializer_range), the published
configuration's own initializer; norm scales are one and norm biases zero.
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp


def nest(flat: dict) -> dict:
    """{'a/b': x} -> {'a': {'b': x}}."""
    tree: dict = {}
    for name, leaf in flat.items():
        node = tree
        *head, last = name.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    return tree


def flatten(tree, prefix: str = "") -> dict:
    """The inverse of ``nest`` for nested dicts."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten(v, name))
        else:
            out[name] = v
    return out


def seed_key(seed: int, salt: str) -> jax.Array:
    """A PRNG key from a seed of any size and a purpose, so that the weights
    and other draws of one seed are independent."""
    digest = hashlib.sha256(f"{seed}:{salt}".encode()).digest()
    return jax.random.PRNGKey(int.from_bytes(digest[:4], "little"))


def _leaf(name: str, shape, key, std: float, dtype):
    if name.endswith("/scale"):
        return jnp.ones(shape, dtype)
    if name.endswith("/bias"):
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(cfg: dict, shapes: dict, key):
    """The tree of the layout ``shapes`` from ``key``, traceable, in the
    configuration's parameter dtype."""
    dtype = jnp.dtype(cfg["training"]["param_dtype"])
    std = float(cfg["initializer_range"])
    keys = jax.random.split(key, len(shapes))
    return nest({name: _leaf(name, shape, k, std, dtype)
                 for (name, shape), k in zip(sorted(shapes.items()), keys)})


def init_params(cfg: dict, shapes: dict, seed: int):
    """The seed's tree of the layout ``shapes``, made on the default device
    in one jitted call."""
    return jax.jit(lambda key: make_params(cfg, shapes, key))(
        seed_key(seed, "weights"))
