"""The benchmark's weights: a parameter tree made on the device from the seed.

The layout is that of a decoder-only language model whose layers are stacked
along a leading axis, as the program under test keeps them. The harness
hands the tree to the program, and the plain reference makes the same tree
again from the same seed: neither takes weights from the other. Matrices
are drawn from normal(0, initializer_range), the published configuration's
own initializer; norm scales are one and norm biases zero.
"""
from __future__ import annotations

import hashlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Shapes = Dict[str, Tuple[int, ...]]


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding: the vocabulary padded to a multiple of 256."""
    return -(-vocab // 256) * 256


def dims(cfg: dict) -> dict:
    """The sizes that the layout needs, from a configuration file."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // h), "f": cfg["intermediate_size"],
            "v": padded_vocab(cfg["vocab_size"]),
            "layers": cfg["num_hidden_layers"],
            "layernorm": cfg["norm"] == "layernorm",
            "gated": cfg["mlp_gated"], "tied": cfg["tie_word_embeddings"]}


def param_shapes(cfg: dict) -> Shapes:
    """Leaf name ('/'-joined tree path) -> shape."""
    z = dims(cfg)
    d, h, kv, hd, f, v, n = (z[k] for k in ("d", "h", "kv", "hd", "f", "v",
                                             "layers"))
    out: Shapes = {"embed/tokens": (v, d)}
    if not z["tied"]:
        out["embed/unembed"] = (d, v)
    norms = ("scale", "bias") if z["layernorm"] else ("scale",)
    for k in norms:
        out[f"final_norm/{k}"] = (d,)
    layer = "segments/seg0/0"
    for nm in ("norm1", "norm2"):
        for k in norms:
            out[f"{layer}/{nm}/{k}"] = (n, d)
    out[f"{layer}/attn/wq"] = (n, d, h, hd)
    out[f"{layer}/attn/wk"] = (n, d, kv, hd)
    out[f"{layer}/attn/wv"] = (n, d, kv, hd)
    out[f"{layer}/attn/wo"] = (n, h, hd, d)
    out[f"{layer}/mlp/w_up"] = (n, d, f)
    out[f"{layer}/mlp/w_down"] = (n, f, d)
    if z["gated"]:
        out[f"{layer}/mlp/w_gate"] = (n, d, f)
    return out


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


def make_params(cfg: dict, key):
    """The whole tree from ``key``, traceable, in the configuration's
    parameter dtype."""
    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg["training"]["param_dtype"])
    std = float(cfg["initializer_range"])
    keys = jax.random.split(key, len(shapes))
    return nest({name: _leaf(name, shape, k, std, dtype)
                 for (name, shape), k in zip(sorted(shapes.items()), keys)})


def init_params(cfg: dict, seed: int):
    """The seed's tree, made on the default device in one jitted call."""
    return jax.jit(lambda key: make_params(cfg, key))(
        seed_key(seed, "weights"))
