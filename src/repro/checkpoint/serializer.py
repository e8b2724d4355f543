"""Pytree <-> key-value serialization for burst-buffer checkpoints.

Each leaf of the train-state pytree becomes one logical segment of the
checkpoint "file" for the step; the key is the tree path (stable across
resharding — shards are keyed by logical position, which is what makes
elastic restore-on-a-different-mesh exact). Optionally leaves are quantized
to blockwise int8 *on device* (kernels/quantize) before the host fetch,
halving bytes into the burst buffer; f32 scales ride along. Exact dtypes are
restored on load (quantization is applied only to leaves explicitly allowed
by the policy — by default optimizer moments, never params/step counters).
"""
from __future__ import annotations

import json
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

QUANT_BLOCK = 2048


def tree_paths(tree) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(_path_str(p) for p in path)
        out.append((name, leaf))
    return out


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def default_quant_policy(path: str, leaf) -> bool:
    """Quantize optimizer moments only (m/v/vr/vc); never params or scalars."""
    if np.ndim(leaf) < 2 or leaf.size < QUANT_BLOCK:
        return False
    head = path.split("/", 1)[0]
    return head in ("opt_state",) and not path.endswith("step")


def serialize_leaf(leaf, quantize: bool) -> Tuple[bytes, dict]:
    """Returns (payload bytes, metadata dict)."""
    arr = np.asarray(jax.device_get(leaf))
    meta = {"shape": list(arr.shape), "dtype": str(arr.dtype),
            "quant": False}
    if not quantize:
        # bf16 has no numpy dtype name round-trip issue under ml_dtypes;
        # store raw bytes + dtype string
        return arr.tobytes(), meta
    flat = jnp.asarray(arr).reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % QUANT_BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    q, scales = kops.quantize_blockwise(flat, block=QUANT_BLOCK)
    qb = np.asarray(jax.device_get(q)).tobytes()
    sb = np.asarray(jax.device_get(scales), np.float32).tobytes()
    meta.update(quant=True, pad=int(pad), nq=len(qb), block=QUANT_BLOCK)
    return qb + sb, meta


def deserialize_leaf(payload: bytes, meta: dict):
    shape = tuple(meta["shape"])
    dtype = np.dtype(meta["dtype"]) if meta["dtype"] != "bfloat16" else None
    if not meta["quant"]:
        if meta["dtype"] == "bfloat16":
            import ml_dtypes
            arr = np.frombuffer(payload, dtype=ml_dtypes.bfloat16)
        else:
            arr = np.frombuffer(payload, dtype=dtype)
        return arr.reshape(shape)
    nq = meta["nq"]
    q = np.frombuffer(payload[:nq], dtype=np.int8)
    scales = np.frombuffer(payload[nq:], dtype=np.float32)
    x = kops.dequantize_blockwise(jnp.asarray(q), jnp.asarray(scales),
                                  block=meta["block"])
    x = np.asarray(jax.device_get(x))
    if meta["pad"]:
        x = x[:-meta["pad"]]
    if meta["dtype"] == "bfloat16":
        import ml_dtypes
        return x.reshape(shape).astype(ml_dtypes.bfloat16)
    return x.reshape(shape).astype(meta["dtype"])


def leaf_nbytes(leaf, quantize: bool) -> int:
    """Payload bytes ``serialize_leaf`` writes for a leaf of this shape and
    dtype (an array or a ShapeDtypeStruct)."""
    n = int(np.prod(leaf.shape))
    if not quantize:
        return n * np.dtype(leaf.dtype).itemsize
    nb = -(-n // QUANT_BLOCK)
    return nb * QUANT_BLOCK + 4 * nb


def tree_nbytes(tree, quant_policy: Optional[Callable] = None) -> int:
    """Checkpoint bytes of a tree, from shapes alone."""
    quant_policy = quant_policy or (lambda p, l: False)
    return sum(leaf_nbytes(leaf, quant_policy(name, leaf))
               for name, leaf in tree_paths(tree))


def serialize_tree(tree, quant_policy: Optional[Callable] = None
                   ) -> Tuple[Dict[str, bytes], dict]:
    """Returns ({key: payload}, manifest). Manifest records order, offsets
    (for the logical checkpoint file), and per-leaf metadata."""
    quant_policy = quant_policy or (lambda p, l: False)
    payloads: Dict[str, bytes] = {}
    manifest = {"leaves": [], "treedef": None}
    offset = 0
    for name, leaf in tree_paths(tree):
        data, meta = serialize_leaf(leaf, quant_policy(name, leaf))
        payloads[name] = data
        meta.update(name=name, offset=offset, nbytes=len(data))
        manifest["leaves"].append(meta)
        offset += len(data)
    manifest["total_bytes"] = offset
    return payloads, manifest


def deserialize_tree(target_tree, payloads: Dict[str, bytes], manifest: dict,
                     shardings: Optional[Dict[str, Any]] = None):
    """Rebuild arrays in the structure of target_tree (an example pytree,
    e.g. jax.eval_shape output or a freshly-initialized state).

    ``shardings`` maps leaf names to the sharding each is placed with,
    straight from the host; other leaves go to the default device."""
    metas = {m["name"]: m for m in manifest["leaves"]}
    shardings = shardings or {}
    flat, treedef = jax.tree_util.tree_flatten_with_path(target_tree)
    leaves = []
    for path, leaf in flat:
        name = "/".join(_path_str(p) for p in path)
        arr = deserialize_leaf(payloads[name], metas[name])
        leaves.append(jax.device_put(arr, shardings.get(name)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def manifest_bytes(manifest: dict) -> bytes:
    return json.dumps(manifest).encode()


def manifest_from_bytes(data: bytes) -> dict:
    return json.loads(data.decode())
