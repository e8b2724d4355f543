"""Blockwise symmetric int8 quantization as a Pallas TPU kernel.

Used by the burst-buffer checkpoint path: optimizer moments are quantized
(f32 -> int8 + f32 scale per 2048-element block) before they enter the burst
buffer, halving its ingress volume. Pure VPU work; tiles are (rows x 2048) so
the reduction (max|x|) runs along lanes.

Tiling: ``rows`` is a multiple of 32 (the int8 sublane tile), and the block
count is padded up to a multiple of ``rows`` with zero blocks that are sliced
off again. Scales travel as a 2-D ``(blocks, 1)`` array, whose block
``(rows, 1)`` the TPU compiler accepts; callers see a flat ``(blocks,)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INT8_SUBLANES = 32


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                    # (rows, block)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int32).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]).astype(x_ref.dtype)


def _tiling(nb: int, rows_per_tile: int):
    """(rows per tile, padded block count) for ``nb`` blocks."""
    rows = min(rows_per_tile, pl.cdiv(nb, INT8_SUBLANES) * INT8_SUBLANES)
    return rows, pl.cdiv(nb, rows) * rows


def quantize_blockwise_pallas(x, *, block=2048, rows_per_tile=64,
                              interpret=False):
    """x: flat (N,), N % block == 0 -> (q int8 (N,), scales f32 (N/block,))."""
    n = x.shape[0]
    if n % block:
        raise ValueError(f"length {n} is not a multiple of block {block}")
    nb = n // block
    rows, nbp = _tiling(nb, rows_per_tile)
    xb = jnp.pad(x.reshape(nb, block), ((0, nbp - nb), (0, 0)))
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nbp // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nbp, block), jnp.int8),
                   jax.ShapeDtypeStruct((nbp, 1), jnp.float32)],
        interpret=interpret,
    )(xb)
    return q[:nb].reshape(n), s[:nb, 0]


def dequantize_blockwise_pallas(q, scale, *, block=2048, rows_per_tile=64,
                                out_dtype=jnp.float32, interpret=False):
    n = q.shape[0]
    nb = n // block
    rows, nbp = _tiling(nb, rows_per_tile)
    qb = jnp.pad(q.reshape(nb, block), ((0, nbp - nb), (0, 0)))
    sb = jnp.pad(scale.reshape(nb, 1), ((0, nbp - nb), (0, 0)))
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(nbp // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, block), out_dtype),
        interpret=interpret,
    )(qb, sb)
    return x[:nb].reshape(n)
