"""RG-LRU linear recurrence as a Pallas TPU kernel (RecurrentGemma/Griffin).

h_t = a_t * h_{t-1} + gx_t, elementwise over the channel dim. The gates
(a_t, gx_t) are computed outside (einsum-friendly); the kernel fuses the
sequential scan so the carry never leaves VMEM.

Grid: (batch, d_blocks, s_blocks) — the trailing seq dimension runs
sequentially on TPU, so the (1, block_d) carry persists in VMEM scratch
across seq blocks. h0 and h_last travel as (B, 1, D) so that their
(1, 1, block_d) blocks span the whole second-minor dim, which the TPU
compiler requires for any batch size. Inside a block the time loop is a
fori_loop over rows of f32 VMEM scratch copies of the block (the TPU
compiler indexes refs at a dynamic row, not values): pure VPU work, one HBM
read per input element and one write per output element (memory-bound
optimal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rg_lru_kernel(a_ref, gx_ref, h0_ref, h_ref, hlast_ref, carry,
                   a_scr, gx_scr, h_scr, *, block_s):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        carry[...] = h0_ref[0].astype(jnp.float32)

    a_scr[...] = a_ref[0].astype(jnp.float32)          # (block_s, block_d)
    gx_scr[...] = gx_ref[0].astype(jnp.float32)

    def step(t, h):                                     # h: (1, block_d)
        row = pl.ds(t, 1)
        h = a_scr[row, :] * h + gx_scr[row, :]
        h_scr[row, :] = h
        return h

    h = jax.lax.fori_loop(0, block_s, step, carry[...])
    carry[...] = h
    h_ref[0] = h_scr[...].astype(h_ref.dtype)

    @pl.when(si == ns - 1)
    def _final():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def rg_lru_pallas(a, gx, h0=None, *, block_s=256, block_d=128,
                  interpret=False):
    """a, gx: (B, S, D); h0: (B, D) or None -> (h (B,S,D), h_last (B,D))."""
    b, s, d = a.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), a.dtype)
    block_s = min(block_s, s)
    block_d = min(block_d, d)
    assert s % block_s == 0 and d % block_d == 0, (s, d, block_s, block_d)

    grid = (b, d // block_d, s // block_s)
    kernel = functools.partial(_rg_lru_kernel, block_s=block_s)

    h, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda b_, di, si: (b_, si, di)),
            pl.BlockSpec((1, block_s, block_d), lambda b_, di, si: (b_, si, di)),
            pl.BlockSpec((1, 1, block_d), lambda b_, di, si: (b_, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda b_, di, si: (b_, si, di)),
            pl.BlockSpec((1, 1, block_d), lambda b_, di, si: (b_, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d), a.dtype),
            jax.ShapeDtypeStruct((b, 1, d), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)]       # carry
        + [pltpu.VMEM((block_s, block_d), jnp.float32)] * 3,         # a, gx, h
        interpret=interpret,
    )(a, gx, h0.reshape(b, 1, d))
    return h, hlast.reshape(b, d)
