"""Fused blockwise attention (flash attention) as a Pallas TPU kernel.

TPU adaptation: the grid's trailing dimension iterates KV blocks sequentially
(TPU grids execute in order), so the online-softmax statistics (m, l) and the
output accumulator live in VMEM scratch and carry across KV iterations —
no HBM round-trips for the S×S score matrix. Q blocks of (block_q × head_dim)
and KV blocks of (block_k × head_dim) are staged HBM→VMEM by BlockSpecs; the
two matmuls per block hit the MXU with 128-aligned shapes.

Supports: causal masking, sliding-window masking, gemma-style logit softcap,
GQA (kv-head indexed via the BlockSpec index_map — no materialized repeat).
Every (q block, KV block) pair is visited; masked scores are set to -inf.

With ``return_stats`` the kernel also writes each row's softmax statistics
(running max m and sum l), which the flash backward in ``kernels/ops.py``
needs to recompute probabilities chunkwise.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, *refs, block_q, block_k, seq_k,
                 causal, window, softcap, scale, q_offset, return_stats):
    if return_stats:
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale           # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                   # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + q_offset
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        if return_stats:
            m_ref[0, 0] = m_scr[...]
            l_ref[0, 0] = l_scr[...]


def flash_attention_pallas(q, k, v, *, causal=True, window=0, softcap=0.0,
                           q_offset=0, block_q=128, block_k=128,
                           return_stats=False, interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D) -> (B, Sq, H, D).

    With ``return_stats``: (o, m, l), where m and l are the f32 row max and
    row sum of exp(s - m) over the scaled, softcapped, masked scores, shaped
    (B, Sq, KV, H // KV) as ``ops._flash_bwd`` takes them."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0
    group = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # pad seq lengths to block multiples (masked out by kpos < seq_k)
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pq, sk + pk

    # (B, S, H, D) -> (B, H, S, D) blocks; kv head via index_map h -> h // group
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, h, sq_p // block_q, sk_p // block_k)

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, seq_k=sk,
        causal=causal, window=window, softcap=softcap, scale=d ** -0.5,
        q_offset=q_offset, return_stats=return_stats)

    o_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    out_specs = [o_spec]
    out_shape = [jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype)]
    if return_stats:
        stat_spec = pl.BlockSpec((1, 1, block_q, 1),
                                 lambda b_, h_, qi, ki: (b_, h_, qi, 0))
        stat_shape = jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32)
        out_specs += [stat_spec, stat_spec]
        out_shape += [stat_shape, stat_shape]

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),   # output accum
        ],
        interpret=interpret,
    )(qt, kt, vt)

    out = outs[0].transpose(0, 2, 1, 3)[:, :sq]
    if not return_stats:
        return out

    def stat(x):                                  # (B,H,Sq_p,1) -> (B,Sq,KV,G)
        return x[:, :, :sq, 0].transpose(0, 2, 1).reshape(b, sq, kvh, group)

    return out, stat(outs[1]), stat(outs[2])
