"""Public kernel API. Dispatches per backend, one rule per kernel:

- TPU: Pallas kernels (flash_attention.py / rg_lru.py / mlstm.py / quantize.py)
- CPU (incl. the 512-device dry-run): pure-jnp implementations with the SAME
  blockwise structure — attention is a lax.scan over KV chunks with online
  softmax, so the lowered HLO never materializes the S x S score matrix and
  the dry-run's memory/FLOP profile matches the fused kernel.

Kernel unit tests call the Pallas entry points with ``interpret=True``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# flash attention


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    q_offset=0, chunk=512):
    """q: (B,Sq,H,D); k/v: (B,Sk,KV,D) -> (B,Sq,H,D).

    Differentiable with a FLASH BACKWARD (custom VJP) on both backends: the
    forward saves only (o, m, l); the backward recomputes scores chunkwise.
    Without this, differentiating through the online-softmax scan saves every
    chunk's probability matrix — measured at ~16 GB per layer on train_4k
    shapes. On TPU the forward is the Pallas kernel, run per shard.
    """
    if _on_tpu():
        return _per_shard(
            lambda q, k, v: _flash_pallas(q, k, v, causal, window, softcap,
                                          q_offset, chunk, False), q, k, v)
    return _flash_vjp(q, k, v, causal, window, softcap, q_offset, chunk)


def _per_shard(fn, q, k, v):
    """Run an attention kernel on each shard of the active rule set's mesh.

    XLA cannot partition a Mosaic kernel, so under a rule set the call goes
    through ``shard_map``: batch over the batch axes, heads over the heads
    axis when the query and KV head counts both divide it (each shard then
    holds whole GQA groups). The sequence stays whole on every shard."""
    from repro.launch.sharding import active_rules
    rules = active_rules()
    if rules is None:
        return fn(q, k, v)
    batch, _, heads, _ = rules.spec(("batch", None, "heads", None), q.shape)
    if heads is not None:
        names = heads if isinstance(heads, tuple) else (heads,)
        if k.shape[2] % math.prod(rules.sizes[a] for a in names):
            heads = None
    spec = P(batch, None, heads, None)
    return jax.shard_map(fn, mesh=rules.mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_pallas(q, k, v, causal, window, softcap, q_offset, chunk,
                  interpret):
    from repro.kernels.flash_attention import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  softcap=softcap, q_offset=q_offset,
                                  interpret=interpret)


def _flash_pallas_fwd(q, k, v, causal, window, softcap, q_offset, chunk,
                      interpret):
    from repro.kernels.flash_attention import flash_attention_pallas
    o, m, l = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     softcap=softcap, q_offset=q_offset,
                                     return_stats=True, interpret=interpret)
    return o, (q, k, v, o, m, l)


def _flash_pallas_bwd(causal, window, softcap, q_offset, chunk, interpret,
                      res, g_out):
    return _flash_bwd(causal, window, softcap, q_offset, chunk, res, g_out)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal, window, softcap, q_offset, chunk):
    return _flash_chunked_jnp(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_offset=q_offset, chunk=chunk)


def _flash_fwd(q, k, v, causal, window, softcap, q_offset, chunk):
    o, m, l = _flash_chunked_jnp(q, k, v, causal=causal, window=window,
                                 softcap=softcap, q_offset=q_offset,
                                 chunk=chunk, return_stats=True)
    return o, (q, k, v, o, m, l)


def _flash_bwd(causal, window, softcap, q_offset, chunk, res, g_out):
    """Chunkwise flash backward: recompute p per KV chunk; no saved scores."""
    q, k, v, o, m, l = res
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    chunk_ = min(chunk, sk)
    pad = (-sk) % chunk_
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkc = (sk + pad) // chunk_
    scale = d ** -0.5

    qf = q.astype(jnp.float32).reshape(b, sq, kvh, g, d)
    go = g_out.astype(jnp.float32).reshape(b, sq, kvh, g, d)
    of = o.astype(jnp.float32).reshape(b, sq, kvh, g, d)
    linv = 1.0 / jnp.maximum(l, 1e-30)                       # (b,sq,kvh,g)
    D = jnp.sum(go * of, axis=-1)                            # (b,sq,kvh,g)
    qpos = (jnp.arange(sq, dtype=jnp.int32) + q_offset)[:, None]

    kc = k.reshape(b, nkc, chunk_, kvh, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nkc, chunk_, kvh, d).transpose(1, 0, 2, 3, 4)

    def body(dq_acc, xs):
        kb, vb, ci = xs                                      # (b,c,kv,d)
        kpos = ci * chunk_ + jnp.arange(chunk_, dtype=jnp.int32)[None, :]
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf * scale,
                       kb.astype(jnp.float32))
        if softcap:
            sc = jnp.tanh(s / softcap) * softcap
            dcap = 1.0 - jnp.square(sc / softcap)
        else:
            sc = s
            dcap = None
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        maskb = mask[None, :, None, None, :]
        p = jnp.where(maskb, jnp.exp(sc - m[..., None]), 0.0) \
            * linv[..., None]                                # (b,q,kv,g,c)
        dv = jnp.einsum("bqkgc,bqkgd->bckd", p, go)
        dp = jnp.einsum("bqkgd,bckd->bqkgc", go, vb.astype(jnp.float32))
        ds = p * (dp - D[..., None])
        if softcap:
            ds = ds * dcap
        ds = ds * scale
        dq_acc = dq_acc + jnp.einsum("bqkgc,bckd->bqkgd", ds,
                                     kb.astype(jnp.float32))
        dk = jnp.einsum("bqkgc,bqkgd->bckd", ds, qf)
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros((b, sq, kvh, g, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        body, dq0, (kc, vc, jnp.arange(nkc, dtype=jnp.int32)))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, sk + pad, kvh, d)[:, :sk]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, sk + pad, kvh, d)[:, :sk]
    return (dq.reshape(b, sq, h, d).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


def _flash_chunked_jnp(q, k, v, *, causal, window, softcap, q_offset, chunk,
                       return_stats=False):
    """Online-softmax over KV chunks (lax.scan). Flash memory profile in HLO."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkc = (sk + pad) // chunk

    qf = q.astype(jnp.float32) * (d ** -0.5)
    qg = qf.reshape(b, sq, kvh, g, d)
    qpos = (jnp.arange(sq, dtype=jnp.int32) + q_offset)[:, None]    # (sq,1)

    kc = k.reshape(b, nkc, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nkc, chunk, kvh, d).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs
        kpos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kb.astype(jnp.float32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, kvh, g), -1e30, jnp.float32)
    l0 = jnp.zeros((b, sq, kvh, g), jnp.float32)
    a0 = jnp.zeros((b, sq, kvh, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(nkc, dtype=jnp.int32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.reshape(b, sq, h, d).astype(q.dtype)
    if return_stats:
        return out, m, l
    return out


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence


def rg_lru(a, gx, h0=None):
    """h_t = a_t * h_{t-1} + gx_t. a/gx: (B,S,D) -> (h, h_last)."""
    if _on_tpu():
        from repro.kernels.rg_lru import rg_lru_pallas
        return rg_lru_pallas(a, gx, h0)
    return _rg_lru_assoc(a, gx, h0)


def _rg_lru_assoc(a, gx, h0=None):
    """O(log S) associative scan — the CPU/compile path."""
    af = a.astype(jnp.float32)
    gf = gx.astype(jnp.float32)
    if h0 is not None:
        # fold h0 into the first element: h_1 = a_1 * h0 + gx_1
        gf = gf.at[:, 0].add(af[:, 0] * h0.astype(jnp.float32))

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, b1 * a2 + b2

    aa, hh = jax.lax.associative_scan(combine, (af, gf), axis=1)
    return hh.astype(a.dtype), hh[:, -1].astype(a.dtype)


# ---------------------------------------------------------------------------
# mLSTM


def mlstm(q, k, v, log_f, log_i, state=None, chunk=128):
    """Chunkwise mLSTM. state: optional (C, n, m) carry (decode path)."""
    if state is None and _on_tpu():
        from repro.kernels.mlstm import mlstm_pallas
        return mlstm_pallas(q, k, v, log_f, log_i)
    s = q.shape[1]
    if s > 1 and s % min(chunk, s) == 0:
        return _mlstm_chunked_jnp(q, k, v, log_f, log_i, state,
                                  chunk=min(chunk, s))
    if state is None:
        return ref.mlstm(q, k, v, log_f, log_i)
    return ref.mlstm(q, k, v, log_f, log_i, *state)


def _mlstm_chunked_jnp(q, k, v, log_f, log_i, state=None, chunk=128):
    """Chunkwise-parallel mLSTM (same math as the Pallas kernel): within a
    chunk the in-chunk contribution is a masked attention-like matmul; the
    (d x d) state carries across chunks via lax.scan. Replaces the O(S)
    per-timestep scan (whose HBM traffic is S x state bytes) with S/chunk
    steps of MXU-friendly matmuls — this is also what makes the dry-run's
    memory roofline reflect the kernel's behaviour."""
    b, s, h, d = q.shape
    nc = s // chunk
    scale = d ** -0.5
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, d)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, d) * scale
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, d)
    lf = log_f.astype(jnp.float32).transpose(0, 2, 1).reshape(b, h, nc, chunk)
    li = log_i.astype(jnp.float32).transpose(0, 2, 1).reshape(b, h, nc, chunk)

    if state is None:
        C0 = jnp.zeros((b, h, d, d), jnp.float32)
        n0 = jnp.zeros((b, h, d), jnp.float32)
        m0 = jnp.full((b, h), -1e30, jnp.float32)
    else:
        C0, n0, m0 = (x.astype(jnp.float32) for x in state)

    t_idx = jnp.arange(chunk)
    causal = t_idx[:, None] >= t_idx[None, :]

    def step(carry, xs):
        C, n, m = carry                                  # (b,h,d,d),(b,h,d),(b,h)
        qc, kc, vc, lfc, lic = xs                        # (b,h,chunk,...)
        F = jnp.cumsum(lfc, axis=-1)                     # (b,h,c)
        src = lic - F
        run_src = jax.lax.cummax(src, axis=src.ndim - 1)
        m_t = F + jnp.maximum(m[..., None], run_src)     # (b,h,c)

        d_mat = F[..., :, None] + src[..., None, :] - m_t[..., :, None]
        d_mat = jnp.where(causal, d_mat, -1e30)
        w = jnp.exp(d_mat)                               # (b,h,c,c)
        sc = jnp.einsum("bhtd,bhud->bhtu", qc, kc)
        ws = w * sc
        intra_num = jnp.einsum("bhtu,bhud->bhtd", ws, vc)
        intra_den = jnp.sum(ws, axis=-1)                 # (b,h,c)

        carry_coeff = jnp.exp(F + m[..., None] - m_t)    # (b,h,c)
        inter_num = jnp.einsum("bhtd,bhdk->bhtk", qc, C)
        inter_den = jnp.einsum("bhtd,bhd->bht", qc, n)
        num = inter_num * carry_coeff[..., None] + intra_num
        den = inter_den * carry_coeff + intra_den
        den = jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
        hout = num / den[..., None]                      # (b,h,c,d)

        m_last = m_t[..., -1]
        f_all = F[..., -1]
        state_coeff = jnp.exp(f_all + m - m_last)
        src_coeff = jnp.exp(f_all[..., None] + src - m_last[..., None])
        kc_s = kc * src_coeff[..., None]
        C_new = C * state_coeff[..., None, None] \
            + jnp.einsum("bhud,bhuk->bhdk", kc_s, vc)
        n_new = n * state_coeff[..., None] + jnp.sum(kc_s, axis=-2)
        return (C_new, n_new, m_last), hout

    xs = tuple(a.transpose(2, 0, 1, 3, 4) if a.ndim == 5
               else a.transpose(2, 0, 1, 3)
               for a in (qf, kf, vf, lf, li))
    (C, n, m), hs = jax.lax.scan(step, (C0, n0, m0), xs)
    hs = hs.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return hs.astype(q.dtype), (C.astype(q.dtype), n.astype(q.dtype), m)


# ---------------------------------------------------------------------------
# checkpoint quantization


def quantize_blockwise(x, *, block=2048):
    if _on_tpu():
        from repro.kernels.quantize import quantize_blockwise_pallas
        return quantize_blockwise_pallas(x, block=block)
    return ref.quantize_blockwise(x, block)


def dequantize_blockwise(q, scale, *, block=2048, out_dtype=jnp.float32):
    if _on_tpu():
        from repro.kernels.quantize import dequantize_blockwise_pallas
        return dequantize_blockwise_pallas(q, scale, block=block,
                                           out_dtype=out_dtype)
    return ref.dequantize_blockwise(q, scale, block).astype(out_dtype)
