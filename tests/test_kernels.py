"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mlstm import mlstm_pallas
from repro.kernels.quantize import (dequantize_blockwise_pallas,
                                    quantize_blockwise_pallas)
from repro.kernels.rg_lru import rg_lru_pallas

RNG = np.random.default_rng(7)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------- attention

ATTN_CASES = [
    # (B, Sq, Sk, H, KV, D, causal, window, softcap, dtype, tol)
    (1, 128, 128, 4, 4, 64, True, 0, 0.0, jnp.float32, 2e-5),
    (2, 96, 96, 4, 2, 32, True, 0, 0.0, jnp.float32, 2e-5),
    (1, 128, 128, 8, 2, 64, True, 48, 0.0, jnp.float32, 2e-5),
    (1, 64, 64, 2, 1, 128, False, 0, 0.0, jnp.float32, 2e-5),
    (1, 128, 128, 4, 4, 64, True, 0, 20.0, jnp.float32, 2e-5),
    (1, 128, 128, 4, 2, 64, True, 0, 0.0, jnp.bfloat16, 3e-2),
    (2, 80, 80, 4, 4, 48, True, 0, 0.0, jnp.float32, 2e-5),  # ragged seq
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_vs_oracle(case):
    b, sq, sk, h, kv, d, causal, window, cap, dtype, tol = case
    q = _rand((b, sq, h, d), dtype)
    k = _rand((b, sk, kv, d), dtype)
    v = _rand((b, sk, kv, d), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 softcap=cap, block_q=64, block_k=64,
                                 interpret=True)
    exp = ref.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", ATTN_CASES[:4])
def test_chunked_jnp_flash_vs_oracle(case):
    b, sq, sk, h, kv, d, causal, window, cap, dtype, tol = case
    q = _rand((b, sq, h, d), dtype)
    k = _rand((b, sk, kv, d), dtype)
    v = _rand((b, sk, kv, d), dtype)
    out = ops._flash_chunked_jnp(q, k, v, causal=causal, window=window,
                                 softcap=cap, q_offset=0, chunk=48)
    exp = ref.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("case", [
    # (B, S, H, KV, D, causal, window, softcap)
    (1, 128, 4, 2, 64, True, 0, 0.0),
    (2, 80, 4, 4, 48, True, 0, 0.0),           # ragged seq (padded blocks)
    (1, 128, 8, 2, 64, True, 48, 20.0),        # window + softcap
])
def test_flash_pallas_grads_match_jnp_vjp(case):
    """The TPU branch (Pallas forward + flash backward) differentiates to
    the same q/k/v gradients as the CPU branch's custom VJP."""
    b, s, h, kv, d, causal, window, cap = case
    q = _rand((b, s, h, d), jnp.float32)
    k = _rand((b, s, kv, d), jnp.float32)
    v = _rand((b, s, kv, d), jnp.float32)
    w = _rand((b, s, h, d), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    pallas = loss(lambda q, k, v: ops._flash_pallas(
        q, k, v, causal, window, cap, 0, 32, True))
    jnp_vjp = loss(lambda q, k, v: ops._flash_vjp(
        q, k, v, causal, window, cap, 0, 32))
    got = jax.grad(pallas, argnums=(0, 1, 2))(q, k, v)
    exp = jax.grad(jnp_vjp, argnums=(0, 1, 2))(q, k, v)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   atol=1e-4, rtol=1e-4)


def test_flash_pallas_stats_match_jnp():
    """The kernel's (m, l) equal the jnp forward's, layout included."""
    q = _rand((2, 96, 4, 32), jnp.float32)
    k = _rand((2, 96, 2, 32), jnp.float32)
    v = _rand((2, 96, 2, 32), jnp.float32)
    o, m, l = flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                     return_stats=True, interpret=True)
    oj, mj, lj = ops._flash_chunked_jnp(q, k, v, causal=True, window=0,
                                        softcap=0.0, q_offset=0, chunk=32,
                                        return_stats=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oj), atol=2e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(mj), atol=1e-5)
    # l is a sum of exp(s - m) over a different chunking; equal to rounding
    np.testing.assert_allclose(np.asarray(l), np.asarray(lj), rtol=1e-5)


def test_flash_attention_q_offset_decode_consistency():
    """Chunked attention over a prefix + offset q equals full attention."""
    b, s, h, d = 1, 96, 2, 32
    q = _rand((b, s, h, d), jnp.float32)
    k = _rand((b, s, h, d), jnp.float32)
    v = _rand((b, s, h, d), jnp.float32)
    full = ref.flash_attention(q, k, v, causal=True)
    tail = ops._flash_chunked_jnp(q[:, -16:], k, v, causal=True, window=0,
                                  softcap=0.0, q_offset=s - 16, chunk=32)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, -16:]),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------- rg_lru

@pytest.mark.parametrize("shape,blocks", [
    ((2, 256, 128), (128, 128)),
    ((1, 512, 256), (256, 128)),
    ((3, 128, 384), (64, 128)),
    ((2, 512, 256), (256, 128)),               # batch > 1 with h0 / h_last
])
def test_rg_lru_vs_oracle(shape, blocks):
    b, s, d = shape
    bs, bd = blocks
    a = jnp.asarray(RNG.uniform(0.7, 0.999, shape), jnp.float32)
    gx = _rand(shape, jnp.float32) * 0.1
    h0 = _rand((b, d), jnp.float32) * 0.1
    hp, hl = rg_lru_pallas(a, gx, h0, block_s=bs, block_d=bd, interpret=True)
    hr, hlr = ref.rg_lru(a, gx, h0)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), atol=1e-5)


def test_rg_lru_assoc_scan_matches():
    b, s, d = 2, 300, 64
    a = jnp.asarray(RNG.uniform(0.7, 0.999, (b, s, d)), jnp.float32)
    gx = _rand((b, s, d), jnp.float32) * 0.1
    ha, hla = ops._rg_lru_assoc(a, gx, None)
    hr, hlr = ref.rg_lru(a, gx, None)
    np.testing.assert_allclose(np.asarray(ha), np.asarray(hr), atol=1e-5)


# -------------------------------------------------------------------- mlstm

@pytest.mark.parametrize("shape,chunk", [
    ((1, 128, 2, 32), 64),
    ((2, 256, 1, 64), 128),
    ((1, 192, 4, 16), 64),
])
def test_mlstm_vs_oracle(shape, chunk):
    b, s, h, d = shape
    q = _rand(shape, jnp.float32)
    k = _rand(shape, jnp.float32)
    v = _rand(shape, jnp.float32)
    lf = jnp.asarray(np.log(RNG.uniform(0.85, 0.999, (b, s, h))), jnp.float32)
    li = _rand((b, s, h), jnp.float32) * 0.5
    hp, (Cp, np_, mp) = mlstm_pallas(q, k, v, lf, li, chunk=chunk,
                                     interpret=True)
    hr, (Cr, nr, mr) = ref.mlstm(q, k, v, lf, li)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr),
                               atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(Cp), np.asarray(Cr),
                               atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(mp), np.asarray(mr), atol=1e-5)


def test_mlstm_stateful_decode_matches_full():
    """Running the ref cell over a split sequence with carried state equals
    one full pass (the decode path contract)."""
    b, s, h, d = 1, 64, 2, 16
    q = _rand((b, s, h, d), jnp.float32)
    k = _rand((b, s, h, d), jnp.float32)
    v = _rand((b, s, h, d), jnp.float32)
    lf = jnp.asarray(np.log(RNG.uniform(0.9, 0.999, (b, s, h))), jnp.float32)
    li = _rand((b, s, h), jnp.float32) * 0.5
    full, _ = ref.mlstm(q, k, v, lf, li)
    cut = 40
    h1, st = ref.mlstm(q[:, :cut], k[:, :cut], v[:, :cut],
                       lf[:, :cut], li[:, :cut])
    h2, _ = ref.mlstm(q[:, cut:], k[:, cut:], v[:, cut:],
                      lf[:, cut:], li[:, cut:], *st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(full), atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------- quantize

@given(st.integers(1, 6), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_quantize_roundtrip_error_bound(nblocks, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 3.0, nblocks * 2048), jnp.float32)
    q, s = quantize_blockwise_pallas(x, interpret=True)
    qr, sr = ref.quantize_blockwise(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    xd = dequantize_blockwise_pallas(q, s, interpret=True)
    # error bounded by half a quantization step per block, with f32
    # round-trip slack: |x/s| reaches 127, so x/s, round, *s accumulates
    # ~127*eps_f32 of relative error on top of the half-step
    err = np.abs(np.asarray(xd) - np.asarray(x)).reshape(nblocks, 2048)
    bound = np.asarray(s)[:, None] * (0.5 + 1e-4) + 1e-7
    assert (err <= bound).all()


@pytest.mark.parametrize("nblocks", [100, 384, 18432])
def test_quantize_roundtrip_padded_tiling(nblocks):
    """Block counts that are not a multiple of the tile (100, 384) or are a
    real leaf's (18432 = 3072 x 12288 / 2048): rows are padded to the int8
    tiling and sliced off again, under the same error bound."""
    rng = np.random.default_rng(nblocks)
    x = rng.normal(0, 3.0, nblocks * 2048).astype(np.float32)
    q, s = quantize_blockwise_pallas(jnp.asarray(x), interpret=True)
    assert q.shape == (nblocks * 2048,) and s.shape == (nblocks,)
    xd = dequantize_blockwise_pallas(q, s, interpret=True)
    err = np.abs(np.asarray(xd) - x).reshape(nblocks, 2048)
    bound = np.asarray(s)[:, None] * (0.5 + 1e-4) + 1e-7
    assert (err <= bound).all()


def test_quantize_preserves_zero_and_extremes():
    x = jnp.asarray([0.0] * 2047 + [12.5], jnp.float32)
    q, s = ref.quantize_blockwise(x)
    xd = ref.dequantize_blockwise(q, s)
    assert float(xd[-1]) == pytest.approx(12.5, rel=1e-2)
    np.testing.assert_allclose(np.asarray(xd[:-1]), 0.0, atol=1e-7)
