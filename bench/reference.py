"""Plain reference of a decoder-only language model's training steps.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the configuration file alone: it imports nothing of the program
under test and takes nothing that the program made. Weights come from
``bench.weights`` with the run's seed, and batches from ``bench.data``.

What the configuration states is kept: parameters are stored in their dtype
(bfloat16) and rounded to it after every update; the optimizer's moments are
stored in theirs. Everything else is computed in float32. Attention runs one
block of queries at a time, and every layer and block is rematerialized, so
that the reference fits on one chip beside nothing else.

``low`` names a lower precision for the control: every matmul operand is
rounded to it, with a scale per tensor as an fp8 path would, before the
float32 product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F32 = jnp.float32
Q_BLOCK = 512


def _quantize(x, low):
    dt = jnp.dtype(low)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dt).max)
    return (x / s).astype(dt).astype(F32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _lowp(x, low):
    return _quantize(x, low)


def _lowp_fwd(x, low):
    return _quantize(x, low), None


def _lowp_bwd(low, _, g):
    # the backward's operands are rounded too, with their own scale
    return (_quantize(g, low),)


_lowp.defvjp(_lowp_fwd, _lowp_bwd)


def _round(x, low):
    return x if low is None else _lowp(x, low)


def _mm(spec, a, b, low):
    return jnp.einsum(spec, _round(a, low), _round(b, low),
                      precision=jax.lax.Precision.HIGHEST)


def _norm(cfg, p, x):
    eps = cfg["norm_epsilon"]
    if cfg["norm"] == "layernorm":
        x = x - x.mean(-1, keepdims=True)
        x = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
        return x * p["scale"].astype(F32) + p["bias"].astype(F32)
    x = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
    return x * p["scale"].astype(F32)


def _rope(x, theta):
    """Rotary embedding on (B, S, H, D): the two halves of D rotated."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(cfg, x):
    if cfg["hidden_act"] in ("gelu_pytorch_tanh", "gelu_tanh"):
        return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                       * (x + 0.044715 * x ** 3)))
    if cfg["hidden_act"] == "silu":
        return x / (1 + jnp.exp(-x))
    raise ValueError(f"unknown activation {cfg['hidden_act']!r}")


def _attention(cfg, q, k, v, low):
    """Causal (and, where the configuration has one, sliding-window) GQA
    attention, one block of queries at a time."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    window = cfg.get("sliding_window") or 0
    blk = min(Q_BLOCK, s)
    kpos = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        qb = qb.reshape(b, blk, kvh, g, d) * d ** -0.5
        sc = _mm("bqkgd,bskd->bkgqs", qb, k, low)
        qpos = i * blk + jnp.arange(blk)[:, None]
        mask = kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        sc = jnp.where(mask, sc, -jnp.inf)
        w = jnp.exp(sc - sc.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        return _mm("bkgqs,bskd->bqkgd", w, v, low).reshape(b, blk, h, d)

    out = jax.lax.map(block, jnp.arange(s // blk))   # (n, B, blk, H, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def _layer(cfg, low, x, p):
    theta = cfg["rope_theta"]
    h = _norm(cfg, p["norm1"], x)
    a = p["attn"]
    q = _rope(_mm("bsd,dhk->bshk", h, a["wq"].astype(F32), low), theta)
    k = _rope(_mm("bsd,dhk->bshk", h, a["wk"].astype(F32), low), theta)
    v = _mm("bsd,dhk->bshk", h, a["wv"].astype(F32), low)
    o = _attention(cfg, q, k, v, low)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"].astype(F32), low)
    h = _norm(cfg, p["norm2"], x)
    m = p["mlp"]
    up = _mm("bsd,df->bsf", h, m["w_up"].astype(F32), low)
    if cfg["mlp_gated"]:
        gate = _mm("bsd,df->bsf", h, m["w_gate"].astype(F32), low)
        up = _act(cfg, gate) * up
    else:
        up = _act(cfg, up)
    return x + _mm("bsf,fd->bsd", up, m["w_down"].astype(F32), low)


def loss_fn(cfg, params, inputs, labels, low=None, half=False):
    """Mean next-token cross entropy over the padded vocabulary. ``half``
    takes the mean over the first half of the tokens alone (a fault)."""
    emb = params["embed"]["tokens"]
    x = emb[inputs].astype(F32)
    body = jax.checkpoint(lambda x, p: (_layer(cfg, low, x, p), None))
    x, _ = jax.lax.scan(body, x, params["segments"]["seg0"]["0"])
    x = _norm(cfg, params["final_norm"], x)
    if cfg["tie_word_embeddings"]:
        logits = _mm("bsd,vd->bsv", x, emb.astype(F32), low)
    else:
        logits = _mm("bsd,dv->bsv", x, params["embed"]["unembed"].astype(F32),
                     low)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    nll = lse - gold
    if half:
        nll = nll[:, : nll.shape[1] // 2]
    return nll.mean()


# ------------------------------------------------------------------ optimizer


def lr_at(tcfg, step):
    """Linear warm-up, then cosine decay to ``final_frac`` of the peak."""
    s = step.astype(F32)
    peak, warm, total = tcfg["peak_lr"], tcfg["warmup"], tcfg["total_steps"]
    frac = tcfg["final_frac"]
    prog = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (frac + (1 - frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(s < warm, peak * s / max(warm, 1), cos)


def _decays(name):
    return not (name.endswith("/scale") or name.endswith("/bias"))


def opt_init(tcfg, flat_params):
    if tcfg["optimizer"] == "adamw":
        dt = jnp.dtype(tcfg["moment_dtype"])
        return {n: (jnp.zeros(p.shape, dt), jnp.zeros(p.shape, dt))
                for n, p in flat_params.items()}
    mdt = jnp.dtype(tcfg["momentum_dtype"])
    out = {}
    for n, p in flat_params.items():
        if p.ndim >= 2:
            vr = jnp.zeros(p.shape[:-1], F32)
            vc = jnp.zeros(p.shape[:-2] + p.shape[-1:], F32)
        else:
            vr, vc = jnp.zeros(p.shape, F32), jnp.zeros((0,), F32)
        out[n] = (vr, vc, jnp.zeros(p.shape, mdt))
    return out


def _adamw(tcfg, decays, g, st, p, step, lr):
    m, v = st
    b1, b2 = tcfg["b1"], tcfg["b2"]
    t = step.astype(F32)
    m = b1 * m.astype(F32) + (1 - b1) * g
    v = b2 * v.astype(F32) + (1 - b2) * g * g
    delta = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + tcfg["eps"])
    if tcfg["weight_decay"] and decays:
        delta = delta + tcfg["weight_decay"] * p
    dt = jnp.dtype(tcfg["moment_dtype"])
    return p - lr * delta, (m.astype(dt), v.astype(dt))


def _adafactor(tcfg, decays, g, st, p, step, lr):
    """Factored second moments over the two trailing axes (Shazeer and
    Stern, 2018), update clipping by RMS over the leaf, then momentum."""
    vr, vc, m = st
    eps = tcfg["eps"]
    beta2 = 1.0 - step.astype(F32) ** (-tcfg["decay"])
    g2 = g * g + eps
    if p.ndim >= 2:
        vr = beta2 * vr + (1 - beta2) * g2.mean(-1)
        vc = beta2 * vc + (1 - beta2) * g2.mean(-2)
        r = vr / jnp.maximum(vr.mean(-1, keepdims=True), eps)
        u = g / (jnp.sqrt(r)[..., None] * jnp.sqrt(vc)[..., None, :] + eps)
    else:
        vr = beta2 * vr + (1 - beta2) * g2
        u = g / (jnp.sqrt(vr) + eps)
    rms = jnp.sqrt((u * u).mean() + 1e-30)
    u = u / jnp.maximum(1.0, rms / tcfg["clip_threshold"])
    m = tcfg["momentum"] * m.astype(F32) + (1 - tcfg["momentum"]) * u
    if tcfg["weight_decay"] and decays:
        m_used = m + tcfg["weight_decay"] * p
    else:
        m_used = m
    return p - lr * m_used, (vr, vc, m.astype(jnp.dtype(tcfg["momentum_dtype"])))


def _sq(x):
    return jnp.sum(jnp.square(x.astype(F32)))


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _grads(cfg_key, params, batch, low, half):
    """The loss, and the gradient clipped to the configuration's global
    norm, in each parameter's dtype."""
    cfg = _CFGS[cfg_key]
    loss, grads = jax.value_and_grad(loss_fn, argnums=1)(
        cfg, params, batch["inputs"], batch["labels"], low, half)
    gflat = weights.flatten(grads)
    gnorm = jnp.sqrt(sum(_sq(g) for g in gflat.values()))
    scale = jnp.minimum(1.0, cfg["training"]["clip_norm"]
                        / jnp.maximum(gnorm, 1e-12))
    return loss, {n: (g.astype(F32) * scale).astype(g.dtype)
                  for n, g in gflat.items()}


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3, 4))
def _update(cfg_key, decays, g, st, p, step):
    """One leaf's optimizer update; the parameter is rounded to its dtype."""
    tcfg = _CFGS[cfg_key]["training"]
    upd = _adamw if tcfg["optimizer"] == "adamw" else _adafactor
    pf, st = upd(tcfg, decays, g.astype(F32), st, p.astype(F32), step,
                 lr_at(tcfg, step))
    return pf.astype(p.dtype), st


def _step(cfg_key, params, opt, batch, step, low, half):
    """One reference step, one leaf's update at a time so that no second
    copy of the state is live."""
    loss, gflat = _grads(cfg_key, params, batch, low, half)
    gn = {n: jnp.sqrt(_sq(g)) for n, g in gflat.items()}
    pflat = weights.flatten(params)
    del params
    for n in sorted(pflat):
        pflat[n], opt[n] = _update(cfg_key, _decays(n), gflat.pop(n),
                                   opt[n], pflat[n], step)
    return weights.nest(pflat), opt, loss, gn


@functools.partial(jax.jit, static_argnums=(0, 3))
def _loss_only(cfg_key, params, batch, low):
    return loss_fn(_CFGS[cfg_key], params, batch["inputs"], batch["labels"],
                   low)


@jax.jit
def _change_norms(p_now, p_then):
    a, b = weights.flatten(p_now), weights.flatten(p_then)
    return {n: jnp.sqrt(_sq(a[n].astype(F32) - b[n].astype(F32))) for n in a}


_CFGS: dict = {}


def _key(cfg: dict) -> str:
    import json
    k = json.dumps(cfg, sort_keys=True)
    _CFGS[k] = cfg
    return k


def train(cfg: dict, seed: int, batches, *, steps: int, low=None,
          half=False, extra_loss_batch=None, extra_loss_after=None) -> dict:
    """Run ``steps`` reference steps from the seed's weights on ``batches``.

    Returns the loss of every step, the norm of every leaf's clipped
    gradient at step 1, the norm of every leaf's change over the steps and,
    with ``extra_loss_batch``, the loss of the parameters after
    ``extra_loss_after`` steps on that batch (forward only)."""
    key = _key(cfg)
    with jax.default_matmul_precision("highest"):
        params = weights.init_params(cfg, seed)
        opt = opt_init(cfg["training"], weights.flatten(params))
        losses, grad_norms, extra = [], None, None
        for i in range(steps):
            if extra_loss_batch is not None and i == extra_loss_after:
                extra = float(_loss_only(key, params, extra_loss_batch, low))
            b = {k: jnp.asarray(v) for k, v in batches[i].items()}
            params, opt, loss, gn = _step(key, params, opt, b,
                                          jnp.asarray(i + 1, jnp.int32), low,
                                          half)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {n: float(v) for n, v in gn.items()}
        if extra_loss_batch is not None and extra_loss_after == steps:
            extra = float(_loss_only(key, params, extra_loss_batch, low))
        del opt
        start = weights.init_params(cfg, seed)
        change = {n: float(v) for n, v in
                  _change_norms(params, start).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "extra_loss": extra}
