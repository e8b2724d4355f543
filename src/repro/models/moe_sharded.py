"""Expert-parallel MoE via shard_map with fixed-capacity all-to-all.

Pure-SPMD sort-based dispatch (moe.py) lowers its cross-shard scatter to
"replicate + mask + all-reduce" of (T*k, d) f32 tensors — measured at ~2/3
of all collective traffic and ~8x the temp memory on deepseek-v3 train.
This module replaces it with the explicit schedule real MoE systems use:

GRID mode (E divisible by data*model, e.g. deepseek 256 on a 16x16 pod —
expert e lives wholly on device (e // ncols, e % ncols)):
  1. tokens are batch-sharded over `data` rows, replicated over `model` cols
  2. each col c filters assignments routed to experts with e % ncols == c
     (cols partition the assignment set — no duplicated expert work)
  3. bin by destination row (e // ncols), capacity-clip, all_to_all over
     `data` (the only cross-row traffic: cap-padded token payloads)
  4. local expert FFN (weights fully resident), reverse all_to_all
  5. scatter-add weighted outputs locally, psum over `model` to merge cols

ROW mode (E divisible by data only, e.g. llama4 16 experts — expert e lives
on row e, f-dim sharded over `model`):
  same dispatch with dest row = e, no col filter (cols replicate dispatch);
  expert FFN contracts its f-shard and psums over `model` inside the expert;
  no final psum.

Capacity per (src device, dest bin): ceil(T_loc * k / bins * cf), padded to
8. Overflow drops (standard dropping MoE); zeros flow through the FFN to a
zero contribution, so no masking is needed on the payload path.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.common import activation


def _cap(n_assign: int, bins: int, cf: float) -> int:
    c = math.ceil(n_assign / bins * cf)
    return max(8, ((c + 7) // 8) * 8)


def sharded_moe_available(cfg, rules) -> bool:
    if rules is None or cfg.num_experts == 0:
        return False
    sizes = rules.sizes
    if "data" not in sizes or "model" not in sizes:
        return False
    e = cfg.num_experts
    grid = e == sizes["data"] * sizes["model"]
    row = (not grid) and e == sizes["data"] \
        and cfg.d_ff_expert % sizes["model"] == 0
    return grid or row


def apply_moe_sharded(cfg, p, x, rules):
    """x: (B, S, d) batch-sharded over (pod?, data). Returns (B, S, d)."""
    mesh = rules.mesh
    sizes = rules.sizes
    nrows, ncols = sizes["data"], sizes["model"]
    e = cfg.num_experts
    grid_mode = e == nrows * ncols

    x_spec = rules.spec(("batch", None, None), x.shape)
    router_spec = P(None, None)
    if grid_mode:
        w_spec = P(("data", "model"), None, None)
    else:
        w_spec = P("data", None, "model")          # experts x d x f-shard
    wd_spec = P(("data", "model"), None, None) if grid_mode \
        else P("data", "model", None)
    out_spec = x_spec

    has_pod = "pod" in sizes

    def local_moe(xl, router, wg, wu, wd):
        b_l, s_l, d = xl.shape
        t = b_l * s_l
        k = cfg.top_k
        xt = xl.reshape(t, d)
        col = jax.lax.axis_index("model")

        # --- routing (replicated across cols; f32) ---
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)

        flat_e = topi.reshape(-1)
        flat_w = topw.reshape(-1).astype(xl.dtype)
        flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

        if grid_mode:
            mine = (flat_e % ncols) == col          # this col's experts
            dest = flat_e // ncols                  # dest data-row
            bins = nrows
            cap = _cap(t * k, nrows * ncols, cfg.capacity_factor)
        else:
            mine = jnp.ones_like(flat_e, dtype=bool)
            dest = flat_e                           # dest row == expert id
            bins = nrows
            cap = _cap(t * k, nrows, cfg.capacity_factor)

        dest = jnp.where(mine, dest, bins)          # invalid -> dump bin
        order = jnp.argsort(dest)
        sdest, stok, sw = dest[order], flat_t[order], flat_w[order]
        counts = jnp.bincount(sdest, length=bins + 1)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(t * k, dtype=jnp.int32) - starts[sdest]
        keep = (rank < cap) & (sdest < bins)
        slot = jnp.where(keep, sdest * cap + rank, bins * cap)

        send = jnp.zeros((bins * cap + 1, d), xl.dtype).at[slot].set(xt[stok])
        send = send[:-1].reshape(bins, cap, d)
        # slot-aligned metadata stays local (a2a preserves slot order)
        meta_tok = jnp.full((bins * cap + 1,), -1, jnp.int32
                            ).at[slot].set(jnp.where(keep, stok, -1))[:-1]
        meta_w = jnp.zeros((bins * cap + 1,), xl.dtype
                           ).at[slot].set(jnp.where(keep, sw, 0))[:-1]

        recv = jax.lax.all_to_all(send, "data", split_axis=0, concat_axis=0,
                                  tiled=True)       # (bins*cap, d) grouped
        h = recv.reshape(bins * cap, d)

        # --- expert FFN (weights local) ---
        wg_l, wu_l, wd_l = wg[0], wu[0], wd[0]      # local expert (1, d, f)
        gate = jnp.einsum("nd,df->nf", h, wg_l.astype(h.dtype))
        up = jnp.einsum("nd,df->nf", h, wu_l.astype(h.dtype))
        y = jnp.einsum("nf,fd->nd", activation(cfg, gate) * up,
                       wd_l.astype(h.dtype))
        if not grid_mode:
            # f is sharded over model: partial sums -> psum inside expert
            y = jax.lax.psum(y, "model")

        back = jax.lax.all_to_all(y.reshape(bins, cap, d), "data",
                                  split_axis=0, concat_axis=0, tiled=True)
        back = back.reshape(bins * cap, d)

        contrib = back * meta_w[:, None]
        tok_safe = jnp.where(meta_tok >= 0, meta_tok, t)
        out = jnp.zeros((t + 1, d), xl.dtype).at[tok_safe].add(contrib)[:-1]
        if grid_mode:
            out = jax.lax.psum(out, "model")        # merge col contributions
        return out.reshape(b_l, s_l, d)

    fn = shard_map(
        local_moe, mesh=mesh,
        in_specs=(x_spec, router_spec, w_spec, w_spec, wd_spec),
        out_specs=out_spec,
        check_vma=False)
    out = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.num_shared_experts:
        sp = p["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sp["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, sp["w_up"].astype(x.dtype))
        out = out + jnp.einsum("bsf,fd->bsd", activation(cfg, g) * u,
                               sp["w_down"].astype(x.dtype))
    return out
