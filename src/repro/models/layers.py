"""Model building blocks, pure-JAX (params = pytrees of jnp arrays).

Covers every assigned architecture: GQA attention (full / sliding-window /
cross), RoPE variants (1d, chatglm 2d-half, qwen2-vl M-RoPE), gated MLP,
top-k MoE with capacity bucketing (EP-shardable), and Mamba2 SSD (chunked
state-space duality) with single-step decode.

Sharding: layers call :func:`shard` (a with_sharding_constraint that is a
no-op outside a mesh) with *logical* axis tuples; ``repro.parallel.sharding``
resolves them to mesh axes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig, MoEConfig, SSMConfig
from repro.parallel.sharding import logical_sharding_constraint as shard

_shard_map = jax.shard_map

Array = jax.Array


# ---------------------------------------------------------------- init utils

def _dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    scale = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def _embed_init(key, shape, dtype=jnp.float32):
    # 1/sqrt(d) keeps tied-head logits O(1) at init
    scale = 1.0 / np.sqrt(shape[-1])
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------- norms

def rmsnorm_init(d):
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + params["scale"])).astype(dt)


# ---------------------------------------------------------------- RoPE

def _rope_angles(positions, dim, theta):
    """positions (..., S) -> cos/sin (..., S, dim/2)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rot(x, cos, sin):
    """x (..., dim) rotate pairs (even, odd) with given cos/sin (..., dim/2)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def apply_rope(x: Array, positions: Array, kind: str, theta: float) -> Array:
    """x: (B, S, H, hd); positions: (B, S) or (B, S, 3) for mrope."""
    hd = x.shape[-1]
    if kind == "none":
        return x
    if kind == "rope":
        cos, sin = _rope_angles(positions, hd, theta)          # (B,S,hd/2)
        return _apply_rot(x, cos[:, :, None, :], sin[:, :, None, :])
    if kind == "rope2d":
        # chatglm: rotary on the first half of head_dim only
        half = hd // 2
        cos, sin = _rope_angles(positions, half, theta)
        rot = _apply_rot(x[..., :half], cos[:, :, None, :], sin[:, :, None, :])
        return jnp.concatenate([rot, x[..., half:]], axis=-1)
    if kind == "mrope":
        # qwen2-vl: head_dim split into (t, h, w) sections (2:1:1)
        if positions.ndim == 2:
            positions = jnp.stack([positions] * 3, axis=-1)
        secs = [hd // 2, hd // 4, hd - hd // 2 - hd // 4]
        outs, start = [], 0
        for s_i, sec in enumerate(secs):
            cos, sin = _rope_angles(positions[..., s_i], sec, theta)
            outs.append(_apply_rot(x[..., start:start + sec],
                                   cos[:, :, None, :], sin[:, :, None, :]))
            start += sec
        return jnp.concatenate(outs, axis=-1)
    raise ValueError(kind)


# ---------------------------------------------------------------- attention

def attention_init(key, cfg: ModelConfig, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, h, hd)),
        "wk": _dense_init(ks[1], (d, kv, hd)),
        "wv": _dense_init(ks[2], (d, kv, hd)),
        "wo": _dense_init(ks[3], (h, hd, d), in_axis=0),
    }


def _expand_kv(k, n_rep):
    """(B,T,KV,hd) -> (B,T,H,hd). A broadcast XLA folds into the dot; keeps
    every attention tensor 4-D so head sharding propagates cleanly (the 5-D
    grouped-query reshape forces involuntary SPMD rematerializations)."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def _gqa_scores(q, k, n_rep):
    """q (B,S,H,hd), k (B,T,KV,hd) -> (B,H,S,T)."""
    k = _expand_kv(k, n_rep)
    return jnp.einsum("bshk,bthk->bhst", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(probs, v, n_rep):
    v = _expand_kv(v, n_rep)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def _banded_attention(q, k, v, q_pos, window, n_rep, scale):
    """Exact sliding-window attention in O(S·2w) instead of O(S²).

    q chunk i only ever attends chunks {i-1, i} when the chunk length equals
    the window, so scores shrink from (B,H,S,S) to (B,H,nq,w,2w) — both the
    HBM-traffic and FLOP terms drop by ~S/2w (4x for gemma3 train_4k).
    """
    b, s, h, hd = q.shape
    w = window
    nq = s // w
    k = _expand_kv(k, n_rep)
    v = _expand_kv(v, n_rep)
    qc = q.reshape(b, nq, w, h, hd)
    kc = k.reshape(b, nq, w, h, hd)
    vc = v.reshape(b, nq, w, h, hd)

    def with_prev(t, pad_val=0.0):
        prev = jnp.concatenate(
            [jnp.full_like(t[:, :1], pad_val), t[:, :-1]], axis=1)
        return jnp.concatenate([prev, t], axis=2)      # (b, nq, 2w, ...)

    k2 = with_prev(kc)
    v2 = with_prev(vc)
    qp = q_pos.reshape(b, nq, w)
    kp2 = jnp.concatenate(
        [jnp.concatenate([jnp.full_like(qp[:, :1], -10**9), qp[:, :-1]],
                         axis=1), qp], axis=2)          # (b, nq, 2w)

    scores = jnp.einsum("bnqhk,bnthk->bhnqt", qc, k2,
                        preferred_element_type=jnp.float32) * scale
    mask = (kp2[:, None, :, None, :] <= qp[:, None, :, :, None]) & \
           (qp[:, None, :, :, None] - kp2[:, None, :, None, :] < w)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhnqt,bnthk->bnqhk", probs, v2)
    return out.reshape(b, s, h, hd).astype(v.dtype)


def attention_apply(params, x, positions, cfg: ModelConfig, *, window: int = 0,
                    kv_x: Optional[Array] = None, causal: bool = True,
                    cache: Optional[dict] = None, rope: bool = True):
    """Full/sliding/cross attention with optional KV cache.

    window > 0  => sliding-window causal mask (gemma3 local layers).
    kv_x        => cross-attention onto encoder output (no mask, no rope).
    cache       => {'k','v','pos','write_idx'} ring buffer: 'pos' (B,T) holds
      each slot's absolute position (-1 = empty), so full caches (T=max_seq)
      and sliding-window rings (T=window+pad) share one code path.  x holds
      the new token(s); decode is s==1, prefill writes the last T positions.
    """
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    n_rep = h // kv
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    src = kv_x if kv_x is not None else x
    k = jnp.einsum("btd,dgk->btgk", src, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dgk->btgk", src, params["wv"].astype(x.dtype))
    if rope and kv_x is None and cfg.rope != "none":
        q = apply_rope(q, positions, cfg.rope, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope, cfg.rope_theta)
    q = shard(q, ("batch", "seq", "heads", None))

    q_pos = positions[..., 0] if positions.ndim == 3 else positions  # (B,S)
    new_cache = None
    slot_pos = None
    if cache is not None:
        T = cache["k"].shape[1]
        if s == 1:                                   # decode: ring write
            widx = cache["write_idx"]
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), widx, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), widx, axis=1)
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], q_pos.astype(jnp.int32), widx, axis=1)
        else:                                        # prefill: keep last T
            start = max(s - T, 0)
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k[:, start:].astype(cache["k"].dtype), 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v[:, start:].astype(cache["v"].dtype), 0, axis=1)
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], q_pos[:, start:].astype(jnp.int32), 0, axis=1)
        new_cache = {"k": ck, "v": cv, "pos": cpos,
                     "write_idx": cache["write_idx"]}
        if s == 1:                       # decode attends over the whole ring
            k, v, slot_pos = ck, cv, cpos
            k = shard(k, ("batch", "kv_seq", None, None))
            v = shard(v, ("batch", "kv_seq", None, None))

    # block-banded fast path for sliding-window layers (train/prefill)
    if (cfg.attn_impl == "banded" and window > 0 and kv_x is None
            and slot_pos is None and s % window == 0 and s // window >= 2):
        out = _banded_attention(q, k, v, q_pos, window, n_rep,
                                1.0 / np.sqrt(hd))
        out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
        out = shard(out, ("batch", "seq", "embed"))
        return out, new_cache

    t = k.shape[1]
    scores = _gqa_scores(q, k, n_rep) / np.sqrt(hd)           # (B,H,S,T) f32

    if slot_pos is not None:
        sp = slot_pos[:, None, None, :]
        mask = (sp >= 0) & (sp <= q_pos[:, None, :, None])
        if window > 0:
            mask = mask & (q_pos[:, None, :, None] - sp < window)
    elif kv_x is not None:
        mask = None                                            # cross: dense
    else:
        kv_pos = q_pos
        mask = kv_pos[:, None, None, :] <= q_pos[:, None, :, None] if causal else None
        if window > 0:
            wmask = q_pos[:, None, :, None] - kv_pos[:, None, None, :] < window
            mask = wmask if mask is None else (mask & wmask)

    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = _gqa_out(probs, v, n_rep)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    out = shard(out, ("batch", "seq", "embed"))
    return out, new_cache


# ---------------------------------------------------------------- gated MLP

def mlp_init(key, d, d_ff):
    ks = jax.random.split(key, 3)
    return {"wi": _dense_init(ks[0], (d, d_ff)),
            "wg": _dense_init(ks[1], (d, d_ff)),
            "wo": _dense_init(ks[2], (d_ff, d))}


def mlp_apply(params, x):
    h = jnp.einsum("bsd,df->bsf", x, params["wi"].astype(x.dtype))
    g = jnp.einsum("bsd,df->bsf", x, params["wg"].astype(x.dtype))
    h = jax.nn.silu(g) * h
    h = shard(h, ("batch", "seq", "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------- MoE

def moe_init(key, d, m: MoEConfig):
    ks = jax.random.split(key, 5)
    e, f = m.n_experts, m.d_ff_expert
    p = {
        "router": _dense_init(ks[0], (d, e)),
        "wi": _dense_init(ks[1], (e, d, f), in_axis=1),
        "wg": _dense_init(ks[2], (e, d, f), in_axis=1),
        "wo": _dense_init(ks[3], (e, f, d), in_axis=1),
    }
    if m.n_shared:
        fs = m.d_ff_shared or f
        p["shared"] = mlp_init(ks[4], d, m.n_shared * fs)
    return p


def _moe_batch_axes(T: int):
    """(mesh, batch_axes, G) for grouped dispatch: G = number of batch
    shards so each group's sort/scatter is physically shard-local.  The
    batch axes come from the active rules (inside a pod-manual region the
    batch maps to 'data' only).  Outside a mesh: (None, (), 1)."""
    from repro.parallel.sharding import _active
    ctx = _active()
    if ctx is None:
        return None, (), 1
    mesh, rules = ctx
    ba = rules.get("batch")
    if ba is None:
        return None, (), 1
    ba = (ba,) if isinstance(ba, str) else tuple(ba)
    g = 1
    for ax in ba:
        g *= mesh.shape[ax]
    if g > 1 and T % g == 0 and T // g >= 8:
        return mesh, ba, g
    return None, (), 1


def moe_apply(params, x, m: MoEConfig):
    """Top-k MoE, capacity-bucketed, grouped dispatch (static shapes).

    x: (B, S, d).  Tokens are split into G groups aligned with the batch
    (pod x data) shards; each group sorts/buckets its own tokens locally
    into (G, E, C_g, d), experts shard over 'model'.  See EXPERIMENTS.md
    §Perf B1/B2 for why the earlier global scatter was catastrophic.
    Returns (out, aux_losses dict).
    """
    b, s, d = x.shape
    T = b * s
    xt = x.reshape(T, d)
    e, k = m.n_experts, m.top_k
    gates = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                       params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(gates, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                      # (T,k)
    topw = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    mesh, ba, G = _moe_batch_axes(T)
    Tg = T // G
    cap = max(int(np.ceil(Tg * k / e * m.capacity_factor)), 4)

    def _dispatch_one(xg_l, ti_l):
        """(Tg, d), (Tg, k) -> local sort + capacity scatter (no comm)."""
        flat = ti_l.reshape(-1)
        sort_idx = jnp.argsort(flat, stable=True)
        sorted_e = flat[sort_idx]
        seg = jnp.searchsorted(sorted_e, sorted_e, side="left")
        rank = jnp.arange(Tg * k) - seg
        rank_c = jnp.where(rank < cap, rank, cap)              # cap => drop
        gathered = xg_l[sort_idx // k]
        bkt = jnp.zeros((e, cap, d), xg_l.dtype)
        bkt = bkt.at[sorted_e, rank_c].set(gathered, mode="drop")
        return bkt, sorted_e, rank_c, sort_idx

    def _combine_one(gb_l, sort_idx_l, topw_l):
        """(Tg*k, d) gathered expert rows -> per-token weighted sum."""
        out_flat = jnp.zeros((Tg * k, d), gb_l.dtype).at[sort_idx_l].set(gb_l)
        return (out_flat.reshape(Tg, k, d)
                * topw_l.astype(gb_l.dtype)[..., None]).sum(axis=1)

    xg = xt.reshape(G, Tg, d)
    ti_g = topi.reshape(G, Tg, k)
    if mesh is None:
        bkt, sorted_e, rank_c, sort_idx = jax.vmap(_dispatch_one)(xg, ti_g)
    else:
        # manual over the batch axes: the data-dependent sort/scatter is
        # compiled shard-local (the auto partitioner otherwise replicates
        # the operands => multi-TB collectives; EXPERIMENTS.md §Perf B1/B2)
        from jax.sharding import PartitionSpec as _P
        bkt, sorted_e, rank_c, sort_idx = _shard_map(
            jax.vmap(_dispatch_one), mesh=mesh,
            in_specs=(_P(ba), _P(ba)), out_specs=(_P(ba),) * 4,
            axis_names=set(ba), check_vma=False)(xg, ti_g)
    buckets = shard(bkt, ("batch", "expert", None, None))

    h = jnp.einsum("gecd,edf->gecf", buckets, params["wi"].astype(x.dtype))
    gt = jnp.einsum("gecd,edf->gecf", buckets, params["wg"].astype(x.dtype))
    h = jax.nn.silu(gt) * h
    expert_out = jnp.einsum("gecf,efd->gecd", h, params["wo"].astype(x.dtype))
    expert_out = shard(expert_out, ("batch", "expert", None, None))

    tw_g = topw.reshape(G, Tg, k)
    model_par = (mesh is not None and "model" in mesh.axis_names
                 and e % mesh.shape["model"] == 0)
    if mesh is None:
        g_idx = jnp.arange(G)[:, None]
        out_sorted = expert_out.at[g_idx, sorted_e, rank_c].get(
            mode="fill", fill_value=0)                         # (G, Tg*k, d)
        out = jax.vmap(_combine_one)(out_sorted, sort_idx, tw_g)
    elif not model_par:
        from jax.sharding import PartitionSpec as _P
        g_idx = jnp.arange(G)[:, None]
        out_sorted = expert_out.at[g_idx, sorted_e, rank_c].get(
            mode="fill", fill_value=0)
        out = _shard_map(
            jax.vmap(_combine_one), mesh=mesh,
            in_specs=(_P(ba), _P(ba), _P(ba)), out_specs=_P(ba),
            axis_names=set(ba), check_vma=False)(out_sorted, sort_idx, tw_g)
    else:
        # fully-manual combine: each model shard scatters only ITS experts'
        # rows into token space, then one bf16 psum of (Tg, d) crosses the
        # model axis — 2 orders of magnitude less traffic than letting SPMD
        # replicate expert_out for a cross-shard gather (§Perf B3)
        from jax.sharding import PartitionSpec as _P
        e_loc = e // mesh.shape["model"]

        def _combine_manual(eo_l, se_l, rc_l, si_l, tw_l):
            midx = jax.lax.axis_index("model")
            off = midx * e_loc
            le = se_l[0] - off
            mine = (le >= 0) & (le < e_loc) & (rc_l[0] < cap)
            rows = eo_l[0][jnp.clip(le, 0, e_loc - 1),
                           jnp.minimum(rc_l[0], cap - 1)]      # (Tg*k, d)
            rows = jnp.where(mine[:, None], rows, 0)
            out_flat = jnp.zeros((Tg * k, d), rows.dtype).at[si_l[0]].set(rows)
            out = (out_flat.reshape(Tg, k, d)
                   * tw_l[0].astype(rows.dtype)[..., None]).sum(axis=1)
            return jax.lax.psum(out, "model")[None]

        out = _shard_map(
            _combine_manual, mesh=mesh,
            in_specs=(_P(ba, "model"), _P(ba), _P(ba), _P(ba), _P(ba)),
            out_specs=_P(ba),
            axis_names=set(ba) | {"model"}, check_vma=False)(
            expert_out, sorted_e, rank_c, sort_idx, tw_g)
    out = out.reshape(T, d)

    if "shared" in params:
        out = out + mlp_apply(params["shared"], x).reshape(T, d)

    # aux losses: load-balance (Switch) + router z-loss
    me = probs.mean(axis=0)                                    # (E,)
    ce = jnp.zeros((e,)).at[topi.reshape(-1)].add(1.0) / (T * k)
    lb_loss = e * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(gates, axis=-1) ** 2)
    aux = {"moe_lb": lb_loss, "moe_z": m.router_zloss * z_loss}
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------- Mamba2 SSD

def mamba_init(key, cfg: ModelConfig):
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    nh = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    ks = jax.random.split(key, 6)
    return {
        # in_proj -> [z (d_in), x (d_in), B (g*n), C (g*n), dt (nh)]
        "in_proj": _dense_init(ks[0], (d, 2 * d_in + 2 * s.n_groups * s.d_state + nh)),
        "conv_w": _dense_init(ks[1], (s.conv_width, conv_ch), in_axis=0),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.clip(jax.random.uniform(ks[2], (nh,), jnp.float32,
                                        minval=1e-3, maxval=0.1), 1e-4, None))),
        "norm": rmsnorm_init(d_in),
        "out_proj": _dense_init(ks[3], (d_in, d)),
    }


def _segsum(a):
    """a: (..., Q) -> (..., Q, Q) lower-tri cumulative sums for SSD decay."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def _ssd_chunked(x, dt, A, B, C, chunk, init_state=None):
    """State-space dual (Mamba2 §6) in chunked form.

    x (b,s,h,p), dt (b,s,h) (already softplus'd), A (h,)<0,
    B, C (b,s,g,n) broadcast over heads-per-group.
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    nc = s // chunk
    r = lambda t: t.reshape(b, nc, chunk, *t.shape[2:])
    xc, dtc = r(x), r(dt)
    Bc = jnp.repeat(r(B), rep, axis=3)     # (b,nc,q,h,n)
    Cc = jnp.repeat(r(C), rep, axis=3)

    a = dtc * A[None, None, None, :]                           # (b,nc,q,h)
    a_cum = jnp.cumsum(a, axis=2)
    L = jnp.exp(_segsum(jnp.moveaxis(a, -1, 2)))               # (b,nc,h,q,q)
    xdt = xc * dtc[..., None]

    y_diag = jnp.einsum("bcqhn,bckhn,bchqk,bckhp->bcqhp", Cc, Bc, L, xdt)

    decay_states = jnp.exp(a_cum[:, :, -1:, :] - a_cum)        # (b,nc,q,h)
    states = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", Bc, decay_states, xdt)

    chunk_decay = jnp.exp(a_cum[:, :, -1, :])                  # (b,nc,h)

    def scan_fn(carry, inp):
        st, dec = inp
        new = carry * dec[..., None, None] + st
        return new, carry

    init = (jnp.zeros((b, h, p, n), x.dtype) if init_state is None
            else init_state.astype(x.dtype))
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)              # (b,nc,h,p,n)

    state_decay = jnp.exp(a_cum)                               # (b,nc,q,h)
    y_off = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp", Cc, prev_states, state_decay)
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, final


def mamba_apply(params, x, cfg: ModelConfig, cache: Optional[dict] = None):
    """Mamba2 block. x (B,S,d). cache = {'conv': (B,w-1,ch), 'ssm': (B,h,p,n)}
    for single-step decode (S==1)."""
    s_cfg: SSMConfig = cfg.ssm
    b, s, d = x.shape
    d_in = s_cfg.expand * d
    nh = d_in // s_cfg.head_dim
    g, n = s_cfg.n_groups, s_cfg.d_state
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(x.dtype))
    z, xs, Bm, Cm, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + g * n, 2 * d_in + 2 * g * n], axis=-1)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)           # (B,S,ch)
    w = params["conv_w"].astype(x.dtype)                       # (cw, ch)
    cw = w.shape[0]
    new_cache = None
    if cache is not None and s == 1:
        ctx = jnp.concatenate([cache["conv"].astype(x.dtype), conv_in], axis=1)
        conv_out = jnp.einsum("bwc,wc->bc", ctx[:, -cw:, :], w)[:, None, :]
        new_conv = ctx[:, -(cw - 1):, :]
    else:
        pad = jnp.pad(conv_in, ((0, 0), (cw - 1, 0), (0, 0)))
        stacked = jnp.stack([pad[:, i:i + s, :] for i in range(cw)], axis=2)
        conv_out = jnp.einsum("bswc,wc->bsc", stacked, w)
        new_conv = pad[:, -(cw - 1):, :] if s >= cw - 1 else None
    conv_out = jax.nn.silu(conv_out + params["conv_b"].astype(x.dtype))
    xs, Bm, Cm = jnp.split(conv_out, [d_in, d_in + g * n], axis=-1)

    xs = xs.reshape(b, -1, nh, s_cfg.head_dim)
    Bm = Bm.reshape(b, -1, g, n)
    Cm = Cm.reshape(b, -1, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"]).astype(x.dtype)  # (B,S,nh)
    A = -jnp.exp(params["A_log"]).astype(x.dtype)              # (nh,)

    if cache is not None and s == 1:
        # single-step recurrence
        st = cache["ssm"].astype(jnp.float32)
        dtq = dt[:, 0]                                         # (B,nh)
        dA = jnp.exp(dtq * A[None, :]).astype(jnp.float32)     # (B,nh)
        Bq = jnp.repeat(Bm[:, 0], nh // g, axis=1)             # (B,nh,n)
        Cq = jnp.repeat(Cm[:, 0], nh // g, axis=1)
        xq = (xs[:, 0] * dtq[..., None]).astype(jnp.float32)   # (B,nh,p)
        st = st * dA[..., None, None] + jnp.einsum("bhp,bhn->bhpn", xq,
                                                   Bq.astype(jnp.float32))
        y = jnp.einsum("bhpn,bhn->bhp", st, Cq.astype(jnp.float32))
        y = y.astype(x.dtype)[:, None] + params["D"].astype(x.dtype)[None, None, :, None] * xs
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype),
                     "ssm": st.astype(cache["ssm"].dtype)}
        y = y.reshape(b, 1, d_in)
    else:
        seq = xs.shape[1]
        chunk = min(s_cfg.chunk, seq)
        if seq % chunk:
            chunk = seq                      # tiny smoke shapes: one chunk
        init_state = cache["ssm"] if cache is not None else None
        y, final = _ssd_chunked(xs, dt, A, Bm, Cm, chunk, init_state=init_state)
        y = y + params["D"].astype(x.dtype)[None, None, :, None] * xs
        y = y.reshape(b, s, d_in)
        if cache is not None:                # prefill: hand state to decode
            new_cache = {"conv": new_conv.astype(cache["conv"].dtype),
                         "ssm": final.astype(cache["ssm"].dtype)}

    y = rmsnorm(params["norm"], y * jax.nn.silu(z))
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(x.dtype))
    return out, new_cache
