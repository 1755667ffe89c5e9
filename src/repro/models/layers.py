"""Model building blocks: norms, rotary, blocked GQA attention, SwiGLU MLP,
and a capacity-based sorted-dispatch MoE.

Conventions:

* params are plain dicts of jnp arrays; every init function returns
  ``(params, axes)`` where ``axes`` mirrors the params tree with a tuple of
  *logical axis names* per dimension (resolved to mesh axes in
  ``distributed/sharding.py``);
* compute dtype = cfg.dtype (bf16 in production), accumulation in f32 via
  ``preferred_element_type``;
* attention over long sequences is *blocked* over query chunks (exact, not
  approximate) so the T x T score matrix never materializes whole -- the
  TPU-native replacement for a CUDA fused kernel;
* the MoE dispatch sorts tokens by expert within each batch row (shard-local
  by construction: the sorted axis is the unsharded T axis), scattering into
  an (E, C, D) capacity buffer -- the standard "dropping" formulation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------- sharding-constraint helpers

TP_AXES = {"heads", "kv", "ff", "vocab", "experts",
           "ssm_inner", "ssm_heads", "ssm_conv_ch"}


def _ambient_mesh():
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def _wsc(x, parts):
    """with_sharding_constraint against the ambient mesh (no-op without)."""
    return jax.lax.with_sharding_constraint(x, jax.sharding.PartitionSpec(*parts))


def gather_fsdp_weights(p_layer, axes_layer):
    """FSDP weight gather: constrain each layer weight to its TP-only spec
    (data axes dropped), so GSPMD all-gathers the (small) weight shards once
    per layer instead of all-reducing (huge) partial-sum activations.

    ``axes_layer`` is the logical-axes tree of one layer's params (leading
    "layers" axis already stripped)."""
    am = _ambient_mesh()
    if am is None or "model" not in am.axis_names:
        return p_layer
    msz = am.shape["model"]

    def one(ax, w):
        parts = []
        used = False
        for dim, a in zip(w.shape, ax):
            if a in TP_AXES and not used and dim % msz == 0:
                parts.append("model")
                used = True
            else:
                parts.append(None)
        return _wsc(w, parts)

    return jax.tree.map(one, axes_layer, p_layer,
                        is_leaf=lambda a: isinstance(a, tuple))


def strip_layer_axis(axes_layer_tree):
    """Drop the leading "layers" stacking axis from an axes tree."""
    return jax.tree.map(
        lambda a: tuple(a[1:]), axes_layer_tree,
        is_leaf=lambda a: isinstance(a, tuple),
    )


def pin_activation_batch(x):
    """Constrain an activation tensor to batch-sharded / feature-replicated.

    With FSDP weight specs, GSPMD's propagation can flip to a
    weight-stationary layout (batch replicated, features sharded over data),
    which turns every projection into a full-batch f32 reshard.  Pinning the
    residual stream at layer boundaries keeps the canonical data-parallel
    layout, so FSDP resolves into cheap per-layer weight all-gathers."""
    am = _ambient_mesh()
    if am is None:
        return x
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    if not dp:
        return x
    dpsz = 1
    for a in dp:
        dpsz *= am.shape[a]
    if x.shape[0] % dpsz != 0:
        return x
    parts = [dp if len(dp) > 1 else dp[0]] + [None] * (x.ndim - 1)
    return _wsc(x, parts)


# ----------------------------------------------------------------- plumbing

def normal_init(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: (..., T, H, D), positions: (..., T)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,T,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention

def init_attention(key, cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd()
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    sc = d ** -0.5
    p = {
        "wq": normal_init(ks[0], (d, h * hd), sc, dt),
        "wk": normal_init(ks[1], (d, kv * hd), sc, dt),
        "wv": normal_init(ks[2], (d, kv * hd), sc, dt),
        "wo": normal_init(ks[3], (h * hd, d), (h * hd) ** -0.5, dt),
    }
    a = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv"),
        "wv": ("embed", "kv"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
        a["bq"], a["bk"], a["bv"] = ("heads",), ("kv",), ("kv",)
    return p, a


def _qkv(p, x, cfg: ModelConfig):
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd()
    q = jnp.einsum("btd,dh->bth", x, p["wq"])
    k = jnp.einsum("btd,dh->bth", x, p["wk"])
    v = jnp.einsum("btd,dh->bth", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    b, t = x.shape[:2]
    return (
        q.reshape(b, t, h, hd),
        k.reshape(b, t, kv, hd),
        v.reshape(b, t, kv, hd),
    )


def _gqa_scores_block(q, k, scale):
    """q: (B,Tq,KV,G,hd), k: (B,S,KV,hd) -> (B,KV,G,Tq,S) f32."""
    return jnp.einsum(
        "btkgh,bskh->bkgts", q, k, preferred_element_type=jnp.float32
    ) * scale


def blocked_causal_attention(
    q, k, v, *, q_block: int, q_offset: int = 0, attn_chunk: int = 0
):
    """Exact causal GQA attention, blocked over query chunks.

    q: (B,T,H,hd); k,v: (B,S,KV,hd).  Query position i attends to key
    positions <= i + q_offset (and, with attn_chunk>0, only keys in the same
    local chunk -- llama4-style chunked attention).
    Returns (B,T,H,hd).
    """
    b, t, h, hd = q.shape
    s = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    scale = hd ** -0.5
    qb = min(q_block, t)
    while t % qb:  # largest block <= q_block that divides t (ragged prefixes)
        qb -= 1
    nq = t // qb
    qr = q.reshape(b, nq, qb, kvh, g, hd)

    kpos = jnp.arange(s)

    def one_block(i):
        qi = qr[:, i]
        qpos = q_offset + i * qb + jnp.arange(qb)
        scores = _gqa_scores_block(qi, k, scale)  # (B,KV,G,qb,S)
        mask = kpos[None, :] <= qpos[:, None]
        if attn_chunk:
            mask &= (kpos[None, :] // attn_chunk) == (qpos[:, None] // attn_chunk)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bkgts,bskh->btkgh", w.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, qb, h, hd).astype(q.dtype)

    if nq == 1:
        return one_block(0)
    outs = jax.lax.map(one_block, jnp.arange(nq))  # (nq,B,qb,H,hd)
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, h, hd)


def seq_sharded_attention(q, k, v, *, q_offset: int = 0, attn_chunk: int = 0):
    """Exact causal GQA attention with the query *time* axis sharded over the
    model mesh axis (context parallelism).

    For architectures whose head count does not divide the TP degree (e.g.
    llama4's 40 heads or smollm's 9 on a 16-way model axis), head-sharding
    degenerates to hd-dim partial sums and GSPMD emits giant score-tensor
    all-reduces.  Sharding query time instead keeps every contraction local:
    the only collective is an all-gather of K/V (tiny by comparison).
    """
    am = _ambient_mesh()
    b, t, h, hd = q.shape
    s_len = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    msz = am.shape["model"]
    tq = t // msz
    dp = tuple(a for a in ("pod", "data") if a in am.axis_names)
    dpsz = 1
    for a in dp:
        dpsz *= am.shape[a]
    bpart = (dp if len(dp) > 1 else dp[0]) if (dp and b % dpsz == 0) else None

    qr = q.reshape(b, msz, tq, kvh, g, hd)
    qr = _wsc(qr, (bpart, "model", None, None, None, None))
    k = _wsc(k, (bpart, None, None, None))
    v = _wsc(v, (bpart, None, None, None))
    scale = hd ** -0.5
    scores = jnp.einsum(
        "bmtkgh,bskh->bmkgts", qr, k, preferred_element_type=jnp.float32
    ) * scale  # (b, msz, kv, g, tq, s)
    kpos = jnp.arange(s_len)
    qpos = (
        q_offset
        + jax.lax.broadcasted_iota(jnp.int32, (msz, tq), 0) * tq
        + jax.lax.broadcasted_iota(jnp.int32, (msz, tq), 1)
    )
    mask = kpos[None, None, :] <= qpos[:, :, None]  # (msz, tq, s)
    if attn_chunk:
        mask &= (kpos[None, None, :] // attn_chunk) == (qpos[:, :, None] // attn_chunk)
    scores = jnp.where(mask[None, :, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bmkgts,bskh->bmtkgh", w.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, h, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, attn_chunk: int = 0):
    """Single-token attention over a KV cache.

    q: (B,1,H,hd); caches: (B,S,KV,hd); cache_len: scalar count of valid
    entries (the new token's K/V must already be written at cache_len-1).
    """
    b, _, h, hd = q.shape
    s = k_cache.shape[1]
    kvh = k_cache.shape[2]
    qr = q.reshape(b, 1, kvh, h // kvh, hd)
    scores = _gqa_scores_block(qr, k_cache, hd ** -0.5)  # (B,KV,G,1,S)
    kpos = jnp.arange(s)
    mask = kpos < cache_len
    if attn_chunk:
        qpos = cache_len - 1
        mask &= (kpos // attn_chunk) == (qpos // attn_chunk)
    scores = jnp.where(mask[None, None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bskh->btkgh", w.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def attention_apply(
    p,
    x,
    cfg: ModelConfig,
    *,
    positions,
    kv_cache=None,
    cache_len=None,
    q_block: int = 512,
):
    """Unified attention: training/prefill (kv_cache=None -> returns fresh
    cache) or decode (kv_cache given, x is (B,1,D))."""
    h, hd = cfg.n_heads, cfg.hd()
    b = x.shape[0]
    q, k, v = _qkv(p, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if kv_cache is None:
        am = _ambient_mesh()
        t = q.shape[1]
        if (
            cfg.attn_seq_shard
            and am is not None
            and "model" in am.axis_names
            and t % am.shape["model"] == 0
        ):
            out = seq_sharded_attention(q, k, v, attn_chunk=cfg.attn_chunk)
        else:
            out = blocked_causal_attention(
                q, k, v, q_block=q_block, attn_chunk=cfg.attn_chunk
            )
        new_cache = (k, v)
    else:
        kc, vc = kv_cache
        idx = cache_len - 1
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, idx, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, idx, axis=1)
        out = decode_attention(q, kc, vc, cache_len, attn_chunk=cfg.attn_chunk)
        new_cache = (kc, vc)
    acc = jnp.bfloat16 if cfg.bf16_reduce else None
    y = jnp.einsum("btf,fd->btd", out.reshape(b, -1, h * hd), p["wo"],
                   preferred_element_type=acc)
    return y, new_cache


# ------------------------------------------------------------------- MLP

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None, gated: bool = True):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    p = {
        "w_in": normal_init(ks[0], (d, ff), d ** -0.5, dt),
        "w_out": normal_init(ks[2], (ff, d), ff ** -0.5, dt),
    }
    a = {"w_in": ("embed", "ff"), "w_out": ("ff", "embed")}
    if gated:
        p["w_gate"] = normal_init(ks[1], (d, ff), d ** -0.5, dt)
        a["w_gate"] = ("embed", "ff")
    return p, a


def mlp_apply(p, x, bf16_reduce: bool = False):
    h = jnp.einsum("btd,df->btf", x, p["w_in"])
    if "w_gate" in p:
        g = jnp.einsum("btd,df->btf", x, p["w_gate"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    acc = jnp.bfloat16 if bf16_reduce else None
    return jnp.einsum("btf,fd->btd", h, p["w_out"], preferred_element_type=acc)


# ------------------------------------------------------------------- MoE

def init_moe(key, cfg: ModelConfig):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": normal_init(ks[0], (d, e), d ** -0.5, jnp.float32),
        "w_gate": normal_init(ks[1], (e, d, ff), d ** -0.5, dt),
        "w_in": normal_init(ks[2], (e, d, ff), d ** -0.5, dt),
        "w_out": normal_init(ks[3], (e, ff, d), ff ** -0.5, dt),
    }
    a = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "ff"),
        "w_in": ("experts", "embed", "ff"),
        "w_out": ("experts", "ff", "embed"),
    }
    if cfg.shared_expert_ff:
        sp, sa = init_mlp(ks[4], cfg, d_ff=cfg.shared_expert_ff)
        p["shared"], a["shared"] = sp, sa
    return p, a


def moe_apply(p, x, cfg: ModelConfig):
    """Capacity-based top-k MoE with shard-local sorted dispatch.

    The sort runs along the (unsharded) token axis of each batch row, so the
    dispatch is local to every data shard; expert FFN weights are sharded on
    (experts x ff) over the model axis.
    """
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = max(1, int(np.ceil(t * k / e * cfg.capacity_factor)))

    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (b,t,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_e.reshape(b, t * k)
    flat_p = top_p.reshape(b, t * k)
    order = jnp.argsort(flat_e, axis=-1)  # (b, tk)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    sorted_p = jnp.take_along_axis(flat_p, order, axis=-1)
    token_of = order // k  # source token per sorted slot
    onehot = jax.nn.one_hot(sorted_e, e, dtype=jnp.int32)  # (b,tk,e)
    pos_in_e = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=1), sorted_e[..., None], axis=-1
    )[..., 0] - 1  # (b,tk)
    keep = pos_in_e < cap
    slot = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # drop -> OOB

    def scatter_row(xr, token_idx, slot_idx):
        gathered = jnp.take(xr, token_idx, axis=0)  # (tk, d)
        buf = jnp.zeros((e * cap + 1, d), xr.dtype)
        return buf.at[slot_idx].add(gathered)[:-1]

    buf = jax.vmap(scatter_row)(x, token_of, slot)  # (b, e*cap, d)
    buf = buf.reshape(b, e, cap, d)
    gate = jnp.einsum("becd,edf->becf", buf, p["w_gate"])
    up = jnp.einsum("becd,edf->becf", buf, p["w_in"])
    act = jax.nn.silu(gate) * up
    out = jnp.einsum("becf,efd->becd", act, p["w_out"])  # (b,e,cap,d)
    out = out.reshape(b, e * cap, d)

    def gather_row(outr, slot_idx, probs_r, keep_r, token_idx):
        vals = jnp.take(
            jnp.concatenate([outr, jnp.zeros((1, d), outr.dtype)], axis=0),
            slot_idx, axis=0,
        )  # (tk, d)
        vals = vals * (probs_r * keep_r)[:, None].astype(vals.dtype)
        y = jnp.zeros((t, d), outr.dtype)
        return y.at[token_idx].add(vals)

    y = jax.vmap(gather_row)(out, slot, sorted_p, keep.astype(jnp.float32), token_of)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x)
    return y.astype(x.dtype)
