"""Attention: GQA self-attention (full / sliding-window / causal), cross
attention (VLM), and cached decode.

 * The prefill/train path is flash-style chunked attention (lax.scan +
   online softmax): [S, S] score matrices are never materialized — required
   for the 32k-prefill / 500k shapes and gives XLA a fusable HLO.
 * GQA is computed with grouped einsums — KV heads are NEVER repeated into
   full head count (a naive repeat at llama-90B decode_32k would materialize
   a 68 TB tensor).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .layers import _dense_init, apply_rope

Params = Dict[str, Any]
NEG = -1e30


def init_attention(key, cfg, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": _dense_init(ks[0], (d, nh * hd), dt),
        "wk": _dense_init(ks[1], (d, nkv * hd), dt),
        "wv": _dense_init(ks[2], (d, nkv * hd), dt),
        "wo": _dense_init(ks[3], (nh * hd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


def _project_qkv(p: Params, x: jax.Array, kv_x: jax.Array, cfg):
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    g = nh // nkv
    q = q.reshape(*x.shape[:-1], nkv, g, hd)       # grouped query heads
    k = k.reshape(*kv_x.shape[:-1], nkv, hd)
    v = v.reshape(*kv_x.shape[:-1], nkv, hd)
    return q, k, v


def flash_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset: int = 0, block_kv: int = 1024) -> jax.Array:
    """Chunked attention with online softmax, GQA-grouped.

    q (B, Sq, Kv, G, Dh); k, v (B, Skv, Kv, Dh).
    window > 0 limits attention to the last `window` positions.
    Returns (B, Sq, Kv, G, Dh).
    """
    b, sq, kv_h, g, hd = q.shape
    skv = k.shape[1]
    block_kv = min(block_kv, skv)
    pad = (-skv) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = k.shape[1] // block_kv
    kb = jnp.moveaxis(k.reshape(b, n_blocks, block_kv, kv_h, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, n_blocks, block_kv, kv_h, hd), 1, 0)
    scale = hd ** -0.5
    q_pos = q_offset + jnp.arange(sq)

    def chunk(carry, xs):
        m_prev, s_prev, o_prev = carry
        kc, vc, blk = xs
        kv_pos = blk * block_kv + jnp.arange(block_kv)
        s = jnp.einsum("bqkgd,bckd->bkgqc", q, kc,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((sq, block_kv), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window:
            mask &= q_pos[:, None] - kv_pos[None, :] < window
        mask &= (kv_pos < skv)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        s_new = s_prev * alpha + jnp.sum(p, axis=-1)
        o_new = o_prev * alpha[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, s_new, o_new), None

    init = (jnp.full((b, kv_h, g, sq), NEG, jnp.float32),
            jnp.zeros((b, kv_h, g, sq), jnp.float32),
            jnp.zeros((b, kv_h, g, sq, hd), jnp.float32))
    chunk_fn = jax.checkpoint(chunk)  # recompute scores in bwd (flash-style)
    (m, s, o), _ = jax.lax.scan(chunk_fn, init,
                                (kb, vb, jnp.arange(n_blocks)))
    out = o / jnp.maximum(s, 1e-30)[..., None]       # (B, Kv, G, Sq, Dh)
    return jnp.moveaxis(out, 3, 1).astype(q.dtype)   # (B, Sq, Kv, G, Dh)


def self_attention(p: Params, x: jax.Array, cfg, *, window: int = 0,
                   positions: Optional[jax.Array] = None) -> jax.Array:
    """Training / prefill self-attention (causal)."""
    q, k, v = _project_qkv(p, x, x, cfg)
    if positions is None:
        positions = jnp.arange(x.shape[1])
    b, s = x.shape[:2]
    qf = q.reshape(b, s, -1, q.shape[-1])            # (B,S,H,Dh) for rope
    qf = apply_rope(qf, positions, cfg.rope_theta)
    q = qf.reshape(q.shape)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = flash_attention(q, k, v, causal=True, window=window)
    return o.reshape(*x.shape[:-1], -1) @ p["wo"]


def cross_attention(p: Params, x: jax.Array, kv_feats: jax.Array,
                    cfg) -> jax.Array:
    """VLM cross-attn: queries from text stream, kv from image embeddings."""
    q, k, v = _project_qkv(p, x, kv_feats, cfg)
    o = flash_attention(q, k, v, causal=False)
    return o.reshape(*x.shape[:-1], -1) @ p["wo"]


class KVCache(NamedTuple):
    k: jax.Array       # (B, S_max, n_kv, Dh)
    v: jax.Array


def ring_slot(pos, window: int = 0):
    """Cache row that the token at absolute position `pos` occupies: `pos`
    itself, or `pos % window` in a sliding-window layer's ring buffer."""
    return pos % window if window else pos


def decode_attend(p: Params, x: jax.Array, cache: KVCache, pos, cfg, *,
                  window: int = 0):
    """Single-token decode attention that reads `cache` and does not write
    it. x (B, 1, d); pos: absolute position — a scalar shared by the batch
    (generate()'s lock-step loop) or an (B,) vector of per-stream positions
    (the continuous-batching slot table, where each lane is at a different
    depth of its own request).

    The query scores the cached positions before `pos` and the token's own
    k, in the cache's dtype, under one softmax: the same set the cache holds
    once the token is written. In a sliding-window ring the slot the token
    will overwrite (position `pos - window`) is left out.

    Returns (out (B, 1, d), (k, v)), k and v (B, 1, n_kv, Dh) in the cache's
    dtype: the rows to write at `ring_slot(pos, window)`.
    """
    q, k, v = _project_qkv(p, x, x, cfg)             # q (B,1,Kv,G,Dh)
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    posv = pos[:, None] if per_slot else pos[None]   # (B,1) | (1,)
    b = x.shape[0]
    qf = apply_rope(q.reshape(b, 1, -1, q.shape[-1]), posv, cfg.rope_theta)
    q = qf.reshape(q.shape)
    k = apply_rope(k, posv, cfg.rope_theta).astype(cache.k.dtype)
    v = v.astype(cache.v.dtype)
    s_max = cache.k.shape[1]
    # the write clamps its row into the cache, so the mask does too
    slot = jnp.clip(ring_slot(pos, window), 0, s_max - 1)
    kv_idx = jnp.arange(s_max)
    posb = pos[:, None] if per_slot else pos[None, None]
    slotb = slot[:, None] if per_slot else slot[None, None]
    mask = (kv_idx < jnp.minimum(posb, s_max)) & (kv_idx != slotb)
    scale = cfg.resolved_head_dim ** -0.5
    s_c = jnp.einsum("bqkgd,bskd->bkgqs", q, cache.k,
                     preferred_element_type=jnp.float32) * scale
    s_c = jnp.where(mask[:, None, None, None, :], s_c, NEG)
    s_t = jnp.einsum("bqkgd,bqkd->bkgq", q, k,
                     preferred_element_type=jnp.float32)[..., None] * scale
    m = jnp.maximum(jnp.max(s_c, axis=-1, keepdims=True), s_t)
    e_c = jnp.exp(s_c - m)
    e_t = jnp.exp(s_t - m)
    den = jnp.sum(e_c, axis=-1, keepdims=True) + e_t
    a_c = (e_c / den).astype(v.dtype)
    a_t = (e_t / den).astype(v.dtype)
    o = (jnp.einsum("bkgqs,bskd->bqkgd", a_c, cache.v,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bkgqs,bqkd->bqkgd", a_t, v,
                      preferred_element_type=jnp.float32))
    out = o.astype(v.dtype).reshape(*x.shape[:-1], -1) @ p["wo"]
    return out, (k, v)


def decode_self_attention(p: Params, x: jax.Array, cache: KVCache, pos,
                          cfg, *, window: int = 0):
    """Single-token decode for one layer: `decode_attend`, then the token's
    rows written into this layer's cache (`write_token_rows`). The grouped
    stacks (local/global, vision, hybrid) decode through it, each layer's
    cache a scan input and its updated cache a scan output. The homogeneous
    stack (`Model.decode_step`) calls `decode_attend` in its layer scan
    instead, reading every layer's cache in place, and writes the rows of
    all layers after the scan.

    Returns (out (B, 1, d), updated cache). For sliding-window layers the
    cache is a ring buffer of length `window`.
    """
    out, (k, v) = decode_attend(p, x, cache, pos, cfg, window=window)
    slot = ring_slot(pos, window)
    with jax.named_scope("kv_cache"):
        new_k = write_token_rows(cache.k, k[:, 0], slot)
        new_v = write_token_rows(cache.v, v[:, 0], slot)
    return out, KVCache(k=new_k, v=new_v)


def write_token_rows(leaf: jax.Array, rows: jax.Array, slot) -> jax.Array:
    """Write one token's rows of every stacked layer into `leaf` (*stack, B,
    S, n_kv, Dh) at position `slot`: rows (*stack, B, n_kv, Dh); slot a
    scalar (one update for the whole batch) or (B,) (one update per lane;
    the lane count is static). Each is a `dynamic_update_slice` that XLA
    does in place on a donated cache: a vmapped or `.at[].set` write lowers
    to a general scatter, for which XLA relays out the whole cache."""
    slot = jnp.asarray(slot, jnp.int32)
    rows = jnp.expand_dims(rows.astype(leaf.dtype), -3)   # (*stack,B,1,..)
    starts = [jnp.int32(0)] * leaf.ndim
    if slot.ndim == 0:
        starts[-3] = slot
        return jax.lax.dynamic_update_slice(leaf, rows, starts)
    for lane in range(leaf.shape[-4]):
        starts[-4] = jnp.int32(lane)
        starts[-3] = slot[lane]
        row = jax.lax.slice_in_dim(rows, lane, lane + 1, axis=rows.ndim - 4)
        leaf = jax.lax.dynamic_update_slice(leaf, row, starts)
    return leaf


# -- lane-window KV block ops (serve.prefix_cache) ---------------------------
#
# The serving decode state keeps the lane batch at axis -4 and token
# positions at axis -3 of every k/v leaf (launch.mesh.serve_cache_spec's
# convention; the model's scanned layers stack extra leading axes). These
# two primitives are the whole traced surface of the shared prefix cache:
# a contiguous multi-token read out of one lane, and a contiguous
# multi-token write back into one lane — both with TRACED lane index and
# start position, so one compilation serves every (slot, offset) pair.


def slice_lane_window(leaf: jax.Array, lane, start, length: int) -> jax.Array:
    """Read `length` consecutive KV rows of one lane: leaf (*stack, S, L,
    n_kv, Dh) -> (*stack, 1, length, n_kv, Dh). `lane`/`start` may be
    traced; `length` is static."""
    nd = leaf.ndim
    starts = [jnp.int32(0)] * nd
    starts[-4] = jnp.asarray(lane, jnp.int32)
    starts[-3] = jnp.asarray(start, jnp.int32)
    sizes = list(leaf.shape)
    sizes[-4] = 1
    sizes[-3] = length
    return jax.lax.dynamic_slice(leaf, starts, sizes)


def write_lane_window(leaf: jax.Array, rows: jax.Array, lane,
                      start) -> jax.Array:
    """Multi-token append: write `rows` (*stack, 1, length, n_kv, Dh) into
    one lane of `leaf` at positions [start, start+length). The per-token
    `write_token_rows` generalized to a contiguous window — the prefix-cache
    copy lands L tokens of KV in one dynamic_update_slice instead of L
    replay steps."""
    nd = leaf.ndim
    starts = [jnp.int32(0)] * nd
    starts[-4] = jnp.asarray(lane, jnp.int32)
    starts[-3] = jnp.asarray(start, jnp.int32)
    return jax.lax.dynamic_update_slice(leaf, rows.astype(leaf.dtype),
                                        starts)
