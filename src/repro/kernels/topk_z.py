"""Fused decode-time scoring: chunked q.W^T + online logsumexp + running top-k.

One pass over the (sharded) vocab produces, per query row, exact log Z and the
top-k candidate (score, id) pairs — the inputs the paper's Eq. 2/3 needs —
without materializing [Q, V] logits in HBM. With vocab sharded over ``model``
this kernel runs on the local shard; the O(k) merge lives in
``repro.core.distributed``.

Mosaic has no generic lax.top_k, so the running top-k is maintained by an
unrolled k-step max/mask sweep over [running_topk ++ tile_scores] using only
max/where/iota reductions (k is small and static: 1-32 for decode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_tpu

NEG = -1e30
BIG = 2 ** 30  # python int — becomes an inline literal inside the kernel


def _select_topk(cand_v, cand_i, k):
    """Top-k of each row via k max/mask sweeps (Mosaic-safe)."""
    out_v, out_i = [], []
    iota = jax.lax.broadcasted_iota(jnp.int32, cand_v.shape, 1)
    for _ in range(k):
        m = jnp.max(cand_v, axis=1, keepdims=True)              # (Q,1)
        pos = jnp.min(jnp.where(cand_v == m, iota, BIG), axis=1,
                      keepdims=True)
        sel = iota == pos
        out_v.append(m)
        out_i.append(jnp.sum(jnp.where(sel, cand_i, 0), axis=1,
                             keepdims=True))
        cand_v = jnp.where(sel, NEG, cand_v)
    return jnp.concatenate(out_v, axis=1), jnp.concatenate(out_i, axis=1)


def _topk_z_kernel(h_ref, w_ref, lse_ref, topv_ref, topi_ref,
                   m_scr, s_scr, tv_scr, ti_scr,
                   *, k: int, block_v: int, v_total: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        s_scr[...] = jnp.zeros_like(s_scr)
        tv_scr[...] = jnp.full_like(tv_scr, NEG)
        ti_scr[...] = jnp.zeros_like(ti_scr)

    h = h_ref[...]
    w = w_ref[...]
    scores = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(col < v_total, scores, NEG)

    # online logsumexp
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    s_scr[...] = (s_scr[...] * jnp.exp(m_prev - m_new) +
                  jnp.sum(jnp.exp(scores - m_new), axis=1, keepdims=True))
    m_scr[...] = m_new

    # running top-k merge
    cand_v = jnp.concatenate([tv_scr[...], scores], axis=1)
    cand_i = jnp.concatenate([ti_scr[...], col], axis=1)
    tv, ti = _select_topk(cand_v, cand_i, k)
    tv_scr[...] = tv
    ti_scr[...] = ti

    @pl.when(vi == pl.num_programs(1) - 1)
    def _fin():
        lse_ref[...] = m_scr[...] + jnp.log(s_scr[...])
        topv_ref[...] = tv_scr[...]
        topi_ref[...] = ti_scr[...]


def topk_z(h, w, k: int, *, block_q=128, block_v=512, interpret=None):
    """h (Q, d), w (V, d) -> (lse (Q,), topv (Q, k), topi (Q, k))."""
    if interpret is None:
        interpret = not on_tpu()
    q, d = h.shape
    v = w.shape[0]
    block_q = min(block_q, max(8, q))
    block_v = min(block_v, max(128, v))
    pad_q = (-q) % block_q
    pad_v = (-v) % block_v
    hp = jnp.pad(h, ((0, pad_q), (0, 0)))
    wp = jnp.pad(w, ((0, pad_v), (0, 0)))
    qp, vp = hp.shape[0], wp.shape[0]
    kernel = functools.partial(_topk_z_kernel, k=k, block_v=block_v,
                               v_total=v)
    lse, topv, topi = pl.pallas_call(
        kernel,
        grid=(qp // block_q, vp // block_v),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, vi: (qi, 0)),
            pl.BlockSpec((block_v, d), lambda qi, vi: (vi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda qi, vi: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, vi: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, vi: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
    )(hp, wp)
    return lse[:q, 0], topv[:q], topi[:q]
