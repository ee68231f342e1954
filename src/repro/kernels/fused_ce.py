"""Streaming fused cross-entropy over the vocabulary (Pallas TPU).

The training-time analogue of the paper's problem: the O(N) softmax
normalization. We cannot make *training* CE sublinear (every class receives
gradient), but we convert it from memory-bound to compute-bound by never
materializing the [tokens, vocab] logits in HBM: scores are produced tile by
tile in VMEM with an online (flash-style) logsumexp, and the backward pass
recomputes each tile's softmax while accumulating dh / dW.

HBM traffic per step drops from  T*V*4 (logits write+read)  to  T*d + V*d
(+ the tiny per-token outputs) — for gemma3-4b's V=262144 at T=8192 that is
~8.6 GB of logits traffic eliminated per microbatch.

VMEM budget per grid step (bf16, defaults block_t=256, block_v=512, d<=8192):
  h tile 256*8192*2 = 4 MiB, w tile 512*8192*2 = 8 MiB, scores f32 0.5 MiB
— fits the ~16 MiB/core budget with double buffering handled by Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import on_tpu

NEG = -1e30


def _fwd_kernel(h_ref, w_ref, lab_ref, nll_ref, lse_ref,
                m_scr, s_scr, p_scr, *, block_v: int, v_total: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        s_scr[...] = jnp.zeros_like(s_scr)
        p_scr[...] = jnp.full_like(p_scr, NEG)

    h = h_ref[...]
    w = w_ref[...]
    scores = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (Tt, Vt)
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(col < v_total, scores, NEG)

    lab = lab_ref[...]                                  # (Tt, 1)
    hit = col == lab
    p_scr[...] = jnp.maximum(
        p_scr[...], jnp.max(jnp.where(hit, scores, NEG), axis=1,
                            keepdims=True))

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    s_scr[...] = (s_scr[...] * jnp.exp(m_prev - m_new) +
                  jnp.sum(jnp.exp(scores - m_new), axis=1, keepdims=True))
    m_scr[...] = m_new

    @pl.when(vi == pl.num_programs(1) - 1)
    def _fin():
        lse = m_scr[...] + jnp.log(s_scr[...])
        lse_ref[...] = lse
        nll_ref[...] = lse - p_scr[...]


def _bwd_kernel(h_ref, w_ref, lab_ref, lse_ref, gn_ref, go_ref, dw_in_ref,
                dh_ref, dw_ref, *, block_v: int, v_total: int,
                alias_dw: bool):
    """One fused backward step: the (Tt, Vt) score tile and its softmax are
    computed ONCE and feed both dh and dW (the seed ran two kernels, paying
    the matmul + softmax recompute and the h/w tile traffic twice).

    Grid is (gt, gv) with the vocab axis innermost:
      * dh block (ti): revisited consecutively across the vi sweep, so it
        accumulates in VMEM and writes back once per sweep.
      * dW block (vi): revisited once per sweep (stride gv). Two modes:
          alias_dw=True (compiled TPU): accumulate through HBM via
            input_output_aliases — read the running total from the aliased
            input, add this tile's contribution, write back. The caller pads
            the vocab grid to gv >= 3, putting >= 2 full grid steps between
            the write-back of step s and the (lookahead-1) prefetch of step
            s+gv. NOTE: this path is exercised only on real TPU — interpret
            mode (CI) takes the alias_dw=False branch below.
          alias_dw=False (interpret): the interpreter loads/stores out blocks
            around every step, so plain out-block accumulation is exact
            (the aliased input is never re-read there, which would drop all
            but the last t-sweep's contribution).
    """
    ti = pl.program_id(0)
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    h = h_ref[...]
    w = w_ref[...]
    scores = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    p = jnp.where(col < v_total, jnp.exp(scores - lse_ref[...]), 0.0)
    onehot = jnp.where(col == lab_ref[...], 1.0, 0.0)
    coef = gn_ref[...] * p - go_ref[...] * onehot       # (Tt, Vt) f32
    dh_ref[...] += jax.lax.dot_general(
        coef.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dw_delta = jax.lax.dot_general(
        coef.astype(h.dtype), h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if alias_dw:
        dw_ref[...] = dw_in_ref[...] + dw_delta
    else:
        @pl.when(ti == 0)
        def _init_dw():
            dw_ref[...] = jnp.zeros_like(dw_ref)
        dw_ref[...] += dw_delta


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def fused_ce_fwd(h, w, labels, *, block_t=256, block_v=512, interpret=None):
    """Forward: (nll (T,), lse (T,)). h (T, d), w (V, d), labels (T,)."""
    if interpret is None:
        interpret = not on_tpu()
    t, d = h.shape
    v = w.shape[0]
    block_t = min(block_t, max(8, t))
    block_v = min(block_v, max(128, v))
    hp = _pad_to(h, block_t, 0)
    wp = _pad_to(w, block_v, 0)
    lab = _pad_to(labels.astype(jnp.int32)[:, None], block_t, 0)
    tp, vp = hp.shape[0], wp.shape[0]
    grid = (tp // block_t, vp // block_v)
    kernel = functools.partial(_fwd_kernel, block_v=block_v, v_total=v)
    nll, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_v, d), lambda ti, vi: (vi, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
        ],
        scratch_shapes=_scratch(block_t),
        interpret=interpret,
    )(hp, wp, lab)
    return nll[:t, 0], lse[:t, 0]


def _scratch(block_t):
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((block_t, 1), jnp.float32) for _ in range(3)]


def fused_ce_bwd(h, w, labels, lse, g_nll, g_lse, *, block_t=256, block_v=512,
                 interpret=None):
    """Backward: (dh, dw) from ONE fused pallas_call.

    gn = g_nll + g_lse (softmax term), go = g_nll. The vocab grid is padded
    to at least three blocks (pad columns contribute exactly zero: p is
    masked by col < v_total and labels never hit pad columns) so the dW
    accumulate-through-HBM revisit stride is >= 3 — see _bwd_kernel.
    """
    if interpret is None:
        interpret = not on_tpu()
    t, d = h.shape
    v = w.shape[0]
    block_t = min(block_t, max(8, t))
    block_v = min(block_v, max(128, v))
    hp = _pad_to(h, block_t, 0)
    wp = _pad_to(w, block_v, 0)
    if wp.shape[0] < 3 * block_v:
        wp = _pad_to(wp, 3 * block_v, 0)
    lab = _pad_to(labels.astype(jnp.int32)[:, None], block_t, 0)
    lsep = _pad_to(lse[:, None], block_t, 0)
    gn = _pad_to((g_nll + g_lse).astype(jnp.float32)[:, None], block_t, 0)
    go = _pad_to(g_nll.astype(jnp.float32)[:, None], block_t, 0)
    tp, vp = hp.shape[0], wp.shape[0]
    gt, gv = tp // block_t, vp // block_v

    dw0 = jnp.zeros((vp, d), jnp.float32)
    dh, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, block_v=block_v, v_total=v,
                          alias_dw=not interpret),
        grid=(gt, gv),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_v, d), lambda ti, vi: (vi, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_v, d), lambda ti, vi: (vi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_v, d), lambda ti, vi: (vi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, d), jnp.float32),
            jax.ShapeDtypeStruct((vp, d), jnp.float32),
        ],
        input_output_aliases={6: 1},
        interpret=interpret,
    )(hp, wp, lab, lsep, gn, go, dw0)

    return dh[:t].astype(h.dtype), dw[:v].astype(w.dtype)
