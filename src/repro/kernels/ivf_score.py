"""Scalar-prefetch block-gather scoring + the fused batched MIMPS decode
kernel — the TPU-native S_k(q) retrieval stage (DESIGN.md SS4).

The sublinear step of MIMPS: only the vocab blocks selected by the coarse
(centroid) stage are pulled HBM->VMEM and scored. Probed block ids are
scalar-prefetched into SMEM so the BlockSpec index_map can address HBM blocks
*data-dependently* — the canonical Pallas block-sparse pattern (MoE dispatch,
block-sparse attention) applied to retrieval.

Two kernels:

 * ``ivf_score``  — the original per-query gather-score kernel. Grid (Q, p),
   query tile (1, d): MXU utilization <= 1/128 and the scores round-trip
   through a (Q, p, br) HBM tensor. Kept as the simple reference/bench kernel.

 * ``ivf_decode`` — the fused batched decode pipeline. Grid
   (Q/block_q, U + l/tail_tile): each grid step scores a **(block_q, d)
   query tile** against one scalar-prefetched vocab block (head phase) or a
   dense ``(tail_tile, d)`` slab of pre-gathered tail rows (tail phase) and
   folds the result directly into per-query online-logsumexp accumulators
   (head and tail separately) and a running top-k (the ``_select_topk``
   sweep shared with ``kernels.topk_z``). Head scoring, tail reduction and
   the top-k merge share the single resident query tile — one pass over the
   probe union per tile, no score tensor in HBM. The tail phase used to
   issue one (1, d) row DMA + matvec per sample (l grid steps of ~1/128 MXU
   utilization); rows are now staged dense once (one XLA gather, the same
   l*d floats) and consumed ``tail_tile`` rows per step, which shrinks the
   grid from U+l to U+l/tail_tile steps of real matmuls.

``block_q`` and ``tail_tile`` are autotuned per (shape, dtype, backend) by
``kernels.autotune`` with on-disk caching.

HBM bytes per decode step drop from  V*d  to  U*br*d + l*d
(+ n_blocks*d for centroids) — e.g. gemma3-4b (V=262144, block 512,
16 shared probes, l=256): ~30x fewer output-embedding bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_tpu
from .topk_z import NEG, _select_topk


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# per-query gather-score (reference kernel; (Q, p, br) output)
# ---------------------------------------------------------------------------

def _ivf_kernel(ids_ref, h_ref, w_ref, out_ref):
    # h_ref: (1, d) query row; w_ref: (1, br, d) gathered block
    h = h_ref[...]
    w = w_ref[0]
    out_ref[0] = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # (1, br)


def ivf_score(w_blocks, h, block_ids, *, interpret=None):
    """w_blocks (nb, br, d), h (Q, d), block_ids (Q, p) -> scores (Q, p, br).

    Only the addressed blocks are read from HBM: the grid is (Q, p) and the
    w_blocks index_map consults the scalar-prefetched id table. The serving
    path uses ``ivf_decode`` instead, which never materializes this tensor.
    """
    if interpret is None:
        interpret = not on_tpu()
    nb, br, d = w_blocks.shape
    q, p = block_ids.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q, p),
        in_specs=[
            pl.BlockSpec((1, d), lambda qi, pi, ids: (qi, 0)),
            pl.BlockSpec((1, br, d), lambda qi, pi, ids: (ids[qi, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, br), lambda qi, pi, ids: (qi, pi, 0)),
    )
    return pl.pallas_call(
        _ivf_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q, p, br), jnp.float32),
        interpret=interpret,
    )(block_ids.astype(jnp.int32), h, w_blocks)


# ---------------------------------------------------------------------------
# deduplicated union scoring: (Q, U_cap, br) scores, U unique blocks of DMA
# ---------------------------------------------------------------------------

def _union_kernel(hid_ref, live_ref, h_ref, w_ref, out_ref):
    si = pl.program_id(1)

    @pl.when(si < live_ref[0])
    def _score():
        h = h_ref[...]                                      # (bq, d)
        w = w_ref[0]                                        # (br, d)
        out_ref[...] = jax.lax.dot_general(
            h, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(si >= live_ref[0])
    def _pad():
        out_ref[...] = jnp.zeros_like(out_ref)   # masked by callers


def union_scores(w_blocks, h, head_ids, head_live, *, block_q: int = 128,
                 interpret=None):
    """Score a deduplicated block union for a whole query batch.

    w_blocks (nb, br, d), h (Q, d), head_ids (U_cap,) (sorted unique ids,
    pad slots repeat the last id), head_live () -> scores (Q, U_cap, br) f32.

    Per (block_q, d) query tile the grid sweeps the union table once:
    identical consecutive BlockSpec indices cost no DMA, and slots past
    ``head_live`` skip their matmul entirely, so embedding reads are the U
    *unique* blocks — the MINCE/FMBE head at MIMPS-kernel traffic (the XLA
    gather reference materializes all U_cap slots instead). Pad-slot outputs
    are zeros; callers mask through the plan's membership mask.
    """
    if interpret is None:
        interpret = not on_tpu()
    nb, br, d = w_blocks.shape
    q = h.shape[0]
    u_cap = head_ids.shape[0]
    block_q = min(block_q, max(8, q))
    pad_q = (-q) % block_q
    hp = jnp.pad(h, ((0, pad_q), (0, 0)))
    qp = hp.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(qp // block_q, u_cap),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, si, hid, lv: (qi, 0)),
            pl.BlockSpec((1, br, d),
                         lambda qi, si, hid, lv: (hid[si], 0, 0)),
        ],
        # slot si's scores are the si-th (block_q, br) tile of a lane-dense
        # (Q, U_cap*br) slab (a (block_q, 1, br) block breaks TPU tiling)
        out_specs=pl.BlockSpec((block_q, br),
                               lambda qi, si, hid, lv: (qi, si)),
    )
    out = pl.pallas_call(
        _union_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((qp, u_cap * br), jnp.float32),
        interpret=interpret,
    )(head_ids.astype(jnp.int32),
      jnp.asarray(head_live, jnp.int32).reshape(1), hp, w_blocks)
    return out[:q].reshape(q, u_cap, br)


# ---------------------------------------------------------------------------
# fused batched decode: probe table -> (head lse, tail lse, top-k) per query
# ---------------------------------------------------------------------------

def _decode_kernel(hid_ref, live_ref,                       # scalar prefetch
                   h_ref, wh_ref, logw_ref, member_ref, wt_ref, acc_ref,
                   hlse_ref, tlse_ref, topv_ref, topi_ref,
                   mh_scr, sh_scr, mt_scr, st_scr, tv_scr, ti_scr,
                   *, k: int, n_head: int, block_rows: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        mh_scr[...] = jnp.full_like(mh_scr, NEG)
        sh_scr[...] = jnp.zeros_like(sh_scr)
        mt_scr[...] = jnp.full_like(mt_scr, NEG)
        st_scr[...] = jnp.zeros_like(st_scr)
        tv_scr[...] = jnp.full_like(tv_scr, NEG)
        ti_scr[...] = jnp.zeros_like(ti_scr)

    h = h_ref[...]                                          # (bq, d)

    # only the live_ref[0] <= n_head slots hold real unique blocks; pad slots
    # repeat the last id (no DMA) and are fully masked, so skip their matmul
    @pl.when(si < live_ref[0])
    def _head_step():
        w = wh_ref[0]                                       # (br, d)
        scores = jax.lax.dot_general(
            h, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, br)
        scores = scores + logw_ref[0]                       # pad rows -> NEG
        member = member_ref[0]                              # (bq, 1) 0/1
        eff = jnp.where(member > 0, scores, NEG)
        m_prev = mh_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(eff, axis=1, keepdims=True))
        contrib = jnp.where(eff > NEG * 0.5,
                            jnp.exp(eff - m_new), 0.0)      # NEG-safe
        sh_scr[...] = (sh_scr[...] * jnp.exp(m_prev - m_new) +
                       jnp.sum(contrib, axis=1, keepdims=True))
        mh_scr[...] = m_new
        # running top-k over global slot ids (block*br + row)
        col = (hid_ref[si] * block_rows +
               jax.lax.broadcasted_iota(jnp.int32, eff.shape, 1))
        cand_v = jnp.concatenate([tv_scr[...], eff], axis=1)
        cand_i = jnp.concatenate([ti_scr[...], col], axis=1)
        tv, ti = _select_topk(cand_v, cand_i, k)
        tv_scr[...] = tv
        ti_scr[...] = ti

    @pl.when(si >= n_head)
    def _tail_step():
        rows = wt_ref[...]                                  # (tt, d)
        s = jax.lax.dot_general(
            h, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, tt)
        acc = acc_ref[0]                                    # (bq, tt) 0/1
        eff = jnp.where(acc > 0, s, NEG)
        m_prev = mt_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(eff, axis=1, keepdims=True))
        contrib = jnp.where(eff > NEG * 0.5, jnp.exp(eff - m_new), 0.0)
        st_scr[...] = (st_scr[...] * jnp.exp(m_prev - m_new) +
                       jnp.sum(contrib, axis=1, keepdims=True))
        mt_scr[...] = m_new

    @pl.when(si == pl.num_programs(1) - 1)
    def _fin():
        hlse_ref[...] = mh_scr[...] + jnp.log(sh_scr[...])
        tlse_ref[...] = mt_scr[...] + jnp.log(st_scr[...])
        topv_ref[...] = tv_scr[...]
        topi_ref[...] = ti_scr[...]


def ivf_decode(w_blocks, h, head_ids, head_live, head_member, row_logw,
               tail_rows_g, tail_accept,
               *, k: int = 1, block_q: int = 128, tail_tile: int = 32,
               interpret=None):
    """Fused batched MIMPS decode over a deduplicated probe plan.

    Inputs (see ``core.decode`` for plan construction):
      w_blocks    (nb, br, d)  block-IVF embedding rows
      h           (Q, d)       query batch
      head_ids    (U,) int32   union of probed block ids (pad = repeat last,
                               masked out via head_member; repeated consecutive
                               ids cost no extra DMA)
      head_live   () int32     number of real (non-pad) union slots; head
                               compute is skipped for slots >= head_live, so
                               per-step head work is O(unique blocks), not
                               O(capacity)
      head_member (Q, U) bool  query q probes union slot u
      row_logw    (nb, br) f32 0 for real rows, NEG for cluster-pad rows
      tail_rows_g (l, d)       shared tail sample rows, staged dense by the
                               caller (one XLA gather; l*d floats, consumed
                               ``tail_tile`` rows per grid step)
      tail_accept (Q, l) bool  sample j survives rejection for query q

    ``block_q`` (query tile) and ``tail_tile`` (tail rows per step) are the
    autotuned knobs (kernels.autotune.tune_ivf_decode).

    Returns (head_lse (Q,), tail_lse (Q,), topv (Q, k), topi (Q, k)) with
    topi global *slot* ids (block*br + row); map through row_id outside.
    Queries with zero accepted tail samples get tail_lse == -inf.
    """
    if interpret is None:
        interpret = not on_tpu()
    nb, br, d = w_blocks.shape
    q = h.shape[0]
    n_head = head_ids.shape[0]
    l = tail_rows_g.shape[0]
    assert l >= 1, "fused decode needs at least one tail sample"
    block_q = min(block_q, max(8, q))
    tail_tile = _round_up(min(tail_tile, l), 8)
    pad_q = (-q) % block_q
    pad_l = (-l) % tail_tile
    hp = jnp.pad(h, ((0, pad_q), (0, 0)))
    qp = hp.shape[0]
    n_tiles = (l + pad_l) // tail_tile
    # TPU blocks need their last two dims (8, 128)-aligned or whole, so the
    # per-step operands that are one column of a query table are laid out
    # with the step index leading: member (U, Qp, 1), accept
    # (tiles, Qp, tail_tile), row_logw (nb, 1, br)
    member_p = jnp.pad(head_member.astype(jnp.float32),
                       ((0, pad_q), (0, 0))).T[:, :, None]
    # pad rows contribute via accept == 0 only — value never read; keep the
    # rows' own dtype (mixed-dtype dot with f32 accumulate, like the head
    # phase) so bf16 queries stay bit-comparable with the XLA reference
    wt_p = jnp.pad(tail_rows_g, ((0, pad_l), (0, 0)))
    accept_p = jnp.pad(tail_accept.astype(jnp.float32),
                       ((0, pad_q), (0, pad_l)))
    accept_p = accept_p.reshape(qp, n_tiles, tail_tile).transpose(1, 0, 2)
    logw_p = row_logw.astype(jnp.float32)[:, None, :]

    def _ts(si):
        return jnp.clip(si - n_head, 0, n_tiles - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(qp // block_q, n_head + n_tiles),
        in_specs=[
            pl.BlockSpec((block_q, d),
                         lambda qi, si, hid, lv: (qi, 0)),
            # head: whole probed block; clamped (hence DMA-elided) on tail steps
            pl.BlockSpec((1, br, d),
                         lambda qi, si, hid, lv:
                         (hid[jnp.minimum(si, lv[0] - 1)], 0, 0)),
            pl.BlockSpec((1, 1, br),
                         lambda qi, si, hid, lv:
                         (hid[jnp.minimum(si, lv[0] - 1)], 0, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda qi, si, hid, lv:
                         (jnp.minimum(si, n_head - 1), qi, 0)),
            # tail: dense (tail_tile, d) slab of the staged rows
            pl.BlockSpec((tail_tile, d),
                         lambda qi, si, hid, lv: (_ts(si), 0)),
            pl.BlockSpec((1, block_q, tail_tile),
                         lambda qi, si, hid, lv: (_ts(si), qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda qi, si, *_: (qi, 0)),
            pl.BlockSpec((block_q, 1), lambda qi, si, *_: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, si, *_: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, si, *_: (qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
    )
    kernel = functools.partial(_decode_kernel, k=k, n_head=n_head,
                               block_rows=br)
    hlse, tlse, topv, topi = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
        ],
        interpret=interpret,
    )(head_ids.astype(jnp.int32),
      jnp.asarray(head_live, jnp.int32).reshape(1),
      hp, w_blocks, logw_p, member_p, wt_p, accept_p)
    return hlse[:q, 0], tlse[:q, 0], topv[:q], topi[:q]
