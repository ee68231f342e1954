"""Fused Hamming-probe decode kernel for the LSH backend (DESIGN.md SS18).

One Pallas pipeline per (block_q, d) query tile:

  1. query codes IN-KERNEL at the first grid step: sign bits of one
     (block_q, d)x(d, L*K) matmul, packed to per-table integer codes by a
     second matmul against a constant power-of-two weight (K <= 24 keeps the
     packed value f32-exact) — no 3D reshapes, both stages run on the MXU;
  2. candidate phase: per (cand_tile,) slab of the dedup'd union, an
     exact-match compare of the query codes against the slab's stored codes
     (a static L-loop of 2D broadcast compares — the packed-word analogue of
     XOR+popcount == 0) yields per-candidate collision COUNTS; membership
     (count > 0, live slots only) gates an online head logsumexp and a
     running top-k over ORIGINAL row ids, scored against the slab's
     embedding rows resident in VMEM;
  3. tail phase: dense (tail_tile, d) slabs of the pre-gathered shared tail
     rows fold into a separate online logsumexp under the plan's rejection
     mask — identical to ``ivf_score.ivf_decode``'s tail.

Head scoring, the Hamming match, the collision counts, and the top-k merge
all share the single resident query tile; no (Q, C) score tensor ever
reaches HBM. Tiles past the measured live candidate count skip compute and
write zero counts, so per-step work tracks the *measured* union, not the
static capacity.

The in-kernel query codes are computed from the raw ``h`` tile; the plan's
donor-adjusted codes differ only on INACTIVE scheduler lanes, whose outputs
the scheduler discards (parity tests pin active=None).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_tpu
from .ivf_score import _round_up
from .topk_z import NEG, _select_topk


def _probe_kernel(live_ref,                                 # scalar prefetch
                  h_ref, projt_ref, packw_ref, wc_ref, cct_ref, okt_ref,
                  cid_ref, wt_ref, acc_ref,
                  hlse_ref, tlse_ref, topv_ref, topi_ref, cnt_ref,
                  mh_scr, sh_scr, mt_scr, st_scr, tv_scr, ti_scr, qc_scr,
                  *, k: int, n_ctiles: int, cand_tile: int, n_tables: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        mh_scr[...] = jnp.full_like(mh_scr, NEG)
        sh_scr[...] = jnp.zeros_like(sh_scr)
        mt_scr[...] = jnp.full_like(mt_scr, NEG)
        st_scr[...] = jnp.zeros_like(st_scr)
        tv_scr[...] = jnp.full_like(tv_scr, NEG)
        ti_scr[...] = jnp.zeros_like(ti_scr)
        # query codes, once per query tile: sign-bit matmul + packing matmul
        s = jax.lax.dot_general(
            h_ref[...].astype(jnp.float32), projt_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, L*K)
        bits = (s > 0).astype(jnp.float32)
        codes = jax.lax.dot_general(
            bits, packw_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, L)
        qc_scr[...] = codes.astype(jnp.int32)

    h = h_ref[...]                                          # (bq, d)
    col_off = si * cand_tile

    @pl.when((si < n_ctiles) & (col_off < live_ref[0]))
    def _cand_step():
        # Hamming match: exact code equality per table, live-routed only
        cnt = jnp.zeros((h.shape[0], cand_tile), jnp.int32)
        for t in range(n_tables):
            qc_t = qc_scr[:, t:t + 1]                       # (bq, 1)
            cc_t = cct_ref[t:t + 1, :]                      # (1, ct)
            ok_t = okt_ref[t:t + 1, :]                      # (1, ct)
            cnt = cnt + ((qc_t == cc_t) & (ok_t > 0)).astype(jnp.int32)
        col_live = (col_off +
                    jax.lax.broadcasted_iota(jnp.int32, cnt.shape, 1)
                    ) < live_ref[0]
        cnt = jnp.where(col_live, cnt, 0)
        cnt_ref[...] = cnt
        member = cnt > 0

        scores = jax.lax.dot_general(
            h, wc_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, ct)
        eff = jnp.where(member, scores, NEG)
        m_prev = mh_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(eff, axis=1, keepdims=True))
        contrib = jnp.where(eff > NEG * 0.5,
                            jnp.exp(eff - m_new), 0.0)      # NEG-safe
        sh_scr[...] = (sh_scr[...] * jnp.exp(m_prev - m_new) +
                       jnp.sum(contrib, axis=1, keepdims=True))
        mh_scr[...] = m_new
        ids = jnp.broadcast_to(cid_ref[...], eff.shape)     # original row ids
        cand_v = jnp.concatenate([tv_scr[...], eff], axis=1)
        cand_i = jnp.concatenate([ti_scr[...], ids], axis=1)
        tv, ti = _select_topk(cand_v, cand_i, k)
        tv_scr[...] = tv
        ti_scr[...] = ti

    @pl.when((si < n_ctiles) & (col_off >= live_ref[0]))
    def _dead_cand_step():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(si >= n_ctiles)
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


def lsh_probe(w_cand, h, proj, cand_rows, cand_codes, cand_ok, cand_live,
              tail_rows, tail_accept, tail_bias, *, k: int = 1,
              block_q: int = 128, cand_tile: int = 128, tail_tile: int = 32,
              interpret=None):
    """Fused LSH probe-and-decode over a dedup'd candidate union.

    Inputs (see ``core.lsh.lsh_plan`` / ``lsh_decode``):
      w_cand      (C, d)       gathered candidate embedding rows
      h           (Q, d)       query batch
      proj        (L, K, d+1)  the index's hyperplanes (the trailing MIPS
                               column hits the rows' augmented coordinate;
                               queries hash with it identically 0, so the
                               kernel just drops it)
      cand_rows   (C,) int32   original row id per union slot (pad = 0)
      cand_codes  (C, L) int32 stored codes of the candidates (pad rows may
                               hold live rows' codes; masked by cand_live)
      cand_ok     (C, L) bool  slot_of_row >= 0 (row routed in that table)
      cand_live   () int32     measured unique candidate count
      tail_rows   (l, d)       shared tail rows, staged dense by the caller
      tail_accept (Q, l) bool  sample survives rejection for query q
      tail_bias   (l,) f32     per-sample importance bias -log(n p_j),
                               ADDED to the sample's score. Folded in via
                               one staged column: queries get a constant 1
                               coordinate, tail rows carry their bias there
                               (candidates a 0, the hyperplanes a 0 row),
                               so the kernel body needs no extra operand

    Returns (head_lse (Q,), tail_lse (Q,), topv (Q, k), topi (Q, k) ORIGINAL
    row ids, counts (Q, C) int32 per-candidate collision table-counts, zero
    past ``cand_live``). Queries with an empty collision set get
    head_lse == log 0; zero accepted tail samples get tail_lse == -inf.
    """
    if interpret is None:
        interpret = not on_tpu()
    c, d = w_cand.shape
    q = h.shape[0]
    ltab, kbits, _ = proj.shape
    l = tail_rows.shape[0]
    assert l >= 1, "fused probe needs at least one tail sample"
    block_q = min(block_q, max(8, q))
    cand_tile = max(8, min(cand_tile, c))
    tail_tile = _round_up(min(tail_tile, l), 8)
    pad_q = (-q) % block_q
    pad_c = (-c) % cand_tile
    pad_l = (-l) % tail_tile

    # the staged width ds = d + 1: the extra column folds the tail
    # importance bias into the one shared query tile (see docstring)
    ds = d + 1
    hp = jnp.pad(h, ((0, pad_q), (0, 0)))
    hp = jnp.concatenate([hp, jnp.ones((hp.shape[0], 1), hp.dtype)], 1)
    wc_p = jnp.pad(w_cand.astype(jnp.float32),
                   ((0, pad_c), (0, 1)))                     # bias col = 0
    # pad codes are -1: query codes are >= 0, so pads can never match
    cct = jnp.pad(cand_codes.astype(jnp.int32), ((0, pad_c), (0, 0)),
                  constant_values=-1).T                      # (L, Cp)
    okt = jnp.pad(cand_ok.astype(jnp.float32), ((0, pad_c), (0, 0))).T
    cid = jnp.pad(cand_rows.astype(jnp.int32), (0, pad_c))[None, :]
    wt_p = jnp.concatenate(
        [jnp.pad(tail_rows.astype(jnp.float32), ((0, pad_l), (0, 0))),
         jnp.pad(tail_bias.astype(jnp.float32), (0, pad_l))[:, None]], 1)
    acc_p = jnp.pad(tail_accept.astype(jnp.float32),
                    ((0, pad_q), (0, pad_l)))
    projt = jnp.pad(proj[..., :d].reshape(
        ltab * kbits, d).T.astype(jnp.float32),
        ((0, 1), (0, 0)))                                    # (ds, L*K)
    packw = jnp.zeros((ltab * kbits, ltab), jnp.float32)
    packw = packw.at[jnp.arange(ltab * kbits),
                     jnp.arange(ltab * kbits) // kbits].set(
        (2.0 ** jnp.arange(kbits))[jnp.arange(ltab * kbits) % kbits])

    qp = hp.shape[0]
    cp = c + pad_c
    n_ctiles = cp // cand_tile
    n_ttiles = (l + pad_l) // tail_tile
    # tile index leading, as in ivf_decode: a (block_q, tail_tile) block of
    # a (Qp, l) table breaks TPU tiling unless tail_tile is 128-aligned
    acc_p = acc_p.reshape(qp, n_ttiles, tail_tile).transpose(1, 0, 2)

    def _cs(si):
        return jnp.clip(si, 0, n_ctiles - 1)

    def _ts(si):
        return jnp.clip(si - n_ctiles, 0, n_ttiles - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qp // block_q, n_ctiles + n_ttiles),
        in_specs=[
            pl.BlockSpec((block_q, ds), lambda qi, si, lv: (qi, 0)),
            pl.BlockSpec((ds, ltab * kbits), lambda qi, si, lv: (0, 0)),
            pl.BlockSpec((ltab * kbits, ltab), lambda qi, si, lv: (0, 0)),
            # candidate slabs (clamped, hence DMA-elided, on tail steps)
            pl.BlockSpec((cand_tile, ds), lambda qi, si, lv: (_cs(si), 0)),
            pl.BlockSpec((ltab, cand_tile), lambda qi, si, lv: (0, _cs(si))),
            pl.BlockSpec((ltab, cand_tile), lambda qi, si, lv: (0, _cs(si))),
            pl.BlockSpec((1, cand_tile), lambda qi, si, lv: (0, _cs(si))),
            # tail slabs
            pl.BlockSpec((tail_tile, ds), lambda qi, si, lv: (_ts(si), 0)),
            pl.BlockSpec((1, block_q, tail_tile),
                         lambda qi, si, lv: (_ts(si), qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda qi, si, lv: (qi, 0)),
            pl.BlockSpec((block_q, 1), lambda qi, si, lv: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, si, lv: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, si, lv: (qi, 0)),
            pl.BlockSpec((block_q, cand_tile),
                         lambda qi, si, lv: (qi, _cs(si))),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
            pltpu.VMEM((block_q, ltab), jnp.int32),
        ],
    )
    kernel = functools.partial(_probe_kernel, k=k, n_ctiles=n_ctiles,
                               cand_tile=cand_tile, n_tables=ltab)
    hlse, tlse, topv, topi, counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
            jax.ShapeDtypeStruct((qp, cp), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(cand_live, jnp.int32).reshape(1),
      hp, projt, packw, wc_p, cct, okt, cid, wt_p, acc_p)
    return (hlse[:q, 0], tlse[:q, 0], topv[:q], topi[:q], counts[:q, :c])


def lsh_probe_ref(w_cand, h, proj, cand_rows, cand_codes, cand_ok,
                  cand_live, tail_rows, tail_accept, tail_bias, *,
                  k: int = 1, block_q: int = 128, cand_tile: int = 128,
                  tail_tile: int = 32, interpret=None):
    """Pure-XLA reference with the fused kernel's exact contract — the
    parity oracle the bf16/f32 tests pin ``lsh_probe`` against."""
    del block_q, cand_tile, tail_tile, interpret
    ltab, kbits, _ = proj.shape
    d = h.shape[-1]
    s = h.astype(jnp.float32) @ proj[..., :d].reshape(ltab * kbits, d).T
    bits = (s > 0).astype(jnp.int32).reshape(-1, ltab, kbits)
    qcodes = (bits * (1 << jnp.arange(kbits, dtype=jnp.int32))).sum(-1)
    hit = ((qcodes[:, None, :] == cand_codes[None, :, :].astype(jnp.int32))
           & cand_ok[None, :, :].astype(bool))
    counts = hit.sum(-1).astype(jnp.int32)                  # (Q, C)
    col_live = jnp.arange(cand_rows.shape[0]) < cand_live
    counts = jnp.where(col_live[None, :], counts, 0)
    member = counts > 0

    scores = jax.lax.dot_general(
        h, w_cand, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (Q, C)
    eff = jnp.where(member, scores, NEG)
    head_lse = jax.nn.logsumexp(eff, axis=-1)
    topv, pos = jax.lax.top_k(eff, k)
    topi = cand_rows[pos].astype(jnp.int32)

    ts = jax.lax.dot_general(
        h, tail_rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) \
        + tail_bias.astype(jnp.float32)[None, :]
    tail_eff = jnp.where(tail_accept, ts, NEG)
    tail_lse = jnp.where(jnp.any(tail_accept, axis=-1),
                         jax.nn.logsumexp(tail_eff, axis=-1), -jnp.inf)
    return head_lse, tail_lse, topv, topi, counts
