"""Serve-time output layer — the paper's Eq. 2/3 under production sharding.

Lowered paths (all used by launch/dryrun.py), dispatched per method through
``sharded_decode`` — the vocab-sharded face of the estimator-backend
registry (``core.backends``):

 * exact   : streaming chunked logits + online LSE + argmax over the
             vocab-sharded head. O(V d / T) compute per chip, O(B) comms.
 * mimps   : the paper's Eq. 5, vocab-sharded block-IVF inside shard_map:
             each model shard probes its local blocks, scores them,
             tail-samples its local complement; combine = one psum (log Z)
             + one O(T) all_gather (argmax candidates).
             O((nb + p.br + l) d / T) compute per chip — sublinear in V.
 * mince   : Eq. 6/7 with the same local probe/tail sets. The NCE root-find
             is nonlinear, so shards cannot combine log Z post hoc; instead
             each shard compresses its local anchored atoms into the
             fixed-size MinceStats histogram and ONE psum of the stacked
             (B, S, 4) sums recovers the global sufficient statistics —
             every shard then solves locally with zero per-iteration
             communication (the seed psum'd f'/f''/f''' every iteration).
 * fmbe    : Ẑ is O(P·M·d) replicated compute with no vocab-sized state, so
             the estimate needs no sharding at all; only the argmax
             candidates go through the sharded IVF probe.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import mince as _mince
from ..core.estimators import combine_head_tail_lse
from ..core.feature_maps import FMBEState, fmbe_z_batch

NEG = -1e30


# ---------------------------------------------------------------------------
# exact: streaming LSE + top-1 (XLA analogue of kernels/topk_z.py)
# ---------------------------------------------------------------------------

def streaming_logz_argmax(h: jax.Array, w: jax.Array, chunk: int = 8192
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h (B, d), w (V, d) -> (log_z (B,), top_id (B,), top_score (B,)).

    Chunks are shard-INTERLEAVED (row r of chunk (j, b) is b*n_chunks + j):
    with the vocab contiguously sharded over 'model', every chunk spans all
    shards so each chunk's logits dot is local — contiguous chunks would be
    materialized with a full-logits all-reduce per chunk (see losses.py)."""
    v, d = w.shape
    pad = (-v) % chunk
    wp = jnp.pad(w, ((0, pad), (0, 0))) if pad else w
    n_chunks = wp.shape[0] // chunk
    wc = wp.reshape(chunk, n_chunks, d).swapaxes(0, 1)
    b = h.shape[0]

    def body(carry, xs):
        m, s, bi, bs = carry
        wi, ci = xs
        scores = (h @ wi.T).astype(jnp.float32)
        col = jnp.arange(chunk) * n_chunks + ci
        scores = jnp.where(col[None, :] < v, scores, NEG)
        m_new = jnp.maximum(m, jnp.max(scores, -1))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(scores - m_new[:, None]),
                                             -1)
        cmax = jnp.max(scores, -1)
        carg = col[jnp.argmax(scores, -1)]
        better = cmax > bs
        return (m_new, s, jnp.where(better, carg, bi),
                jnp.maximum(bs, cmax)), None

    init = (jnp.full((b,), NEG, jnp.float32), jnp.zeros((b,), jnp.float32),
            jnp.zeros((b,), jnp.int32), jnp.full((b,), NEG, jnp.float32))
    (m, s, bi, bs), _ = lax.scan(body, init, (wc, jnp.arange(n_chunks)))
    return m + jnp.log(s), bi, bs


# ---------------------------------------------------------------------------
# vocab-sharded block-IVF machinery shared by the mimps/mince/fmbe bodies
# ---------------------------------------------------------------------------

class IVFSpecs(NamedTuple):
    """Device-resident IVF arrays; leading (block) dim sharded over 'model'."""
    v_blocks: jax.Array      # (nb, br, d)
    centroids: jax.Array     # (nb, d)
    radius: jax.Array        # (nb,)
    valid: jax.Array         # (nb, br) bool


def ivf_specs_for(vocab: int, d: int, block_rows: int, dtype,
                  shard_multiple: int = 16) -> IVFSpecs:
    """ShapeDtypeStruct skeleton for the dry run (perfect packing assumed).
    Block count is rounded up to `shard_multiple` so the leading dim shards
    over 'model' (the real builder pads clusters the same way)."""
    nb = -(-vocab // block_rows)
    nb = -(-nb // shard_multiple) * shard_multiple
    sds = jax.ShapeDtypeStruct
    return IVFSpecs(v_blocks=sds((nb, block_rows, d), dtype),
                    centroids=sds((nb, d), dtype),
                    radius=sds((nb,), jnp.float32),
                    valid=sds((nb, block_rows), jnp.bool_))


def ivf_partition_specs() -> IVFSpecs:
    return IVFSpecs(v_blocks=P("model", None, None),
                    centroids=P("model", None),
                    radius=P("model"),
                    valid=P("model", None))


def _local_probe(ivf: IVFSpecs, h: jax.Array, n_probe_local: int):
    """Coarse-probe the local shard, batched (ball upper-bound ranking).

    Returns (bids (B, p), scores (B, p, br) pad-masked to NEG, bvalid,
    k_eff (B,))."""
    qn = jnp.linalg.norm(h.astype(jnp.float32), axis=-1, keepdims=True)
    cs = (h @ ivf.centroids.T).astype(jnp.float32) + ivf.radius[None] * qn
    _, bids = lax.top_k(cs, n_probe_local)                 # (B, p)
    blocks = ivf.v_blocks[bids]                            # (B, p, br, d)
    scores = jnp.einsum("bpRd,bd->bpR", blocks, h,
                        preferred_element_type=jnp.float32)
    bvalid = ivf.valid[bids]                               # (B, p, br)
    scores = jnp.where(bvalid, scores, NEG)
    return bids, scores, bvalid, bvalid.sum(axis=(-2, -1))


def _local_tail(ivf: IVFSpecs, key: jax.Array, bids: jax.Array, h: jax.Array,
                l_local: int, axis_name: str):
    """Shared uniform tail sample over local slots + per-query rejection.

    Returns (tail (B, l), ok (B, l), n_valid_local ())."""
    nb_l, br, d = ivf.v_blocks.shape
    n_slots = nb_l * br
    flat = ivf.v_blocks.reshape(n_slots, d)
    flat_valid = ivf.valid.reshape(n_slots)
    shard = lax.axis_index(axis_name)
    slots = jax.random.randint(jax.random.fold_in(key, shard),
                               (l_local,), 0, n_slots)
    sblk = slots // br
    unprobed = ~jnp.any(sblk[None, :, None] == bids[:, None, :], axis=-1)
    ok = unprobed & flat_valid[slots][None, :]             # (B, l)
    tail = jnp.einsum("bd,ld->bl", h, flat[slots],
                      preferred_element_type=jnp.float32)
    return tail, ok, flat_valid.sum()


def _merge_candidates(bids: jax.Array, scores: jax.Array, nb_l: int, br: int,
                      axis_name: str):
    """Local argmax candidate -> O(T) all_gather merge -> global slot id."""
    fs = scores.reshape(scores.shape[0], -1)               # (B, p*br)
    am = jnp.argmax(fs, axis=-1)
    cand_s = jnp.take_along_axis(fs, am[:, None], -1)[:, 0]
    cand_i = (jnp.take_along_axis(bids, (am // br)[:, None], -1)[:, 0] * br
              + am % br)
    all_s = lax.all_gather(cand_s, axis_name, axis=0)      # (T, B)
    all_i = lax.all_gather(cand_i, axis_name, axis=0)
    best = jnp.argmax(all_s, axis=0)                       # (B,)
    top_score = jnp.take_along_axis(all_s, best[None], 0)[0]
    top_slot = jnp.take_along_axis(all_i, best[None], 0)[0]
    top_global = best.astype(jnp.int32) * nb_l * br + top_slot
    return top_global, top_score


def _logspace_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """Distributed logsumexp of per-shard partial LSEs: O(1) floats."""
    m = lax.pmax(x, axis_name)
    return m + jnp.log(lax.psum(jnp.exp(x - m), axis_name))


# ---------------------------------------------------------------------------
# per-method shard_map bodies
# ---------------------------------------------------------------------------

def _local_ivf_logz(ivf: IVFSpecs, h: jax.Array, key: jax.Array,
                    n_probe_local: int, l_local: int,
                    axis_name: str = "model"):
    """MIMPS (Eq. 5) body: each shard = its own local IVF over its vocab rows.

    Batched like core.decode: one (B, d) x (d, nb_l) centroid matmul probes
    every query at once, and the l_local tail slots are drawn once and shared
    across the batch (one (B, d) x (d, l) matmul). Eq. 5 scale uses the
    per-query unprobed population and post-rejection sample count.
    """
    nb_l, br, d = ivf.v_blocks.shape
    bids, scores, bvalid, k_eff = _local_probe(ivf, h, n_probe_local)
    head_lse = jax.nn.logsumexp(scores.reshape(h.shape[0], -1), axis=-1)
    tail, ok, n_valid = _local_tail(ivf, key, bids, h, l_local, axis_name)
    tail_lse = jax.nn.logsumexp(jnp.where(ok, tail, NEG), axis=-1)
    n_tail_total = jnp.maximum(n_valid - k_eff, 0).astype(jnp.float32)
    n_acc = ok.sum(axis=-1).astype(jnp.float32)
    local_logz = combine_head_tail_lse(head_lse, tail_lse, n_tail_total,
                                       n_acc)
    log_z = _logspace_psum(local_logz, axis_name)
    top_global, top_score = _merge_candidates(bids, scores, nb_l, br,
                                              axis_name)
    return log_z, top_global, top_score


def _local_mince_logz(ivf: IVFSpecs, h: jax.Array, key: jax.Array,
                      n_probe_local: int, l_local: int, iters: int = 3,
                      solver: str = "halley", n_bins: int = 128,
                      axis_name: str = "model"):
    """MINCE (Eq. 6/7) body: the global NCE problem, stats-combined ONCE.

    Each shard holds its slice of the atom set (local probe head + local
    tail sample) and compresses it into the fixed-size ``mince.MinceStats``
    histogram around the globally-psum'd Eq. 5 anchor. Histograms are plain
    weighted sums over samples, so ONE psum of the stacked (B, S, 4) stats
    recovers the exact global sufficient statistics — every shard then runs
    the identical bracketed Halley solve locally on one shared theta. The
    seed psum'd (f', f'', f''') every iteration; the pre-solve combine
    removes the per-iteration collective entirely (iters x 3 scalars ->
    one (B, S, 4) array, and the solve no longer serializes on the wire).
    """
    nb_l, br, d = ivf.v_blocks.shape
    b = h.shape[0]
    bids, scores, bvalid, k_eff_l = _local_probe(ivf, h, n_probe_local)
    tail, ok, n_valid_l = _local_tail(ivf, key, bids, h, l_local, axis_name)

    k_eff = lax.psum(k_eff_l, axis_name).astype(jnp.float32)
    n_acc = lax.psum(ok.sum(axis=-1), axis_name).astype(jnp.float32)
    n_valid = lax.psum(n_valid_l, axis_name).astype(jnp.float32)
    n_tail = jnp.maximum(n_valid - k_eff, 0.0)
    c_t = n_tail / jnp.maximum(n_acc, 1.0)

    head_lse_l = jax.nn.logsumexp(scores.reshape(b, -1), axis=-1)
    theta0 = _logspace_psum(head_lse_l, axis_name)
    tail_lse = _logspace_psum(
        jax.nn.logsumexp(jnp.where(ok, tail, NEG), axis=-1), axis_name)
    anchor = combine_head_tail_lse(theta0, tail_lse, n_tail, n_acc)  # (B,)

    # local anchored atoms -> local histograms on the shared (global-anchor)
    # bins -> ONE psum of the stacked sums -> identical local solves
    s_all = jnp.concatenate([scores.reshape(b, -1), tail], axis=-1)
    m_all = jnp.concatenate(
        [bvalid.reshape(b, -1).astype(jnp.float32),
         ok.astype(jnp.float32) * c_t[:, None]], axis=-1)
    alpha, wd, wn = _mince.anchored_atoms(s_all, m_all, n_valid, k_eff,
                                          n_acc, anchor)
    st = _mince.mince_stats(alpha, wd, wn, anchor, n_bins=n_bins)
    stacked = jnp.stack([st.w_data, st.w_noise,
                         st.a_data * st.w_data,
                         st.a_noise * st.w_noise], axis=-1)   # (B, S, 4)
    g = lax.psum(stacked, axis_name)
    stats = _mince.MinceStats(
        a_data=g[..., 2] / jnp.maximum(g[..., 0], 1e-30),
        w_data=g[..., 0],
        a_noise=g[..., 3] / jnp.maximum(g[..., 1], 1e-30),
        w_noise=g[..., 1], lo=st.lo, hi=st.hi)
    theta = _mince.solve_from_stats(stats, anchor, iters=iters,
                                    solver=solver)

    uniform = tail_lse + jnp.log(jnp.maximum(n_valid, 1.0)) - \
        jnp.log(jnp.maximum(n_acc, 1.0))
    log_z = jnp.where(k_eff == 0, uniform, theta)
    log_z = jnp.where((n_acc == 0) | (n_tail == 0), theta0, log_z)
    top_global, top_score = _merge_candidates(bids, scores, nb_l, br,
                                              axis_name)
    return log_z, top_global, top_score


def _local_ivf_topk(ivf: IVFSpecs, h: jax.Array,
                    n_probe_local: int, axis_name: str = "model"):
    """Candidates-only body (FMBE): probe + argmax merge, no estimate."""
    nb_l, br, _ = ivf.v_blocks.shape
    bids, scores, _, _ = _local_probe(ivf, h, n_probe_local)
    return _merge_candidates(bids, scores, nb_l, br, axis_name)


# ---------------------------------------------------------------------------
# jit-composable wrappers + the sharded dispatch
# ---------------------------------------------------------------------------

def _shard_wrap(mesh, fn, ivf, h, key, batch_spec, n_out=3):
    h_spec = P(*batch_spec, None)
    in_specs = (ivf_partition_specs(), h_spec) + ((P(),) if key is not None
                                                  else ())
    out_specs = tuple(P(*batch_spec) for _ in range(n_out))
    args = (ivf, h) + ((key,) if key is not None else ())
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def sharded_ivf_decode(mesh, ivf: IVFSpecs, h: jax.Array, key: jax.Array,
                       *, n_probe_local: int, l_local: int,
                       batch_spec=P("data")):
    """Sharded MIMPS decode. h (B, d) sharded over data."""
    fn = functools.partial(_local_ivf_logz, n_probe_local=n_probe_local,
                           l_local=l_local)
    return _shard_wrap(mesh, fn, ivf, h, key, batch_spec)


def sharded_mince_decode(mesh, ivf: IVFSpecs, h: jax.Array, key: jax.Array,
                         *, n_probe_local: int, l_local: int,
                         iters: int = 3, solver: str = "halley",
                         batch_spec=P("data")):
    """Sharded MINCE decode (one pre-solve stats psum, local Halley)."""
    fn = functools.partial(_local_mince_logz, n_probe_local=n_probe_local,
                           l_local=l_local, iters=iters, solver=solver)
    return _shard_wrap(mesh, fn, ivf, h, key, batch_spec)


def sharded_fmbe_decode(mesh, ivf: IVFSpecs, h: jax.Array, key: jax.Array,
                        *, n_probe_local: int, fmbe_state: FMBEState,
                        batch_spec=P("data"), l_local: int = 0):
    """Sharded FMBE decode: replicated O(P·M·d) Ẑ + sharded candidates."""
    del key, l_local
    z = fmbe_z_batch(fmbe_state, h)
    log_z = jnp.log(jnp.maximum(z, 1e-30))
    fn = functools.partial(_local_ivf_topk, n_probe_local=n_probe_local)
    top_id, top_s = _shard_wrap(mesh, fn, ivf, h, None, batch_spec, n_out=2)
    return log_z, top_id, top_s


SHARDED_BACKENDS = {
    "mimps": sharded_ivf_decode,
    "mince": sharded_mince_decode,
    "fmbe": sharded_fmbe_decode,
}


# ---------------------------------------------------------------------------
# Mesh-serving bodies (DESIGN.md SS15): full DecodeOut inside the scheduler's
# one shard_map step, bit-identical to the single-device core.decode paths
# ---------------------------------------------------------------------------
#
# The dry-run bodies above shard EVERYTHING per shard (local probe, local
# tail) and merge top-1 — right for throughput studies, but a serving lane
# must emit the SAME tokens it would emit solo, and tokens come from the full
# sorted top-k candidate list. The mesh bodies below get bitwise identity by
# splitting the index differently:
#
#  * ``v_blocks`` (the O(V d) payload) is sharded over 'model'; everything
#    else — centroids, radius, valid, row_id, slot_of_row — is per-block
#    METADATA, O(V/br (d + br)) floats, and stays replicated.
#  * probe / dedup / trim / tail plan / top-k therefore run the *verbatim*
#    ``core.decode`` code on replicated metadata: every shard derives the
#    same DecodePlan the single device would.
#  * only the embedding-row fetch is distributed: each shard contributes its
#    owned rows of the step's working set (union head + shared tail — the
#    paper's sublinear set) and ONE psum assembles the (U*br + l, d) staging
#    buffer; the scoring matmul then runs on identical operands, so every
#    output — log Ẑ included — is bit-equal to ``mimps_decode`` & friends.
#
# Comms per step: one psum of the sublinear working set (+ the health
# guard's log-domain psum on its exact-fallback branch) — the paper's
# sublinearity lifted to the collective level, with none of the
# "distributed estimator" numerics leaking into token identity.

from ..core import decode as _decode
from ..core import mips as _mips
from ..core.decode import DecodeOut
from ..core.distributed import logspace_psum, sharded_top_k
from ..core.estimators import NEG_INF, combine_head_tail_lse


def _gather_rows_psum(flat_local: jax.Array, slots: jax.Array,
                      axis_name: str) -> jax.Array:
    """Assemble global embedding rows from the model-sharded flat block
    table: each shard gathers the slots it owns (zeros elsewhere), one psum
    of (len(slots), d) makes every shard hold the exact rows — bitwise the
    single-device ``jnp.take`` (one real addend per element, rest zero)."""
    n_loc = flat_local.shape[0]
    me = lax.axis_index(axis_name)
    loc = slots - me * n_loc
    own = (loc >= 0) & (loc < n_loc)
    rows = jnp.where(own[:, None],
                     flat_local[jnp.clip(loc, 0, n_loc - 1)],
                     jnp.zeros((), flat_local.dtype))
    return lax.psum(rows, axis_name)


def _mesh_plan(index, h: jax.Array, key: jax.Array, n_probe: int, l: int,
               active) -> "_decode.DecodePlan":
    """``core.decode.make_plan`` against an index whose ``v_blocks`` leaf is
    the LOCAL shard: identical code except capacity comes from the
    replicated ``valid`` (global block count), since ``index.n_blocks``
    would report the local shard's."""
    block_ids = _mips.probe_batch(index, h, n_probe)
    if active is not None:
        donor = block_ids[jnp.argmax(active)]
        block_ids = jnp.where(active[:, None], block_ids, donor[None, :])
    capacity = min(h.shape[0] * n_probe, index.valid.shape[0])
    head_ids, member, n_unique = _decode.plan_heads(block_ids, capacity)
    tb, tr, accept = _decode.plan_tail(index, key, l, block_ids)
    k_eff = _mips.head_count(index, block_ids)
    return _decode.DecodePlan(block_ids=block_ids, head_ids=head_ids,
                              head_live=n_unique.astype(jnp.int32),
                              head_member=member, tail_blocks=tb,
                              tail_rows=tr, tail_accept=accept, k_eff=k_eff,
                              n_accept=accept.sum(axis=-1))


def _mesh_head_scores(index, h: jax.Array, head_ids, member, tail_slots,
                      axis_name: str):
    """``core.decode._head_scores_xla`` with the row gather distributed:
    same staging-buffer layout, same fused (Q,d)x(d, U*br [+ l]) dot on
    psum-assembled operands -> bitwise-identical scores."""
    _, br, d = index.v_blocks.shape
    flat = index.v_blocks.reshape(-1, d)
    slot = (head_ids[:, None] * br +
            jnp.arange(br, dtype=jnp.int32)[None, :]).reshape(-1)
    n_head = slot.shape[0]
    if tail_slots is not None:
        slot = jnp.concatenate([slot, tail_slots])
    w = _gather_rows_psum(flat, slot, axis_name)
    scores = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    mask = (member[:, :, None] & index.valid[head_ids][None]
            ).reshape(h.shape[0], -1)
    if tail_slots is not None:
        return scores[:, :n_head], mask, scores[:, n_head:]
    return scores, mask


def mesh_mimps_decode(index, h: jax.Array, key: jax.Array, *, n_probe: int,
                      l: int, k: int = 1, head_cap: int = 0, active=None,
                      axis_name: str = "model") -> DecodeOut:
    """MIMPS (Eq. 5) under the serving mesh — bit-equal to
    ``mimps_decode(..., use_pallas=False)`` at every mesh size."""
    plan = _mesh_plan(index, h, key, n_probe, l, active)
    br = index.v_blocks.shape[1]
    tail_slots = plan.tail_blocks * br + plan.tail_rows
    cap = _decode._resolve_head_cap(head_cap, n_probe,
                                    plan.head_ids.shape[0])

    def branch(ids, member):
        scores, mask, ts = _mesh_head_scores(index, h, ids, member,
                                             tail_slots, axis_name)
        tl = _decode._masked_tail_lse(ts, plan.tail_accept)
        return _decode._head_topk(index, ids, scores, mask, k) + (tl,)

    head_lse, topv, topi, tail_lse = _decode._with_trimmed_head(plan, cap,
                                                                branch)
    log_z = combine_head_tail_lse(
        head_lse, tail_lse,
        (index.n - plan.k_eff).astype(jnp.float32),
        plan.n_accept.astype(jnp.float32))
    top_id = index.row_id.reshape(-1)[topi]
    return DecodeOut(log_z=log_z, top_score=topv, top_id=top_id,
                     head_lse=head_lse, tail_lse=tail_lse, k_eff=plan.k_eff,
                     head_live=plan.head_live)


def mesh_mince_decode(index, h: jax.Array, key: jax.Array, *, n_probe: int,
                      l: int, k: int = 1, iters: int = 2,
                      solver: str = "halley", head_cap: int = 0, active=None,
                      axis_name: str = "model") -> DecodeOut:
    """MINCE (Eq. 6/7) under the serving mesh: the anchored closed form of
    ``mince_decode`` on psum-assembled rows (``iters``/``solver`` kept for
    signature parity with the cold-start solvers)."""
    del iters, solver
    assert l >= 1, "MINCE needs at least one noise sample"
    plan = _mesh_plan(index, h, key, n_probe, l, active)
    br = index.v_blocks.shape[1]
    tail_slots = plan.tail_blocks * br + plan.tail_rows
    cap = _decode._resolve_head_cap(head_cap, n_probe,
                                    plan.head_ids.shape[0])
    n = index.n
    k_eff = plan.k_eff.astype(jnp.float32)
    n_acc = plan.n_accept.astype(jnp.float32)
    n_tail = jnp.maximum(n - k_eff, 0.0)

    def branch(ids, member):
        scores, mask, ts = _mesh_head_scores(index, h, ids, member,
                                             tail_slots, axis_name)
        hl = jax.nn.logsumexp(jnp.where(mask, scores, NEG_INF), axis=-1)
        tl = _decode._masked_tail_lse(ts, plan.tail_accept)
        theta = combine_head_tail_lse(hl, tl, n_tail, n_acc)
        _, topv, topi = _decode._head_topk(index, ids, scores, mask, k)
        return hl, tl, theta, topv, topi

    head_lse, tail_lse, theta, topv, topi = _decode._with_trimmed_head(
        plan, cap, branch)
    uniform = combine_head_tail_lse(
        jnp.full_like(head_lse, NEG_INF), tail_lse,
        jnp.zeros_like(n_acc) + jnp.asarray(n, jnp.float32), n_acc)
    log_z = jnp.where(k_eff == 0, uniform, theta)
    log_z = jnp.where((n_acc == 0) | (n_tail == 0), head_lse, log_z)
    top_id = index.row_id.reshape(-1)[topi]
    return DecodeOut(log_z=log_z, top_score=topv, top_id=top_id,
                     head_lse=head_lse, tail_lse=tail_lse, k_eff=plan.k_eff,
                     head_live=plan.head_live)


def mesh_topk_decode(index, h: jax.Array, key: jax.Array, *, n_probe: int,
                     k: int = 1, head_cap: int = 0, active=None,
                     axis_name: str = "model") -> DecodeOut:
    """Head-only ladder rung (``topk_head_decode``) under the serving mesh."""
    plan = _mesh_plan(index, h, key, n_probe, 0, active)
    cap = _decode._resolve_head_cap(head_cap, n_probe,
                                    plan.head_ids.shape[0])

    def branch(ids, member):
        scores, mask = _mesh_head_scores(index, h, ids, member, None,
                                         axis_name)
        return _decode._head_topk(index, ids, scores, mask, k)

    head_lse, topv, topi = _decode._with_trimmed_head(plan, cap, branch)
    top_id = index.row_id.reshape(-1)[topi]
    return DecodeOut(log_z=head_lse, top_score=topv, top_id=top_id,
                     head_lse=head_lse,
                     tail_lse=jnp.full_like(head_lse, -jnp.inf),
                     k_eff=plan.k_eff, head_live=plan.head_live)


def mesh_fmbe_decode(state: FMBEState, index, h: jax.Array, key: jax.Array,
                     *, n_probe: int, k: int = 1, head_cap: int = 0,
                     active=None, axis_name: str = "model") -> DecodeOut:
    """FMBE under the serving mesh: the sketch (and its per-block lambda
    table) is V-independent and replicated; only the candidate head rows are
    fetched through the sharded gather."""
    plan = _mesh_plan(index, h, key, n_probe, 0, active)
    cap = _decode._resolve_head_cap(head_cap, n_probe,
                                    plan.head_ids.shape[0])

    def branch(ids, member):
        scores, mask = _mesh_head_scores(index, h, ids, member, None,
                                         axis_name)
        return _decode._head_topk(index, ids, scores, mask, k)

    head_lse, topv, topi = _decode._with_trimmed_head(plan, cap, branch)
    if state.lambda_blocks is not None:
        from ..core.feature_maps import fmbe_tail_z
        z_tail = fmbe_tail_z(state, h, plan.block_ids, use_pallas=False)
        log_z = jnp.logaddexp(head_lse,
                              jnp.log(jnp.maximum(z_tail, 1e-30)))
    else:
        z = fmbe_z_batch(state, h)
        log_z = jnp.log(jnp.maximum(z, 1e-30))
    top_id = index.row_id.reshape(-1)[topi]
    return DecodeOut(log_z=log_z, top_score=topv, top_id=top_id,
                     head_lse=head_lse,
                     tail_lse=jnp.full_like(log_z, -jnp.inf),
                     k_eff=plan.k_eff, head_live=plan.head_live)


def mesh_exact_decode(w_local: jax.Array, h: jax.Array, *, k: int = 1,
                      active=None, axis_name: str = "model") -> DecodeOut:
    """Exact log Z + top-k with the embedding row-sharded over 'model':
    local logits + log-domain psum (log Z) and the O(kT) candidate merge.
    Candidate (score, id) pairs match the dense single-device pass (each is
    a selected local dot); log Z agrees to reduction-order rounding."""
    del active
    logits = (h @ w_local.T).astype(jnp.float32)
    log_z = logspace_psum(jax.nn.logsumexp(logits, -1), axis_name)
    tk = sharded_top_k(w_local, h, k, axis_name)
    q = h.shape[0]
    v = w_local.shape[0] * lax.psum(1, axis_name)
    return DecodeOut(log_z=log_z, top_score=tk.scores.astype(jnp.float32),
                     top_id=tk.ids.astype(jnp.int32), head_lse=log_z,
                     tail_lse=jnp.full((q,), -jnp.inf),
                     k_eff=jnp.full((q,), v, jnp.int32))


def mesh_selfnorm_decode(w_local: jax.Array, h: jax.Array, *, k: int = 1,
                         active=None, axis_name: str = "model") -> DecodeOut:
    out = mesh_exact_decode(w_local, h, k=k, active=active,
                            axis_name=axis_name)
    return out._replace(log_z=jnp.zeros_like(out.log_z))


def mesh_lsh_decode(lsh_index, w_local: jax.Array, h: jax.Array,
                    key: jax.Array, *, l: int, k: int = 1, cand_cap: int = 0,
                    active=None, axis_name: str = "model") -> DecodeOut:
    """LSH collision-head decode under the serving mesh — bit-equal to
    ``lsh.lsh_decode(..., use_pallas=False)`` at every mesh size.

    The whole LSH index (hyperplanes, codes, buckets, slots — metadata,
    no embedding payload) is replicated, so ``lsh.lsh_plan`` runs VERBATIM
    and every shard derives the identical plan; only the embedding rows are
    sharded, and the step's working set (trimmed candidate union + shared
    tail) is assembled with the one ``_gather_rows_psum`` — global row ids
    against the 'model'-row-sharded ``w``."""
    from ..core import lsh as _lshmod
    assert l >= 1, "lsh decode needs at least one tail sample"
    plan = _lshmod.lsh_plan(lsh_index, h, key, l, active=active,
                            cand_cap=cand_cap)

    def branch(rows, member, col_live):
        del col_live       # membership already encodes dead columns
        slots = jnp.concatenate([rows, plan.tail_ids])
        w = _gather_rows_psum(w_local, slots,
                              axis_name).astype(jnp.float32)
        scores = jax.lax.dot_general(
            h, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        c = rows.shape[0]
        eff = jnp.where(member, scores[:, :c], NEG_INF)
        head_lse = jax.nn.logsumexp(eff, axis=-1)
        topv, pos = jax.lax.top_k(eff, k)
        topi = rows[pos]
        tail_lse = _decode._masked_tail_lse(scores[:, c:]
                                            + plan.tail_bias[None, :],
                                            plan.tail_accept)
        return head_lse, tail_lse, topv, topi.astype(jnp.int32)

    head_lse, tail_lse, topv, topi = _lshmod._with_trimmed_cands(
        plan, branch)
    log_z = combine_head_tail_lse(
        head_lse, tail_lse,
        (lsh_index.n - plan.k_eff).astype(jnp.float32),
        plan.n_accept.astype(jnp.float32))
    return DecodeOut(log_z=log_z, top_score=topv, top_id=topi,
                     head_lse=head_lse, tail_lse=tail_lse,
                     k_eff=plan.k_eff, head_live=plan.cand_live)


def mesh_health_guard(out: DecodeOut, w_local: jax.Array, h: jax.Array,
                      k: int, active=None, axis_name: str = "model"):
    """``core.decode.apply_health_guard`` with the exact fallback sharded.

    Flags are computed on outputs that are replicated across the model axis
    (psum-assembled scores, replicated metadata), so every shard of a model
    group agrees on the ``lax.cond`` branch and the fallback's collectives
    (the log-domain psum + candidate all_gather of ``mesh_exact_decode``)
    line up; data replicas branch independently — their collective groups
    are disjoint. Healthy lanes take the bit-identity branch, exactly as on
    a single device."""
    flags = _decode.health_flags(out)
    if active is not None:
        flags = jnp.where(active, flags, 0)
    bad = flags > 0

    def fallback():
        ex = mesh_exact_decode(w_local, h, k=k, axis_name=axis_name)
        row = bad[:, None]
        return DecodeOut(
            log_z=jnp.where(bad, ex.log_z, out.log_z),
            top_score=jnp.where(row, ex.top_score, out.top_score),
            top_id=jnp.where(row, ex.top_id, out.top_id),
            head_lse=jnp.where(bad, ex.head_lse, out.head_lse),
            tail_lse=jnp.where(bad, ex.tail_lse, out.tail_lse),
            k_eff=out.k_eff, head_live=out.head_live)

    return jax.lax.cond(jnp.any(bad), fallback, lambda: out), flags


def sharded_decode(mesh, method: str, ivf: IVFSpecs, h: jax.Array,
                   key: jax.Array, *, n_probe_local: int, l_local: int,
                   batch_spec=P("data"), **method_kwargs):
    """Vocab-sharded face of the estimator-backend registry: dispatches to
    the method's shard_map body, returning (log_z, top_id, top_score) each
    (B,). 'exact' has no IVF state — call ``streaming_logz_argmax`` with the
    sharded embedding instead."""
    try:
        fn = SHARDED_BACKENDS[method]
    except KeyError:
        raise ValueError(
            f"no sharded backend for method {method!r}; have "
            f"{sorted(SHARDED_BACKENDS)} + 'exact' via streaming_logz_argmax"
        ) from None
    return fn(mesh, ivf, h, key, n_probe_local=n_probe_local,
              l_local=l_local, batch_spec=batch_spec, **method_kwargs)
