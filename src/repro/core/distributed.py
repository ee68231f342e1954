"""Vocab-sharded partition estimation (DESIGN.md SS6).

The output embedding table V (N, d) is sharded over the ``model`` mesh axis
(rows). These helpers run *inside* shard_map/pjit: each shard computes its
local head/tail contributions and the combine is

  * log Z        : pmax/psum log-domain reduction            (O(1) comms)
  * global top-k : all_gather of k local candidates           (O(k T) comms)

i.e. communication is sublinear in N — the paper's property lifted to the
collective level.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def logspace_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """psum of exp(x) carried in log domain, -inf-safe.

    The one cross-shard combine every sharded estimator body uses for
    partial log-Z terms (head LSEs, tail LSEs, anchored sums)."""
    m = lax.pmax(x, axis_name)
    safe = jnp.where(jnp.isfinite(m), m, 0.0)
    s = lax.psum(jnp.exp(x - safe), axis_name)
    return jnp.where(jnp.isfinite(m), safe + jnp.log(s), m)


def _dist_lse(local_lse: jax.Array, axis_name: str) -> jax.Array:
    """logsumexp across shards from per-shard logsumexps."""
    return logspace_psum(local_lse, axis_name)


def sharded_exact_log_z(v_local: jax.Array, q: jax.Array,
                        axis_name: str = "model") -> jax.Array:
    """Exact log Z with V row-sharded. q replicated: (d,) or (B, d)."""
    scores = q @ v_local.T if q.ndim == 2 else v_local @ q
    local = jax.nn.logsumexp(scores, axis=-1)
    return _dist_lse(local, axis_name)


class ShardedTopK(NamedTuple):
    scores: jax.Array   # (..., k) global top-k scores (descending)
    ids: jax.Array      # (..., k) global row ids


def sharded_top_k(v_local: jax.Array, q: jax.Array, k: int,
                  axis_name: str = "model") -> ShardedTopK:
    """Global top-k via local top-k + O(kT) all_gather merge."""
    n_local = v_local.shape[0]
    shard = lax.axis_index(axis_name)
    scores = q @ v_local.T if q.ndim == 2 else v_local @ q
    lv, li = lax.top_k(scores, min(k, n_local))
    gi = li + shard * n_local
    av = lax.all_gather(lv, axis_name, axis=-1, tiled=True)
    ai = lax.all_gather(gi, axis_name, axis=-1, tiled=True)
    mv, mi = lax.top_k(av, k)
    return ShardedTopK(scores=mv, ids=jnp.take_along_axis(ai, mi, axis=-1))


def sharded_mimps_log_z(v_local: jax.Array, q: jax.Array,
                        k_local: int, l_local: int, key: jax.Array,
                        axis_name: str = "model"
                        ) -> Tuple[jax.Array, ShardedTopK]:
    """MIMPS with V row-sharded (k_local/l_local are *per-shard*, static).

    Per-shard head of k_local rows + per-shard tail of l_local uniform
    samples; combined in log domain. The shard-wise head union always covers
    at least the global top-k_local, so this dominates single-host MIMPS with
    (k_local*T, l_local*T) in head coverage. Returns (log_z, merged top-k
    candidates) — the candidate merge is what serving needs for p(i_hat).
    """
    shard = lax.axis_index(axis_name)
    n_local = v_local.shape[0]
    key = jax.random.fold_in(key, shard)
    scores = v_local @ q                              # (n_local,)
    hv, hi = lax.top_k(scores, k_local)
    # local tail: uniform over local rows, reject head members by rank trick:
    # sample positions in the local sorted order beyond k_local.
    order = jnp.argsort(-scores)
    pos = k_local + jax.random.randint(key, (l_local,), 0, n_local - k_local)
    tail = scores[order[pos]]
    log_head = jax.nn.logsumexp(hv)
    log_tail = (jnp.log(jnp.float32(n_local - k_local)) -
                jnp.log(jnp.float32(l_local)) + jax.nn.logsumexp(tail))
    local_lse = jnp.logaddexp(log_head, log_tail)
    log_z = _dist_lse(local_lse, axis_name)
    gi = hi + shard * n_local
    av = lax.all_gather(hv, axis_name, axis=0, tiled=True)
    ai = lax.all_gather(gi, axis_name, axis=0, tiled=True)
    mv, mi = lax.top_k(av, k_local)
    return log_z, ShardedTopK(scores=mv, ids=ai[mi])
