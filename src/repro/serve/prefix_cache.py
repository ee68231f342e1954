"""Shared-prefix KV cache for the slot scheduler (DESIGN.md SS16a).

Under shared-context traffic every admitted request replays its full prompt
through its own KV lane one token per step — even when hundreds of prompts
open with the same system preamble. This module keeps a fixed-capacity
device-resident **prefix pool**: KV rows for previously-served prompt
prefixes at token-block granularity, matched host-side on admission and
copied into the new lane with ONE traced gather + window write, so a
request whose first L prompt tokens are cached starts replay at position L
instead of 0.

Design split (mirrors the scheduler's own host/device split):

 * **Host: a radix-trie-lite.** Nodes are keyed ``(parent_block_id,
   token_bytes)`` — one node per ``block_tokens``-token chunk, chained
   through parent ids, so matching a prompt is a dict walk and two prompts
   share exactly their common block-aligned prefix. Eviction is ref-counted
   LRU over *leaf* nodes (a node with children is pinned: evicting it would
   orphan longer cached prefixes). All of this is plain python — it runs
   once per admission/completion, never per token.
 * **Device: a block pool per KV leaf.** For every cache leaf
   (*stack, S, L, n_kv, Dh) the pool holds (*stack, n_blocks,
   block_tokens, n_kv, Dh). ``load`` gathers a traced id vector of blocks
   and lands them in the lane with one ``write_lane_window``; ``save``
   copies one block out of a finished lane. Both are jitted once — traced
   lane/offset/ids, static shapes — so the pool adds exactly two
   executables to the scheduler's zero-recompile budget.

Correctness leans on two facts. (1) KV rows are a pure function of the
token prefix, absolute positions, and the (frozen) params, so pool rows
are bit-identical to the rows replay would have produced — tokens after a
prefix hit are bit-identical to a cold lane. (2) ``load`` writes the full
static match window (padded ids gather garbage); positions >= the matched
length L are garbage, but the lane resumes at t_stream = L and the decode
step overwrites each position before it is ever attended (the same
sequential-overwrite argument the speculative rollback relies on), while
the per-lane validity mask hides everything beyond the frontier. Neither
argument survives sliding-window ring buffers or recurrent decode states,
so the scheduler gates the pool on full-attention KV states.

Copy-vs-share: lanes COPY pool blocks instead of page-sharing them, so a
loaded lane never references the pool again — eviction needs no lane
refcounts and can never corrupt an in-flight request.

Under the (data, model) serving mesh the pool's block axis is sharded over
``data`` exactly like the slot table's lane axis; blocks are allocated
replica-local so a chain lives with its owner replica, admission prefers
that replica (see ``Scheduler.admit`` / server lookahead), and a forced
cross-replica admission just forfeits the hit (t0 = 0) rather than paying
a cross-replica gather.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.attention import slice_lane_window, write_lane_window


def cache_is_kv_only(cache) -> bool:
    """True when every decode-state leaf is a full-attention KV buffer
    ((*stack, S, L, n_kv, Dh) named 'k'/'v') — the only states whose rows
    can be block-copied and position-offset. Recurrent leaves (wkv/ssm/
    conv) fold history into O(1) state and cannot be rewound or spliced."""
    ok = [True]
    def check(path, leaf):
        name = None
        for p in reversed(path):
            if isinstance(p, jax.tree_util.DictKey):
                name = p.key
                break
        if name not in ("k", "v") or np.ndim(leaf) < 4:
            ok[0] = False
    jax.tree_util.tree_map_with_path(check, cache)
    return ok[0]


class PrefixPool:
    """Fixed-capacity shared-prefix KV pool. Built by the scheduler against
    its own decode-state template and (optional) mesh shardings."""

    def __init__(self, cache_template, n_blocks: int, block_tokens: int,
                 max_match_blocks: int, mesh=None, cache_shardings=None,
                 n_replicas: int = 1):
        if n_blocks < 1 or block_tokens < 1:
            raise ValueError("prefix pool needs n_blocks/block_tokens >= 1")
        if n_blocks % n_replicas:
            raise ValueError(
                f"prefix_cache_blocks {n_blocks} must divide the data "
                f"degree {n_replicas} (blocks are replica-local)")
        if not cache_is_kv_only(cache_template):
            raise NotImplementedError(
                "the prefix cache block-copies full-attention KV rows; "
                "this model's decode state has recurrent/windowed leaves")
        self.n_blocks = n_blocks
        self.block_tokens = block_tokens
        self.max_match_blocks = max_match_blocks
        self.n_replicas = n_replicas
        self.blocks_per_replica = n_blocks // n_replicas

        def make(leaf):
            shape = list(leaf.shape)
            shape[-4] = n_blocks
            shape[-3] = block_tokens
            return jnp.zeros(shape, leaf.dtype)

        self.pool = jax.tree.map(make, cache_template)
        pool_sh = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            from ..launch.mesh import serve_cache_spec
            specs = jax.tree_util.tree_map_with_path(serve_cache_spec,
                                                     self.pool)
            pool_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            self.pool = jax.device_put(self.pool, pool_sh)

        # -- trie-lite: (parent_block_id, chunk_bytes) -> block_id
        self._node: Dict[Tuple[int, bytes], int] = {}
        self._key_of: Dict[int, Tuple[int, bytes]] = {}
        self._children: Dict[int, int] = {}
        self._lru: Dict[int, int] = {}
        self._tick = 0
        self._free: List[List[int]] = [
            list(range(r * self.blocks_per_replica,
                       (r + 1) * self.blocks_per_replica))
            for r in range(n_replicas)]
        # -- counters (surfaced through scheduler.step records / reports)
        self.hits = 0               # admissions that loaded >= 1 block
        self.saved_steps = 0        # replay steps skipped (sum of t0)
        self.inserted = 0           # blocks written into the pool
        self.evictions = 0
        self.load_traces = 0
        self.save_traces = 0

        load_kw = {} if cache_shardings is None else \
            {"out_shardings": cache_shardings}
        save_kw = {} if pool_sh is None else {"out_shardings": pool_sh}
        bt = block_tokens
        mcap = max_match_blocks

        @partial(jax.jit, donate_argnums=0, **load_kw)
        def load(cache, pool, ids, lane):
            self.load_traces += 1

            def leaf_load(cleaf, pleaf):
                got = jnp.take(pleaf, ids, axis=-4)     # (..., Mcap, Bt, ...)
                lead = got.shape[:-4]
                rows = got.reshape(*lead, 1, mcap * bt, *got.shape[-2:])
                return write_lane_window(cleaf, rows, lane, 0)

            return jax.tree.map(leaf_load, cache, pool)

        @partial(jax.jit, donate_argnums=0, **save_kw)
        def save(pool, cache, lane, start, block_id):
            self.save_traces += 1

            def leaf_save(pleaf, cleaf):
                rows = slice_lane_window(cleaf, lane, start, bt)
                return write_lane_window(pleaf, rows, block_id, 0)

            return jax.tree.map(leaf_save, pool, cache)

        self._load_fn = load
        self._save_fn = save

    # -- host trie ----------------------------------------------------------

    def _chunks(self, tokens: np.ndarray, n: int):
        bt = self.block_tokens
        for i in range(n):
            yield np.asarray(tokens[i * bt:(i + 1) * bt],
                             np.int32).tobytes()

    def match(self, tokens, p_len: int) -> Tuple[int, List[int],
                                                 Optional[int]]:
        """Longest cached block-aligned prefix of ``tokens``. Returns
        (matched_blocks, block_ids, owner_replica). The usable match is
        capped at (p_len - 1) // block_tokens: the lane's LAST replay step
        must still execute to emit the first token."""
        limit = min((p_len - 1) // self.block_tokens, self.max_match_blocks)
        ids: List[int] = []
        parent = -1
        for chunk in self._chunks(np.asarray(tokens), limit):
            bid = self._node.get((parent, chunk))
            if bid is None:
                break
            ids.append(bid)
            parent = bid
        self._tick += 1
        for bid in ids:
            self._lru[bid] = self._tick
        owner = ids[0] // self.blocks_per_replica if ids else None
        return len(ids), ids, owner

    def _alloc(self, replica: int, protect) -> Optional[int]:
        free = self._free[replica]
        if free:
            return free.pop(0)
        lo, hi = (replica * self.blocks_per_replica,
                  (replica + 1) * self.blocks_per_replica)
        leaves = [b for b in range(lo, hi)
                  if self._children.get(b, 1) == 0 and b not in protect]
        if not leaves:
            return None
        victim = min(leaves, key=lambda b: self._lru.get(b, 0))
        key = self._key_of.pop(victim)
        del self._node[key]
        del self._children[victim]
        self._lru.pop(victim, None)
        if key[0] >= 0:
            self._children[key[0]] -= 1
        self.evictions += 1
        return victim

    # -- device ops (called by the scheduler) -------------------------------

    def load(self, cache, ids: List[int], lane: int):
        """Copy matched pool blocks into lane ``lane`` of ``cache``; padded
        id slots gather block 0 — garbage beyond the matched length is
        overwritten by replay before it is ever attended."""
        padded = np.zeros((self.max_match_blocks,), np.int32)
        padded[:len(ids)] = ids
        self.hits += 1
        self.saved_steps += len(ids) * self.block_tokens
        return self._load_fn(cache, self.pool, jnp.asarray(padded),
                             jnp.int32(lane))

    def insert(self, tokens, p_len: int, cache, lane: int,
               replica: int = 0) -> int:
        """Register a cleanly-finished lane's prompt blocks: walk the trie,
        save each missing fully-shadowed block out of the lane's KV (one
        jitted copy per new block). Returns the number of blocks saved."""
        limit = min((p_len - 1) // self.block_tokens, self.max_match_blocks)
        parent = -1
        path: set = set()
        saved = 0
        for i, chunk in enumerate(self._chunks(np.asarray(tokens), limit)):
            bid = self._node.get((parent, chunk))
            if bid is None:
                bid = self._alloc(replica, path)
                if bid is None:
                    break
                self._node[(parent, chunk)] = bid
                self._key_of[bid] = (parent, chunk)
                self._children[bid] = 0
                if parent >= 0:
                    self._children[parent] += 1
                self.pool = self._save_fn(
                    self.pool, cache, jnp.int32(lane),
                    jnp.int32(i * self.block_tokens), jnp.int32(bid))
                self.inserted += 1
                saved += 1
            self._tick += 1
            self._lru[bid] = self._tick
            path.add(bid)
            parent = bid
        return saved

    @property
    def n_cached_blocks(self) -> int:
        return len(self._key_of)

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "saved_steps": self.saved_steps,
                "inserted": self.inserted, "evictions": self.evictions,
                "cached_blocks": self.n_cached_blocks}
