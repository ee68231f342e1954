"""Continuous-batching slot scheduler: ONE compiled mixed step for a
fixed-capacity slot table (DESIGN.md SS12).

``Engine.generate`` serves one synchronous same-length batch per call; under
real traffic that leaves slots idle while the longest request drains and
recompiles whenever shapes drift. This module holds per-request decode state
in a padded device batch of ``n_slots`` lanes — KV-cache lane, position,
remaining-token budget, per-slot PRNG key, per-slot sampling params
(temperature / sample_k as *traced arrays*) — and advances every live lane
together with a single jitted step:

 * **Mixed prefill/decode.** Prompt replay is chunked into the decode path
   one token per step (the same replay-through-cache trick generate() uses),
   so a lane mid-replay and a lane mid-generation ride the SAME executable:
   admitting a request never stalls in-flight decodes and never recompiles.
 * **Shared estimator work.** The batched backend decode runs once over all
   lanes; the probe-union dedup that makes retrieval estimators pay off
   under load (U <= min(Q*n_probe, nb)) happens across *requests*. Inactive
   lanes are masked out of the union (``core.decode.make_plan(active=...)``)
   so a half-empty table never pays for garbage probes.
 * **Per-slot sampling on generate()'s key schedule.** Each lane folds its
   own request key with its own stream-step index (``fold_in(key, t)`` on
   replay, ``fold_in(key, 10000 + t)`` after), splits off the sampling key,
   and draws its own Gumbel noise — so a request decoded in a busy slot
   table emits bit-identical tokens to the same request run alone through
   ``generate()`` (tests/test_scheduler.py pins this).
 * **Slot recycling.** A finished lane is marked inactive on device and
   returned to the host free list; the next admission rewinds the lane to
   position 0 — stale KV above the new request's frontier is masked by the
   per-slot validity window, so no cache zeroing is needed.

Both jitted entry points (``_step``, ``_admit``) carry trace counters:
after one step and one admission, NOTHING recompiles — asserted by tests
and by ``benchmarks/serving_bench.py``.

Robustness layer (DESIGN.md SS14)
---------------------------------
 * **Deadlines.** Each lane carries a traced countdown next to its budget;
   a lane whose deadline lapses mid-decode is *evicted* — folded into the
   same ``finished`` path as normal completion, so the slot recycles next
   step with no extra dispatch and no recompile. Neighbors are unaffected
   bit-for-bit (per-lane keys; masked rows never contribute probes).
 * **Estimator tiers.** ``set_tier`` switches which backend the NEXT step
   decodes with (the server's degradation ladder). Each tier's step is
   compiled once, lazily, against the same SlotTable — stepping down under
   overload is a host pointer update.
 * **Health guard.** The compiled step routes any lane whose estimate went
   non-finite / empty through the exact dense fallback under ``lax.cond``
   (``core.decode.apply_health_guard``): no NaN ever reaches sampling, and
   healthy steps take a bit-identical identity branch.
 * **Fault injection.** An attached ``serve.faults`` injector can raise
   before the compiled step runs, corrupt engine retrieval state (caught by
   the digest verify/restore cadence), or flip per-lane fault masks — the
   masks are traced arguments (all-False in normal service), so injection
   never recompiles and an injected lane's blast radius is itself.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.backends import (get_backend, shadow_exact_log_z,
                             state_partition_specs, verify_decode)
from ..core.decode import (HEALTH_EMPTY_HEAD, HEALTH_NONFINITE_SCORE,
                           HEALTH_NONFINITE_Z, apply_health_guard,
                           health_flags)
from ..obs.metrics import (TIER_IX, init_metric_state, observe_step,
                           shadow_rel_err)
from ..obs.metrics import harvest as harvest_metric_state
from .prefix_cache import PrefixPool, cache_is_kv_only

_REQ_IDS = itertools.count()

# deadline sentinel: far above any real step count, small enough that the
# int32 countdown never wraps
NO_DEADLINE = 1 << 30


@dataclasses.dataclass
class Request:
    """One serving request. ``key`` may be a PRNG key array or an int seed;
    it drives this request's sampling exactly as the same key would in
    ``generate()``. ``sample_k=0`` means the engine's configured
    ``sample_k``; smaller values restrict Gumbel-max to the top candidates.
    """
    prompt: Any                       # (L,) ints (list / np / jax array)
    max_new_tokens: int
    key: Any = 0
    temperature: float = 0.0
    sample_k: int = 0
    deadline: int = 0                 # virtual steps from submission before
                                      # the request is shed/evicted (0 = none;
                                      # the server may stamp its default)
    on_token: Optional[Callable] = None     # fn(request, token, wall_time)
    on_complete: Optional[Callable] = None  # fn(request, completion)
    req_id: int = dataclasses.field(default_factory=lambda: next(_REQ_IDS))

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if np.ndim(self.key) == 0:
            self.key = jax.random.PRNGKey(int(self.key))


@dataclasses.dataclass
class Completion:
    """Streamed back through ``Request.on_complete`` and returned by
    ``Scheduler.step`` when a lane finishes."""
    request: Request
    tokens: List[int]
    log_probs: List[float]
    log_zs: List[float]
    admit_time: float
    first_token_time: Optional[float]
    done_time: float
    overflowed: bool = False
    error: Optional[str] = None    # set when the request did not complete
                                   # normally (admission rejected: tokens
                                   # empty; evicted mid-decode: tokens
                                   # partial)
    reason: Optional[str] = None   # machine-readable code for error
                                   # completions: 'queue_full',
                                   # 'deadline_queue', 'deadline_evicted',
                                   # 'admit_rejected', 'fault_injected',
                                   # 'server_stopped'
    tiers: List[str] = dataclasses.field(default_factory=list)
                                   # estimator tier(s) this request's tokens
                                   # were served at, in order (degradation
                                   # audit trail; normally one entry)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SlotTable:
    """Device-resident per-lane decode state (everything the mixed step
    reads or writes; one pytree, one dispatch)."""
    cache: Any              # model decode state, batch = n_slots
    prompt: jax.Array       # (S, P_cap) padded prompt tokens
    last_token: jax.Array   # (S,)  lane's previous sampled token
    t_stream: jax.Array     # (S,)  step index within the lane's request ==
                            #       the lane's next KV position (one token
                            #       is consumed at position t per step)
    t_replay: jax.Array     # (S,)  lane's true prompt length
    budget: jax.Array       # (S,)  tokens still to emit
    req_key: jax.Array      # (S, 2) per-request PRNG key
    temperature: jax.Array  # (S,)  per-slot sampling temperature
    sample_k: jax.Array     # (S,)  per-slot candidate restriction
    deadline: jax.Array     # (S,)  remaining virtual steps before eviction
                            #       (NO_DEADLINE = none)
    active: jax.Array       # (S,)  lane holds a live request
    step_idx: jax.Array     # ()    global step counter (estimator PRNG)


def sample_slots(out, keys: jax.Array, temperature: jax.Array,
                 sample_k: Optional[jax.Array] = None):
    """Per-slot Gumbel-max over retrieved candidates: the traced-array
    generalization of ``engine._sample_candidates`` — one temperature, key
    and candidate budget PER ROW. Bit-compatible with the batch-shared
    sampler lane-for-lane when ``sample_k`` equals the retrieved width (the
    gumbel draw per lane matches the solo (1, k) draw exactly).

    keys (S, 2) are each lane's k_samp; temperature (S,) with 0 = greedy
    (index 0 of the sorted candidates); sample_k (S,) restricts lane s to
    its top ``sample_k[s]`` candidates.
    """
    kc = out.top_score.shape[1]
    g = jax.vmap(lambda k: jax.random.gumbel(k, (1, kc))[0])(keys)
    t = jnp.asarray(temperature, jnp.float32)
    safe_t = jnp.where(t > 0.0, t, 1.0)
    noisy = out.top_score / safe_t[:, None] + g
    if sample_k is not None:
        allowed = jnp.arange(kc)[None, :] < \
            jnp.maximum(sample_k, 1)[:, None]
        noisy = jnp.where(allowed, noisy, -jnp.inf)
    pick = jnp.where(t > 0.0, jnp.argmax(noisy, axis=-1),
                     jnp.zeros(t.shape, jnp.int32)).astype(jnp.int32)
    tok = jnp.take_along_axis(out.top_id, pick[:, None], 1)[:, 0]
    score = jnp.take_along_axis(out.top_score, pick[:, None], 1)[:, 0]
    return tok.astype(jnp.int32), score


def spec_accept(n_ok: jax.Array, t_stream: jax.Array, t_replay: jax.Array,
                budget: jax.Array, active: jax.Array, draft_bad: jax.Array,
                max_len: int, spec_k: int) -> jax.Array:
    """Accepted-position count per lane for one speculative round — the
    variable-advance algebra, factored out so the property tests can hammer
    it directly (DESIGN.md SS16b).

    ``n_ok`` is the leading-correct-input count over the round's spec_k
    positions (position 0's input is forced correct, so n_ok >= 1). The
    accepted count ``a`` is n_ok capped three ways: a lane may not emit
    past its budget (replay positions don't emit — the first
    r = clip(t_replay-1-t_stream, 0, k) accepted positions are free), may
    not advance past the KV capacity, and a lane whose DRAFT pass was
    health-flagged collapses to a = 1 — literally the non-speculative step
    for that lane this round (the chaos-fault fallback). Inactive lanes
    advance 0. Invariants (property-tested): active lanes get 1 <= a <=
    spec_k; emitted count max(0, a - r) never exceeds budget; t_stream + a
    never exceeds max_len + 1 with equality only at the overflow finish.
    """
    r = jnp.clip(t_replay - 1 - t_stream, 0, spec_k)
    a = jnp.minimum(n_ok, r + jnp.maximum(budget, 0))
    a = jnp.where(draft_bad, 1, a)
    a = jnp.clip(a, 1, spec_k)
    a = jnp.minimum(a, jnp.maximum(max_len - t_stream, 1))
    return jnp.where(active, a, 0).astype(jnp.int32)


class Scheduler:
    """Fixed-capacity continuous-batching scheduler over one ``Engine``.

    Host-side: a free-slot list, per-slot request bookkeeping, streaming
    callbacks. Device-side: the ``SlotTable`` plus two jitted functions —
    ``_admit`` (traced slot index: one compile serves every slot) and
    ``_step`` (the mixed replay/decode step). Audio (multi-codebook) heads
    have no slot-table path; use ``generate``.
    """

    def __init__(self, engine, n_slots: int, prompt_cap: Optional[int] = None,
                 key: Optional[jax.Array] = None, injector=None,
                 health_guard: bool = True,
                 spec_draft: Optional[str] = None, spec_k: int = 1,
                 spec_draft_probes: int = 0, prefix_cache_blocks: int = 0,
                 prefix_block_tokens: int = 8):
        if engine.cfg.n_codebooks:
            raise NotImplementedError(
                "the slot scheduler serves single-stream text heads; "
                "audio codebook decoding goes through serve.generate")
        self.engine = engine
        self.n_slots = n_slots
        # (data, model) serving mesh (Engine(mesh=...)): slot lanes are laid
        # out replica-major over the FLAT (S,) table — lane s lives on data
        # replica s // lanes_per_replica — and the one compiled step runs
        # under shard_map (DESIGN.md SS15). mesh=None is the single-device
        # path, byte-for-byte the PR-6 scheduler.
        self.mesh = getattr(engine, "mesh", None)
        if self.mesh is not None:
            self.n_replicas = int(self.mesh.shape["data"])
            if n_slots % self.n_replicas:
                raise ValueError(
                    f"n_slots {n_slots} must divide the mesh's data degree "
                    f"{self.n_replicas} (each replica owns an equal set of "
                    f"KV lanes)")
        else:
            self.n_replicas = 1
        self.lanes_per_replica = n_slots // self.n_replicas
        self.prompt_cap = int(prompt_cap or engine.max_len)
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.health_guard = health_guard
        self.injector = injector           # serve.faults.FaultInjector | None
        self.verify_index_every = 0        # digest-check cadence (0 = off);
                                           # set by the server from its
                                           # ServingConfig
        self.tier = engine.backend.method  # estimator tier the next step
                                           # decodes with
        self.step_traces = 0
        self.admit_traces = 0
        self.traces_by_tier: Dict[str, int] = {}
        self.steps_done = 0
        self._free = list(range(n_slots))
        self._slot_req: List[Optional[Request]] = [None] * n_slots
        self._slot_acc: List[Optional[Completion]] = [None] * n_slots
        self._no_fault = jnp.zeros((n_slots,), bool)
        # -- observability (obs/, DESIGN.md SS17): the metric pytree is
        # ALWAYS threaded through the compiled step — enabling harvesting
        # or shadow sampling later changes only traced data, never the
        # executable, so tokens stay bit-exact and trace counters pinned
        self.shadow_every = 0              # shadow-oracle cadence in steps
                                           # (0 = off); obs.Observability
                                           # sets it from ObsConfig
        self.metrics_state = init_metric_state()
        self._last_step_ms = -1.0          # previous step's device phase,
                                           # fed forward into the device
                                           # latency histogram (< 0: none)
        self._last_step_tier = engine.backend.method
        self.table = self._init_table()
        if self.mesh is not None:
            # canonical shardings: jit keys its compile cache on INPUT
            # shardings, so every table/params/state argument is pinned to
            # these exact NamedShardings (init + drain via device_put; admit
            # via out_shardings; step via out_specs) — that is what makes
            # "zero recompiles after warmup" survive the mesh
            self._table_sh = self._shardings_of(self._table_specs())
            self._lane_sh = NamedSharding(self.mesh, P("data"))
            self._repl_sh = NamedSharding(self.mesh, P())
            self._placements: Dict[Any, tuple] = {}
            self.table = jax.device_put(self.table, self._table_sh)
            self._no_fault = jax.device_put(self._no_fault, self._lane_sh)
            # metric counters are replicated (each replica accumulates the
            # same psum-reduced globals); pin them so the step executable's
            # input-sharding cache key never drifts
            self.metrics_state = jax.device_put(self.metrics_state,
                                                self._repl_sh)
        # -- estimator-speculative decoding (DESIGN.md SS16b): a cheap
        # registry backend drafts spec_k tokens per lane inside the step;
        # ONE batched pass of the lane's serving tier verifies them. The
        # draft runs a REDUCED probe budget — with the verifier's own
        # probes the candidates (and hence the deterministic Gumbel-max
        # sample) would match trivially and speculation would buy nothing.
        self.spec_draft = spec_draft
        self.spec_k = max(1, int(spec_k)) if spec_draft else 1
        pc = engine.cfg.partition
        self.spec_draft_probes = int(spec_draft_probes) or \
            max(1, pc.n_probe // 2)
        self.prefix: Optional[PrefixPool] = None
        if self.spec_k > 1 or prefix_cache_blocks:
            if engine.cfg.sliding_window or \
                    not cache_is_kv_only(self.table.cache):
                raise NotImplementedError(
                    "speculative decoding and the prefix cache rely on "
                    "rewindable full-attention KV lanes (a rejected or "
                    "stale position is overwritten before it is attended); "
                    "sliding-window ring buffers and recurrent decode "
                    "states break that argument")
        if self.spec_k > 1:
            get_backend(spec_draft)      # unknown drafts fail at init
        if prefix_cache_blocks:
            self.prefix = PrefixPool(
                self.table.cache, prefix_cache_blocks, prefix_block_tokens,
                max_match_blocks=max(
                    1, (self.prompt_cap - 1) // prefix_block_tokens),
                mesh=self.mesh,
                cache_shardings=None if self.mesh is None
                else self._table_sh.cache,
                n_replicas=self.n_replicas)
        self._step_fns: Dict[str, Callable] = {}
        self._bstate_sh: Dict[str, Any] = {}
        self._dstate_sh: Dict[str, Any] = {}
        self._admit_fn = self._build_admit()

    # -- device state --------------------------------------------------------

    def _init_table(self) -> SlotTable:
        s = self.n_slots
        eng = self.engine
        return SlotTable(
            cache=eng.model.init_decode_state(s, eng.max_len),
            prompt=jnp.zeros((s, self.prompt_cap), jnp.int32),
            last_token=jnp.zeros((s,), jnp.int32),
            t_stream=jnp.zeros((s,), jnp.int32),
            t_replay=jnp.ones((s,), jnp.int32),
            budget=jnp.zeros((s,), jnp.int32),
            req_key=jnp.zeros((s, 2), jnp.uint32),
            temperature=jnp.zeros((s,), jnp.float32),
            sample_k=jnp.ones((s,), jnp.int32),
            deadline=jnp.full((s,), NO_DEADLINE, jnp.int32),
            active=jnp.zeros((s,), bool),
            step_idx=jnp.zeros((), jnp.int32))

    # -- mesh plumbing -------------------------------------------------------

    def _table_specs(self) -> SlotTable:
        """PartitionSpec tree of the SlotTable under the serving mesh: every
        per-lane (S, ...) leaf — including each KV-cache lane batch — shards
        dim 0 over 'data'; the step counter is replicated. The table stays
        FLAT (S,), replica-major: host bookkeeping (``_slot_req[s]``,
        ``out["emitted"][s]``) is layout-blind."""
        from ..launch.mesh import serve_cache_spec
        cache = jax.tree_util.tree_map_with_path(serve_cache_spec,
                                                 self.table.cache)
        lane = P("data")
        return SlotTable(cache=cache, prompt=P("data", None),
                         last_token=lane, t_stream=lane, t_replay=lane,
                         budget=lane, req_key=P("data", None),
                         temperature=lane, sample_k=lane, deadline=lane,
                         active=lane, step_idx=P())

    def _shardings_of(self, specs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def _placed(self, cache_key, obj, shardings):
        """device_put ``obj`` to its canonical shardings, memoized by object
        identity: params/tier states are long-lived engine objects, so
        steady-state steps re-place nothing; a ``swap_index`` swaps in new
        objects and misses the cache exactly once."""
        ent = self._placements.get(cache_key)
        if ent is not None and ent[0] is obj:
            return ent[1]
        placed = jax.device_put(obj, shardings)
        self._placements[cache_key] = (obj, placed)
        return placed

    def _build_step(self, method: str):
        if self.spec_k > 1:
            return self._build_spec_step(method)
        eng = self.engine
        model = eng.model
        pc = eng.cfg.partition
        backend = get_backend(method)
        # measured kernel tiles were swept for the engine's own backend;
        # degradation tiers run library defaults (correctness never depends
        # on the tile choice)
        kernel_cfg = dict(eng.kernel_cfg) \
            if method == eng.backend.method else {}
        use_pallas = eng.use_pallas
        health_guard = self.health_guard
        max_len = eng.max_len
        est_key = jax.random.fold_in(self.key, 0xE57)
        # the step donates the table (donate_argnums=0): the KV cache is
        # updated in place instead of allocated + copied n_slots x max_len
        # per token

        mesh = self.mesh
        tier_ix = TIER_IX[method]
        n_slots = self.n_slots

        # the step body, shared verbatim by both compilation paths: plain
        # jit on a single device, or shard_map over the (data, model) mesh —
        # where ``table`` is each replica's local lanes, ``bstate``'s
        # payloads are the local model shard, and the only mesh-specific
        # lines are the estimator dispatch (backend.shard_decode — the
        # psum-row-gather bodies in serve.output_layer, bit-identical to
        # decode), the mesh health guard, and the data-psum of the two
        # step scalars
        def body(table: SlotTable, params, bstate, fault_nan, fault_inf,
                 metrics, extras):
            # -- input token: next prompt token while replaying, else the
            #    lane's own previous sample
            is_replay = table.t_stream < table.t_replay
            t_clamp = jnp.minimum(table.t_stream, self.prompt_cap - 1)
            ptok = jnp.take_along_axis(table.prompt, t_clamp[:, None],
                                       1)[:, 0]
            tok_in = jnp.where(is_replay, ptok, table.last_token)
            # -- cache-capacity guard: traced positions clamp-with-flag
            #    (Engine.decode_step's compiled-path contract)
            overflow = table.active & (table.t_stream >= max_len)
            pos_safe = jnp.minimum(table.t_stream, max_len - 1)
            h, new_cache = model.decode_step(params, table.cache, tok_in,
                                             pos_safe)
            # -- per-slot sampling keys on generate()'s fold schedule
            fold = jnp.where(is_replay, table.t_stream,
                             10_000 + table.t_stream - table.t_replay)
            step_keys = jax.vmap(jax.random.fold_in)(table.req_key, fold)
            k_samp = jax.vmap(lambda k: jax.random.split(k)[1])(step_keys)
            # -- ONE shared estimator decode across every lane; masked lanes
            #    stay out of the probe union
            k_est = jax.random.fold_in(est_key, table.step_idx)
            if mesh is None:
                out = backend.decode(bstate, h, k_est, pc, k=pc.sample_k,
                                     use_pallas=use_pallas,
                                     active=table.active, **kernel_cfg)
            else:
                out = backend.shard_decode(bstate, h, k_est, pc,
                                           k=pc.sample_k,
                                           active=table.active,
                                           axis_name="model")
            # -- lane-scoped fault injection: the masks are traced arguments
            #    (all-False arrays in normal service — same executable), and
            #    every downstream consumer is per-lane, so a corrupted lane's
            #    blast radius is exactly itself
            corrupt = fault_nan | fault_inf
            bad_val = jnp.where(fault_inf, jnp.inf, jnp.nan)
            out = out._replace(
                log_z=jnp.where(corrupt, bad_val, out.log_z),
                top_score=jnp.where(corrupt[:, None], bad_val[:, None],
                                    out.top_score))
            # -- health guard: unhealthy lanes (non-finite log Ẑ / empty
            #    probe union / non-finite scores — whether injected or
            #    organic) fall back to the exact dense path; healthy steps
            #    take the bit-identical identity branch
            if health_guard and mesh is None:
                out, flags = apply_health_guard(out, bstate.w, h,
                                                pc.sample_k,
                                                active=table.active)
            elif health_guard:
                from .output_layer import mesh_health_guard
                out, flags = mesh_health_guard(out, bstate.w, h,
                                               pc.sample_k,
                                               active=table.active,
                                               axis_name="model")
            else:
                flags = jnp.zeros(table.active.shape, jnp.int32)
            tok, score = sample_slots(out, k_samp, table.temperature,
                                      table.sample_k)
            # -- lifecycle: the lane's first kept sample is emitted by its
            #    LAST replay step (t_stream == t_replay - 1), same as
            #    generate(); budget counts emitted tokens
            emitted = table.active & (table.t_stream >= table.t_replay - 1) \
                & ~overflow
            new_budget = table.budget - emitted.astype(jnp.int32)
            done = (emitted & (new_budget <= 0)) | overflow
            act = table.active
            # -- deadline countdown: one virtual step of service per step; a
            #    lane that lapses without finishing is evicted through the
            #    SAME finished path (slot recycles next step, no recompile).
            #    It still emits this step's token — eviction returns partial
            #    output, it does not discard work already done.
            new_ddl = table.deadline - act.astype(jnp.int32)
            expired = act & ~done & (new_ddl <= 0)
            finished = done | expired
            new_table = dataclasses.replace(
                table,
                cache=new_cache,
                last_token=jnp.where(act, tok, table.last_token),
                t_stream=table.t_stream + act.astype(jnp.int32),
                budget=new_budget,
                deadline=new_ddl,
                active=act & ~finished,
                step_idx=table.step_idx + 1)
            head_live = out.head_live if out.head_live is not None \
                else jnp.zeros((), jnp.int32)
            n_active = act.astype(jnp.int32).sum()
            if mesh is not None:
                # per-replica scalars -> global (head_live sums each
                # replica's probe-union size; replicated over 'model'
                # already — the plan runs on replicated metadata)
                n_active = jax.lax.psum(n_active, "data")
                head_live = jax.lax.psum(head_live, "data")
            # -- observability (obs/): shadow-sampled exact log Z on the
            # traced cadence flag (both cond branches ride the same
            # executable — the mesh_health_guard replicated-predicate
            # pattern licenses the collectives inside) + the metric-state
            # accumulation. Reads only values the step already computed;
            # nothing feeds back into sampling.
            shadow = jax.lax.cond(
                extras["do_shadow"],
                lambda: shadow_rel_err(
                    out.log_z,
                    shadow_exact_log_z(
                        bstate, h, None if mesh is None else "model"),
                    act),
                lambda: (jnp.float32(0.0), jnp.float32(0.0), jnp.int32(0)))
            new_metrics = observe_step(
                metrics, tier_ix, n_slots,
                n_active=n_active, head_live=head_live,
                n_emitted=emitted.astype(jnp.int32).sum(),
                health_flags=flags, queue_depth=extras["queue_depth"],
                last_ms=extras["last_ms"], last_tier=extras["last_tier"],
                shadow=shadow,
                axis_name=None if mesh is None else "data")
            outs = {"token": tok, "log_prob": score - out.log_z,
                    "log_z": out.log_z, "emitted": emitted,
                    "finished": finished, "overflow": overflow,
                    "expired": expired, "health": flags,
                    "n_active": n_active, "head_live": head_live}
            return new_table, new_metrics, outs

        if mesh is None:
            # params and the retrieval state are traced ARGUMENTS, not
            # closure constants: Engine.swap_index can hand a freshly
            # trained checkpoint to a live server and the very next step
            # serves it from the same executable (shapes are identical
            # under device_index=True)
            @partial(jax.jit, donate_argnums=0)
            def step(table: SlotTable, params, bstate, fault_nan, fault_inf,
                     metrics, extras):
                self.step_traces += 1   # python side effect: counts traces
                self.traces_by_tier[method] = \
                    self.traces_by_tier.get(method, 0) + 1
                return body(table, params, bstate, fault_nan, fault_inf,
                            metrics, extras)

            return step

        # mesh path: the SAME body under shard_map. Per-lane leaves split
        # over 'data' (each replica advances its own lanes + KV), the
        # retrieval payloads over 'model' (state_partition_specs), params
        # replicated. The trace counters live OUT here — shard_map may
        # re-trace the body while specializing, which is not a recompile.
        table_specs = self._table_specs()
        bstate = self.engine.tier_state(method)
        bspecs = state_partition_specs(bstate, self.mesh.shape["model"])
        self._bstate_sh[method] = self._shardings_of(bspecs)
        lane = P("data")
        # metric state + host scalars ride replicated (P() prefix covers the
        # whole pytree): every replica accumulates identical psum-reduced
        # counters, so the host may harvest any one shard
        out_specs = (table_specs, P(),
                     {"token": lane, "log_prob": lane, "log_z": lane,
                      "emitted": lane, "finished": lane, "overflow": lane,
                      "expired": lane, "health": lane,
                      "n_active": P(), "head_live": P()})
        sharded = jax.shard_map(body, mesh=mesh,
                                in_specs=(table_specs, P(), bspecs, lane,
                                          lane, P(), P()),
                                out_specs=out_specs, check_vma=False)

        @partial(jax.jit, donate_argnums=0)
        def step(table: SlotTable, params, bstate, fault_nan, fault_inf,
                 metrics, extras):
            self.step_traces += 1
            self.traces_by_tier[method] = \
                self.traces_by_tier.get(method, 0) + 1
            return sharded(table, params, bstate, fault_nan, fault_inf,
                           metrics, extras)

        return step

    def _build_spec_step(self, method: str):
        """Draft/verify twin of ``_build_step`` (DESIGN.md SS16b): the ONE
        compiled step drafts ``spec_k`` tokens per lane with the cheap
        ``spec_draft`` backend at a reduced probe budget, then verifies all
        positions with ONE batched pass of the lane's serving tier
        (``core.backends.verify_decode``) and advances each lane by its
        accepted count — traced data, so variable per-lane acceptance never
        recompiles.

        Exactness is deterministic, not stochastic: sampling is Gumbel-max
        under the per-position fold key, so the verifier's sample at
        position j is bit-identical to what the non-speculative step would
        emit there — PROVIDED position j's input token was correct. The
        accepted prefix is precisely the positions whose inputs were
        correct (replay positions are forced correct; a generation
        position's input is the previous draft token, correct iff it
        matched the previous verifier token), so emitted tokens are
        bit-identical to the non-speculative scheduler for greedy AND
        temperature lanes, with no rejection-resampling residual. Rejected
        positions leave garbage KV above the accepted frontier; every such
        position is rewritten by a later sequential step before it is ever
        attended, and the per-lane validity mask hides the rest — the same
        argument that gates this path to full-attention KV states.

        A tier walk (serve.server's degradation ladder) swaps ``method`` —
        the VERIFIER — while the draft stays fixed: the protocol is
        unchanged, only who checks the drafts."""
        eng = self.engine
        model = eng.model
        pc = eng.cfg.partition
        backend = get_backend(method)
        draft = get_backend(self.spec_draft)
        draft_pc = dataclasses.replace(pc, method=self.spec_draft,
                                       n_probe=self.spec_draft_probes)
        kernel_cfg = dict(eng.kernel_cfg) \
            if method == eng.backend.method else {}
        use_pallas = eng.use_pallas
        health_guard = self.health_guard
        max_len = eng.max_len
        kk = self.spec_k
        prompt_cap = self.prompt_cap
        est_key = jax.random.fold_in(self.key, 0xE57)
        draft_key = jax.random.fold_in(self.key, 0xD4AF)
        mesh = self.mesh
        tier_ix = TIER_IX[method]
        n_slots = self.n_slots

        def body(table: SlotTable, params, bstate, dstate, fault_nan,
                 fault_inf, metrics, extras):
            act = table.active
            corrupt = fault_nan | fault_inf
            bad_val = jnp.where(fault_inf, jnp.inf, jnp.nan)
            cache = table.cache
            hs, ksamps, reps, ovfls, dtoks = [], [], [], [], []
            draft_bad = jnp.zeros_like(act)
            d_prev = table.last_token
            # -- draft phase: kk sequential model steps threading the KV
            #    cache exactly as kk non-spec steps would; the j-th input is
            #    the prompt token while replaying, else the (j-1)-th draft
            for j in range(kk):
                pos = table.t_stream + j
                is_rep = pos < table.t_replay
                t_clamp = jnp.minimum(pos, prompt_cap - 1)
                ptok = jnp.take_along_axis(table.prompt, t_clamp[:, None],
                                           1)[:, 0]
                tok_in = jnp.where(is_rep, ptok, d_prev)
                ovfls.append(act & (pos >= max_len))
                pos_safe = jnp.minimum(pos, max_len - 1)
                h, cache = model.decode_step(params, cache, tok_in,
                                             pos_safe)
                fold = jnp.where(is_rep, pos, 10_000 + pos - table.t_replay)
                step_keys = jax.vmap(jax.random.fold_in)(table.req_key,
                                                         fold)
                k_samp = jax.vmap(lambda k: jax.random.split(k)[1])(
                    step_keys)
                hs.append(h)
                ksamps.append(k_samp)
                reps.append(is_rep)
                if j < kk - 1:
                    dk = jax.random.fold_in(
                        jax.random.fold_in(draft_key, table.step_idx), j)
                    if mesh is None:
                        dout = draft.decode(dstate, h, dk, draft_pc,
                                            k=pc.sample_k,
                                            use_pallas=use_pallas,
                                            active=act)
                    else:
                        dout = draft.shard_decode(dstate, h, dk, draft_pc,
                                                  k=pc.sample_k, active=act,
                                                  axis_name="model")
                    # lane-fault masks corrupt the DRAFT pass too: a flagged
                    # draft forces that lane to a = 1 below — per-lane
                    # fallback to plain non-speculative decode
                    dout = dout._replace(
                        log_z=jnp.where(corrupt, bad_val, dout.log_z),
                        top_score=jnp.where(corrupt[:, None],
                                            bad_val[:, None],
                                            dout.top_score))
                    draft_bad = draft_bad | (health_flags(dout) > 0)
                    d_tok, _ = sample_slots(dout, k_samp, table.temperature,
                                            table.sample_k)
                    dtoks.append(d_tok)
                    d_prev = d_tok
            # -- verify phase: ONE accurate-backend pass over all S*kk
            #    drafted positions, on the SAME estimator key schedule as
            #    the non-spec step (candidates per row are key-independent;
            #    the key only drives tail sampling, i.e. log Ẑ)
            hseq = jnp.stack(hs, 1)
            k_est = jax.random.fold_in(est_key, table.step_idx)
            out = verify_decode(backend, bstate, hseq, k_est, pc,
                                k=pc.sample_k, active=act,
                                use_pallas=use_pallas,
                                axis_name=None if mesh is None else "model",
                                **kernel_cfg)
            corrupt_r = jnp.repeat(corrupt, kk)
            bad_r = jnp.repeat(bad_val, kk)
            out = out._replace(
                log_z=jnp.where(corrupt_r, bad_r, out.log_z),
                top_score=jnp.where(corrupt_r[:, None], bad_r[:, None],
                                    out.top_score))
            act_r = jnp.repeat(act, kk)
            hflat = hseq.reshape(-1, hseq.shape[-1])
            if health_guard and mesh is None:
                out, vflags = apply_health_guard(out, bstate.w, hflat,
                                                 pc.sample_k, active=act_r)
            elif health_guard:
                from .output_layer import mesh_health_guard
                out, vflags = mesh_health_guard(out, bstate.w, hflat,
                                                pc.sample_k, active=act_r,
                                                axis_name="model")
            else:
                vflags = jnp.zeros(act_r.shape, jnp.int32)
            ks_flat = jnp.stack(ksamps, 1).reshape(-1, 2)
            v_tok, v_score = sample_slots(
                out, ks_flat, jnp.repeat(table.temperature, kk),
                jnp.repeat(table.sample_k, kk))
            S = act.shape[0]
            v_tok = v_tok.reshape(S, kk)
            v_score = v_score.reshape(S, kk)
            log_z = out.log_z.reshape(S, kk)
            vflags = vflags.reshape(S, kk)
            # -- acceptance: leading-correct-input prefix, capped by budget
            #    / capacity / draft health (spec_accept)
            ok = jnp.ones_like(act)
            oks = [ok]
            for j in range(1, kk):
                ok = ok & (reps[j] | (dtoks[j - 1] == v_tok[:, j - 1]))
                oks.append(ok)
            n_ok = jnp.stack(oks, 1).astype(jnp.int32).sum(1)
            a = spec_accept(n_ok, table.t_stream, table.t_replay,
                            table.budget, act, draft_bad, max_len, kk)
            jpos = jnp.arange(kk)[None, :]
            accepted_m = jpos < a[:, None]
            ovfl_m = jnp.stack(ovfls, 1)
            emit = accepted_m & act[:, None] \
                & ((table.t_stream[:, None] + jpos)
                   >= (table.t_replay[:, None] - 1)) & ~ovfl_m
            e = emit.astype(jnp.int32).sum(1)
            new_budget = table.budget - e
            overflow = ovfl_m[:, 0]
            done = (act & (e > 0) & (new_budget <= 0)) | overflow
            # one speculative round = one virtual step of deadline service
            new_ddl = table.deadline - act.astype(jnp.int32)
            expired = act & ~done & (new_ddl <= 0)
            finished = done | expired
            idx = jnp.clip(a - 1, 0, kk - 1)
            lt = jnp.take_along_axis(v_tok, idx[:, None], 1)[:, 0]
            new_table = dataclasses.replace(
                table,
                cache=cache,
                last_token=jnp.where(act, lt, table.last_token),
                t_stream=table.t_stream + a,
                budget=new_budget,
                deadline=new_ddl,
                active=act & ~finished,
                step_idx=table.step_idx + 1)
            flags_l = jnp.zeros_like(n_ok)
            for j in range(kk):
                flags_l = flags_l | jnp.where(accepted_m[:, j],
                                              vflags[:, j], 0)
            head_live = out.head_live if out.head_live is not None \
                else jnp.zeros((), jnp.int32)
            n_active = act.astype(jnp.int32).sum()
            if mesh is not None:
                n_active = jax.lax.psum(n_active, "data")
                head_live = jax.lax.psum(head_live, "data")
            # -- observability: the shadow oracle scores the SAME flattened
            # (S*kk) verify rows the serving tier just estimated, so one
            # cadenced pass samples every drafted position's rel-err
            shadow = jax.lax.cond(
                extras["do_shadow"],
                lambda: shadow_rel_err(
                    out.log_z,
                    shadow_exact_log_z(
                        bstate, hflat, None if mesh is None else "model"),
                    act_r),
                lambda: (jnp.float32(0.0), jnp.float32(0.0), jnp.int32(0)))
            new_metrics = observe_step(
                metrics, tier_ix, n_slots,
                n_active=n_active, head_live=head_live,
                n_emitted=e.sum(),
                health_flags=flags_l, queue_depth=extras["queue_depth"],
                last_ms=extras["last_ms"], last_tier=extras["last_tier"],
                shadow=shadow,
                spec_proposed=act.astype(jnp.int32).sum() * kk,
                spec_accepted=a.sum(),
                draft_flagged=(draft_bad & act).astype(jnp.int32).sum(),
                axis_name=None if mesh is None else "data")
            outs = {"token": v_tok, "log_prob": v_score - log_z,
                    "log_z": log_z, "emitted": emit,
                    "finished": finished, "overflow": overflow,
                    "expired": expired, "health": flags_l,
                    "accepted": a, "draft_flagged": draft_bad & act,
                    "n_active": n_active, "head_live": head_live}
            return new_table, new_metrics, outs

        if mesh is None:
            @partial(jax.jit, donate_argnums=0)
            def step(table: SlotTable, params, bstate, dstate, fault_nan,
                     fault_inf, metrics, extras):
                self.step_traces += 1
                self.traces_by_tier[method] = \
                    self.traces_by_tier.get(method, 0) + 1
                return body(table, params, bstate, dstate, fault_nan,
                            fault_inf, metrics, extras)

            return step

        table_specs = self._table_specs()
        bstate = self.engine.tier_state(method)
        bspecs = state_partition_specs(bstate, self.mesh.shape["model"])
        self._bstate_sh[method] = self._shardings_of(bspecs)
        dstate = self.engine.tier_state(self.spec_draft)
        dspecs = state_partition_specs(dstate, self.mesh.shape["model"])
        self._dstate_sh[self.spec_draft] = self._shardings_of(dspecs)
        lane = P("data")
        lane_k = P("data", None)
        out_specs = (table_specs, P(),
                     {"token": lane_k, "log_prob": lane_k, "log_z": lane_k,
                      "emitted": lane_k, "finished": lane, "overflow": lane,
                      "expired": lane, "health": lane, "accepted": lane,
                      "draft_flagged": lane,
                      "n_active": P(), "head_live": P()})
        sharded = jax.shard_map(body, mesh=mesh,
                                in_specs=(table_specs, P(), bspecs, dspecs,
                                          lane, lane, P(), P()),
                                out_specs=out_specs, check_vma=False)

        @partial(jax.jit, donate_argnums=0)
        def step(table: SlotTable, params, bstate, dstate, fault_nan,
                 fault_inf, metrics, extras):
            self.step_traces += 1
            self.traces_by_tier[method] = \
                self.traces_by_tier.get(method, 0) + 1
            return sharded(table, params, bstate, dstate, fault_nan,
                           fault_inf, metrics, extras)

        return step

    def _get_step(self, method: str):
        fn = self._step_fns.get(method)
        if fn is None:
            fn = self._step_fns[method] = self._build_step(method)
        return fn

    def set_tier(self, method: str) -> None:
        """Switch which estimator tier the NEXT step decodes with (the
        server walks its degradation ladder through this). Each tier's step
        compiles once, lazily, and tier states reuse the engine's index
        (``Engine.tier_state``) — after warmup a tier switch is two host
        pointer updates, zero device work, zero recompiles."""
        if method == self.tier:
            return
        get_backend(method)   # unknown tiers fail loudly, not at trace time
        self.tier = method

    def _build_admit(self):
        # under a mesh, pin the admitted table to the canonical shardings:
        # .at[slot].set on a 'data'-sharded lane would otherwise leave XLA
        # free to emit a differently-sharded (or replicated) result, and the
        # step executable — keyed on input shardings — would recompile
        jit_kw = {} if self.mesh is None else \
            {"out_shardings": self._table_sh}

        @partial(jax.jit, donate_argnums=0, **jit_kw)
        def admit(table: SlotTable, slot, prompt_row, p_len, budget, key,
                  temp, sample_k, deadline, t0):
            self.admit_traces += 1
            upd = lambda arr, val: arr.at[slot].set(val)
            # t0 > 0 = prefix-cache hit: the pool already landed the first
            # t0 positions of KV (Scheduler.admit), so replay resumes there
            return dataclasses.replace(
                table,
                prompt=jax.lax.dynamic_update_slice(
                    table.prompt, prompt_row[None, :], (slot, 0)),
                last_token=upd(table.last_token, prompt_row[0]),
                t_stream=upd(table.t_stream, t0),
                t_replay=upd(table.t_replay, p_len),
                budget=upd(table.budget, budget),
                req_key=table.req_key.at[slot].set(key),
                temperature=upd(table.temperature, temp),
                sample_k=upd(table.sample_k, sample_k),
                deadline=upd(table.deadline, deadline),
                active=upd(table.active, True))

        return admit

    # -- host API -------------------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    def _pick_slot(self, preferred_replica: Optional[int] = None) -> int:
        """Claim a free lane. Single device: lowest index (FIFO order over
        a sorted free list — the PR-6 behavior, unchanged). Under a mesh,
        route to the LEAST-LOADED data replica (most free lanes; ties to
        the lowest replica) and take its lowest lane — staggered admissions
        spread across replicas instead of piling onto replica 0, which is
        what makes goodput scale with the data degree under partial load.
        ``preferred_replica`` (prefix-cache affinity: the replica owning a
        matched block chain) is tried first; when it has no free lane the
        admission falls through to least-loaded and forfeits the hit."""
        if self.n_replicas == 1:
            return self._free.pop(0)
        if preferred_replica is not None:
            cand = [s for s in self._free
                    if s // self.lanes_per_replica == preferred_replica]
            if cand:
                slot = min(cand)
                self._free.remove(slot)
                return slot
        free_per = [0] * self.n_replicas
        for s in self._free:
            free_per[s // self.lanes_per_replica] += 1
        rep = max(range(self.n_replicas), key=lambda r: (free_per[r], -r))
        slot = min(s for s in self._free
                   if s // self.lanes_per_replica == rep)
        self._free.remove(slot)
        return slot

    def free_in_replica(self, replica: int) -> int:
        """Free lanes owned by one data replica (1 replica == the whole
        table on a single device). The server's bounded-lookahead admission
        uses this to decide whether holding a request for its preferred
        replica is worth a skip."""
        if self.n_replicas == 1:
            return len(self._free)
        return sum(1 for s in self._free
                   if s // self.lanes_per_replica == replica)

    def prefix_preview(self, request: "Request"):
        """(cached_prefix_tokens, owner_replica) the prefix pool would give
        this request at admission — None owner when the pool is off or the
        prompt misses. Host-only dict walk; used by the server's admission
        lookahead to route requests toward their cached blocks."""
        if self.prefix is None:
            return 0, None
        p_len = int(request.prompt.shape[0])
        if p_len < 1:
            return 0, None
        m, _, owner = self.prefix.match(request.prompt, p_len)
        return m * self.prefix.block_tokens, owner

    @property
    def n_in_flight(self) -> int:
        return self.n_slots - len(self._free)

    def admit(self, request: Request,
              deadline_steps: Optional[int] = None) -> int:
        """Place a request in a free lane; returns the slot index. Raises
        when the table is full (callers queue — see serve.server) or when
        the request cannot fit the engine's caches (host-path guard:
        admission is the last point where a python error is possible).
        ``deadline_steps`` is the lane's eviction countdown in scheduler
        steps (None = no deadline); the server passes the request's
        *remaining* deadline so queue wait counts against it."""
        if self.injector is not None:
            # fault hook BEFORE any state mutates: a rejected admission
            # leaves the scheduler exactly as it was
            self.injector.on_admit(request, self)
        if deadline_steps is not None and deadline_steps < 1:
            raise ValueError("deadline already expired at admission")
        p_len = int(request.prompt.shape[0])
        if p_len < 1:
            raise ValueError("request needs a non-empty prompt")
        if p_len > self.prompt_cap:
            raise ValueError(
                f"prompt length {p_len} > scheduler prompt_cap "
                f"{self.prompt_cap}")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        need = p_len + request.max_new_tokens - 1
        if need > self.engine.max_len:
            raise ValueError(
                f"request needs {need} cache positions (prompt {p_len} + "
                f"{request.max_new_tokens} tokens) but engine max_len is "
                f"{self.engine.max_len}")
        if not self._free:
            raise RuntimeError("no free slot; queue the request instead")
        # -- prefix cache: host trie match, then ONE traced block-gather
        #    lands the cached KV in the lane and replay resumes at t0
        pref_ids: List[int] = []
        owner = None
        if self.prefix is not None:
            _, pref_ids, owner = self.prefix.match(request.prompt, p_len)
        slot = self._pick_slot(owner)
        t0 = 0
        if pref_ids and (self.n_replicas == 1
                         or slot // self.lanes_per_replica == owner):
            new_cache = self.prefix.load(self.table.cache, pref_ids, slot)
            self.table = dataclasses.replace(self.table, cache=new_cache)
            t0 = len(pref_ids) * self.prefix.block_tokens
        prompt_row = np.zeros((self.prompt_cap,), np.int32)
        prompt_row[:p_len] = request.prompt
        sk = request.sample_k or self.engine.cfg.partition.sample_k
        sk = max(1, min(sk, self.engine.cfg.partition.sample_k))
        ddl = NO_DEADLINE if deadline_steps is None else int(deadline_steps)
        self.table = self._admit_fn(
            self.table, jnp.int32(slot), jnp.asarray(prompt_row),
            jnp.int32(p_len), jnp.int32(request.max_new_tokens),
            jnp.asarray(request.key, jnp.uint32), jnp.float32(
                request.temperature), jnp.int32(sk), jnp.int32(ddl),
            jnp.int32(t0))
        self._slot_req[slot] = request
        self._slot_acc[slot] = Completion(
            request=request, tokens=[], log_probs=[], log_zs=[],
            admit_time=time.perf_counter(), first_token_time=None,
            done_time=0.0)
        return slot

    def step(self, queue_depth: int = 0) -> dict:
        """Advance every live lane one token. Returns a host-side record:
        emitted tokens (streamed through ``on_token``), finished requests
        (``on_complete`` + listed under ``"completions"``), occupancy,
        probe-dedup, tier and estimator-health metrics for this step.
        ``queue_depth`` is the server's admission backlog, recorded into the
        device-resident queue gauge (traced data — never a recompile).

        Fault-injection order matters: the injector fires FIRST (a raised
        ``FaultError`` leaves the table unadvanced — the server retries the
        step), then the digest verify/restore cadence runs so a corrupted
        retrieval state is repaired BEFORE the compiled step consumes it.

        Timing: ``wall_device_s`` covers dispatch + compiled step + the
        outs readback; ``wall_host_s`` is everything else (injector, state
        lookups, completion bookkeeping, ``on_token``/``on_complete``
        callbacks); ``wall_s`` is their sum. The raw ``t_*`` perf_counter
        stamps ride along for the span tracer."""
        t0 = time.perf_counter()
        if self.injector is not None:
            self.injector.on_step_begin(self)
        restored = False
        if self.verify_index_every and \
                self.steps_done % self.verify_index_every == 0:
            restored = self.engine.verify_and_restore(self.tier)
        fault_nan = fault_inf = self._no_fault
        if self.injector is not None:
            lanes = self.injector.lane_faults(self)
            if lanes is not None:
                fault_nan = jnp.asarray(np.asarray(lanes[0], bool))
                fault_inf = jnp.asarray(np.asarray(lanes[1], bool))
        step_fn = self._get_step(self.tier)
        bstate = self.engine.tier_state(self.tier)
        params = self.engine.params
        spec = self.spec_k > 1
        dstate = self.engine.tier_state(self.spec_draft) if spec else None
        if self.mesh is not None:
            # canonical placements (identity-memoized: free in steady state)
            params = self._placed("params", params, self._repl_sh)
            bstate = self._placed(("bstate", self.tier), bstate,
                                  self._bstate_sh[self.tier])
            if spec:
                dstate = self._placed(("dstate", self.spec_draft), dstate,
                                      self._dstate_sh[self.spec_draft])
            if fault_nan is not self._no_fault:
                fault_nan = jax.device_put(fault_nan, self._lane_sh)
                fault_inf = jax.device_put(fault_inf, self._lane_sh)
        # observability scalars: traced data with a fixed pytree structure,
        # so toggling the shadow cadence or a moving queue depth hits the
        # same executable
        do_shadow = bool(self.shadow_every
                         and self.steps_done % self.shadow_every == 0)
        extras = {"queue_depth": jnp.int32(max(queue_depth, 0)),
                  "last_ms": jnp.float32(self._last_step_ms),
                  "last_tier": jnp.int32(TIER_IX[self._last_step_tier]),
                  "do_shadow": jnp.bool_(do_shadow)}
        t_dispatch = time.perf_counter()
        if spec:
            self.table, self.metrics_state, out = step_fn(
                self.table, params, bstate, dstate, fault_nan, fault_inf,
                self.metrics_state, extras)
        else:
            self.table, self.metrics_state, out = step_fn(
                self.table, params, bstate, fault_nan, fault_inf,
                self.metrics_state, extras)
        self.steps_done += 1
        out = jax.device_get(out)
        now = time.perf_counter()
        self._last_step_ms = (now - t_dispatch) * 1e3
        self._last_step_tier = self.tier
        # normalize to (S, k) position-major token matrices: the non-spec
        # step is the k = 1 column
        if np.asarray(out["token"]).ndim == 1:
            tok = np.asarray(out["token"])[:, None]
            em = np.asarray(out["emitted"])[:, None]
            lp = np.asarray(out["log_prob"])[:, None]
            lz = np.asarray(out["log_z"])[:, None]
        else:
            tok = np.asarray(out["token"])
            em = np.asarray(out["emitted"])
            lp = np.asarray(out["log_prob"])
            lz = np.asarray(out["log_z"])
        completions = []
        for s in range(self.n_slots):
            req = self._slot_req[s]
            if req is None:
                continue
            acc = self._slot_acc[s]
            for j in range(tok.shape[1]):
                if not em[s, j]:
                    continue
                if acc.first_token_time is None:
                    acc.first_token_time = now
                acc.tokens.append(int(tok[s, j]))
                acc.log_probs.append(float(lp[s, j]))
                acc.log_zs.append(float(lz[s, j]))
                if not acc.tiers or acc.tiers[-1] != self.tier:
                    acc.tiers.append(self.tier)
                if req.on_token is not None:
                    req.on_token(req, int(tok[s, j]), now)
            if out["finished"][s]:
                acc.done_time = now
                acc.overflowed = bool(out["overflow"][s])
                if out["expired"][s]:
                    acc.error = "deadline exceeded (evicted mid-decode)"
                    acc.reason = "deadline_evicted"
                if self.prefix is not None and acc.error is None \
                        and not acc.overflowed:
                    # cleanly-finished lane: its prompt KV is fully valid —
                    # register the block-aligned prefix in the pool BEFORE
                    # the slot recycles
                    self.prefix.insert(
                        req.prompt, int(req.prompt.shape[0]),
                        self.table.cache, s,
                        s // self.lanes_per_replica)
                self._slot_req[s] = None
                self._slot_acc[s] = None
                self._free.append(s)
                self._free.sort()
                completions.append(acc)
                if req.on_complete is not None:
                    req.on_complete(req, acc)
        flags = np.asarray(out["health"])
        t_done = time.perf_counter()
        rec = {"wall_s": t_done - t0,
               "wall_device_s": now - t_dispatch,
               "wall_host_s": (t_dispatch - t0) + (t_done - now),
               "t_start": t0, "t_dispatch": t_dispatch,
               "t_device_done": now, "t_done": t_done,
               "n_active": int(out["n_active"]),
               "head_live": int(out["head_live"]),
               "occupancy": int(out["n_active"]) / self.n_slots,
               "completions": completions,
               "tier": self.tier,
               "n_emitted": int(em.sum()),
               "index_restored": restored,
               "health_flagged": int((flags > 0).sum()),
               "health_nonfinite_z":
                   int((flags & HEALTH_NONFINITE_Z > 0).sum()),
               "health_empty_head":
                   int((flags & HEALTH_EMPTY_HEAD > 0).sum()),
               "health_nonfinite_score":
                   int((flags & HEALTH_NONFINITE_SCORE > 0).sum())}
        if spec:
            rec["spec_proposed"] = int(out["n_active"]) * self.spec_k
            rec["spec_accepted"] = int(np.asarray(out["accepted"]).sum())
            rec["draft_flagged"] = \
                int(np.asarray(out["draft_flagged"]).sum())
        return rec

    def harvest_metrics(self) -> dict:
        """ONE device->host read of the cumulative metric pytree (the obs
        layer calls this on its harvest cadence; see obs.metrics.harvest).
        Counters are monotone — harvesting never resets them."""
        return harvest_metric_state(self.metrics_state, self.n_slots)

    def reset_metrics(self) -> None:
        """Zero the device metric state (between benchmark phases). The
        fresh pytree has identical shapes/shardings, so the next step hits
        its existing executable — pinned under the mesh exactly like the
        init-time state."""
        self.metrics_state = init_metric_state()
        if self.mesh is not None:
            self.metrics_state = jax.device_put(self.metrics_state,
                                                self._repl_sh)
        self._last_step_ms = -1.0

    def drain(self, reason: str = "server_stopped") -> List[Completion]:
        """Forcibly close out every in-flight lane host-side: each open
        request becomes an errored completion carrying whatever tokens it
        already emitted, its lane returns to the free list, and the device
        table is deactivated in one update. The server flushes through this
        at shutdown / ``max_steps`` instead of silently stranding work."""
        now = time.perf_counter()
        completions = []
        for s in range(self.n_slots):
            req = self._slot_req[s]
            if req is None:
                continue
            acc = self._slot_acc[s]
            acc.done_time = now
            acc.error = f"evicted: {reason}"
            acc.reason = reason
            self._slot_req[s] = None
            self._slot_acc[s] = None
            self._free.append(s)
            completions.append(acc)
            if req.on_complete is not None:
                req.on_complete(req, acc)
        if completions:
            self._free.sort()
            n = self.n_slots
            self.table = dataclasses.replace(
                self.table,
                active=jnp.zeros((n,), bool),
                budget=jnp.zeros((n,), jnp.int32),
                deadline=jnp.full((n,), NO_DEADLINE, jnp.int32))
            if self.mesh is not None:
                # the freshly-built host arrays above are uncommitted; pin
                # the table back to canonical shardings so the next step
                # hits its existing executable
                self.table = jax.device_put(self.table, self._table_sh)
        return completions
