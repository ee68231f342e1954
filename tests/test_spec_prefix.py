"""Raw-speed serving: estimator-speculative decoding + shared-prefix KV
cache (DESIGN.md SS16).

The contract under test: both accelerations are INVISIBLE in the tokens —
a lane decoded speculatively (cheap registry draft proposes k tokens, the
lane's serving tier verifies them in one batched pass) or admitted on top
of cached prefix blocks emits bit-identical tokens to the same request
run alone through ``generate()`` — while the accepted-token count is
traced data (variable per-lane advance, zero recompiles after warmup),
the prefix pool ref-counts/evicts on the host with one compiled load and
one compiled save, a health-flagged draft collapses that lane to
non-speculative decode for the round, and admission lookahead never
starves a held request past its deadline.
"""
import dataclasses
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import ServingConfig, reduced_config
from repro.models import Model
from repro.serve import (Engine, NanLogitsFault, Request, Scheduler, Server,
                         generate, trace_arrivals)
from repro.serve.prefix_cache import PrefixPool, cache_is_kv_only
from repro.serve.scheduler import spec_accept

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def served(rng):
    """One shared engine (mimps, IVF engaged) for the whole module."""
    cfg = reduced_config("qwen1.5-4b")
    cfg = dataclasses.replace(
        cfg, vocab=1024, partition=dataclasses.replace(
            cfg.partition, method="mimps", block_rows=64, n_probe=4, l=64))
    m = Model(cfg)
    eng = Engine(m, m.init(jax.random.fold_in(rng, 42)), max_len=24)
    return eng, cfg


def _solo(eng, prompt, n, key, temperature=0.0):
    toks = generate(eng, jnp.asarray(prompt)[None], n, key,
                    temperature=temperature)
    return [int(t) for t in np.asarray(toks)[0]]


def _mixed_requests(cfg, rng, base=100):
    mk = lambda i, n: np.asarray(
        jax.random.randint(jax.random.fold_in(rng, base + i), (n,), 0,
                           cfg.vocab), np.int32)
    return [
        Request(prompt=mk(0, 3), max_new_tokens=5,
                key=jax.random.fold_in(rng, 7), temperature=0.0),
        Request(prompt=mk(1, 6), max_new_tokens=4,
                key=jax.random.fold_in(rng, 8), temperature=0.9),
        Request(prompt=mk(2, 4), max_new_tokens=6,
                key=jax.random.fold_in(rng, 9), temperature=0.5),
    ]


def _tokens_by_id(rep):
    return {c.request.req_id: c.tokens for c in rep.completions}


# ---------------------------------------------------------------------------
# speculative decoding: bit-exactness + compile stability
# ---------------------------------------------------------------------------

class TestSpecParity:
    @pytest.mark.parametrize("spec_k,draft", [(2, "topk"), (4, "topk"),
                                              (2, "fmbe")])
    def test_spec_bit_identical_to_solo(self, served, rng, spec_k, draft):
        """Acceptance: greedy AND temperature lanes emit the exact solo
        token stream at spec_k in {2, 4} — acceptance only decides how
        many verified positions land per round, never which token."""
        eng, cfg = served
        reqs = _mixed_requests(cfg, rng)
        solo = [_solo(eng, r.prompt, r.max_new_tokens, r.key,
                      r.temperature) for r in reqs]
        sched = Scheduler(eng, n_slots=4, key=rng, spec_draft=draft,
                          spec_k=spec_k)
        server = Server(sched)
        for r in reqs:
            server.submit(r)
        rep = server.run()
        got = _tokens_by_id(rep)
        for r, want in zip(reqs, solo):
            assert got[r.req_id] == want
        assert 0.0 < rep.spec_acceptance <= 1.0
        assert rep.spec_accepted <= rep.spec_proposed
        assert sched.step_traces == 1
        assert sched.admit_traces == 1

    def test_zero_recompiles_under_variable_acceptance(self, served, rng):
        """Pinned acceptance criterion: per-lane accepted counts vary
        round to round (temperature lanes reject at different depths) and
        across two traffic waves — all of it is traced data through ONE
        executable."""
        eng, cfg = served
        sched = Scheduler(eng, n_slots=3, key=rng, spec_draft="topk",
                          spec_k=4)
        server = Server(sched)
        server.submit(Request(prompt=[5, 7], max_new_tokens=2, key=1))
        server.run()
        assert sched.step_traces == 1 and sched.admit_traces == 1
        for base in (100, 300):
            reqs = _mixed_requests(cfg, rng, base=base) + [
                Request(prompt=[3], max_new_tokens=7, key=2,
                        temperature=2.0),
                Request(prompt=list(range(8)), max_new_tokens=1, key=3),
            ]
            rep = Server(sched).run(
                arrivals=trace_arrivals(reqs, [0, 0, 1, 2, 4]))
            assert len(rep.completions) == len(reqs)
            assert 0.0 < rep.spec_acceptance <= 1.0
        assert sched.step_traces == 1, "variable acceptance recompiled"
        assert sched.admit_traces == 1

    def test_spec_with_prefix_cache_warm_rerun_parity(self, served, rng):
        """Speculation + prefix cache composed: the warm second wave hits
        the pool (saving replay steps) and still matches solo bit-for-bit
        — cached KV rows are bit-identical to replayed rows."""
        eng, cfg = served
        reqs = _mixed_requests(cfg, rng)
        solo = [_solo(eng, r.prompt, r.max_new_tokens, r.key,
                      r.temperature) for r in reqs]
        sched = Scheduler(eng, n_slots=4, key=rng, spec_draft="topk",
                          spec_k=2, prefix_cache_blocks=8,
                          prefix_block_tokens=2)
        server = Server(sched)
        for r in reqs:
            server.submit(r)
        rep1 = server.run()
        for r, want in zip(reqs, solo):
            assert _tokens_by_id(rep1)[r.req_id] == want
        reqs2 = _mixed_requests(cfg, rng)      # same prompts, fresh ids
        for r in reqs2:
            server.submit(r)
        rep2 = server.run()
        got = _tokens_by_id(rep2)
        for r, want in zip(reqs2, solo):
            assert got[r.req_id] == want
        assert rep2.prefix["hits"] > 0
        assert rep2.prefix["saved_steps"] > 0
        assert rep2.steps < rep1.steps, "cache hits must shorten replay"
        assert sched.step_traces == 1
        assert sched.prefix.load_traces == 1
        assert sched.prefix.save_traces == 1

    def test_deadline_eviction_mid_speculation(self, served, rng):
        """Satellite 3 (integration half): a lane evicted mid-speculation
        leaves the surviving lane bit-identical, keeps a PREFIX of its own
        stream, and the slot table comes back clean (positions/budget/
        finished invariants intact — every lane recycled)."""
        eng, cfg = served
        keep = Request(prompt=[5, 9, 2], max_new_tokens=6,
                       key=jax.random.fold_in(rng, 77), temperature=0.6)
        evicted = Request(prompt=[8, 1], max_new_tokens=12, deadline=4,
                          key=jax.random.fold_in(rng, 78), temperature=0.3)
        solo_keep = _solo(eng, keep.prompt, keep.max_new_tokens, keep.key,
                          keep.temperature)
        solo_evicted = _solo(eng, evicted.prompt, evicted.max_new_tokens,
                             evicted.key, evicted.temperature)
        sched = Scheduler(eng, n_slots=2, key=rng, spec_draft="topk",
                          spec_k=4)
        server = Server(sched)
        server.submit(keep)
        server.submit(evicted)
        rep = server.run()
        by_id = {c.request.req_id: c for c in rep.completions}
        assert by_id[keep.req_id].tokens == solo_keep
        assert by_id[keep.req_id].error is None
        ev = by_id[evicted.req_id]
        assert ev.reason == "deadline_evicted"
        assert 0 < len(ev.tokens) < evicted.max_new_tokens
        assert ev.tokens == solo_evicted[:len(ev.tokens)]
        # table invariants: every lane recycled, positions inside capacity
        assert sched.n_free == 2
        assert np.all(np.asarray(sched.table.t_stream) <= eng.max_len)
        assert np.all(np.asarray(sched.table.budget) >= 0)

    def test_spec_composes_with_degradation_ladder(self, served, rng):
        """The tier walk swaps the VERIFIER, not the protocol: each tier's
        spec step compiles once, acceptance is tracked per tier, and no
        recompile happens across transitions."""
        eng, cfg = served
        long_req = Request(prompt=[3, 4], max_new_tokens=20,
                           key=jax.random.fold_in(rng, 501))
        shorts = _mixed_requests(cfg, rng) + _mixed_requests(cfg, rng, 200)
        sched = Scheduler(eng, n_slots=2, key=rng, spec_draft="topk",
                          spec_k=4)
        server = Server(sched, ServingConfig(
            degrade_high=3, degrade_low=1, degrade_after=2,
            restore_after=4))
        server.submit(long_req)
        for r in shorts:
            server.submit(r)
        rep = server.run()
        assert len(rep.completions) == len(shorts) + 1
        assert rep.tier_transitions, "pressure never engaged the ladder"
        assert all(v == 1 for v in sched.traces_by_tier.values()), \
            sched.traces_by_tier
        assert rep.spec_acceptance_by_tier
        for tier, acc in rep.spec_acceptance_by_tier.items():
            assert 0.0 < acc <= 1.0, (tier, acc)
        for c in rep.completions:
            assert np.all(np.isfinite(c.log_probs)), c.request.req_id


class TestSpecChaos:
    def test_nan_draft_falls_back_per_lane(self, served, rng):
        """Chaos acceptance: NaN logits in the DRAFT pass are caught by
        the health guard; the flagged lane collapses to a = 1 (literally
        non-speculative decode for that round) while every other lane
        stays bit-identical to the fault-free run. Nothing recompiles —
        the fault mask and the collapse are traced data."""
        eng, cfg = served
        reqs = _mixed_requests(cfg, rng)
        base_server = Server(Scheduler(eng, n_slots=3, key=rng,
                                       spec_draft="topk", spec_k=4))
        for r in reqs:
            base_server.submit(r)
        base = _tokens_by_id(base_server.run())
        victim = reqs[1]
        reqs2 = _mixed_requests(cfg, rng)
        inj = NanLogitsFault([reqs2[1].req_id], steps=range(1, 20))
        sched = Scheduler(eng, n_slots=3, key=rng, spec_draft="topk",
                          spec_k=4, injector=inj)
        server = Server(sched)
        for r in reqs2:
            server.submit(r)
        rep = server.run()
        got = _tokens_by_id(rep)
        for r, r0 in zip(reqs2, reqs):
            if r.req_id != reqs2[1].req_id:
                assert got[r.req_id] == base[r0.req_id], \
                    "draft fault leaked into a non-injected lane"
        assert len(got[reqs2[1].req_id]) == victim.max_new_tokens
        assert rep.draft_flagged > 0, \
            "the draft health guard never saw the NaN"
        for c in rep.completions:
            assert np.all(np.isfinite(c.log_probs)), c.request.req_id
            assert np.all(np.isfinite(c.log_zs)), c.request.req_id
        assert sched.step_traces == 1
        assert sched.admit_traces == 1


# ---------------------------------------------------------------------------
# the accepted-count algebra (satellite 3, property half)
# ---------------------------------------------------------------------------

class TestSpecAcceptProperty:
    MAX_LEN = 24
    K = 4
    # compiled once, outside @given: hypothesis times every example against
    # its deadline, and an example that pays for the compile (or for eager
    # per-op dispatch) measures JAX, not the algebra under test
    accept = staticmethod(jax.jit(spec_accept, static_argnums=(6, 7)))

    @classmethod
    def setup_class(cls):
        jax.block_until_ready(cls.accept(
            jnp.int32(1), jnp.int32(0), jnp.int32(1), jnp.int32(1),
            jnp.bool_(True), jnp.bool_(False), cls.MAX_LEN, cls.K))

    @settings(max_examples=200)
    @given(st.integers(1, 4),        # n_ok (position 0 forced correct)
           st.integers(0, 24),       # t_stream
           st.integers(1, 24),       # t_replay
           st.integers(1, 8),        # budget (active lanes have budget >= 1)
           st.integers(0, 1),        # active
           st.integers(0, 1))        # draft_bad
    def test_accept_invariants(self, n_ok, t_stream, t_replay, budget,
                               active, draft_bad):
        """For ANY accepted-length pattern: inactive lanes advance 0;
        active lanes advance 1..k; emissions never exceed budget; the
        stream never runs past KV capacity (+1 overflow finish); a flagged
        draft collapses to exactly the non-speculative advance of 1."""
        k, max_len = self.K, self.MAX_LEN
        a = int(self.accept(
            jnp.int32(n_ok), jnp.int32(t_stream), jnp.int32(t_replay),
            jnp.int32(budget), jnp.bool_(bool(active)),
            jnp.bool_(bool(draft_bad)), max_len, k))
        if not active:
            assert a == 0
            return
        assert 1 <= a <= k
        assert a <= n_ok or draft_bad or a == 1
        # emitted = accepted minus the replay positions covered this round
        r = min(max(t_replay - 1 - t_stream, 0), k)
        assert max(0, a - r) <= budget
        # never past capacity (equality at max_len -> the overflow finish;
        # a lane AT capacity still advances 1 and flags overflow)
        assert t_stream + a <= max_len or \
            (t_stream >= max_len and a == 1)
        if draft_bad:
            assert a == 1

    def test_vectorized_matches_scalar(self):
        """The traced call site is vectorized over lanes; it must agree
        with the per-lane scalar evaluation element-wise."""
        rng = np.random.default_rng(0)
        n = 64
        n_ok = rng.integers(1, 5, n)
        t_stream = rng.integers(0, 25, n)
        t_replay = rng.integers(1, 25, n)
        budget = rng.integers(1, 9, n)
        active = rng.integers(0, 2, n).astype(bool)
        bad = rng.integers(0, 2, n).astype(bool)
        vec = np.asarray(spec_accept(
            jnp.asarray(n_ok, jnp.int32), jnp.asarray(t_stream, jnp.int32),
            jnp.asarray(t_replay, jnp.int32), jnp.asarray(budget, jnp.int32),
            jnp.asarray(active), jnp.asarray(bad), self.MAX_LEN, self.K))
        for i in range(n):
            got = int(spec_accept(
                jnp.int32(n_ok[i]), jnp.int32(t_stream[i]),
                jnp.int32(t_replay[i]), jnp.int32(budget[i]),
                jnp.bool_(active[i]), jnp.bool_(bad[i]),
                self.MAX_LEN, self.K))
            assert got == int(vec[i]), i


# ---------------------------------------------------------------------------
# prefix pool host structure (trie / refcount / LRU)
# ---------------------------------------------------------------------------

def _kv(batch=2, t=16, n_kv=1, dh=4, fill=0.0):
    leaf = jnp.full((batch, t, n_kv, dh), fill, jnp.float32)
    return {"layers": [{"k": leaf, "v": leaf + 1.0}]}


class TestPrefixPoolUnit:
    def test_cache_is_kv_only(self):
        assert cache_is_kv_only(_kv())
        bad = {"layers": [{"k": jnp.zeros((2, 16, 1, 4)),
                           "conv": jnp.zeros((2, 16, 1, 4))}]}
        assert not cache_is_kv_only(bad)       # recurrent/conv state leaf
        low_rank = {"layers": [{"k": jnp.zeros((2, 16))}]}
        assert not cache_is_kv_only(low_rank)  # no (batch, pos) window

    def test_match_insert_roundtrip(self):
        pool = PrefixPool(_kv(), n_blocks=4, block_tokens=2,
                          max_match_blocks=4)
        cache = jax.tree.map(
            lambda l: l + jnp.arange(l.shape[-3],
                                     dtype=l.dtype)[None, :, None, None],
            _kv())
        toks = np.asarray([3, 1, 4, 1, 5], np.int32)
        # usable match capped at (p_len-1)//bt = 2 blocks even though the
        # prompt spans 2.5
        assert pool.insert(toks, 5, cache, lane=0) == 2
        m, ids, owner = pool.match(toks, 5)
        assert m == 2 and owner == 0
        # a different tail shares only the first block (trie split)
        toks2 = np.asarray([3, 1, 9, 9, 9], np.int32)
        m2, ids2, _ = pool.match(toks2, 5)
        assert m2 == 1 and ids2[0] == ids[0]
        # loading the match writes the SAME rows replay would produce
        dst = pool.load(_kv(), ids, lane=1)
        src_rows = np.asarray(cache["layers"][0]["k"][0, :4])
        np.testing.assert_array_equal(
            np.asarray(dst["layers"][0]["k"][1, :4]), src_rows)
        assert pool.hits == 1 and pool.saved_steps == 4

    def test_refcounted_eviction_never_orphans_children(self):
        """LRU eviction only takes LEAVES: a parent block with a live
        child is never evicted, so every surviving trie path stays walkable
        root-to-leaf (the refcount invariant)."""
        pool = PrefixPool(_kv(), n_blocks=4, block_tokens=2,
                          max_match_blocks=4)
        cache = _kv(fill=2.0)
        rng = np.random.default_rng(1)
        pool.insert(np.asarray([1, 2, 3, 4, 0], np.int32), 5, cache, 0)
        for i in range(6):    # force eviction churn past the 4-block pool
            toks = rng.integers(0, 100, size=(5,)).astype(np.int32)
            pool.insert(toks, 5, cache, 0)
        assert pool.evictions > 0
        assert pool.n_cached_blocks <= 4
        # invariant: every cached block's parent chain is intact
        for bid, (parent, _) in list(pool._key_of.items()):
            while parent >= 0:
                assert parent in pool._key_of, \
                    f"block {bid} orphaned (parent {parent} evicted)"
                parent = pool._key_of[parent][0]

    def test_insert_on_full_pool_of_protected_blocks_degrades(self):
        """When every block is an ancestor of the path being inserted
        (nothing evictable), insert saves what fits and stops — no raise,
        no corruption."""
        pool = PrefixPool(_kv(t=32), n_blocks=2, block_tokens=2,
                          max_match_blocks=8)
        cache = _kv(t=32, fill=1.0)
        toks = np.arange(10, dtype=np.int32)
        saved = pool.insert(toks, 10, cache, 0)
        assert saved == 2                      # pool capacity, not prompt
        assert pool.n_cached_blocks == 2
        m, _, _ = pool.match(toks, 10)
        assert m == 2

    def test_rejects_non_kv_cache(self):
        bad = {"layers": [{"k": jnp.zeros((2, 16, 1, 4)),
                           "s": jnp.zeros((2, 16, 1, 4))}]}
        with pytest.raises(NotImplementedError, match="KV"):
            PrefixPool(bad, n_blocks=4, block_tokens=2, max_match_blocks=2)


# ---------------------------------------------------------------------------
# admission lookahead (satellite 1)
# ---------------------------------------------------------------------------

class _FakeSched:
    """Just enough scheduler for Server._admit_ready: 2 replicas x 1 free
    lane each; requests carry .want_replica to drive prefix_preview."""
    tier = "mimps"
    verify_index_every = 0
    health_guard = True
    _step_fns = {"mimps": None}    # non-empty: Server must not touch guard

    def __init__(self, free_by_replica):
        self.free_by_replica = dict(free_by_replica)
        self.admitted = []

    @property
    def n_free(self):
        return sum(self.free_by_replica.values())

    def prefix_preview(self, req):
        want = getattr(req, "want_replica", None)
        return (4, want) if want is not None else (0, None)

    def free_in_replica(self, replica):
        return self.free_by_replica.get(replica, 0)

    def admit(self, req, deadline_steps=None):
        self.admitted.append(req.req_id)
        # consume a lane anywhere (preferred if free)
        want = getattr(req, "want_replica", None)
        if want is not None and self.free_by_replica.get(want, 0):
            self.free_by_replica[want] -= 1
            return
        for rep, n in self.free_by_replica.items():
            if n:
                self.free_by_replica[rep] -= 1
                return
        raise ValueError("no free lane")


class TestAdmissionLookahead:
    def _mk(self, want=None, **kw):
        r = Request(prompt=[1, 2, 3, 4], max_new_tokens=2, key=0, **kw)
        r.want_replica = want
        return r

    def test_window_admits_past_blocked_head(self):
        """Head-of-line fix: the queue head prefers full replica 0; with a
        window the next request (fits replica 1) admits THIS pass, the
        head is held in order, and the hold is counted."""
        sched = _FakeSched({0: 0, 1: 1})
        srv = Server(sched, ServingConfig(admit_window=2, admit_hold=8))
        blocked, free = self._mk(want=0), self._mk(want=1)
        srv.submit(blocked)
        srv.submit(free)
        srv._admit_ready()
        assert sched.admitted == [free.req_id]
        assert list(srv.queue) == [blocked]      # held, order preserved
        assert srv.admit_skipped == 1

    def test_strict_fifo_when_window_zero(self):
        """admit_window=0 is byte-identical PR-6 FIFO: the blocked head is
        admitted (anywhere) before anything behind it."""
        sched = _FakeSched({0: 0, 1: 1})
        srv = Server(sched)
        blocked, free = self._mk(want=0), self._mk(want=1)
        srv.submit(blocked)
        srv.submit(free)
        srv._admit_ready()
        assert sched.admitted == [blocked.req_id]
        assert srv.admit_skipped == 0

    def test_hold_count_bounds_starvation(self):
        """After admit_hold holds the request force-admits anywhere —
        forfeiting its cache hit, never starving."""
        srv = None
        sched = _FakeSched({0: 0, 1: 3})
        srv = Server(sched, ServingConfig(admit_window=1, admit_hold=3))
        blocked = self._mk(want=0)
        srv.submit(blocked)
        for i in range(2):
            srv._admit_ready()
            assert blocked.req_id not in sched.admitted
        srv._admit_ready()                       # 3rd pass: starving
        assert blocked.req_id in sched.admitted
        assert srv.admit_skipped == 2

    def test_deadline_near_forces_admission(self):
        """A held request whose deadline is within admit_hold steps
        force-admits immediately — no request starves past
        default_deadline."""
        sched = _FakeSched({0: 0, 1: 2})
        srv = Server(sched, ServingConfig(admit_window=1, admit_hold=8,
                                          default_deadline=5))
        blocked = self._mk(want=0)
        srv.submit(blocked)                      # deadline at step 5 <= 8
        srv._admit_ready()
        assert sched.admitted == [blocked.req_id]
        assert srv.admit_skipped == 0

    def test_lookahead_end_to_end_counts_skips(self, served, rng):
        """Real scheduler path: admit_window on with the pool off is a
        no-op (no owner preference -> pure FIFO), counts stay zero."""
        eng, cfg = served
        reqs = _mixed_requests(cfg, rng)
        sched = Scheduler(eng, n_slots=2, key=rng)
        server = Server(sched, ServingConfig(admit_window=2))
        for r in reqs:
            server.submit(r)
        rep = server.run()
        assert len(rep.completions) == len(reqs)
        assert rep.admit_skipped == 0


# ---------------------------------------------------------------------------
# mesh composition (subprocess: 8 placeholder host devices)
# ---------------------------------------------------------------------------

_MESH_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import reduced_config
from repro.models import Model
from repro.serve import Engine, Request, Scheduler, Server, generate
from repro.launch.mesh import make_serving_mesh

cfg = reduced_config("qwen1.5-4b")
cfg = dataclasses.replace(
    cfg, vocab=512, partition=dataclasses.replace(
        cfg.partition, method="mimps", block_rows=64, n_probe=2, l=32))
m = Model(cfg)
key = jax.random.PRNGKey(0)
params = m.init(jax.random.fold_in(key, 42))

solo_eng = Engine(m, params, max_len=20, key=key)
mk = lambda i, n: np.asarray(jax.random.randint(
    jax.random.fold_in(key, 100 + i), (n,), 0, cfg.vocab), np.int32)
def reqs():
    return [Request(prompt=mk(i, 3 + i % 4), max_new_tokens=3 + i % 3,
                    key=jax.random.fold_in(key, 200 + i),
                    temperature=0.0 if i % 2 == 0 else 0.8)
            for i in range(6)]
want = []
for r in reqs():
    t = generate(solo_eng, jnp.asarray(r.prompt)[None], r.max_new_tokens,
                 r.key, temperature=r.temperature)
    want.append([int(x) for x in np.asarray(t)[0]])

mesh = make_serving_mesh(data=2, model=2)
eng = Engine(m, params, max_len=20, key=key, mesh=mesh)
sched = Scheduler(eng, n_slots=4, key=key, spec_draft="topk", spec_k=4,
                  prefix_cache_blocks=8, prefix_block_tokens=2)
for wave in range(2):
    rs = reqs()
    srv = Server(sched)
    for r in rs:
        srv.submit(r)
    rep = srv.run()
    got = {c.request.req_id: c.tokens for c in rep.completions}
    for r, w in zip(rs, want):
        assert got[r.req_id] == w, (wave, r.req_id, got[r.req_id], w)
assert sched.step_traces == 1, sched.step_traces
assert sched.admit_traces == 1
assert rep.prefix["hits"] > 0, rep.prefix
print("ALL_OK")
"""


class TestMeshSpec:
    def test_mesh_spec_prefix_parity(self):
        """data=2,model=2 mesh + speculation + prefix pool: tokens match
        the single-device solo oracle on both waves, the warm wave hits
        the replica-local pool, zero retraces."""
        r = subprocess.run([sys.executable, "-c", _MESH_SNIPPET],
                           capture_output=True, text=True,
                           env=dict(os.environ, PYTHONPATH="src"),
                           cwd=REPO, timeout=900)
        assert r.returncode == 0 and "ALL_OK" in r.stdout, \
            r.stdout + r.stderr
