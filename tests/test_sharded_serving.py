"""Mesh-sharded traffic serving (DESIGN.md SS15).

In-process pieces: slot-lane routing across data replicas, IVF block
padding neutrality, engine mesh validations, tier-state partition-spec
rules. The device-count-dependent pieces run in subprocesses with 8
placeholder host devices (the tests/test_backends.py pattern, so the XLA
override never leaks into this process):

 * per-backend ``shard_decode`` body parity: one shard_map step over a
   (data, model) mesh must be BITWISE identical to the single-device
   decode on the unpadded index, for every servable estimator,
 * end-to-end scheduler parity: tokens from the mesh scheduler ==
   solo ``generate()`` per request, staggered admissions spread across
   replicas, and a second traffic wave retraces NOTHING,
 * sharded health guard: a NaN-injected lane falls back to the
   psum-combined exact splice; neighbors stay bit-identical to the
   fault-free mesh run with zero recompiles,
 * degradation ladder under the mesh: every tier compiles once during
   warmup; the overload walk traces nothing new.
"""
import os
import subprocess
import sys
import types

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run8(snippet: str, timeout: int = 900):
    r = subprocess.run([sys.executable, "-c", snippet],
                       capture_output=True, text=True,
                       env=dict(os.environ, PYTHONPATH="src"),
                       cwd=REPO, timeout=timeout)
    assert r.returncode == 0 and "ALL_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# in-process units
# ---------------------------------------------------------------------------

class TestSlotRouting:
    def test_least_loaded_replica_round_robin(self):
        """Admissions land on DISTINCT replicas first (least-loaded, ties
        to the lowest replica), lowest lane within a replica — staggered
        arrivals spread work across the data axis instead of piling onto
        replica 0."""
        from repro.serve.scheduler import Scheduler
        s = types.SimpleNamespace(n_replicas=4, lanes_per_replica=2,
                                  _free=list(range(8)))
        picks = [Scheduler._pick_slot(s) for _ in range(8)]
        assert picks == [0, 2, 4, 6, 1, 3, 5, 7]
        assert s._free == []

    def test_single_replica_keeps_fifo(self):
        from repro.serve.scheduler import Scheduler
        s = types.SimpleNamespace(n_replicas=1, lanes_per_replica=4,
                                  _free=[2, 0, 3])
        assert Scheduler._pick_slot(s) == 2

    def test_slots_must_divide_replicas(self):
        """The ctor rejects lane counts the data axis can't split evenly
        — validated before any device work, so a stub engine suffices
        (a real data=2 mesh would need 2 devices)."""
        from repro.serve.scheduler import Scheduler
        eng = types.SimpleNamespace(
            cfg=types.SimpleNamespace(n_codebooks=0),
            mesh=types.SimpleNamespace(shape={"data": 2, "model": 1}))
        with pytest.raises(ValueError, match="divide"):
            Scheduler(eng, n_slots=3)


class TestIndexPadding:
    def test_pad_is_decode_neutral(self, rng):
        """Dead pad blocks change nothing: probe ranks them -inf, scoring
        masks them, so decode over the padded index is bitwise identical —
        the property that lets the mesh shard a padded block dim while
        solo decode runs unpadded."""
        from repro.core.decode import mimps_decode
        from repro.core.mips import build_ivf, pad_ivf_blocks
        v = jax.random.normal(jax.random.fold_in(rng, 1), (1024, 32)) * 0.3
        h = jax.random.normal(jax.random.fold_in(rng, 2), (4, 32))
        idx = build_ivf(rng, v, block_rows=32, n_clusters=16)
        padded = pad_ivf_blocks(idx, 8)
        assert padded.v_blocks.shape[0] % 8 == 0
        assert padded.v_blocks.shape[0] >= idx.v_blocks.shape[0]
        a = mimps_decode(idx, h, rng, n_probe=4, l=64, k=4,
                         use_pallas=False)
        b = mimps_decode(padded, h, rng, n_probe=4, l=64, k=4,
                         use_pallas=False)
        for f in ("log_z", "top_score", "top_id", "head_lse", "tail_lse",
                  "k_eff"):
            np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                          np.asarray(getattr(b, f)), f)

    def test_pad_multiple_one_is_identity(self, rng):
        from repro.core.mips import build_ivf, pad_ivf_blocks
        v = jax.random.normal(rng, (256, 16))
        idx = build_ivf(rng, v, block_rows=32, n_clusters=4)
        assert pad_ivf_blocks(idx, 1) is idx


class TestEngineMeshValidation:
    @pytest.fixture(scope="class")
    def small(self, rng):
        from repro.configs import reduced_config
        from repro.models import Model
        cfg = reduced_config("qwen1.5-4b")
        cfg = dataclasses.replace(
            cfg, vocab=512, partition=dataclasses.replace(
                cfg.partition, method="mimps", block_rows=64, n_probe=2,
                l=32))
        m = Model(cfg)
        return m, m.init(jax.random.fold_in(rng, 3))

    def test_mesh_needs_both_axes(self, small, rng):
        from repro.serve import Engine
        m, params = small
        mesh = jax.make_mesh((1,), ("data",))
        with pytest.raises(ValueError, match="model"):
            Engine(m, params, max_len=16, mesh=mesh)

    def test_mesh_rejects_pallas(self, small, rng):
        from repro.launch.mesh import make_serving_mesh
        from repro.serve import Engine
        m, params = small
        with pytest.raises(ValueError, match="pallas"):
            Engine(m, params, max_len=16, mesh=make_serving_mesh(1, 1),
                   use_pallas=True)

    def test_mesh_pads_index_blocks(self, small, rng):
        """A (1,1) mesh engine works on the single real device and pads
        the IVF block dim to the model extent (trivially 1 here) while
        still matching solo generate() token-for-token."""
        from repro.launch.mesh import make_serving_mesh
        from repro.serve import Engine, Request, Scheduler, Server, generate
        m, params = small
        solo = Engine(m, params, max_len=16)
        eng = Engine(m, params, max_len=16, mesh=make_serving_mesh(1, 1))
        prompt = np.asarray(
            jax.random.randint(jax.random.fold_in(rng, 9), (3,), 0, 512),
            np.int32)
        want = [int(t) for t in np.asarray(generate(
            solo, jnp.asarray(prompt)[None], 4, rng))[0]]
        server = Server(Scheduler(eng, n_slots=2, key=rng))
        server.submit(Request(prompt=prompt, max_new_tokens=4, key=rng,
                              temperature=0.0))
        rep = server.run()
        assert rep.completions[0].tokens == want


class TestPartitionSpecs:
    def test_tier_state_specs_shard_only_output_layer(self, rng):
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import PartitionConfig
        from repro.core import backends as B
        cfg = PartitionConfig(block_rows=32, n_probe=2, l=32, n_clusters=8,
                              method="mimps", fmbe_features=64)
        w = jax.random.normal(rng, (512, 16)) * 0.3
        st = B.get_backend("mimps").build(cfg, w, rng, block_multiple=4)
        specs = B.state_partition_specs(st, 4)
        assert specs.w == P("model", None)
        assert specs.index.v_blocks == P("model", None, None)
        # every other leaf — centroids, radius, valid, row ids — replicated
        assert specs.index.block_centroids == P()
        assert specs.index.valid == P()
        assert specs.index.slot_of_row == P()

    def test_indivisible_falls_back_to_replicated(self, rng):
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import PartitionConfig
        from repro.core import backends as B
        cfg = PartitionConfig(block_rows=32, n_probe=2, l=32, n_clusters=8,
                              method="mimps", fmbe_features=64)
        w = jax.random.normal(rng, (510, 16)) * 0.3
        st = B.get_backend("mimps").build(cfg, w, rng)
        specs = B.state_partition_specs(st, 4)
        assert specs.w == P()


# ---------------------------------------------------------------------------
# 8-virtual-device subprocesses
# ---------------------------------------------------------------------------

BODY_PARITY_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs.base import PartitionConfig
from repro.core import backends as B
from repro.launch.mesh import make_serving_mesh

cfg = PartitionConfig(block_rows=32, n_probe=4, l=64, n_clusters=16,
                      method="mimps", fmbe_features=128)
key = jax.random.PRNGKey(0)
w = jax.random.normal(jax.random.PRNGKey(1), (1024, 32)) * 0.3
h = jax.random.normal(jax.random.PRNGKey(2), (8, 32))
active = jnp.array([1, 1, 0, 1, 1, 1, 0, 1], bool)
kd = jax.random.PRNGKey(7)

for (dp, mp) in [(1, 4), (2, 4)]:
    mesh = make_serving_mesh(dp, mp)
    for method in ["mimps", "mince", "topk", "fmbe", "exact", "selfnorm"]:
        bk = B.get_backend(method)
        ref = bk.decode(bk.build(cfg, w, key, device=True), h, kd, cfg,
                        k=4, use_pallas=False, active=active)
        st = bk.build(cfg, w, key, device=True, block_multiple=mp)
        specs = B.state_partition_specs(st, mp)
        body = lambda s, hh: bk.shard_decode(s, hh, kd, cfg, k=4,
                                             active=active)
        out = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=(specs, P()), out_specs=P(),
                                    check_vma=False))(st, h)
        if method in ("exact", "selfnorm"):
            # candidates exact; log_z only to psum reduction-order rounding
            assert bool(jnp.all(ref.top_score == out.top_score)), method
            assert bool(jnp.all(ref.top_id == out.top_id)), method
            assert bool(jnp.allclose(ref.log_z, out.log_z,
                                     atol=1e-5)), method
        else:
            for f in ("log_z", "top_score", "top_id", "head_lse",
                      "tail_lse", "k_eff"):
                assert bool(jnp.all(getattr(ref, f) == getattr(out, f))), \
                    (dp, mp, method, f)
print("ALL_OK")
"""


SCHED_PARITY_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import reduced_config
from repro.models import Model
from repro.serve import (Engine, Request, Scheduler, Server, generate,
                         trace_arrivals)
from repro.launch.mesh import make_serving_mesh

rng = jax.random.PRNGKey(0)
cfg = reduced_config("qwen1.5-4b")
cfg = dataclasses.replace(
    cfg, vocab=1024, partition=dataclasses.replace(
        cfg.partition, method="mimps", block_rows=64, n_probe=4, l=64))
m = Model(cfg)
params = m.init(jax.random.fold_in(rng, 42))

mk = lambda i, n: np.asarray(
    jax.random.randint(jax.random.fold_in(rng, 100 + i), (n,), 0,
                       cfg.vocab), np.int32)
spec = [(mk(0, 3), 5, 7, 0.0), (mk(1, 6), 4, 8, 0.9),
        (mk(2, 4), 6, 9, 0.5), (mk(3, 5), 5, 10, 0.3),
        (mk(4, 2), 7, 11, 0.0), (mk(5, 7), 3, 12, 0.7)]
mkreqs = lambda: [Request(prompt=p, max_new_tokens=n,
                          key=jax.random.fold_in(rng, s), temperature=t)
                  for (p, n, s, t) in spec]

solo_eng = Engine(m, params, max_len=24)
solo = [[int(x) for x in np.asarray(generate(
            solo_eng, jnp.asarray(p)[None], n, jax.random.fold_in(rng, s),
            temperature=t))[0]] for (p, n, s, t) in spec]

for (dp, mp) in [(4, 1), (2, 2)]:
    mesh = make_serving_mesh(dp, mp)
    eng = Engine(m, params, max_len=24, mesh=mesh)
    sched = Scheduler(eng, n_slots=2 * dp, key=rng)
    server = Server(sched)
    # staggered arrivals: one request per virtual step, so admissions hit
    # the least-loaded-replica router one at a time
    reqs = mkreqs()
    rep = server.run(arrivals=trace_arrivals(
        reqs, [float(i) for i in range(len(reqs))]))
    got = {c.request.req_id: c.tokens for c in rep.completions}
    assert all(got[r.req_id] == solo[i] for i, r in enumerate(reqs)), \
        (dp, mp, "wave-1 parity")
    # second wave through the warm scheduler: parity again AND zero
    # retraces of either executable
    t0, a0 = sched.step_traces, sched.admit_traces
    reqs2 = mkreqs()
    server2 = Server(sched)
    rep2 = server2.run(arrivals=trace_arrivals(
        reqs2, [0.0] * len(reqs2)))
    got2 = {c.request.req_id: c.tokens for c in rep2.completions}
    assert all(got2[r.req_id] == solo[i] for i, r in enumerate(reqs2)), \
        (dp, mp, "wave-2 parity")
    assert sched.step_traces == t0 and sched.admit_traces == a0, \
        (dp, mp, "retraced after warmup")

# staggered admission spreads lanes across replicas: with 4 replicas and
# one-arrival-per-step, the first 4 admissions occupy 4 DISTINCT replicas
mesh = make_serving_mesh(4, 1)
eng = Engine(m, params, max_len=24, mesh=mesh)
sched = Scheduler(eng, n_slots=8, key=rng)
lanes = sched.lanes_per_replica
slots = [sched._pick_slot() for _ in range(4)]
assert sorted(s // lanes for s in slots) == [0, 1, 2, 3], slots
print("ALL_OK")
"""


FAULT_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import ServingConfig, reduced_config
from repro.models import Model
from repro.serve import (Engine, NanLogitsFault, Request, Scheduler,
                         Server, default_ladder)
from repro.launch.mesh import make_serving_mesh

rng = jax.random.PRNGKey(0)
cfg = reduced_config("qwen1.5-4b")
cfg = dataclasses.replace(
    cfg, vocab=1024, partition=dataclasses.replace(
        cfg.partition, method="mimps", block_rows=64, n_probe=4, l=64))
m = Model(cfg)
params = m.init(jax.random.fold_in(rng, 42))
mesh = make_serving_mesh(2, 2)
eng = Engine(m, params, max_len=24, mesh=mesh)

mk = lambda i, n: np.asarray(
    jax.random.randint(jax.random.fold_in(rng, 300 + i), (n,), 0,
                       cfg.vocab), np.int32)
mkreqs = lambda: [Request(prompt=mk(i, 2 + i % 3), max_new_tokens=4,
                          key=jax.random.fold_in(rng, 400 + i),
                          temperature=0.0 if i % 2 else 0.7)
                  for i in range(4)]

# fault-free mesh oracle
base = Server(Scheduler(eng, n_slots=4, key=rng))
reqs0 = mkreqs()
for r in reqs0:
    base.submit(r)
rep0 = base.run()
toks0 = {c.request.req_id % 4: c.tokens for c in rep0.completions}

# NaN-injected lane under the mesh: guard must splice the psum-combined
# exact fallback into the victim only; neighbors bit-identical
reqs = mkreqs()
victim = reqs[1]
sched = Scheduler(eng, n_slots=4, key=rng,
                  injector=NanLogitsFault([victim.req_id],
                                          steps=range(1, 20)))
server = Server(sched)
for r in reqs:
    server.submit(r)
rep = server.run()
got = {c.request.req_id % 4: c.tokens for c in rep.completions}
for i in range(4):
    if i != 1:
        assert got[i] == toks0[i], ("fault leaked into lane", i)
for c in rep.completions:
    assert np.all(np.isfinite(np.asarray(c.log_probs))), c.request.req_id
    assert np.all(np.isfinite(np.asarray(c.log_zs))), c.request.req_id
assert rep.health["flagged"] > 0
assert rep.health["nonfinite_z"] > 0
assert sched.step_traces == 1, "fault masks must be traced data"

# degradation ladder under the mesh: warm every tier once, then sustained
# queue pressure (one long request hogging a lane + a backlog of shorts)
# walks the ladder without tracing anything new
sched2 = Scheduler(eng, n_slots=2, key=rng)
for tier in default_ladder(sched2.tier):
    sched2.set_tier(tier)
    warm = Server(sched2)
    for r in mkreqs()[:2]:
        warm.submit(r)
    warm.run()
sched2.set_tier("mimps")
t0, a0 = sched2.step_traces, sched2.admit_traces
srv = Server(sched2, ServingConfig(degrade_high=3, degrade_low=1,
                                   degrade_after=2, restore_after=4))
srv.submit(Request(prompt=mk(9, 2), max_new_tokens=20,
                   key=jax.random.fold_in(rng, 501)))
for i in range(6):
    srv.submit(Request(prompt=mk(10 + i, 2 + i % 3), max_new_tokens=2,
                       key=jax.random.fold_in(rng, 510 + i),
                       temperature=0.0 if i % 2 else 0.7))
rep2 = srv.run()
assert rep2.tier_transitions, "overload never walked the ladder"
assert rep2.degraded_token_frac > 0, rep2.tokens_by_tier
assert sched2.step_traces == t0 and sched2.admit_traces == a0, \
    "ladder walk retraced under mesh"
print("ALL_OK")
"""


class TestMeshServing8Dev:
    def test_shard_decode_body_parity_all_backends(self):
        _run8(BODY_PARITY_SNIPPET)

    def test_scheduler_token_parity_staggered_zero_retrace(self):
        _run8(SCHED_PARITY_SNIPPET)

    def test_health_guard_splice_and_ladder_under_mesh(self):
        _run8(FAULT_SNIPPET)
