"""LSH sampler backend (DESIGN.md SS18): packed SimHash index invariants,
O(1)-per-row update == fresh rebuild, fused Hamming-probe kernel parity,
importance-sampled tail correctness, unbiasedness of the collision
estimator over the hyperplane draw, zero-recompile maintenance, the lsh_ce
training loss, and the registry-derived serve CLI.

The 8-virtual-device sharded-decode parity case runs in a subprocess (the
tests/test_sharded_serving.py pattern) so the XLA device-count override
never leaks into this process.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PartitionConfig
from repro.core import lsh as _lsh
from repro.core.backends import BACKENDS, get_backend

from conftest import make_clustered_vectors

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _index(key, w, **kw):
    kw.setdefault("n_bits", 5)
    kw.setdefault("n_tables", 6)
    kw.setdefault("bucket_cap", 2048)  # >= n: no-overflow regime
    return _lsh.build_lsh_device(key, w, **kw)


@pytest.fixture(scope="module")
def small_setup():
    key = jax.random.PRNGKey(3)
    w = make_clustered_vectors(key, 2048, 32, n_centers=16)
    h = jax.random.normal(jax.random.fold_in(key, 1), (8, 32)) * 0.4
    return key, w, h


class TestBuildInvariants:
    def test_packed_tables_route_back(self, small_setup):
        """Every routed row (slot >= 0) sits at exactly its recorded bucket
        slot; every live bucket entry points back at a row whose code is
        that bucket."""
        key, w, _ = small_setup
        idx = _index(key, w)
        codes = np.asarray(idx.codes)
        slots = np.asarray(idx.slot_of_row)
        buckets = np.asarray(idx.buckets)
        n, ltab = codes.shape
        assert codes.min() >= 0 and codes.max() < idx.n_buckets
        for t in range(ltab):
            routed = slots[:, t] >= 0
            r = np.nonzero(routed)[0]
            assert (buckets[t, codes[r, t], slots[r, t]] == r).all()
            live = buckets[t][buckets[t] >= 0]
            assert len(live) == len(set(live)) == routed.sum()

    def test_proj_carries_mips_coordinate(self, small_setup):
        key, w, _ = small_setup
        idx = _index(key, w)
        assert idx.proj.shape == (6, 5, w.shape[1] + 1)
        # default policy is angle-only: the augmented coordinate clamps to 0
        assert float(idx.aug_scale) == 0.0

    def test_tail_logits_track_norms(self, small_setup):
        key, w, _ = small_setup
        idx = _index(key, w, tail_beta=16.0)
        norms = jnp.linalg.norm(w, axis=-1)
        np.testing.assert_allclose(
            np.asarray(idx.tail_logits),
            np.asarray(idx.tail_scale * norms), rtol=1e-6)


class TestUpdateEqualsRebuild:
    """Satellite: O(1)-per-row ``update_rows`` must land in the SAME state a
    fresh pack of the updated embedding reaches — identical codes and
    bit-identical downstream candidate sets — in the low-overflow regime
    (generous caps; overflow changes which table drops a row, which is a
    documented divergence, not a bug)."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_update_rows_matches_fresh_pack(self, small_setup, seed):
        key, w, h = small_setup
        idx = _index(key, w)
        kr = jax.random.PRNGKey(100 + seed)
        rows = jax.random.choice(kr, w.shape[0], (64,), replace=False)
        w2 = w.at[rows].add(
            0.3 * jax.random.normal(jax.random.fold_in(kr, 1),
                                    (64, w.shape[1])))
        upd = _lsh.update_rows(idx, w2, rows)
        fresh = _lsh.pack_lsh(idx.proj, w2, idx.aug_scale, idx.tail_scale,
                              bucket_cap=idx.bucket_cap)
        assert bool(jnp.all(upd.codes == fresh.codes))
        np.testing.assert_allclose(np.asarray(upd.tail_logits),
                                   np.asarray(fresh.tail_logits), atol=1e-6)
        # routing sets per table agree (slot ORDER may differ — update
        # splices into the first free slot, pack fills in row order)
        for t in range(idx.n_tables):
            a = np.asarray(upd.buckets[t]); b = np.asarray(fresh.buckets[t])
            for bk in range(idx.n_buckets):
                assert set(a[bk][a[bk] >= 0]) == set(b[bk][b[bk] >= 0])
        kd = jax.random.fold_in(kr, 2)
        pa = _lsh.lsh_plan(upd, h, kd, 128)
        pb = _lsh.lsh_plan(fresh, h, kd, 128)
        assert int(pa.cand_live) > 0, "degenerate: no candidates routed"
        for f in ("occ_q", "cand_rows", "cand_live", "member", "k_eff",
                  "tail_ids", "tail_accept"):
            assert bool(jnp.all(getattr(pa, f) == getattr(pb, f))), f
        oa = _lsh.lsh_decode(upd, w2, h, kd, l=128)
        ob = _lsh.lsh_decode(fresh, w2, h, kd, l=128)
        np.testing.assert_allclose(np.asarray(oa.log_z),
                                   np.asarray(ob.log_z), atol=1e-6)
        assert bool(jnp.all(oa.top_id == ob.top_id))

    def test_rehash_metrics_contract(self, small_setup):
        key, w, _ = small_setup
        idx = _index(key, w)
        new, m = _lsh.rehash_lsh(idx, w * 1.5)
        assert set(m) == {"churn", "drift"}
        # pure rescale flips no sign bits: churn == 0, and the packed
        # tables must be reproduced exactly
        assert float(m["churn"]) == 0.0
        assert bool(jnp.all(new.buckets == idx.buckets))


class TestDecodeCorrectness:
    def test_close_to_exact(self, small_setup):
        key, w, h = small_setup
        idx = _index(key, w, n_bits=4, n_tables=8, tail_beta=16.0)
        out = _lsh.lsh_decode(idx, w, h, jax.random.fold_in(key, 7), l=256)
        exact = jax.nn.logsumexp((h @ w.T).astype(jnp.float32), -1)
        rel = jnp.abs(1.0 - jnp.exp(out.log_z - exact))
        assert float(rel.mean()) < 0.15, float(rel.mean())
        # top-1 over the collision head must be the true argmax whenever
        # the true argmax collides (it does here: clustered data, 8 tables)
        s = h @ w.T
        agree = (out.top_id[:, 0] == jnp.argmax(s, -1)).mean()
        assert float(agree) >= 0.75

    def test_overflow_dense_fallback_matches(self, small_setup):
        """cand_cap below the measured union flips consumers to the dense
        occ_q branch — identical math, so log Z must agree to float
        reduction order."""
        key, w, h = small_setup
        idx = _index(key, w, n_bits=4, n_tables=8)
        kd = jax.random.fold_in(key, 8)
        big = _lsh.lsh_decode(idx, w, h, kd, l=128, cand_cap=w.shape[0])
        plan = _lsh.lsh_plan(idx, h, kd, 128)
        tiny_cap = max(8, int(plan.cand_live) // 4)
        small = _lsh.lsh_decode(idx, w, h, kd, l=128, cand_cap=tiny_cap)
        np.testing.assert_allclose(np.asarray(big.log_z),
                                   np.asarray(small.log_z), atol=1e-5)
        assert bool(jnp.all(big.top_id == small.top_id))

    def test_active_mask_keeps_live_rows(self, small_setup):
        key, w, h = small_setup
        idx = _index(key, w)
        kd = jax.random.fold_in(key, 9)
        active = jnp.array([1, 1, 0, 1, 0, 1, 1, 1], bool)
        solo = _lsh.lsh_decode(idx, w, h, kd, l=64)
        masked = _lsh.lsh_decode(idx, w, h, kd, l=64, active=active)
        live = np.nonzero(np.asarray(active))[0]
        np.testing.assert_allclose(np.asarray(masked.log_z)[live],
                                   np.asarray(solo.log_z)[live], atol=1e-5)


class TestImportanceTail:
    def test_beta_zero_reduces_to_uniform(self, small_setup):
        """tail_beta = 0 makes the defensive mixture exactly uniform: zero
        per-sample bias and the Hajek denominator degrades to the plain
        accept count."""
        key, w, h = small_setup
        idx = _index(key, w, tail_beta=0.0)
        plan = _lsh.lsh_plan(idx, h, jax.random.fold_in(key, 11), 128)
        assert float(jnp.max(jnp.abs(plan.tail_bias))) < 1e-5
        np.testing.assert_allclose(
            np.asarray(plan.n_accept),
            np.asarray(plan.tail_accept.sum(-1)), rtol=1e-5)

    def test_mixture_floors_sample_weight(self, small_setup):
        """Defensive mixture: every row keeps p >= 1/(2n), so the count
        weight exp(tail_bias) = 1/(n p) never exceeds 2 (the property that
        keeps the Hajek denominator estimable under heavy tilt)."""
        key, w, h = small_setup
        idx = _index(key, w, tail_beta=48.0)
        plan = _lsh.lsh_plan(idx, h, jax.random.fold_in(key, 12), 256)
        assert float(jnp.max(jnp.exp(plan.tail_bias))) <= 2.0 + 1e-5

    def test_tail_estimator_unbiased_over_draws(self, small_setup):
        """E over tail draws of the Eq. 5 tail term ~= the exact tail mass
        at fixed head (Hajek ratio: consistent, O(1/l) bias)."""
        key, w, h = small_setup
        idx = _index(key, w, tail_beta=16.0)
        h1 = h[:1]
        exact = float(jax.nn.logsumexp(
            (h1 @ w.T).astype(jnp.float32), -1)[0])
        zs = []
        for s in range(48):
            out = _lsh.lsh_decode(idx, w, h1, jax.random.PRNGKey(500 + s),
                                  l=256)
            zs.append(float(out.log_z[0]))
        z_mean = np.log(np.mean(np.exp(np.array(zs) - exact)))
        assert abs(z_mean) < 0.1, z_mean


class TestUnbiasedness:
    def test_sns_over_hyperplane_draws(self):
        """Spring & Shrivastava's estimator is unbiased over the TABLE
        draw: averaging Ẑ across independent hyperplane sets converges on
        the exact partition function."""
        key = jax.random.PRNGKey(17)
        w = make_clustered_vectors(key, 512, 16, n_centers=8)
        h = jax.random.normal(jax.random.fold_in(key, 1), (2, 16)) * 0.4
        exact = jax.nn.logsumexp((h @ w.T).astype(jnp.float32), -1)
        ratios = []
        for s in range(64):
            idx = _index(jax.random.PRNGKey(700 + s), w, n_bits=4,
                         n_tables=4, bucket_cap=512)
            lz = _lsh.sns_log_z(idx, w, h)
            ratios.append(np.exp(np.asarray(lz - exact, np.float64)))
        mean = np.mean(ratios, axis=0)
        assert np.all(np.abs(mean - 1.0) < 0.25), mean


class TestKernelParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fused_matches_reference(self, small_setup, dtype):
        key, w, h = small_setup
        idx = _index(key, w.astype(dtype), n_bits=4, n_tables=8)
        kd = jax.random.fold_in(key, 21)
        ref = _lsh.lsh_decode(idx, w.astype(dtype), h.astype(dtype), kd,
                              l=128, k=4, use_pallas=False)
        pal = _lsh.lsh_decode(idx, w.astype(dtype), h.astype(dtype), kd,
                              l=128, k=4, use_pallas=True)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(pal.log_z),
                                   np.asarray(ref.log_z), atol=tol)
        np.testing.assert_allclose(np.asarray(pal.head_lse),
                                   np.asarray(ref.head_lse), atol=tol)
        if dtype == jnp.float32:
            assert bool(jnp.all(pal.top_id == ref.top_id))


class TestZeroRecompiles:
    def test_decode_across_update_and_rehash(self, small_setup):
        """Index maintenance is data, not shape: N decodes interleaved with
        update_rows and a full rehash reuse ONE decode executable."""
        key, w, h = small_setup
        idx = _index(key, w)
        traces = {"n": 0}

        def body(index, ww, hh, kk):
            traces["n"] += 1
            return _lsh.lsh_decode(index, ww, hh, kk, l=64).log_z

        dec = jax.jit(body)
        rows = jnp.arange(32, dtype=jnp.int32)
        for i in range(4):
            kk = jax.random.fold_in(key, 30 + i)
            jax.block_until_ready(dec(idx, w, h, kk))
            w = w.at[rows].add(0.01)
            idx = _lsh.update_rows(idx, w, rows)
        idx, _ = _lsh.rehash_lsh(idx, w)
        jax.block_until_ready(dec(idx, w, h, jax.random.fold_in(key, 40)))
        assert traces["n"] == 1, f"{traces['n'] - 1} decode recompiles"


class TestLshCeLoss:
    def test_registered_and_grads_touch_scored_rows(self):
        from repro.train.losses import ESTIMATOR_LOSSES, lsh_estimator_ce
        assert "lsh_ce" in ESTIMATOR_LOSSES
        key = jax.random.PRNGKey(5)
        w = make_clustered_vectors(key, 1024, 32, n_centers=8)
        # 6 bits: 16 queries x 6 tables of 16 buckets (4 bits) would cover
        # all but ~0.2% of the 1024 rows in expectation, so the collision
        # union, and with it the gradient, is all but dense; 64 buckets
        # keep the head a strict subset the sparsity claim can be read on
        idx = _index(key, w, n_bits=6, n_tables=6, bucket_cap=512)
        t = 16
        h = jax.random.normal(jax.random.fold_in(key, 1), (t, 32)) * 0.4
        labels = jax.random.randint(jax.random.fold_in(key, 2), (t,), 0,
                                    1024)
        kd = jax.random.fold_in(key, 3)

        def full(hh, ww):
            logits = (hh @ ww.T).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, -1)
            s = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
            return (lse - s).mean()

        def est(hh, ww):
            nll, _, _ = lsh_estimator_ce(idx, hh, ww, labels, kd, l=256)
            return nll.mean()

        g_full = np.asarray(jax.grad(full, argnums=1)(h, w))
        g_est = np.asarray(jax.grad(est, argnums=1)(h, w))
        touched = np.abs(g_est).sum(-1) > 0
        assert 0 < touched.sum() < w.shape[0]
        plan = _lsh.lsh_plan(idx, h, kd, 256, cand_cap=idx.n)
        allowed = set(np.asarray(plan.cand_rows).tolist()) \
            | set(np.asarray(plan.tail_ids).tolist()) \
            | set(np.asarray(labels).tolist())
        assert set(np.nonzero(touched)[0].tolist()) <= allowed
        a, b = g_full[touched].ravel(), g_est[touched].ravel()
        cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
        assert cos > 0.97, cos

    def test_train_state_lifecycle_zero_recompiles(self):
        """init -> lsh_ce steps -> rehash refresh -> more steps: ONE step
        executable, ONE refresh executable (the train_bench contract at
        test scale)."""
        import dataclasses
        from repro.configs import reduced_config
        from repro.configs.base import TrainConfig
        from repro.data import DataIterator, SyntheticCorpus
        from repro.models import Model
        from repro.train import (init_train_state, make_train_step)
        from repro.train.train_loop import make_index_refresh
        cfg = reduced_config("qwen1.5-4b")
        cfg = dataclasses.replace(
            cfg, vocab=2048,
            partition=dataclasses.replace(cfg.partition, l=128,
                                          lsh_bits=4, lsh_tables=6))
        model = Model(cfg)
        tc = TrainConfig(lr=1e-3, loss="lsh_ce", total_steps=6,
                         warmup_steps=1)
        state = init_train_state(model, tc, jax.random.PRNGKey(0))
        assert isinstance(state.index, _lsh.LSHIndex)
        traces = {"n": 0}
        raw = make_train_step(model, tc)

        def counted(s, b):
            traces["n"] += 1
            return raw(s, b)

        step = jax.jit(counted)
        refresh = make_index_refresh(model, tc)
        it = DataIterator(SyntheticCorpus(vocab=cfg.vocab, seed=0), 2, 8)
        for i in range(4):
            toks, labels = next(it)
            state, m = step(state, {"tokens": jnp.asarray(toks),
                                    "labels": jnp.asarray(labels)})
            if i == 1:
                state, rm = refresh(state)
                assert set(rm) == {"churn", "drift"}
        jax.block_until_ready(m["loss_total"])
        assert np.isfinite(float(m["loss_total"]))
        assert traces["n"] == 1, f"{traces['n'] - 1} step recompiles"


class TestServeRegistry:
    def test_backend_registered_and_servable(self):
        assert "lsh" in BACKENDS
        bk = get_backend("lsh")
        assert bk.sublinear

    def test_cli_choices_derive_from_registry(self):
        """Satellite: launch/serve.py --method/--spec-draft choices come
        from the BACKENDS registry, not a hand-written list."""
        from repro.launch import serve as serve_mod
        import argparse
        captured = {}
        real = argparse.ArgumentParser.add_argument

        def spy(self, *a, **kw):
            if a and a[0] in ("--method", "--spec-draft"):
                captured[a[0]] = kw.get("choices")
            return real(self, *a, **kw)

        argparse.ArgumentParser.add_argument = spy
        try:
            old_argv = sys.argv
            sys.argv = ["serve", "--help"]
            with pytest.raises(SystemExit):
                serve_mod.main()
        finally:
            argparse.ArgumentParser.add_argument = real
            sys.argv = old_argv
        for flag in ("--method", "--spec-draft"):
            assert captured.get(flag) == [None] + sorted(BACKENDS), flag

    def test_embedding_floats_sublinear(self, small_setup):
        key, w, _ = small_setup
        cfg = PartitionConfig(method="lsh", l=128, lsh_bits=4, lsh_tables=6,
                              lsh_bucket_cap=128, head_cap=512)
        bk = get_backend("lsh")
        st = bk.build(cfg, w, key)
        q = 8
        floats = bk.embedding_floats(st, cfg, q, u=400)
        assert floats < w.shape[0] * w.shape[1]
        assert floats <= bk.floats_bound(st, cfg, q)


SHARDED_PARITY_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs.base import PartitionConfig
from repro.core import backends as B
from repro.launch.mesh import make_serving_mesh

cfg = PartitionConfig(method="lsh", l=64, head_cap=512, lsh_bits=4,
                      lsh_tables=6, lsh_bucket_cap=128, lsh_tail_beta=16.0)
key = jax.random.PRNGKey(0)
w = jax.random.normal(jax.random.PRNGKey(1), (1024, 32)) * 0.3
h = jax.random.normal(jax.random.PRNGKey(2), (8, 32))
active = jnp.array([1, 1, 0, 1, 1, 1, 0, 1], bool)
kd = jax.random.PRNGKey(7)
bk = B.get_backend("lsh")

for (dp, mp) in [(1, 4), (2, 4), (1, 8)]:
    mesh = make_serving_mesh(dp, mp)
    ref = bk.decode(bk.build(cfg, w, key), h, kd, cfg, k=4,
                    use_pallas=False, active=active)
    st = bk.build(cfg, w, key, block_multiple=mp)
    specs = B.state_partition_specs(st, mp)
    body = lambda s, hh: bk.shard_decode(s, hh, kd, cfg, k=4, active=active)
    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                                out_specs=P(), check_vma=False))(st, h)
    for f in ("log_z", "top_score", "top_id", "head_lse", "tail_lse",
              "k_eff"):
        assert bool(jnp.all(getattr(ref, f) == getattr(out, f))), \
            (dp, mp, f)
print("ALL_OK")
"""


class TestShardedParity:
    def test_mesh_decode_bitwise_parity_8dev(self):
        """mesh_lsh_decode under (data, model) meshes is BITWISE identical
        to the single-device XLA decode — the plan replicates, only
        embedding rows shard."""
        r = subprocess.run([sys.executable, "-c", SHARDED_PARITY_SNIPPET],
                           capture_output=True, text=True,
                           env=dict(os.environ, PYTHONPATH="src"),
                           cwd=REPO, timeout=900)
        assert r.returncode == 0 and "ALL_OK" in r.stdout, \
            r.stdout + r.stderr
