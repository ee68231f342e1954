"""Unit + property tests for the paper's estimators (core/)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (exact_log_z, mimps_log_z, uniform_log_z, nmimps_log_z,
                        mince_log_z, head_tail_log_z, combine_head_tail_lse,
                        relative_error, build_ivf, mimps_ivf, probe,
                        gather_scores, exact_top_k, kmeans, make_feature_map,
                        build_fmbe, fmbe_z, apply_feature_map, solve_log_z,
                        solver_convergence_trace)
from repro.core.estimators import oracle_retrieve


def _q(vectors, i=123):
    return vectors[i]


class TestExact:
    def test_matches_numpy(self, vectors):
        q = _q(vectors)
        ours = exact_log_z(vectors, q)
        ref = np.log(np.sum(np.exp(np.asarray(vectors @ q, np.float64))))
        np.testing.assert_allclose(float(ours), ref, rtol=1e-5)

    def test_batched_vmap(self, vectors):
        qs = vectors[:8]
        out = jax.vmap(lambda q: exact_log_z(vectors, q))(qs)
        assert out.shape == (8,)
        assert bool(jnp.all(jnp.isfinite(out)))


class TestMIMPS:
    def test_full_head_is_exact(self, vectors, rng):
        """k = N, l = 0 degenerates to exact Z."""
        q = _q(vectors)
        lz = mimps_log_z(vectors, q, vectors.shape[0] - 1, 1, rng)
        np.testing.assert_allclose(float(lz), float(exact_log_z(vectors, q)),
                                   rtol=5e-4)

    def test_error_decreases_with_k(self, vectors, rng):
        """Paper Table 1 row pattern: error monotone down the k column."""
        q = _q(vectors)
        lzt = exact_log_z(vectors, q)
        errs = []
        for k in (1, 10, 100, 1000):
            samples = [relative_error(
                mimps_log_z(vectors, q, k, 100, jax.random.fold_in(rng, 17*k + s)),
                lzt) for s in range(5)]
            errs.append(float(np.mean(samples)))
        assert errs[-1] < errs[0]
        assert errs[-1] < 0.05

    def test_unbiased_tail(self, vectors, rng):
        """E[Z_hat] == Z over tail sampling (property of Eq. 5)."""
        q = _q(vectors)
        lzt = float(exact_log_z(vectors, q))
        keys = jax.random.split(rng, 1024)
        zs = jax.vmap(lambda k: jnp.exp(
            mimps_log_z(vectors, q, 100, 50, k)))(keys)
        rel = abs(float(jnp.mean(zs)) / np.exp(lzt) - 1.0)
        assert rel < 0.05, f"tail estimator biased: {rel}"

    def test_retrieval_error_rank1_worst(self, vectors, rng):
        """Paper Table 3: dropping rank-1 hurts much more than rank-2."""
        q = _q(vectors)
        lzt = exact_log_z(vectors, q)
        base = relative_error(mimps_log_z(vectors, q, 1000, 1000, rng), lzt)
        e1 = relative_error(
            mimps_log_z(vectors, q, 1000, 1000, rng, drop_ranks=(0,)), lzt)
        e2 = relative_error(
            mimps_log_z(vectors, q, 1000, 1000, rng, drop_ranks=(1,)), lzt)
        assert float(e1) > float(e2) >= 0.0
        assert float(e1) > float(base)

    def test_uniform_is_k0(self, vectors, rng):
        q = _q(vectors)
        lz = uniform_log_z(vectors, q, 500, rng)
        assert bool(jnp.isfinite(lz))

    def test_nmimps_underestimates(self, vectors):
        q = _q(vectors)
        lz = nmimps_log_z(vectors, q, 100)
        assert float(lz) < float(exact_log_z(vectors, q))


class TestHeadTail:
    @given(st.integers(1, 50), st.integers(1, 50), st.floats(-3, 3))
    @settings(max_examples=20, deadline=None)
    def test_headtail_property(self, nh, nt, shift):
        """head+tail == exact when tail sample == full tail (scale 1)."""
        rng = np.random.RandomState(nh * 100 + nt)
        head = jnp.array(rng.randn(nh) + shift, jnp.float32)
        tail = jnp.array(rng.randn(nt) - 1.0 + shift, jnp.float32)
        lz = head_tail_log_z(head, tail, jnp.float32(nt), jnp.float32(nt))
        ref = np.log(np.exp(np.asarray(head, np.float64)).sum()
                     + np.exp(np.asarray(tail, np.float64)).sum())
        np.testing.assert_allclose(float(lz), ref, rtol=1e-4)

    @given(st.integers(1, 64), st.integers(1, 64), st.floats(-3, 3),
           st.integers(1, 100000))
    @settings(max_examples=25, deadline=None)
    def test_fused_combine_matches_unfused(self, nh, nt, shift, n_total):
        """The fused-kernel interface (combine precomputed LSEs) must equal
        the unfused score-level head_tail_log_z within 1e-4 for any head/tail
        sizes, score shifts and tail populations (Eq. 5 equivalence)."""
        rng = np.random.RandomState(nh * 1000 + nt * 7 + n_total % 97)
        head = jnp.array(rng.randn(nh) + shift, jnp.float32)
        tail = jnp.array(rng.randn(nt) - 1.0 + shift, jnp.float32)
        fused = combine_head_tail_lse(
            jax.nn.logsumexp(head), jax.nn.logsumexp(tail),
            jnp.float32(n_total), jnp.float32(nt))
        unfused = head_tail_log_z(head, tail, jnp.float32(n_total),
                                  jnp.float32(nt))
        np.testing.assert_allclose(float(fused), float(unfused), atol=1e-4,
                                   rtol=1e-5)
        ref = np.log(np.exp(np.asarray(head, np.float64)).sum() +
                     (n_total / nt) *
                     np.exp(np.asarray(tail, np.float64)).sum())
        np.testing.assert_allclose(float(fused), ref, rtol=1e-4)


class TestMINCE:
    def test_solver_finds_root_on_synthetic(self):
        """With well-separated alpha/beta the NCE objective's optimum is
        recoverable; check f'(theta*) ~ 0."""
        rng = np.random.RandomState(0)
        alpha = jnp.array(rng.randn(100) + 8.0, jnp.float32)
        beta = jnp.array(rng.randn(100), jnp.float32)
        theta = solve_log_z(alpha, beta, jnp.float32(4.0), iters=40)
        trace = solver_convergence_trace(alpha, beta, jnp.float32(4.0), 40)
        assert float(trace[-1]) < 1e-2

    def test_halley_converges_at_least_as_fast(self):
        rng = np.random.RandomState(1)
        alpha = jnp.array(rng.randn(200) + 6.0, jnp.float32)
        beta = jnp.array(rng.randn(200), jnp.float32)
        th0 = jnp.float32(2.0)
        h = solver_convergence_trace(alpha, beta, th0, 15, solver="halley")
        n = solver_convergence_trace(alpha, beta, th0, 15, solver="newton")
        # compare first-iteration residual drop (paper: Halley speeds up opt)
        assert float(h[3]) <= float(n[3]) * 2.0  # not catastrophically worse
        assert float(h[-1]) < 1e-2

    def test_mince_runs_and_is_worse_than_mimps(self, vectors, rng):
        """Paper's empirical finding (Table 1): MINCE >> MIMPS error.

        Pinned to weighting='paper' — the literal Eq. 6/7 estimator Table 1
        reproduces. (The anchored serving weighting provably collapses onto
        the Eq. 5 estimate, so its error ties MIMPS by construction; the
        paper's gap is exactly the sampling noise the anchoring removes.)
        Averaged over several sampling draws — a single draw of either
        estimator is noisy enough to flip the comparison.
        """
        q = _q(vectors)
        lzt = exact_log_z(vectors, q)
        e_mince, e_mimps = [], []
        for s in range(8):
            k = jax.random.fold_in(rng, s)
            e_mince.append(float(relative_error(
                mince_log_z(vectors, q, 100, 100, k, weighting="paper"),
                lzt)))
            e_mimps.append(float(relative_error(
                mimps_log_z(vectors, q, 100, 100, k), lzt)))
        assert np.mean(e_mimps) < np.mean(e_mince)


class TestFMBE:
    def test_kernel_approx_unbiased(self, rng):
        """E[phi(x).phi(y)] ~= exp(x.y) for moderate dot products."""
        d = 16
        kx, kf = jax.random.split(rng)
        x = jax.random.normal(kx, (d,)) * 0.3
        y = -x * 0.5
        fm = make_feature_map(kf, d, 65536, max_degree=8)
        approx = float(jnp.sum(apply_feature_map(fm, x) * apply_feature_map(fm, y)))
        true = float(jnp.exp(jnp.dot(x, y)))
        assert abs(approx - true) / true < 0.15

    def test_fmbe_z_estimate(self, vectors, rng):
        v = vectors[:2048]
        q = v[7]
        fm = make_feature_map(rng, v.shape[1], 16384)
        st_ = build_fmbe(fm, v)
        z = float(fmbe_z(st_, q))
        zt = float(jnp.exp(exact_log_z(v, q)))
        # paper shows FMBE is a poor estimator at practical P — just require
        # the right order of magnitude.
        assert z > 0
        assert abs(np.log(max(z, 1e-9)) - np.log(zt)) < 2.0


class TestIVF:
    def test_kmeans_reduces_distortion(self, vectors, rng):
        v = vectors[:2048]
        c1, a1 = kmeans(rng, v, 16, iters=1)
        c2, a2 = kmeans(rng, v, 16, iters=10)
        d1 = float(jnp.sum((v - c1[a1]) ** 2))
        d2 = float(jnp.sum((v - c2[a2]) ** 2))
        assert d2 <= d1 * 1.001

    def test_index_covers_all_rows(self, vectors, rng):
        idx = build_ivf(rng, vectors, block_rows=128)
        ids = np.asarray(idx.row_id).ravel()
        real = np.sort(ids[ids >= 0])
        np.testing.assert_array_equal(real, np.arange(vectors.shape[0]))

    def test_probe_recall_top1(self, vectors, rng):
        """Rank-1 recall (the paper's critical retrieval property, Table 3)."""
        idx = build_ivf(rng, vectors, block_rows=128)
        hits = 0
        queries = vectors[:64]
        for i in range(64):
            q = queries[i]
            blocks = probe(idx, q, 8)
            s, valid = gather_scores(idx, q, blocks)
            s = jnp.where(valid, s, -1e30)
            _, ids = exact_top_k(vectors, q, 1)
            best_slot = int(jnp.argmax(s))
            rid = int(idx.row_id[blocks[best_slot // idx.block_rows],
                                 best_slot % idx.block_rows])
            hits += int(rid == int(ids[0]))
        assert hits >= 58, f"rank-1 recall too low: {hits}/64"

    def test_ivf_mimps_accuracy(self, vectors, rng):
        idx = build_ivf(rng, vectors, block_rows=128)
        q = _q(vectors)
        lzt = exact_log_z(vectors, q)
        r = mimps_ivf(idx, q, 8, 256, rng)
        assert float(relative_error(r.log_z, lzt)) < 0.25

    def test_ivf_cost_is_sublinear(self, vectors, rng):
        """FLOP accounting: probed rows + centroids << N."""
        idx = build_ivf(rng, vectors, block_rows=128)
        n_scored = idx.n_blocks + 8 * idx.block_rows + 256
        assert n_scored < vectors.shape[0] // 3


class TestOracle:
    def test_sorted_order(self, vectors):
        r = oracle_retrieve(vectors, _q(vectors))
        s = np.asarray(r.scores_sorted)
        assert (np.diff(s) <= 1e-6).all()
