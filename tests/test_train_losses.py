"""Estimator-backed training: sparse CE gradients, index lifecycle,
train->serve handoff (DESIGN.md SS13)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import reduced_config
from repro.configs.base import TrainConfig
from repro.core import build_ivf_device, kmeans, kmeans_step, refresh_ivf
from repro.core.kmeans import _assign
from repro.models import Model
from repro.train import init_train_state, make_index_refresh, make_train_step
from repro.train.losses import ESTIMATOR_LOSSES, LOSSES, estimator_ce


def _full_ce(h, w, labels):
    logits = (h @ w.T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    s = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return (lse - s).mean()


@pytest.fixture(scope="module")
def ce_setup(rng):
    v, d, t = 8192, 64, 32
    w = jax.random.normal(rng, (v, d)) * 0.3
    h = jax.random.normal(jax.random.fold_in(rng, 1), (t, d)) * 0.3
    labels = jax.random.randint(jax.random.fold_in(rng, 2), (t,), 0, v)
    index = build_ivf_device(rng, w, block_rows=64, n_clusters=32)
    return index, h, w, labels


class TestSparseCE:
    def test_logz_close_to_exact(self, ce_setup, rng):
        index, h, w, labels = ce_setup
        nll, lz, aux = estimator_ce(index, h, w, labels,
                                    jax.random.fold_in(rng, 3),
                                    n_probe=8, l=512)
        exact = jax.nn.logsumexp((h @ w.T).astype(jnp.float32), -1)
        err = np.abs(1 - np.exp(np.asarray(lz) - np.asarray(exact)))
        assert err.mean() < 0.1, err.mean()
        # nll >= 0: the label's mass is always inside the estimate
        assert bool(jnp.all(nll >= 0))

    def test_grad_cosine_vs_full_ce(self, ce_setup, rng):
        """Acceptance: cosine >= 0.99 vs the full-CE embedding gradient on
        the probed rows, and on dh."""
        index, h, w, labels = ce_setup
        key = jax.random.fold_in(rng, 3)

        def est(h, w):
            nll, _, _ = estimator_ce(index, h, w, labels, key,
                                     n_probe=8, l=512)
            return nll.mean()

        gh0, gw0 = jax.grad(_full_ce, argnums=(0, 1))(h, w, labels)
        gh1, gw1 = jax.grad(est, argnums=(0, 1))(h, w)
        touched = np.abs(np.asarray(gw1)).sum(-1) > 0
        # the backward writes a strict subset of rows — that IS the point
        assert touched.sum() < 0.6 * w.shape[0], touched.sum()

        def cos(a, b):
            a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
            return a @ b / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cos(gw0[touched], gw1[touched]) >= 0.99
        assert cos(gh0, gh1) >= 0.99

    def test_untouched_rows_have_zero_grad(self, ce_setup, rng):
        """The sparse contract: rows outside head ∪ tail ∪ labels get
        EXACTLY zero gradient (scatter-add, not a dense masked matmul)."""
        index, h, w, labels = ce_setup
        key = jax.random.fold_in(rng, 7)

        def est(w):
            nll, _, _ = estimator_ce(index, h, w, labels, key,
                                     n_probe=2, l=64)
            return nll.mean()

        gw = np.asarray(jax.grad(est)(w))
        zero_rows = np.abs(gw).sum(-1) == 0
        assert zero_rows.sum() > 0.5 * w.shape[0]

    def test_head_cap_trim_matches_full(self, ce_setup, rng):
        """head_cap trimming (cond fallback) never changes the math when the
        union fits, and overflows to the identical full-capacity trace."""
        index, h, w, labels = ce_setup
        key = jax.random.fold_in(rng, 11)
        n0, _, _ = estimator_ce(index, h, w, labels, key, n_probe=4, l=64)
        # generous cap: trimmed branch taken, same estimate
        n1, _, _ = estimator_ce(index, h, w, labels, key, n_probe=4, l=64,
                                head_cap=120)
        # cap of 1 block: always overflows -> full-capacity branch
        n2, _, _ = estimator_ce(index, h, w, labels, key, n_probe=4, l=64,
                                head_cap=1)
        np.testing.assert_allclose(np.asarray(n0), np.asarray(n1), atol=1e-5)
        np.testing.assert_allclose(np.asarray(n0), np.asarray(n2), atol=1e-5)


def _tiny_train(loss, steps=8, seed=0, refresh_every=0):
    cfg = reduced_config("qwen1.5-4b")
    cfg = dataclasses.replace(cfg, vocab=2048, partition=dataclasses.replace(
        cfg.partition, block_rows=64, n_probe=4, l=128, n_clusters=8))
    m = Model(cfg)
    tc = TrainConfig(lr=1e-3, loss=loss, total_steps=steps,
                     index_refresh_every=max(refresh_every, 1))
    state = init_train_state(m, tc, jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(m, tc))
    refresh = make_index_refresh(m, tc) if loss in ESTIMATOR_LOSSES else None
    key = jax.random.PRNGKey(seed + 1)
    batch = {"tokens": jax.random.randint(key, (2, 17), 0, cfg.vocab)[:, :-1],
             "labels": jax.random.randint(key, (2, 17), 0, cfg.vocab)[:, 1:]}
    losses = []
    for i in range(steps):
        if refresh is not None and refresh_every and i and \
                i % refresh_every == 0:
            state, _ = refresh(state)
        state, met = step(state, batch)
        losses.append(float(met["loss_total"]))
    return m, tc, state, losses


class TestEstimatorTraining:
    @pytest.mark.parametrize("loss", ["mimps_ce", "mince_ce"])
    def test_registered_and_trains(self, loss):
        assert loss in LOSSES
        _, _, state, losses = _tiny_train(loss, steps=8, refresh_every=3)
        assert state.index is not None
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_refresh_zero_recompiles(self):
        """Refresh-every-K reuses ONE executable (static pack shapes)."""
        cfg = reduced_config("qwen1.5-4b")
        cfg = dataclasses.replace(
            cfg, vocab=2048, partition=dataclasses.replace(
                cfg.partition, block_rows=64, n_probe=4, l=128,
                n_clusters=8))
        m = Model(cfg)
        tc = TrainConfig(lr=1e-3, loss="mimps_ce")
        state = init_train_state(m, tc, jax.random.PRNGKey(0))
        traces = [0]
        n_clusters = 8

        # same (index, params) -> (index, metrics) shape make_index_refresh
        # compiles (narrow on purpose: no full-state output copies)
        @jax.jit
        def refresh(index, params):
            traces[0] += 1
            return refresh_ivf(index, m.head_matrix(params),
                               n_clusters=n_clusters)

        for _ in range(4):
            new_index, metrics = refresh(state.index, state.params)
            state = state._replace(index=new_index)
        assert traces[0] == 1, f"refresh retraced {traces[0]} times"
        assert 0.0 <= float(metrics["churn"]) <= 1.0

    def test_index_rows_track_params(self):
        """After a refresh the index's embedded rows equal the CURRENT head
        matrix rows (the staleness the refresh exists to remove)."""
        m, tc, state, _ = _tiny_train("mimps_ce", steps=4)
        refresh = make_index_refresh(m, tc)
        state2, metrics = refresh(state)
        w = np.asarray(m.head_matrix(state2.params))
        idx = state2.index
        got = np.asarray(
            idx.v_blocks.reshape(-1, w.shape[1])[idx.slot_of_row])
        np.testing.assert_allclose(got, w, atol=1e-6)
        assert float(metrics["drift"]) > 0


class TestKmeansReseed:
    def test_empty_cluster_reseeds_to_farthest(self, rng):
        # two tight groups + one far outlier; third centroid starts dead
        x = jnp.concatenate([
            jnp.zeros((8, 2)) + jnp.array([0.0, 0.0]),
            jnp.zeros((8, 2)) + jnp.array([10.0, 0.0]),
            jnp.array([[50.0, 50.0]]),
        ])
        c0 = jnp.array([[0.0, 0.0], [10.0, 0.0], [-100.0, -100.0]])
        c1 = kmeans_step(x, c0)
        # the dead centroid must move to the farthest-assigned point (the
        # outlier, which sits 50+ from its centroid) — not stay stale
        assert float(jnp.linalg.norm(c1[2] - jnp.array([50.0, 50.0]))) < 1e-5
        counts = np.bincount(np.asarray(_assign(x, c1)), minlength=3)
        assert (counts > 0).all(), counts

    @given(st.integers(min_value=2, max_value=12),
           st.integers(min_value=0, max_value=10_000))
    @settings(max_examples=15, deadline=None)
    def test_reseed_repairs_empty_clusters_property(self, n_clusters, seed):
        """Property: every cluster that enters a Lloyd step empty leaves it
        reseeded onto a data point — and therefore nonempty in the very
        next assignment (distance 0 to its own point)."""
        key = jax.random.PRNGKey(seed)
        x = jax.random.normal(key, (64, 4))
        # adversarial init: every centroid at the same point -> all but one
        # cluster starts empty
        c0 = jnp.tile(x[:1], (n_clusters, 1))
        counts0 = np.bincount(np.asarray(_assign(x, c0)),
                              minlength=n_clusters)
        c1 = kmeans_step(x, c0)
        counts1 = np.bincount(np.asarray(_assign(x, c1)),
                              minlength=n_clusters)
        empty0 = counts0 == 0
        assert empty0.any()
        assert counts1[empty0].min() > 0, (counts0, counts1)

    def test_kmeans_end_to_end_no_empty(self, rng):
        x = jax.random.normal(rng, (256, 8))
        _, assign = kmeans(rng, x, n_clusters=16, iters=8)
        counts = np.bincount(np.asarray(assign), minlength=16)
        assert counts.min() > 0, counts
