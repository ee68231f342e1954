"""Kernel autotuner (kernels/autotune.py): sweep, cache, failure handling,
and the backend/engine integration surface."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune as at


class TestAutotuneCore:
    def test_picks_fastest_and_caches(self, tmp_path):
        path = str(tmp_path / "cache.json")
        calls = []

        def build(cfg):
            def run():
                calls.append(cfg["x"])
                if cfg["x"] == 2:           # "fast" config: no busy work
                    return jnp.zeros(())
                sum(i * i for i in range(50_000))
                return jnp.zeros(())
            return run

        cands = [{"x": 1}, {"x": 2}, {"x": 3}]
        args = (jnp.zeros((4, 8)),)
        best = at.autotune("fake", cands, build, args, reps=2, path=path)
        assert best == {"x": 2}
        assert os.path.exists(path)
        # second call: cache hit, no sweeps run
        calls.clear()
        again = at.autotune("fake", cands, build, args, reps=2, path=path)
        assert again == {"x": 2}
        assert calls == []

    def test_cache_key_varies_with_shape_dtype_and_kernel(self):
        a32 = jnp.zeros((4, 8), jnp.float32)
        a16 = jnp.zeros((4, 8), jnp.bfloat16)
        b = jnp.zeros((8, 8), jnp.float32)
        k1 = at.cache_key("k", (a32,))
        assert k1 != at.cache_key("k", (a16,))
        assert k1 != at.cache_key("k", (b,))
        assert k1 != at.cache_key("other", (a32,))
        assert at.cache_key("k", (a32, 7)) != at.cache_key("k", (a32, 8))
        # deterministic
        assert k1 == at.cache_key("k", (jnp.zeros((4, 8), jnp.float32),))

    def test_failing_candidates_skipped(self, tmp_path):
        path = str(tmp_path / "cache.json")

        def build(cfg):
            def run():
                if cfg["x"] != 1:
                    raise RuntimeError("tile too large")
                return jnp.zeros(())
            return run

        best = at.autotune("flaky", [{"x": 0}, {"x": 1}, {"x": 2}],
                           build, (jnp.zeros((2,)),), reps=1, path=path)
        assert best == {"x": 1}
        rec = json.load(open(path))
        swept = next(iter(rec.values()))["swept"]
        assert sum("error" in r for r in swept) == 2

    def test_all_failing_returns_first_default(self, tmp_path):
        path = str(tmp_path / "cache.json")

        def build(cfg):
            def run():
                raise RuntimeError("no")
            return run

        # no candidate compiles: the sweep raises (naming every failure)
        # rather than hand back an untested default that fails again later
        with pytest.raises(RuntimeError, match="every candidate failed"):
            at.autotune("dead", [{"x": 5}, {"x": 6}], build,
                        (jnp.zeros(()),), reps=1, path=path)
        assert not os.path.exists(path)    # nothing worth caching


class TestKernelSweeps:
    def test_tune_ivf_decode_returns_runnable_config(self, tmp_path, rng):
        from repro.core import build_ivf
        from repro.core.decode import _tail_rows, make_plan, mimps_decode
        path = str(tmp_path / "cache.json")
        v = jax.random.normal(rng, (1024, 32)) * 0.3
        index = build_ivf(rng, v, block_rows=64)
        h = v[:8]
        plan = make_plan(index, h, rng, 2, 16)
        rows = _tail_rows(index, plan)
        row_logw = jnp.where(index.valid, 0.0, -1e30).astype(jnp.float32)
        cfg = at.tune_ivf_decode(index.v_blocks, h, plan.head_ids,
                                 plan.head_live, plan.head_member, row_logw,
                                 rows, plan.tail_accept, reps=1, path=path)
        assert set(cfg) == {"block_q", "tail_tile"}
        # the tuned config must run through the real decode path
        out = mimps_decode(index, h, rng, n_probe=2, l=16, k=1,
                           use_pallas=True, **cfg)
        ref = mimps_decode(index, h, rng, n_probe=2, l=16, k=1,
                           use_pallas=False)
        np.testing.assert_allclose(np.asarray(out.log_z),
                                   np.asarray(ref.log_z), atol=1e-4)

    def test_backend_tune_integration(self, tmp_path, rng):
        """Every registered backend's tune() returns decode-able kwargs."""
        import dataclasses

        from repro.configs.base import PartitionConfig
        from repro.core.backends import get_backend
        path = str(tmp_path / "cache.json")
        v = jax.random.normal(rng, (1024, 32)) * 0.3
        h = v[:8]
        cfg = PartitionConfig(method="mimps", block_rows=64, n_probe=2, l=16,
                              n_clusters=0, fmbe_features=256,
                              fmbe_max_degree=3)
        for method in ("mimps", "mince", "fmbe"):
            c = dataclasses.replace(cfg, method=method)
            bk = get_backend(method)
            state = bk.build(c, v, rng)
            kcfg = bk.tune(state, c, h, rng, path=path)
            assert isinstance(kcfg, dict)
            out = bk.decode(state, h, rng, c, k=1, use_pallas=True, **kcfg)
            ref = bk.decode(state, h, rng, c, k=1, use_pallas=False)
            np.testing.assert_allclose(np.asarray(out.log_z),
                                       np.asarray(ref.log_z), atol=1e-4,
                                       err_msg=method)
