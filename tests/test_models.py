"""Per-architecture smoke tests: reduced configs of the same family run a
forward + one train step + one decode step on CPU, asserting shapes + finite
outputs. (Full configs are exercised only via the dry run.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, reduced_config
from repro.configs.base import TrainConfig
from repro.data import SyntheticCorpus, DataIterator
from repro.models import Model
from repro.train import init_train_state, make_train_step


def _batch(cfg, key, b=2, s=32):
    if cfg.n_codebooks:
        toks = jax.random.randint(key, (b, s + 1, cfg.n_codebooks), 0,
                                  cfg.vocab)
    else:
        toks = jax.random.randint(key, (b, s + 1), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if cfg.family == "vlm":
        batch["img"] = jax.random.normal(
            key, (b, cfg.n_image_tokens, cfg.d_model)).astype(cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch, rng):
        cfg = reduced_config(arch)
        m = Model(cfg)
        p = m.init(rng)
        batch = _batch(cfg, rng)
        h, aux = jax.jit(lambda p, b: m.forward(
            p, b["tokens"], img=b.get("img")))(p, batch)
        assert h.shape == (2, 32, cfg.d_model)
        assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))

    def test_train_step_reduces_loss_no_nan(self, arch, rng):
        cfg = reduced_config(arch)
        m = Model(cfg)
        tc = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=50,
                         loss="fused_ce", microbatches=1)
        state = init_train_state(m, tc, rng)
        step = jax.jit(make_train_step(m, tc, backend="xla"))
        batch = _batch(cfg, rng)
        losses = []
        for i in range(5):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss_total"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses  # same batch -> must overfit

    def test_decode_step(self, arch, rng):
        cfg = reduced_config(arch)
        m = Model(cfg)
        p = m.init(rng)
        st = m.init_decode_state(2, 64)
        batch = _batch(cfg, rng)
        tok = batch["tokens"][:, 0]
        h, st2 = jax.jit(lambda p, s, t: m.decode_step(
            p, s, t, 3, img=batch.get("img")))(p, st, tok)
        assert h.shape == (2, cfg.d_model)
        assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))
        # state structure preserved
        assert jax.tree.structure(st) == jax.tree.structure(st2)


class TestFullConfigs:
    """Full configs: structural checks only (never allocate real weights)."""

    @pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
    def test_config_matches_assignment(self, arch):
        cfg = get_config(arch)
        spec = {
            "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
            "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
            "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
            "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
            "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
            "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
            "moonshot-v1-16b-a3b": (48, 2048, 16, 16, 1408, 163840),
            "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
            "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
            "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        }[arch]
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab) == spec

    def test_param_counts_in_range(self):
        """Sanity: analytic counts land near the advertised sizes."""
        expect = {"mistral-nemo-12b": (11e9, 14e9),
                  "llama-3.2-vision-90b": (80e9, 100e9),
                  "deepseek-moe-16b": (14e9, 20e9),
                  "rwkv6-7b": (6e9, 9e9),
                  "gemma3-4b": (3e9, 6e9)}
        for arch, (lo, hi) in expect.items():
            n = get_config(arch).param_count()
            assert lo < n < hi, f"{arch}: {n/1e9:.2f}B not in [{lo},{hi}]"

    def test_moe_active_params_smaller(self):
        cfg = get_config("moonshot-v1-16b-a3b")
        assert cfg.active_param_count() < 0.5 * cfg.param_count()


class TestTrainSubstrate:
    def test_microbatch_accumulation_matches(self, rng):
        """grad accumulation == single big batch (linearity of grads)."""
        cfg = reduced_config("qwen1.5-4b")
        m = Model(cfg)
        tc1 = TrainConfig(lr=1e-3, loss="ce", microbatches=1, seed=1)
        tc2 = dataclasses.replace(tc1, microbatches=2)
        s1 = init_train_state(m, tc1, rng)
        s2 = init_train_state(m, tc2, rng)
        batch = _batch(cfg, rng, b=4)
        s1b, m1 = jax.jit(make_train_step(m, tc1))(s1, batch)
        s2b, m2 = jax.jit(make_train_step(m, tc2))(s2, batch)
        p1 = jax.tree.leaves(s1b.params)
        p2 = jax.tree.leaves(s2b.params)
        for a, b in zip(p1, p2):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-4)

    @pytest.mark.parametrize("loss", ["ce", "fused_ce", "selfnorm", "nce",
                                      "sampled"])
    def test_all_losses_finite_and_trainable(self, rng, loss):
        cfg = reduced_config("qwen1.5-4b")
        m = Model(cfg)
        tc = TrainConfig(lr=1e-3, loss=loss)
        state = init_train_state(m, tc, rng)
        step = jax.jit(make_train_step(m, tc))
        batch = _batch(cfg, rng)
        for _ in range(3):
            state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss_total"]))

    def test_selfnorm_drives_logz_to_zero(self, rng):
        cfg = reduced_config("qwen1.5-4b")
        m = Model(cfg)
        tc = TrainConfig(lr=3e-3, loss="selfnorm", selfnorm_alpha=0.5)
        state = init_train_state(m, tc, rng)
        step = jax.jit(make_train_step(m, tc))
        batch = _batch(cfg, rng)
        zs = []
        for _ in range(15):
            state, metrics = step(state, batch)
            zs.append(abs(float(metrics["mean_log_z"])))
        assert zs[-1] < zs[0], zs  # |log Z| shrinking (paper's SS2 baseline)

    def test_data_pipeline_deterministic_and_sharded(self):
        c = SyntheticCorpus(vocab=1000, seed=3)
        a = c.batch(5, 4, 16, shard=0, n_shards=2)
        b = c.batch(5, 4, 16, shard=0, n_shards=2)
        np.testing.assert_array_equal(a, b)
        other = c.batch(5, 4, 16, shard=1, n_shards=2)
        assert not np.array_equal(a, other)
        it = DataIterator(c, 4, 16)
        x0, y0 = next(it)
        assert x0.shape == (4, 16) and y0.shape == (4, 16)
        np.testing.assert_array_equal(x0[:, 1:], y0[:, :-1])


def _decode_case(case):
    """(config, scalar_pos) for one decode-against-forward case."""
    if case == "gqa":
        cfg = reduced_config("mistral-nemo-12b")
        assert cfg.n_heads // cfg.n_kv_heads > 1
    elif case == "moe":
        cfg = reduced_config("deepseek-moe-16b")
        # room for every token, so the forward over whole sequences drops
        # none where one-token decode drops none either; float32, because
        # top-k routing is discontinuous: in bf16 a near-tie of two experts'
        # router logits flips on the rounding difference between the
        # forward's and the decode's attention sums
        cfg = dataclasses.replace(cfg, dtype="float32",
                                  moe=dataclasses.replace(
                                      cfg.moe, capacity_factor=float(
                                          cfg.moe.n_experts)))
    elif case == "local_global":
        # the grouped stack, whose layers write their own rows in its
        # scans; a window short enough that the local layers' rings wrap
        cfg = dataclasses.replace(reduced_config("gemma3-4b"),
                                  sliding_window=4)
        assert cfg.local_global_ratio
    else:
        cfg = reduced_config("qwen1.5-4b")
        assert cfg.qkv_bias and cfg.n_heads == cfg.n_kv_heads
        if case == "window":
            cfg = dataclasses.replace(cfg, sliding_window=4)
    return cfg, case == "scalar_pos"


class TestDecodeMatchesForward:
    """Cached decode, one token a step, gives the hidden state of the full
    forward over the same prefix, for every lane at its own depth."""

    STEPS, LANES = 11, 3

    @pytest.mark.parametrize("case", ["mha_bias", "gqa", "moe", "window",
                                      "scalar_pos", "local_global"])
    def test_decode_hidden_matches_forward(self, case, rng):
        cfg, scalar = _decode_case(case)
        m = Model(cfg)
        p = m.init(rng)
        n, b = self.STEPS, self.LANES
        # lane l decodes sequence 2l from position 0; at step restart[l] it
        # restarts at position 0 on sequence 2l+1, over the stale rows of
        # the first (all lanes together when `pos` is one scalar)
        restart = [5] * b if scalar else [n, 3, 6]
        seqs = jax.random.randint(jax.random.fold_in(rng, 7), (2 * b, n), 0,
                                  cfg.vocab)
        want, _ = jax.jit(m.forward)(p, seqs)          # (2b, n, d)
        step = jax.jit(m.decode_step)
        state = m.init_decode_state(b, 16)
        if case == "window":
            assert state["kv"]["k"].shape[2] == cfg.sliding_window < n
        elif case == "local_global":
            assert state["local"]["k"].shape[3] == cfg.sliding_window < n
        errs = []
        for t in range(n):
            seq = [2 * l + (t >= restart[l]) for l in range(b)]
            pos = [t - restart[l] if t >= restart[l] else t for l in range(b)]
            tok = jnp.asarray([seqs[s, q] for s, q in zip(seq, pos)],
                              jnp.int32)
            pos_in = jnp.int32(pos[0]) if scalar else jnp.asarray(
                pos, jnp.int32)
            h, state = step(p, state, tok, pos_in)
            ref = jnp.stack([want[s, q] for s, q in zip(seq, pos)])
            errs.append(np.abs(np.asarray(h, np.float32)
                               - np.asarray(ref, np.float32)))
        errs = np.stack(errs)                          # (n, b, d); NaN fails
        assert np.all(errs < 0.1), np.nanmax(errs)


class TestDecodeCacheInPlace:
    def test_layer_scan_does_not_output_the_cache(self, rng):
        """The homogeneous stack's layer scan takes the KV cache as a
        read-only input and emits only the token's rows; the cache is
        written after the scan. A scan that returns the cache (or anything
        of its shape) copies it every step."""
        cfg = reduced_config("qwen1.5-4b")
        m = Model(cfg)
        p = m.init(rng)
        state = m.init_decode_state(3, 16)
        cache_shape = state["kv"]["k"].shape
        tok = jnp.zeros((3,), jnp.int32)
        pos = jnp.asarray([4, 0, 9], jnp.int32)
        closed = jax.make_jaxpr(m.decode_step)(p, state, tok, pos)
        n_params = len(jax.tree.leaves(p))
        cache_vars = closed.jaxpr.invars[n_params:n_params + 2]
        assert all(v.aval.shape == cache_shape for v in cache_vars)

        def scans(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "scan":
                    yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from scans(sub)

        found = list(scans(closed.jaxpr))
        layer = [e for e in found
                 if any(v in e.invars for v in cache_vars)]
        assert len(layer) == 1, "the layer scan reads the cache directly"
        for e in found:
            assert not any(v.aval.shape == cache_shape for v in e.outvars)
        # the new cache comes from the in-place row writes, not from a scan
        made_by = {v: e for e in closed.jaxpr.eqns for v in e.outvars}
        new_cache = closed.jaxpr.outvars[1:]
        assert [v.aval.shape for v in new_cache] == [cache_shape] * 2
        assert all(made_by[v].primitive.name == "dynamic_update_slice"
                   for v in new_cache)
