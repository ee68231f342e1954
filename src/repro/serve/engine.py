"""Serving engine: prefill + cached decode with partition-estimated
probabilities — the paper's inference-time use case (Eq. 2/3).

Every non-audio method dispatches through the estimator-backend registry
(``core.backends``): one batched decode returns log Ẑ plus retrieved top-k
candidates, and sampling (greedy or Gumbel-max at temperature T) happens
once on top — no per-method branching here.

decode_step cost at the output layer (embedding floats per step, Q queries):
  exact     V·d + Q·d                    (fused one-pass: kernels.topk_z)
  mimps     nb·d + U·br·d + l·d + Q·d    — fused Eq. 5 pipeline (core.decode)
  mince     nb·d + U·br·d + l·d + Q·d    — same plan; batched Halley solve
  fmbe      P·M·d + P + nb·d + U·br·d + Q·d — V-independent Ẑ, IVF head
                                           for candidates only
  selfnorm  V·d + Q·d head only          (assumes Z == 1)
U = deduplicated probed blocks <= min(Q·n_probe, nb); full accounting in
DESIGN.md SS5/SS8 and BENCH_estimators.json.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.backends import BACKENDS, BackendState, get_backend
from ..core.decode import DecodeOut, apply_health_guard
from ..kernels import on_tpu
from ..models import Model


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ServeState:
    cache: Any
    pos: jax.Array           # scalar int32: next position to write
    last_token: jax.Array    # (B,) or (B, C)


@jax.jit
def _index_digest(v_blocks: jax.Array):
    """Two-scalar integrity checksum of an IVF block tensor. The
    position-weighted sum catches row/block *permutations* (a plain sum
    would not); the sum of squares catches zeroing and drift. Deterministic:
    the same jitted reduction over the same data yields bit-equal scalars,
    so digests compare with ==."""
    x = v_blocks.astype(jnp.float32)
    nb, br, _ = x.shape
    wts = (1.0 + jnp.arange(nb * br, dtype=jnp.float32)).reshape(nb, br, 1)
    return jnp.sum(x * wts), jnp.sum(x * x)


def _digest(v_blocks) -> tuple:
    a, b = _index_digest(v_blocks)
    return (float(a), float(b))


class Engine:
    """Batched serving for one model. Retrieval state (IVF index, FMBE
    sketch) is built once from the output embedding at engine construction
    ("index build time") by the method's registered backend.

    The platform picks the output layer's path (``kernels.on_tpu``): the
    Pallas kernels on a TPU, their XLA reference bodies elsewhere. A mesh
    engine always runs the XLA bodies under ``shard_map``.
    ``use_pallas=True/False`` overrides the choice; the parity tests use it
    to pin a kernel (interpreted on the CPU) to its reference."""

    def __init__(self, model: Model, params, max_len: int,
                 key: Optional[jax.Array] = None,
                 use_pallas: Optional[bool] = None,
                 autotune: bool = False, autotune_batch: int = 64,
                 device_index: bool = False, health_guard: bool = False,
                 mesh=None):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.max_len = max_len
        if use_pallas is None:
            use_pallas = mesh is None and on_tpu()
        self.use_pallas = use_pallas
        self.device_index = device_index
        self.health_guard = health_guard
        # (data, model) serving mesh (launch.mesh.make_serving_mesh) — the
        # slot scheduler runs its one compiled step under shard_map on it.
        # The engine's own jitted paths (generate(), prefill) stay
        # single-device: they are the parity oracle the mesh step is
        # measured against.
        self.mesh = mesh
        if mesh is not None:
            for ax in ("data", "model"):
                if ax not in mesh.axis_names:
                    raise ValueError(
                        f"serving mesh must have ('data','model') axes, got "
                        f"{mesh.axis_names}")
            m = mesh.shape["model"]
            if use_pallas:
                raise ValueError(
                    "mesh serving runs the XLA estimator bodies under "
                    "shard_map; use_pallas is single-device only")
            if self.cfg.n_codebooks:
                raise ValueError("mesh serving does not support audio heads")
            if m > 1 and self.cfg.vocab % m:
                raise ValueError(
                    f"vocab {self.cfg.vocab} must divide the model-parallel "
                    f"degree {m} to shard the output embedding rows")
            self._block_multiple = m
        else:
            self._block_multiple = 1
        pc = self.cfg.partition
        key = key if key is not None else jax.random.PRNGKey(0)
        self._build_key = key
        # oracle-only study estimators have no batched serving path; they
        # serve exact Z rather than failing (documented fallthrough).
        method = pc.method if pc.method in BACKENDS else "exact"
        self.backend = get_backend(method)
        if self.cfg.n_codebooks:
            # audio: small per-codebook vocab, exact softmax per codebook
            self.state = None
        else:
            self.state = self.backend.build(
                pc, model.head_matrix(params), key, device=device_index,
                block_multiple=self._block_multiple)
        self.index = self.state.index if self.state is not None else None
        # degradation-tier states (serve.server tier ladder) + integrity
        # digests, recorded at every build/swap/restore
        self._tier_states: Dict[str, Any] = {}
        self._digests: Dict[str, tuple] = {}
        self.index_restores = 0
        # observability sink (obs.Observability.attach): index-lifecycle
        # events (swap / restore) land in the trace as instants. None = off.
        self.obs = None
        if self.index is not None:
            self._digests[method] = _digest(self.index.v_blocks)
        # measured Pallas tile sizes, swept once at engine build on a
        # representative decode batch and cached on disk (kernels.autotune);
        # the per-query tiles clamp to the live batch, so one sweep covers
        # the serving range
        self.kernel_cfg: dict = {}
        if autotune and use_pallas and self.state is not None:
            h_rep = 0.1 * jax.random.normal(
                jax.random.fold_in(key, 0xA07),
                (autotune_batch, self.cfg.d_model)).astype(self.cfg.dtype)
            self.kernel_cfg = self.backend.tune(self.state, pc, h_rep, key)

    # -- train -> serve handoff ----------------------------------------------

    def swap_index(self, params, key: Optional[jax.Array] = None) -> None:
        """Hot-swap a freshly trained checkpoint into this live engine:
        replace ``params`` and rebuild the retrieval state (IVF index /
        FMBE sketch) from the new output embedding.

        Zero-recompile contract: when the engine was constructed with
        ``device_index=True``, the rebuilt state has bit-identical pytree
        structure and shapes (``mips.build_ivf_device`` fixed capacity), so
        compiled steps that take (params, backend state) as ARGUMENTS — the
        slot-table scheduler's mixed step — keep serving from their existing
        executables; the swap is one host pointer update plus the jitted
        rebuild. ``generate()``'s cached scans bake params in as constants
        and are dropped instead (they recompile lazily on next use — the
        traffic path is the scheduler, not generate()).

        ``key`` defaults to the engine's build key, so two engines built
        and swapped with the same keys hold identical state (the parity
        tests' oracle).
        """
        key = key if key is not None else self._build_key
        if self.cfg.n_codebooks:
            self.params = params
            self._scan_runners = {}
            return
        w = self.model.head_matrix(params)
        new_state = self.backend.refresh(
            self.state, self.cfg.partition, w, key, device=self.device_index,
            block_multiple=self._block_multiple)
        if self.state is not None and self.device_index:
            old = jax.tree.map(lambda x: (x.shape, x.dtype)
                               if hasattr(x, "shape") else x, self.state)
            new = jax.tree.map(lambda x: (x.shape, x.dtype)
                               if hasattr(x, "shape") else x, new_state)
            if jax.tree_util.tree_structure(old) != \
                    jax.tree_util.tree_structure(new) or \
                    jax.tree.leaves(old) != jax.tree.leaves(new):
                raise ValueError(
                    "swap_index produced a retrieval state with different "
                    "shapes — the new checkpoint's head does not match the "
                    "engine's (vocab/d_model/partition config changed?)")
        self.params = params
        self.state = new_state
        self.index = new_state.index if new_state is not None else None
        self._scan_runners = {}
        # tier states / digests derive from the old embedding: drop and
        # re-record (tiers rebuild lazily on next use)
        self._tier_states = {}
        self._digests = {}
        if self.index is not None:
            self._digests[self.backend.method] = _digest(self.index.v_blocks)
        if self.obs is not None:
            self.obs.instant("index_swap",
                             args={"method": self.backend.method})

    # -- degradation tiers + retrieval-state integrity ------------------------

    def tier_state(self, method: str):
        """The retrieval state that serves ``method`` as a degradation tier.

        Index-routed tiers (mimps / mince / topk) REUSE the engine's IVF
        index — stepping down the ladder swaps which compiled step consumes
        the same device-resident state, no rebuild. Anything else the tier
        needs beyond that (the FMBE sketch; a fresh index when the base
        method built none) is built once on first use and cached."""
        if method == self.backend.method or self.state is None:
            return self.state
        st = self._tier_states.get(method)
        if st is None:
            st = self._build_tier_state(method)
            self._tier_states[method] = st
            if st is not None and st.index is not None \
                    and method not in self._digests:
                self._digests[method] = _digest(st.index.v_blocks)
        return st

    def _build_tier_state(self, method: str):
        backend = get_backend(method)
        if method in ("exact", "selfnorm"):
            return BackendState(w=self.state.w)
        if method in ("mimps", "mince", "topk") and self.state.index is not None:
            return BackendState(w=self.state.w, index=self.state.index)
        if method == "fmbe" and self.state.index is not None:
            # fmbe as a tier / speculative-draft backend shares the engine's
            # IVF index too — only the V-independent feature sketch and its
            # per-block lambda table are built fresh (one phi pass), so a
            # draft tier costs no second kmeans and hot-swaps with the index
            from ..core.feature_maps import (FMBEState, build_fmbe_blocks,
                                             make_feature_map)
            pc = self.cfg.partition
            kf, _ = jax.random.split(self._build_key)
            fm = make_feature_map(kf, self.state.w.shape[-1],
                                  pc.fmbe_features,
                                  max_degree=pc.fmbe_max_degree, p=pc.fmbe_p)
            idx = self.state.index
            lam_b = build_fmbe_blocks(fm, idx.v_blocks, idx.valid)
            fmbe = FMBEState(fm=fm, lambda_tilde=lam_b.sum(0),
                             lambda_blocks=lam_b)
            return BackendState(w=self.state.w, index=idx, fmbe=fmbe)
        return backend.build(self.cfg.partition,
                             self.model.head_matrix(self.params),
                             self._build_key, device=self.device_index,
                             block_multiple=self._block_multiple)

    def verify_and_restore(self, method: Optional[str] = None) -> bool:
        """Checksum ``method``'s retrieval state against the digest recorded
        when it was built/swapped; on mismatch (bit-rot, bad swap, stale
        drift) rebuild every retrieval state from params BEFORE any step
        consumes the corruption. Returns True iff a restore happened."""
        method = method or self.backend.method
        st = self.tier_state(method)
        if st is None or st.index is None:
            return False
        ref = self._digests.get(method)
        d = _digest(st.index.v_blocks)
        if ref is None:
            self._digests[method] = d
            return False
        if d == ref:
            return False
        self.restore_index()
        return True

    def restore_index(self, key: Optional[jax.Array] = None) -> None:
        """Rebuild the retrieval state from the CURRENT params with the
        engine's build key. ``backend.build`` is deterministic given (params,
        key, device), so the restored state is bit-identical to the original
        build — the chaos tests' token-parity guarantee rests on this."""
        if self.cfg.n_codebooks:
            return
        key = key if key is not None else self._build_key
        w = self.model.head_matrix(self.params)
        self.state = self.backend.build(
            self.cfg.partition, w, key, device=self.device_index,
            block_multiple=self._block_multiple)
        self.index = self.state.index
        self._tier_states = {}
        self._digests = {}
        self.index_restores += 1
        if self.index is not None:
            self._digests[self.backend.method] = _digest(self.index.v_blocks)
        if self.obs is not None:
            self.obs.instant("index_restore",
                             args={"method": self.backend.method,
                                   "restores": self.index_restores})

    def _install_state(self, state, method: Optional[str] = None) -> None:
        """Fault-injection hook: install a (possibly corrupted) retrieval
        state WITHOUT updating its recorded digest — simulates a bad
        ``swap_index`` / in-place bit-rot that ``verify_and_restore`` must
        catch. Not a public serving API."""
        method = method or self.backend.method
        if method == self.backend.method:
            self.state = state
            self.index = state.index if state is not None else None
        else:
            self._tier_states[method] = state

    # -- steps (jit-compiled by callers / launch scripts) ---------------------

    def prefill(self, tokens, img=None) -> Tuple[jax.Array, ServeState]:
        """Full-sequence prefill; returns hidden of last position + state
        primed for decode. (KV caches are rebuilt decode-side for simplicity
        of the scan layout; see launch/dryrun.py for the lowered prefill.)"""
        hidden, _ = self.model.forward(self.params, tokens, img=img)
        h_last = hidden[:, -1]
        batch = tokens.shape[0]
        state = ServeState(
            cache=self.model.init_decode_state(batch, self.max_len),
            pos=jnp.zeros((), jnp.int32),
            last_token=tokens[:, -1])
        return h_last, state

    def decode_step(self, state: ServeState, key: jax.Array, img=None,
                    temperature: float = 0.0
                    ) -> Tuple[Dict[str, jax.Array], ServeState]:
        """One token for every stream; returns sampling outputs + new state.
        ``temperature`` may be a python float or a traced scalar (0 =
        greedy) — it is sampling data, not a compile-time constant.

        Cache-capacity guard: a concrete (eager / host-loop) position past
        ``max_len`` raises — the KV write would silently clobber or wrap.
        Inside a compiled step the position is clamped to the last slot and
        the step is flagged in ``out["overflow"]`` instead (a traced value
        cannot raise); callers that loop (generate, the slot scheduler)
        bound their step counts so the flag never fires in normal service.
        """
        pos = state.pos
        if not isinstance(pos, jax.core.Tracer):
            if int(jnp.max(jnp.asarray(pos))) >= self.max_len:
                raise ValueError(
                    f"decode position {jnp.max(jnp.asarray(pos))} is past "
                    f"the KV-cache capacity max_len={self.max_len}; the "
                    f"write would wrap/clobber earlier positions")
        overflow = pos >= self.max_len
        pos_safe = jnp.minimum(pos, self.max_len - 1)
        h, new_cache = self.model.decode_step(
            self.params, state.cache, state.last_token, pos_safe, img=img)
        out = self.next_token_distribution(h, key, temperature)
        out["overflow"] = overflow
        new_state = ServeState(cache=new_cache, pos=state.pos + 1,
                               last_token=out["token"])
        return out, new_state

    # -- the paper's Eq. 2/3 at the output layer ------------------------------

    def next_token_distribution(self, h: jax.Array, key: jax.Array,
                                temperature: float = 0.0
                                ) -> Dict[str, jax.Array]:
        """Sample one token per stream. Greedy at temperature == 0;
        otherwise Gumbel-max over the retrieved head candidates with the
        reported probability normalized by the estimated log Ẑ.

        ``temperature`` is *traced data* (float or scalar array): changing
        it never recompiles, so the per-slot scheduler can thread one
        temperature per stream through the same executable. The backend
        always retrieves ``sample_k`` candidates — greedy decodes take the
        top-1 of the same (sorted) retrieval, so the candidate shape stays
        temperature-independent."""
        cfg = self.cfg
        k_est, k_samp = jax.random.split(key)
        if cfg.n_codebooks:
            # audio: exact per-codebook softmax; temperature over full logits
            t = jnp.asarray(temperature, jnp.float32)
            w = self.model.head_matrix(self.params)
            logits = jnp.einsum("bd,cvd->bcv", h, w)
            log_z = jax.nn.logsumexp(logits, -1)
            g = jax.random.gumbel(k_samp, logits.shape)
            safe_t = jnp.where(t > 0.0, t, 1.0)
            tok = jnp.where(t > 0.0,
                            jnp.argmax(logits / safe_t + g, -1),
                            jnp.argmax(logits, -1))
            tok = tok.astype(jnp.int32)
            top = jnp.take_along_axis(logits, tok[..., None], -1)[..., 0]
            return {"token": tok, "log_prob": top - log_z, "log_z": log_z}

        pc = cfg.partition
        out = self.backend.decode(self.state, h, k_est, pc, k=pc.sample_k,
                                  use_pallas=self.use_pallas,
                                  **self.kernel_cfg)
        if self.health_guard and self.state is not None:
            # identity when every lane is healthy (the lax.cond keep branch
            # returns the estimate bit-unchanged), exact fused fallback for
            # any lane whose estimate went non-finite/empty
            out, _ = apply_health_guard(out, self.state.w, h, pc.sample_k)
        return _sample_candidates(out, k_samp, temperature)


def _sample_candidates(out: DecodeOut, key: jax.Array,
                       temperature) -> Dict[str, jax.Array]:
    """Gumbel-max over retrieved candidates: token ~ softmax(s/T) restricted
    to the head. log_prob reports the model's T=1 probability of the chosen
    token, normalized with the estimated log Ẑ (selfnorm's Ẑ == 1).
    ``temperature`` is a traced scalar (0 = greedy: index 0 of the sorted
    candidates); the gumbel draw happens unconditionally so the executable
    is shared across temperatures — counter-based keys mean the unused draw
    perturbs nothing else."""
    t = jnp.asarray(temperature, jnp.float32)
    g = jax.random.gumbel(key, out.top_score.shape)
    safe_t = jnp.where(t > 0.0, t, 1.0)
    pick = jnp.where(t > 0.0,
                     jnp.argmax(out.top_score / safe_t + g, axis=-1),
                     jnp.zeros(out.top_score.shape[:1], jnp.int32)
                     ).astype(jnp.int32)
    tok = jnp.take_along_axis(out.top_id, pick[:, None], 1)[:, 0]
    score = jnp.take_along_axis(out.top_score, pick[:, None], 1)[:, 0]
    return {"token": tok.astype(jnp.int32), "log_prob": score - out.log_z,
            "log_z": out.log_z}


def generate(engine: Engine, prompt, n_tokens: int, key: jax.Array,
             img=None, temperature: float = 0.0, host_loop: bool = False,
             return_aux: bool = False):
    """Generation loop; greedy at temperature == 0.0, Gumbel-max candidate
    sampling otherwise. Returns (B, n_tokens) ids.

    Device-resident by default: prompt replay and generation run as ONE
    compiled ``jax.lax.scan`` over decode steps — per-step keys are
    pre-split, every replay step force-feeds its prompt token, and the whole
    loop is a single XLA dispatch (the seed dispatched one jitted step per
    token from Python, paying a host round-trip per generated token).
    ``host_loop=True`` keeps the step-by-step Python loop as a debug mode;
    both paths produce bit-identical tokens / log_prob / log_z
    (tests/test_generate.py pins this).

    The prompt is replayed through the decode cache; the last replay step
    already emits position 0's sample, so there is no separate prefill
    forward or full-output-layer pass (the seed engine ran both and
    discarded their results)."""
    if prompt.shape[1] == 0:
        raise ValueError(
            "generate() needs a non-empty prompt: the first sample is "
            "emitted by the last prompt-replay step, so there is nothing "
            "to condition on (the seed crashed here with UnboundLocalError)")
    if n_tokens < 1:
        raise ValueError(f"n_tokens must be >= 1, got {n_tokens}")
    t_replay = prompt.shape[1]
    if t_replay + n_tokens - 1 > engine.max_len:
        raise ValueError(
            f"prompt length {t_replay} + {n_tokens} generated tokens needs "
            f"{t_replay + n_tokens - 1} cache positions but the engine was "
            f"built with max_len={engine.max_len}; the KV write past "
            f"capacity would clobber earlier positions")
    if host_loop:
        return _generate_host(engine, prompt, n_tokens, key, img=img,
                              temperature=temperature, return_aux=return_aux)
    # Bucket the replay length to the next power of two so heterogeneous
    # prompt lengths share ONE compiled scan per bucket (the seed compiled a
    # fresh replay+decode scan for every distinct prompt length). The scan
    # runs `bucket + n_tokens - 1` steps; replay/generation switchover gates
    # on the TRUE length via the traced is_replay flags and fold schedule,
    # and the emitted window is cut out with a traced dynamic slice — pad
    # steps trail the real ones, burn a few decode steps, and are discarded.
    bucket = 1 << (t_replay - 1).bit_length()
    total = bucket + n_tokens - 1
    step_ix = jnp.arange(total, dtype=jnp.int32)
    fold_ids = jnp.where(step_ix < t_replay, step_ix,
                         10_000 + step_ix - t_replay)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(fold_ids)
    # prompt tokens step-major, padded to the full scan length (the padding
    # is never read: is_replay gates on t < t_replay)
    prompt_sm = jnp.moveaxis(prompt, 1, 0)
    pad = total - t_replay
    prompt_sm = jnp.concatenate(
        [prompt_sm, jnp.zeros((pad,) + prompt_sm.shape[1:],
                              prompt_sm.dtype)]) if pad else prompt_sm
    is_replay = step_ix < t_replay
    batch_shape = prompt.shape[:1] + prompt.shape[2:]
    run = _scan_runner(engine, batch_shape, str(jnp.asarray(prompt).dtype),
                       bucket, n_tokens)
    toks, lp, lz = run(prompt_sm, keys, is_replay,
                       jnp.asarray(t_replay - 1, jnp.int32),
                       jnp.asarray(temperature, jnp.float32), img)
    if return_aux:
        return toks, {"log_prob": lp, "log_z": lz}
    return toks


def _scan_runner(engine: Engine, batch_shape, prompt_dtype, bucket: int,
                 n_tokens: int):
    """Build (or fetch) the compiled scan for one (engine, batch, replay
    bucket, n_tokens) cell.

    The executable is cached on the engine: jit keys its trace cache on the
    function object, so a fresh inner ``run`` per generate() call would
    recompile the whole replay+decode scan every request — exactly the
    dispatch overhead the device-resident loop exists to remove. ``img`` is
    a traced *argument* (not a closure constant) so cached executables serve
    changing images; the true replay length (as ``t_start``: the step index
    of the first emitted sample) and the temperature are traced arguments
    too, so neither prompt-length variation within a bucket nor a sampling-
    parameter change ever recompiles.
    """
    cache = getattr(engine, "_scan_runners", None)
    if cache is None:
        cache = engine._scan_runners = {}
    key = (batch_shape, prompt_dtype, bucket, n_tokens)
    run = cache.get(key)
    if run is not None:
        return run

    @jax.jit
    def run(prompt_sm, keys, is_replay, t_start, temperature, img):
        state = ServeState(
            cache=engine.model.init_decode_state(batch_shape[0],
                                                 engine.max_len),
            pos=jnp.zeros((), jnp.int32),
            last_token=prompt_sm[0])

        def step(state, xs):
            k_t, tok_t, replay_t = xs
            last = jnp.where(replay_t, tok_t, state.last_token)
            state = dataclasses.replace(state, last_token=last)
            out, state = engine.decode_step(state, k_t, img=img,
                                            temperature=temperature)
            return state, (out["token"], out["log_prob"], out["log_z"])

        _, (toks, lp, lz) = jax.lax.scan(step, state,
                                         (keys, prompt_sm, is_replay))
        # steps 0..t_start-1 replay the prompt; the emitted samples start at
        # the last replay step (position 0 of the generation) and any
        # bucket-padding steps trail behind the emitted window
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, t_start, n_tokens, 0)
        return (jnp.moveaxis(cut(toks), 0, 1),
                jnp.moveaxis(cut(lp), 0, 1), jnp.moveaxis(cut(lz), 0, 1))

    cache[key] = run
    return run


def _generate_host(engine: Engine, prompt, n_tokens: int, key: jax.Array,
                   img=None, temperature: float = 0.0,
                   return_aux: bool = False):
    """Debug path: one jitted decode_step dispatch per token (the seed
    loop). Key schedule matches the scan path exactly."""
    batch = prompt.shape[0]
    state = ServeState(
        cache=engine.model.init_decode_state(batch, engine.max_len),
        pos=jnp.zeros((), jnp.int32),
        last_token=prompt[:, 0])
    outs = []
    step_fn = jax.jit(lambda s, k: engine.decode_step(
        s, k, img=img, temperature=temperature))
    out = None
    for t in range(prompt.shape[1]):
        tok_t = prompt[:, t] if not engine.cfg.n_codebooks \
            else prompt[:, t, :]
        state = dataclasses.replace(state, last_token=tok_t)
        out, state = step_fn(state, jax.random.fold_in(key, t))
    outs.append(out)
    for t in range(n_tokens - 1):
        out, state = step_fn(state, jax.random.fold_in(key, 10_000 + t))
        outs.append(out)
    toks = jnp.stack([o["token"] for o in outs], axis=1)
    if return_aux:
        return toks, {
            "log_prob": jnp.stack([o["log_prob"] for o in outs], axis=1),
            "log_z": jnp.stack([o["log_z"] for o in outs], axis=1)}
    return toks
