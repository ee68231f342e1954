"""Device-resident serving metrics (DESIGN.md SS17).

The scheduler threads ONE registered pytree of counters/histograms
(``MetricState``) through its compiled step, unconditionally: observability
"on" vs "off" differ only in host-side harvest cadence and the traced
shadow-sampling flag, never in which executable runs — that is what keeps
tokens bit-identical and the trace counters pinned. Updates read only from
values the step already computed (emitted counts, health flags, the
probe-union size); nothing here feeds back into the token path.

Under the (data, model) serving mesh the state is replicated (``P()`` in and
out of ``shard_map``): each replica's local contributions are psum-reduced
over ``'data'`` inside ``observe_step`` before accumulation, so every
replica holds the same global counters and the host can harvest any one
shard.

Harvesting is a cadence-controlled ``jax.device_get`` of the whole pytree —
the only device->host traffic observability adds (the per-step ``outs``
readback already exists for token streaming and stays untouched).

The queue-depth and step-latency histograms are host values, so they stay
on the host (``StepHistograms``): the scheduler fills them at the end of
each step from the step's own spans, and uploads nothing for them.
"""
from __future__ import annotations

import bisect
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core.backends import BACKENDS
from ..core.decode import (HEALTH_EMPTY_HEAD, HEALTH_NONFINITE_SCORE,
                           HEALTH_NONFINITE_Z)

# canonical tier order: every per-tier row in the metric state is indexed by
# position in this tuple (static per compiled tier step, so the .at[] adds
# constant-fold their row index)
TIERS: tuple = tuple(sorted(BACKENDS))
TIER_IX: dict = {t: i for i, t in enumerate(TIERS)}

# bucket UPPER edges, shared by accumulation, harvest, the serving
# benchmark rows and obs_report: value v lands in the first bucket whose
# edge exceeds it; the trailing bucket is the +inf overflow
LATENCY_EDGES_MS: tuple = (0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0,
                           200.0, 500.0, 1000.0, 5000.0)
QUEUE_EDGES: tuple = (0, 1, 2, 4, 8, 16, 32, 64, 128)
OCC_EDGES: tuple = (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9)

_NT = len(TIERS)
_NL = len(LATENCY_EDGES_MS) + 1
_NQ = len(QUEUE_EDGES) + 1
_NO = len(OCC_EDGES) + 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MetricState:
    """One pytree of monotone counters (Prometheus semantics: harvest reads
    cumulative values and never resets them mid-run)."""
    steps: jax.Array            # ()   steps observed
    tokens_total: jax.Array     # ()   emitted tokens
    tokens_by_tier: jax.Array   # (T,) emitted tokens per estimator tier
    active_sum: jax.Array       # ()   sum of live lanes per step (gauge avg)
    fill_sum: jax.Array         # ()   sum of probe-union live blocks
    occ_hist: jax.Array         # (NO,) occupancy-fraction histogram
    health_flagged: jax.Array   # ()   lane-steps health-guard flagged
    health_by_cause: jax.Array  # (3,) [nonfinite_z, empty_head,
                                #       nonfinite_score] lane-steps
    spec_proposed: jax.Array    # ()   speculative positions offered
    spec_accepted: jax.Array    # ()   speculative positions advanced
    draft_flagged: jax.Array    # ()   draft-health fallbacks to k=1
    shadow_count: jax.Array     # (T,) lane-steps shadow-sampled per tier
    shadow_err_sum: jax.Array   # (T,) f32 sum of |Ẑ/Z - 1| over samples
    shadow_err_max: jax.Array   # (T,) f32 max |Ẑ/Z - 1| seen


def init_metric_state() -> MetricState:
    z = lambda *s: jnp.zeros(s, jnp.int32)
    zf = lambda *s: jnp.zeros(s, jnp.float32)
    return MetricState(
        steps=z(), tokens_total=z(), tokens_by_tier=z(_NT),
        active_sum=z(), fill_sum=z(), occ_hist=z(_NO),
        health_flagged=z(), health_by_cause=z(3),
        spec_proposed=z(), spec_accepted=z(), draft_flagged=z(),
        shadow_count=z(_NT), shadow_err_sum=zf(_NT), shadow_err_max=zf(_NT))


def _bucket(edges: tuple, v) -> jax.Array:
    return jnp.searchsorted(jnp.asarray(edges, jnp.float32),
                            jnp.asarray(v, jnp.float32), side="left")


def observe_step(m: MetricState, tier_ix: int, n_slots: int, *,
                 n_active, head_live, n_emitted, health_flags, shadow=None,
                 spec_proposed=None, spec_accepted=None, draft_flagged=None,
                 axis_name=None) -> MetricState:
    """Accumulate one step into the metric state (traced; runs inside the
    compiled scheduler step).

    ``n_active`` / ``head_live`` are already GLOBAL (the step body psums
    them for its own outs); ``n_emitted``, ``health_flags`` (per local
    lane), the spec scalars and the ``shadow`` triple are this replica's
    local contributions and get psum-reduced here when ``axis_name`` is
    set.
    """
    i32 = jnp.int32
    hf = jnp.asarray(health_flags)
    flagged = (hf > 0).sum().astype(i32)
    causes = jnp.stack([
        ((hf & HEALTH_NONFINITE_Z) > 0).sum(),
        ((hf & HEALTH_EMPTY_HEAD) > 0).sum(),
        ((hf & HEALTH_NONFINITE_SCORE) > 0).sum()]).astype(i32)
    n_emitted = jnp.asarray(n_emitted, i32)
    sp = i32(0) if spec_proposed is None else jnp.asarray(spec_proposed, i32)
    sa = i32(0) if spec_accepted is None else jnp.asarray(spec_accepted, i32)
    df = i32(0) if draft_flagged is None else jnp.asarray(draft_flagged, i32)
    if shadow is None:
        sh_sum, sh_max, sh_n = (jnp.float32(0.0), jnp.float32(0.0), i32(0))
    else:
        sh_sum, sh_max, sh_n = shadow
    if axis_name is not None:
        n_emitted = jax.lax.psum(n_emitted, axis_name)
        flagged = jax.lax.psum(flagged, axis_name)
        causes = jax.lax.psum(causes, axis_name)
        sp = jax.lax.psum(sp, axis_name)
        sa = jax.lax.psum(sa, axis_name)
        df = jax.lax.psum(df, axis_name)
        sh_sum = jax.lax.psum(sh_sum, axis_name)
        sh_n = jax.lax.psum(sh_n, axis_name)
        sh_max = jax.lax.pmax(sh_max, axis_name)
    n_active = jnp.asarray(n_active, i32)
    occ_b = _bucket(OCC_EDGES, n_active.astype(jnp.float32) / n_slots)
    return dataclasses.replace(
        m,
        steps=m.steps + 1,
        tokens_total=m.tokens_total + n_emitted,
        tokens_by_tier=m.tokens_by_tier.at[tier_ix].add(n_emitted),
        active_sum=m.active_sum + n_active,
        fill_sum=m.fill_sum + jnp.asarray(head_live, i32),
        occ_hist=m.occ_hist.at[occ_b].add(1),
        health_flagged=m.health_flagged + flagged,
        health_by_cause=m.health_by_cause + causes,
        spec_proposed=m.spec_proposed + sp,
        spec_accepted=m.spec_accepted + sa,
        draft_flagged=m.draft_flagged + df,
        shadow_count=m.shadow_count.at[tier_ix].add(sh_n),
        shadow_err_sum=m.shadow_err_sum.at[tier_ix].add(sh_sum),
        shadow_err_max=m.shadow_err_max.at[tier_ix].max(sh_max))


def shadow_rel_err(log_z, ref_log_z, active) -> tuple:
    """Masked relative error of the serving estimate against the exact
    shadow oracle: rel = |exp(log Ẑ - log Z) - 1| = |Ẑ/Z - 1|, the paper's
    multiplicative-guarantee error. Inactive lanes and non-finite values
    (injected faults; lanes the guard already replaced) are excluded.
    Returns the (sum, max, count) triple ``observe_step`` accumulates.

    Unbiasedness: the sampling cadence is a host counter, independent of
    the data each step decodes, so the sampled steps are a deterministic
    systematic sample of the step stream — E[err_sum/count] is the mean
    per-lane rel-err over sampled steps with no selection on the value.
    """
    rel = jnp.abs(jnp.expm1(jnp.asarray(log_z, jnp.float32)
                            - jnp.asarray(ref_log_z, jnp.float32)))
    ok = jnp.asarray(active, bool) & jnp.isfinite(rel)
    relm = jnp.where(ok, rel, 0.0)
    return (relm.sum(), relm.max(initial=0.0),
            ok.sum().astype(jnp.int32))


class StepHistograms:
    """Queue depth and step latency of every step, per tier for the
    latency, kept on the host. Cumulative, like the device counters."""

    def __init__(self):
        self.steps = 0
        self.queue_sum = 0
        self.queue_hist = [0] * _NQ
        self.latency_hist = [[0] * _NL for _ in TIERS]

    def observe(self, queue_depth: int, step_ms: float, tier: str) -> None:
        self.steps += 1
        self.queue_sum += queue_depth
        self.queue_hist[bisect.bisect_left(QUEUE_EDGES, queue_depth)] += 1
        self.latency_hist[TIER_IX[tier]][
            bisect.bisect_left(LATENCY_EDGES_MS, step_ms)] += 1

    def harvest(self) -> dict:
        return {
            "queue_depth_mean": self.queue_sum / max(self.steps, 1),
            "queue_hist": list(self.queue_hist),
            "queue_edges": list(QUEUE_EDGES),
            "latency_hist_by_tier": {t: list(self.latency_hist[i])
                                     for t, i in TIER_IX.items()
                                     if sum(self.latency_hist[i])},
            "latency_edges_ms": list(LATENCY_EDGES_MS),
        }


def harvest(m: MetricState, n_slots: int) -> dict:
    """ONE device->host read of the whole metric pytree, flattened into a
    plain dict (python scalars + per-tier sub-dicts) for the registry,
    snapshots and the serving benchmark. Non-destructive: counters stay
    cumulative on device. The host-side ``StepHistograms`` add their own
    keys (``Scheduler.harvest_metrics``)."""
    g = jax.device_get(m)
    steps = int(g.steps)
    tiers_tok = {t: int(g.tokens_by_tier[i]) for t, i in TIER_IX.items()
                 if int(g.tokens_by_tier[i])}
    shadow = {}
    for t, i in TIER_IX.items():
        n = int(g.shadow_count[i])
        if n:
            shadow[t] = {"count": n,
                         "rel_err_mean": float(g.shadow_err_sum[i]) / n,
                         "rel_err_max": float(g.shadow_err_max[i])}
    return {
        "steps": steps,
        "tokens_total": int(g.tokens_total),
        "tokens_by_tier": tiers_tok,
        "occupancy_mean": float(g.active_sum) / (max(steps, 1) * n_slots),
        "fill_mean": float(g.fill_sum) / max(steps, 1),
        "occ_hist": [int(c) for c in g.occ_hist],
        "occ_edges": list(OCC_EDGES),
        "health_flagged": int(g.health_flagged),
        "health_by_cause": {
            "nonfinite_z": int(g.health_by_cause[0]),
            "empty_head": int(g.health_by_cause[1]),
            "nonfinite_score": int(g.health_by_cause[2])},
        "spec_proposed": int(g.spec_proposed),
        "spec_accepted": int(g.spec_accepted),
        "draft_flagged": int(g.draft_flagged),
        "shadow_by_tier": shadow,
    }


def hist_quantile(counts, edges, q: float) -> float:
    """Quantile from a bucketed histogram: the upper edge of the bucket
    where the cumulative count crosses q (clamped to the last finite edge
    for the overflow bucket — histogram quantiles are bucket-resolution
    upper bounds, never interpolated guesses)."""
    counts = np.asarray(counts, np.int64)
    total = int(counts.sum())
    if total == 0:
        return float("nan")
    cum = np.cumsum(counts)
    b = int(np.searchsorted(cum, q * total))
    return float(edges[min(b, len(edges) - 1)])
