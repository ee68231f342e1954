"""Kernel autotuner: measured config sweeps with on-disk caching.

The Pallas kernels in this package expose a small set of static tile knobs
(``block_q``, ``tail_tile``, ``block_v``, ``block_p``). The right values
depend on shapes, dtype and backend generation, so they are picked by
measurement, not heuristics:

    cfg = tune_ivf_decode(index, h, plan_args...)   # {'block_q':…, 'tail_tile':…}
    ivf_decode(..., **cfg)

Sweeps run the real kernel on the caller's real operands, time a few
repetitions (median of means), and persist the winner to a JSON cache keyed
by ``(kernel, operand shapes, dtypes, backend, device kind)`` — the same
key scheme as Triton/XLA autotuning caches, so a tuned serving binary never
re-sweeps. Configs that fail to compile or run (e.g. a tile too large for
VMEM) are skipped; a sweep in which every config fails raises. Cache
location: ``$REPRO_AUTOTUNE_CACHE``, else ``~/.cache/repro/autotune.json``.

On CPU the Pallas kernels execute in interpret mode, where timings reflect
the interpreter rather than the lowered kernel; sweeps still *work* (the
machinery is exercised by tier-1 tests) but the benchmark artifacts record
``backend: cpu`` so the numbers are read accordingly.

Adding a kernel: write a ``tune_<kernel>`` wrapper that (1) builds the
candidate list, (2) closes the kernel over everything but the swept knobs,
and (3) calls ``autotune`` — see ``tune_ivf_decode`` for the template.
DESIGN.md SS9 documents the scheme.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax

_DEF_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                          "autotune.json")


def cache_path(path: Optional[str] = None) -> str:
    return path or os.environ.get("REPRO_AUTOTUNE_CACHE", _DEF_CACHE)


def _sig(args) -> str:
    parts = []
    for a in args:
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            parts.append(f"{tuple(a.shape)}:{a.dtype}")
        else:
            parts.append(repr(a))
    return ",".join(parts)


def cache_key(kernel: str, args: Iterable[Any], extra: str = "") -> str:
    """Deterministic key: kernel + operand shapes/dtypes + backend/device."""
    backend = jax.default_backend()
    try:
        kind = jax.devices()[0].device_kind
    except Exception:       # pragma: no cover - device enumeration quirks
        kind = backend
    return f"{kernel}|{_sig(args)}|{extra}|{backend}|{kind}"


def _load(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _store(path: str, cache: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    os.replace(tmp, path)    # atomic — concurrent tuners last-write-win


def _time(fn: Callable[[], Any], reps: int) -> float:
    jax.block_until_ready(fn())                    # compile + warm
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(kernel: str, candidates: List[Dict[str, int]],
             build: Callable[[Dict[str, int]], Callable[[], Any]],
             args: Iterable[Any], *, reps: int = 3,
             path: Optional[str] = None) -> Dict[str, int]:
    """Return the fastest candidate config (cached on disk).

    ``build(cfg)`` must return a zero-arg callable running the kernel with
    that config on the caller's operands. A candidate that raises during
    compile/run is skipped; if every candidate fails, RuntimeError names
    each failure (a default handed back untested would only fail later,
    inside the serving step).
    """
    path = cache_path(path)
    key = cache_key(kernel, args)
    cache = _load(path)
    hit = cache.get(key)
    if hit is not None:
        return dict(hit["config"])
    best_cfg, best_t = None, float("inf")
    results = []
    for cfg in candidates:
        try:
            t = _time(build(cfg), reps)
        except Exception as e:                     # invalid tile/VMEM/etc.
            results.append({"config": cfg,
                            "error": f"{type(e).__name__}: {e}"[:500]})
            continue
        results.append({"config": cfg, "s": t})
        if t < best_t:
            best_cfg, best_t = cfg, t
    if best_cfg is None:
        raise RuntimeError(
            f"autotune {kernel}: every candidate failed: "
            + "; ".join(f"{r['config']} -> {r['error']}" for r in results))
    cache[key] = {"config": best_cfg, "s": best_t, "swept": results}
    _store(path, cache)
    return dict(best_cfg)


# ---------------------------------------------------------------------------
# per-kernel sweeps
# ---------------------------------------------------------------------------

def _pow2s(lo: int, hi: int) -> List[int]:
    out, v = [], lo
    while v <= hi:
        out.append(v)
        v *= 2
    return out


def tune_ivf_decode(w_blocks, h, head_ids, head_live, head_member, row_logw,
                    tail_rows_g, tail_accept, *, k: int = 1,
                    path: Optional[str] = None,
                    reps: int = 3) -> Dict[str, int]:
    """Sweep (block_q, tail_tile) for the fused MIMPS decode kernel."""
    from .ivf_score import ivf_decode
    q = h.shape[0]
    l = tail_rows_g.shape[0]
    cands = [{"block_q": bq, "tail_tile": tt}
             for bq in _pow2s(8, max(8, min(256, q)))
             for tt in _pow2s(8, max(8, min(128, l)))]

    def build(cfg):
        return lambda: ivf_decode(w_blocks, h, head_ids, head_live,
                                  head_member, row_logw, tail_rows_g,
                                  tail_accept, k=k, **cfg)

    return autotune("ivf_decode", cands, build,
                    (w_blocks, h, head_ids, tail_rows_g, k), reps=reps,
                    path=path)


def tune_union_scores(w_blocks, h, head_ids, head_live, *,
                      path: Optional[str] = None,
                      reps: int = 3) -> Dict[str, int]:
    """Sweep block_q for the deduplicated union-scoring kernel (the MINCE /
    FMBE candidate head)."""
    from .ivf_score import union_scores
    q = h.shape[0]
    cands = [{"block_q": bq} for bq in _pow2s(8, max(8, min(256, q)))]

    def build(cfg):
        return lambda: union_scores(w_blocks, h, head_ids, head_live, **cfg)

    return autotune("union_scores", cands, build, (w_blocks, h, head_ids),
                    reps=reps, path=path)


def tune_lsh_probe(lsh_index, w, h, key, *, l: int, cand_cap: int = 0,
                   k: int = 1, path: Optional[str] = None,
                   reps: int = 3) -> Dict[str, int]:
    """Sweep (block_q, cand_tile, tail_tile) for the fused Hamming-probe
    decode kernel, on the trimmed candidate set a real decode would score."""
    from ..core import lsh as _lsh
    from .lsh_probe import lsh_probe
    plan = _lsh.lsh_plan(lsh_index, h, key, l, cand_cap=cand_cap)
    rows = plan.cand_rows
    cap = rows.shape[0]
    w_cand = w[rows].astype(jax.numpy.float32)
    cand_codes = lsh_index.codes[rows]
    cand_ok = lsh_index.slot_of_row[rows] >= 0
    tail_rows = w[plan.tail_ids].astype(jax.numpy.float32)
    q = h.shape[0]
    cands = [{"block_q": bq, "cand_tile": ct, "tail_tile": tt}
             for bq in _pow2s(8, max(8, min(256, q)))
             for ct in _pow2s(64, max(64, min(512, cap)))
             for tt in _pow2s(8, max(8, min(128, l)))]

    def build(cfg):
        return lambda: lsh_probe(w_cand, h, lsh_index.proj, rows,
                                 cand_codes, cand_ok, plan.cand_live,
                                 tail_rows, plan.tail_accept,
                                 plan.tail_bias, k=k, **cfg)

    return autotune("lsh_probe", cands, build,
                    (w_cand, h, lsh_index.proj, tail_rows, k), reps=reps,
                    path=path)


def tune_fmbe_z(omega, degree, coef, lam, x, *, path: Optional[str] = None,
                reps: int = 3) -> Dict[str, int]:
    """Sweep (block_q, block_p) for the fused feature-map estimate."""
    from .fmbe import fmbe_z
    q = x.shape[0]
    p = omega.shape[0]
    cands = [{"block_q": bq, "block_p": bp}
             for bq in _pow2s(8, max(8, min(256, q)))
             for bp in _pow2s(128, max(128, min(1024, p)))]

    def build(cfg):
        return lambda: fmbe_z(omega, degree, coef, lam, x, **cfg)

    return autotune("fmbe_z", cands, build, (omega, lam, x), reps=reps,
                    path=path)


def tune_topk_z(h, w, k: int, *, path: Optional[str] = None,
                reps: int = 3) -> Dict[str, int]:
    """Sweep (block_q, block_v) for the fused exact log-Z/top-k kernel."""
    from .topk_z import topk_z
    q = h.shape[0]
    v = w.shape[0]
    cands = [{"block_q": bq, "block_v": bv}
             for bq in _pow2s(8, max(8, min(256, q)))
             for bv in _pow2s(128, max(128, min(2048, v)))]

    def build(cfg):
        return lambda: topk_z(h, w, k, **cfg)

    return autotune("topk_z", cands, build, (h, w, k), reps=reps, path=path)
