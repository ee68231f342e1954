"""Fused Kar–Karnick feature-map kernels — the FMBE substrate (paper Eq. 9/10)
as a tiled Pallas pipeline.

The XLA reference (``core.feature_maps.apply_feature_map``) materializes the
projection tensor ``proj (..., P, max_degree)`` with one einsum and reduces it
with a masked product — at serving shapes that intermediate is
``Q * P * max_degree`` floats of HBM round-trip per decode step. Here each
``(block_q, block_p)`` tile of the feature matrix is built as ``max_degree``
successive ``(block_q, d) x (d, block_p)`` MXU matmuls whose running degree
product lives in registers/VMEM:

    prod := 1
    for m in 0..max_degree-1:                # static unroll, M is 4-8
        prod *= where(degree > m, x @ omega[:, m, :].T, 1)
    phi_tile = prod * coef

Two entry points share that tile routine:

 * ``fmbe_phi``  — writes the (Q, P) feature matrix (parity / build-time use).
 * ``fmbe_z``    — the decode path: folds each tile straight into
   ``z += (phi_tile * lambda_tile).sum(feature axis)`` in VMEM, so HBM sees
   only the operands and the (Q, 1) estimate — no (Q, P) tensor at all.

HBM floats per decode step: ``P*max_degree*d (omega) + P (lambda) + Q*d`` —
independent of the vocab size V, the FMBE selling point the SS5/SS8 byte
accounting tracks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_tpu


def _phi_tile(x, om_ref, deg_ref, coef_ref, max_degree: int):
    """One (block_q, block_p) tile of phi. x (bq, d) f32; om (bp, M, d);
    deg/coef (1, bp). Factor order matches apply_feature_map exactly."""
    deg = deg_ref[...]                                    # (1, bp) int32
    prod = jnp.ones((x.shape[0], deg.shape[1]), jnp.float32)
    for m in range(max_degree):
        w_m = om_ref[:, m, :]                             # (bp, d)
        proj = jax.lax.dot_general(
            x, w_m, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bp)
        prod = prod * jnp.where(deg > m, proj, 1.0)
    return prod * coef_ref[...]


def _fmbe_phi_kernel(x_ref, om_ref, deg_ref, coef_ref, out_ref,
                     *, max_degree: int):
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = _phi_tile(x, om_ref, deg_ref, coef_ref, max_degree)


def _fmbe_z_kernel(x_ref, om_ref, deg_ref, coef_ref, lam_ref, out_ref,
                   z_scr, *, max_degree: int):
    # lam_ref is (1, bp) (one shared lambda) or (bq, bp) (per-query lambda,
    # the block-partitioned tail-sketch path) — broadcasting covers both
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        z_scr[...] = jnp.zeros_like(z_scr)

    x = x_ref[...].astype(jnp.float32)
    phi = _phi_tile(x, om_ref, deg_ref, coef_ref, max_degree)   # (bq, bp)
    lam = lam_ref[...]                                          # (1|bq, bp)
    z_scr[...] += jnp.sum(phi * lam, axis=1, keepdims=True)

    @pl.when(pi == pl.num_programs(1) - 1)
    def _fin():
        out_ref[...] = z_scr[...]


def _compiler_params(block_q: int, block_p: int, max_degree: int, d: int,
                     x_itemsize: int, lam_rows: int):
    """Scoped-VMEM budget for one grid step. The omega tile alone is
    block_p * max_degree * d f32 (10.5 MB at P-tile 128, M 8, d 2560) and
    Pallas double-buffers every input, which overflows the 16 MiB default
    scope at published widths; ask for what the tiles need plus headroom
    for the (block_q, block_p) intermediates."""
    tile_bytes = (block_p * max_degree * d * 4 + block_q * d * x_itemsize
                  + (2 + lam_rows) * block_p * 4)
    limit = max(2 * tile_bytes + 8 * 2 ** 20, 16 * 2 ** 20)
    return pltpu.CompilerParams(vmem_limit_bytes=int(limit))


def _pad_features(omega, degree, coef, block_p):
    """Pad the feature axis to a block multiple; pad features get coef == 0
    so they contribute exactly zero to phi and to z."""
    n_feat = omega.shape[0]
    pad_p = (-n_feat) % block_p
    om = jnp.pad(omega.astype(jnp.float32), ((0, pad_p), (0, 0), (0, 0)))
    deg = jnp.pad(degree.astype(jnp.int32), (0, pad_p)).reshape(1, -1)
    cf = jnp.pad(coef.astype(jnp.float32), (0, pad_p)).reshape(1, -1)
    return om, deg, cf


def fmbe_phi(omega, degree, coef, x, *, block_q: int = 128,
             block_p: int = 128, interpret=None):
    """phi(x) without the (Q, P, max_degree) intermediate.

    omega (P, max_degree, d), degree (P,), coef (P,), x (Q, d) -> (Q, P) f32.
    """
    if interpret is None:
        interpret = not on_tpu()
    n_feat, max_degree, d = omega.shape
    q = x.shape[0]
    block_q = min(block_q, max(8, q))
    block_p = min(block_p, max(128, n_feat))
    pad_q = (-q) % block_q
    xp = jnp.pad(x, ((0, pad_q), (0, 0)))
    om, deg, cf = _pad_features(omega, degree, coef, block_p)
    qp, pp = xp.shape[0], om.shape[0]
    out = pl.pallas_call(
        functools.partial(_fmbe_phi_kernel, max_degree=max_degree),
        grid=(qp // block_q, pp // block_p),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, pi: (qi, 0)),
            pl.BlockSpec((block_p, max_degree, d), lambda qi, pi: (pi, 0, 0)),
            pl.BlockSpec((1, block_p), lambda qi, pi: (0, pi)),
            pl.BlockSpec((1, block_p), lambda qi, pi: (0, pi)),
        ],
        out_specs=pl.BlockSpec((block_q, block_p), lambda qi, pi: (qi, pi)),
        out_shape=jax.ShapeDtypeStruct((qp, pp), jnp.float32),
        compiler_params=_compiler_params(block_q, block_p, max_degree, d,
                                         xp.dtype.itemsize, block_q),
        interpret=interpret,
    )(xp, om, deg, cf)
    return out[:q, :n_feat]


def fmbe_z(omega, degree, coef, lam, x, *, block_q: int = 128,
           block_p: int = 128, interpret=None):
    """Fused decode estimate: Ẑ(x) = phi(x) . lambda, (Q,) signed f32.

    ``lam`` is (P,) — one shared sketch sum, the global-Z path — or (Q, P) —
    a per-query lambda, the block-partitioned complement path
    (``core.feature_maps.fmbe_tail_z``). The feature axis rides the inner
    grid dimension; per-query z accumulates in VMEM across feature tiles
    and is written once — HBM traffic is the operands plus Q floats.

    ``block_q``/``block_p`` are autotuned (kernels.autotune.tune_fmbe_z).
    """
    if interpret is None:
        interpret = not on_tpu()
    n_feat, max_degree, d = omega.shape
    q = x.shape[0]
    block_q = min(block_q, max(8, q))
    block_p = min(block_p, max(128, n_feat))
    pad_q = (-q) % block_q
    xp = jnp.pad(x, ((0, pad_q), (0, 0)))
    om, deg, cf = _pad_features(omega, degree, coef, block_p)
    pad_p = om.shape[0] - n_feat
    if lam.ndim == 1:
        lam_p = jnp.pad(lam.astype(jnp.float32), (0, pad_p)).reshape(1, -1)
        lam_spec = pl.BlockSpec((1, block_p), lambda qi, pi: (0, pi))
    else:
        lam_p = jnp.pad(lam.astype(jnp.float32),
                        ((0, pad_q), (0, pad_p)))
        lam_spec = pl.BlockSpec((block_q, block_p), lambda qi, pi: (qi, pi))
    qp, pp = xp.shape[0], om.shape[0]
    out = pl.pallas_call(
        functools.partial(_fmbe_z_kernel, max_degree=max_degree),
        grid=(qp // block_q, pp // block_p),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, pi: (qi, 0)),
            pl.BlockSpec((block_p, max_degree, d), lambda qi, pi: (pi, 0, 0)),
            pl.BlockSpec((1, block_p), lambda qi, pi: (0, pi)),
            pl.BlockSpec((1, block_p), lambda qi, pi: (0, pi)),
            lam_spec,
        ],
        out_specs=pl.BlockSpec((block_q, 1), lambda qi, pi: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((qp, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(block_q, block_p, max_degree, d,
                                         xp.dtype.itemsize,
                                         1 if lam.ndim == 1 else block_q),
        interpret=interpret,
    )(xp, om, deg, cf, lam_p)
    return out[:q, 0]
