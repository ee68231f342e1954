"""The serving kernels compile for a TPU v5e at qwen1.5-4b widths.

Interpret mode (every other kernel test) cannot see TPU tiling or VMEM
refusals, so each kernel of the output layer is lowered and compiled here
for a described ``v5e:2x2`` chip: d_model 2560, vocab 151,936, bf16, a
decode batch of 8, block_rows 512, l = 1000 tail samples, 128 probed
blocks (8 queries x n_probe 16), and an FMBE sketch of 4096 features of
degree <= 8. Nothing runs: a pass means the TPU compiler accepted the
kernel (``tpu_custom_call`` in the compiled program), not that it is fast
or correct on the chip.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fmbe import fmbe_z
from repro.kernels.fused_ce import fused_ce_fwd
from repro.kernels.ivf_score import ivf_decode, union_scores
from repro.kernels.lsh_probe import lsh_probe
from repro.kernels.topk_z import topk_z

Q, D, V = 8, 2560, 151936          # decode batch, d_model, vocab
BR, L, U = 512, 1000, 128          # block_rows, tail samples, probed blocks
NB = -(-V // BR) + 256             # IVF capacity: ceil(V/br) + n_clusters
C, TABLES, BITS = 1024, 8, 8       # LSH candidate union, hash tables, bits
P, M = 4096, 8                     # FMBE features, max degree
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """Shape factory on one described chip, with the persistent compile
    cache off: entries compiled for a described chip cannot be read back
    without one, and would only warn on the next compile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_ivf_decode(spec):
    _compiles_to_kernel(
        partial(ivf_decode, k=8, interpret=False),
        spec((NB, BR, D), BF), spec((Q, D), BF), spec((U,), I32),
        spec((), I32), spec((Q, U), jnp.bool_), spec((NB, BR), F32),
        spec((L, D), BF), spec((Q, L), jnp.bool_))


def test_union_scores(spec):
    """The candidate head of the topk / mince / fmbe tiers."""
    _compiles_to_kernel(
        partial(union_scores, interpret=False),
        spec((NB, BR, D), BF), spec((Q, D), BF), spec((U,), I32),
        spec((), I32))


def test_lsh_probe(spec):
    _compiles_to_kernel(
        partial(lsh_probe, k=8, interpret=False),
        spec((C, D), F32), spec((Q, D), BF),
        spec((TABLES, BITS, D + 1), F32), spec((C,), I32),
        spec((C, TABLES), I32), spec((C, TABLES), jnp.bool_), spec((), I32),
        spec((L, D), F32), spec((Q, L), jnp.bool_), spec((L,), F32))


@pytest.mark.parametrize("per_query_lambda", [False, True])
def test_fmbe_z(spec, per_query_lambda):
    lam = spec((Q, P) if per_query_lambda else (P,), F32)
    _compiles_to_kernel(
        partial(fmbe_z, interpret=False),
        spec((P, M, D), F32), spec((P,), I32), spec((P,), F32), lam,
        spec((Q, D), BF))


def test_topk_z(spec):
    _compiles_to_kernel(partial(topk_z, k=8, interpret=False),
                        spec((Q, D), BF), spec((V, D), BF))


def test_fused_ce_fwd(spec):
    _compiles_to_kernel(partial(fused_ce_fwd, interpret=False),
                        spec((256, D), BF), spec((V, D), BF),
                        spec((256,), I32))
