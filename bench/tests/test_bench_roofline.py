"""Peaks table and the shape-derived operation and byte counts."""
import json
import os

import pytest

from bench import roofline
from bench.model import Arch

HERE = os.path.dirname(os.path.abspath(__file__))


def test_known_device_has_its_peaks():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "tpu v5 lite", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks(kind)


def _arch(**kw):
    a = dict(name="t", d=4, d_ff=6, n_layers=2, n_heads=2, n_kv=1,
             head_dim=2, vocab=10, qkv_bias=False, rope_theta=1e4,
             norm_eps=1e-6, act="silu", dtype="bfloat16")
    a.update(kw)
    return Arch(**a)


def test_trunk_params_by_hand():
    # per layer: q 4x4, k 4x2, v 4x2, o 4x4 = 48; mlp 3 x 4x6 = 72
    assert _arch().trunk_params() == 2 * (48 + 72)


def test_decode_flops_by_hand():
    a = _arch()
    # 3 lanes: 2 x (240 trunk + 40 head) each; attention 4 x L x H x hd
    # = 4 x 2 x 2 x 2 = 32 per attended position, 10 positions in all
    assert roofline.decode_flops(a, 3, 10) == 3 * 2 * (240 + 40) + 32 * 10


def test_ivf_decode_cost_by_hand():
    flops, nbytes = roofline.ivf_decode_cost(
        q=16, head_blocks=3, block_rows=8, tail_rows=10, d=4, block_q=128,
        tail_tile=32)
    # one query tile of 16; tail tile min(32, 10) -> 16, tail padded to 16
    rows, lp = 3 * 8, 16
    assert flops == 2 * 16 * (rows + lp) * 4
    assert nbytes == (rows * 4 * 2 + rows * 4 + lp * 4 * 2 + 16 * 4 * 2
                      + 3 * 16 * 4 + lp * 16 * 4)
    # two query tiles read everything twice
    _, nb2 = roofline.ivf_decode_cost(q=32, head_blocks=3, block_rows=8,
                                      tail_rows=10, d=4, block_q=16)
    assert nb2 == 2 * nbytes


@pytest.mark.parametrize("flops,nbytes,bound", [(1e9, 1e12, "bytes"),
                                                (1e15, 1e6, "flops")])
def test_share_at_the_bound_reads_100(flops, nbytes, bound):
    pk = roofline.peaks("TPU v5 lite")
    t = max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    share, which = roofline.roofline_share(flops, nbytes, t, pk)
    assert share == pytest.approx(100.0)
    assert which == bound
    assert roofline.roofline_share(flops, nbytes, 2 * t, pk)[0] == \
        pytest.approx(50.0)


def test_peaks_file_names_its_source():
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
