"""The chip's peaks, and the operations and bytes that the work needs,
counted from shapes.

Peaks come from ``peaks.json``, keyed by ``device_kind``; a device that is
not there is an error, never a default. The counts are what an exact
computation of the same result needs, whatever kernel or estimator serves
it, so a cheaper estimate cannot flatter them.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(KeyError):
    pass


def peaks(kind: str, path: str = os.path.join(HERE, "peaks.json")) -> Dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise UnknownDevice(f"device kind {kind!r} is not in {path}; "
                            f"known: {sorted(table)}")
    return table[kind]


def decode_flops(arch, lanes: int, attended: int) -> float:
    """Operations of ``lanes`` one-token decode positions of an exact
    model: 2 per matrix parameter of the trunk and of the head, plus
    attention's q.k and p.v over ``attended`` cached positions summed over
    the lanes, in every layer."""
    matrix = 2.0 * (arch.trunk_params() + arch.vocab * arch.d)
    attn = 4.0 * arch.n_layers * arch.n_heads * arch.head_dim
    return lanes * matrix + attn * attended


def ivf_decode_cost(*, q: int, head_blocks: int, block_rows: int,
                    tail_rows: int, d: int, w_bytes: int = 2,
                    h_bytes: int = 2, block_q: int = 128,
                    tail_tile: int = 32) -> Tuple[float, float]:
    """(operations, bytes) of one ``ivf_decode`` call: each query tile
    scores ``head_blocks`` probed blocks and the shared tail sample.

    Bytes are what the kernel must read: the probed blocks and their row
    masks (f32), the tail rows and their (Q, l) accept mask (f32), the
    queries and the (Q, U) membership mask, once per query tile, plus its
    outputs. Operations are 2 per query per scored row per dimension."""
    bq = min(block_q, max(8, q))
    qp = math.ceil(q / bq) * bq
    tiles = qp // bq
    tt = math.ceil(min(tail_tile, tail_rows) / 8) * 8
    lp = math.ceil(tail_rows / tt) * tt
    rows = head_blocks * block_rows
    per_tile = (rows * d * w_bytes + rows * 4 + lp * d * w_bytes
                + bq * d * h_bytes + head_blocks * bq * 4 + lp * bq * 4)
    flops = 2.0 * qp * (rows + lp) * d
    return flops, float(tiles * per_tile)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   pk: Dict) -> Tuple[float, str]:
    """Share (%) of the least time the chip needs for the work, and which
    peak bounds it."""
    t_flops = flops / pk["bf16_flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
