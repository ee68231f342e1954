"""The traffic generator, and the harness's refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

with open(os.path.join(ROOT, "bench", "traffic", "chat.json")) as f:
    CHAT = json.load(f)


def _sched(seed, rate=1.6, seconds=30.0, vocab=151936):
    return traffic.schedule(CHAT, rate, seconds, seed, vocab)


def _key(p):
    return (p.segment, round(p.at_s, 9), tuple(p.prompt.tolist()),
            p.answer_tokens, p.temperature, tuple(p.key.tolist()))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_schedule(seed):
    a, b = _sched(seed), _sched(seed)
    assert [_key(p) for p in a] == [_key(p) for p in b]


def test_different_seed_different_schedule():
    a, b = _sched(1), _sched(2)
    assert [p.at_s for p in a] != [p.at_s for p in b]
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert [p.answer_tokens for p in a] != [p.answer_tokens for p in b]
    assert [tuple(p.key) for p in a] != [tuple(p.key) for p in b]


def test_every_seed_gets_the_same_work():
    """Each segment holds the same lengths and gaps for every seed, in
    another order."""
    for seg in ("warmup", "window", "follow"):
        sets = []
        for seed in (3, 4):
            ps = [p for p in _sched(seed) if p.segment == seg]
            sets.append((sorted(len(p.prompt) for p in ps),
                         sorted(p.answer_tokens for p in ps), len(ps)))
        assert sets[0] == sets[1]


def test_each_block_of_a_segment_holds_the_same_mix_for_every_seed():
    """Sizes are dealt over blocks of block_s seconds: a block's lengths
    are the same for every seed; only their order in it differs."""
    def blocks(seed):
        w = [p for p in _sched(seed, rate=1.6, seconds=51.0)
             if p.segment == "window"]
        nb = round(51.0 / CHAT["block_s"])
        sizes = [0] * nb                  # as the deal fills the blocks
        for i in range(len(w)):
            lap, k = divmod(i, nb)
            sizes[k if lap % 2 == 0 else nb - 1 - k] += 1
        edges = np.cumsum([0] + sizes)
        return [(sorted(p.answer_tokens for p in w[a:b]),
                 sorted(len(p.prompt) for p in w[a:b]))
                for a, b in zip(edges, edges[1:])]

    a, b = blocks(8), blocks(9)
    assert a == b
    means = [np.mean(ans) for ans, _ in a]
    assert max(means) / min(means) < 1.2


def test_lengths_stay_inside_their_clips_and_arrivals_in_order():
    ps = _sched(5, rate=3.0, seconds=51.0)
    pt, at = CHAT["prompt_tokens"], CHAT["answer_tokens"]
    assert all(pt["min"] <= len(p.prompt) <= pt["max"] for p in ps)
    assert all(at["min"] <= p.answer_tokens <= at["max"] for p in ps)
    assert all(0 <= p.prompt.min() and p.prompt.max() < 151936 for p in ps)
    times = [p.at_s for p in ps]
    assert times == sorted(times)
    w = [p for p in ps if p.segment == "window"]
    assert len(w) == round(3.0 * 51.0)
    lo = CHAT["warmup_s"]
    assert lo <= w[0].at_s and w[-1].at_s < lo + 51.0


def test_half_greedy_and_medians_near_the_mix():
    ps = _sched(6, rate=3.0, seconds=51.0)
    temps = [p.temperature for p in ps]
    assert abs(temps.count(0.0) - len(temps) / 2) <= 1
    med = np.median([len(p.prompt) for p in ps])
    assert abs(med - CHAT["prompt_tokens"]["median"]) <= 3


def test_harness_without_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "qwen1.5-4b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
