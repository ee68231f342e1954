"""The harness end to end at a size the CPU holds: a cell, a mix and a
metric that exist only as files in a temporary directory; a sound run
that comes out correct; and runs with the timed path broken underneath
(the chip check skipped) that must come out not correct."""
import json
import os
import shutil
import time

import numpy as np
import pytest

from bench import check, faults, harness, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")

# Limits for the tiny configuration below, from its own readings on the
# CPU (bf16 program against the float32 reference): logit_err 0.012-0.019,
# logz_rel_err 0.05-0.15 with its 64-row tail sample, greedy_rank 11-24
# (the best of 512 probed rows); the int8 control reads logit_err
# 0.038-0.061 and greedy_rank 0-2; the last retrieved candidate reads
# greedy_rank 78-88, the next lane's token 3,100-3,600.
TINY_LIMITS = {"logit_err": 0.03, "logz_rel_err": 0.5, "greedy_rank": 50,
               "missing": 0, "recompiles": 0, "health_flagged": 0}

COUNT_READER = '''"""Requests due in the window (a metric only this test defines)."""


def read(run):
    return float(len(run.due()))
'''


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A benchmark of one tiny cell, all of it in a temporary directory;
    its mix, cell file and one metric reader exist nowhere else."""
    root = tmp_path_factory.mktemp("bench_root")
    b = os.path.join(str(root), "b")
    shutil.copy(os.path.join(BENCH, "peaks.json"), _mk(b))
    for name in ("ttft_p95_ms", "itl_p95_ms", "tokens_per_s", "setup_s",
                 "logz_rel_err"):
        shutil.copy(os.path.join(BENCH, "metrics", name + ".py"),
                    _mk(os.path.join(b, "metrics")))
    _write(os.path.join(b, "metrics", "due_count.py"), COUNT_READER)
    with open(os.path.join(BENCH, "configs", "qwen1.5-4b.json")) as f:
        conf = json.load(f)
    conf.update(name="tiny", hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, vocab_size=4096)
    conf["serving"].update(n_probe=4, block_rows=128, n_clusters=8, l=64,
                           k=64, n_slots=4, max_len=64)
    _write(os.path.join(b, "configs", "tiny.json"), conf)
    _write(os.path.join(b, "traffic", "short.json"), {
        "name": "short", "loop": "open", "why": "a test's mix",
        "warmup_s": 0.5, "follow_s": 20, "block_s": 1,
        "prompt_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                          "min": 2, "max": 24},
        "answer_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                          "min": 2, "max": 24},
        "token_ids": "uniform", "temperatures": [0.0, 0.8]})
    _write(os.path.join(b, "cells", "tiny.short.json"), {
        "rate_per_s": 4.0, "sample_requests": 4, "trace_s": 1,
        "limits": TINY_LIMITS})
    metric = {"unit": "ms", "better": "lower", "bound": 0.25,
              "source": "host_clock"}
    _write(os.path.join(str(root), "BENCHMARK.json"), {
        "command": ["python3", "b/run.py"], "paths": ["b"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "b/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.short", "config": "tiny",
                       "traffic": "short", "chips": 1, "why": "test"}],
        "end_to_end": [dict(metric, name=n) for n in
                       ("ttft_p95_ms", "itl_p95_ms", "setup_s")]
        + [dict(metric, name="due_count", unit="requests")],
        "per_layer": [{"name": "due_count", "unit": "requests",
                       "better": "higher", "source": "program_counter",
                       "layer": "open loop", "moves": "ttft_p95_ms",
                       "workloads": ["another.cell"]}]})
    return os.path.join(str(root), "BENCHMARK.json")


def _mk(d):
    os.makedirs(d, exist_ok=True)
    return d


def test_cell_mix_and_reader_resolve_from_files_alone(tiny):
    cell = harness.resolve(tiny, "tiny.short")
    assert cell.arch.d == 128 and cell.arch.n_kv == 2
    assert cell.mix["name"] == "short"
    assert cell.cell["rate_per_s"] == 4.0
    assert [m["name"] for m in cell.end_to_end][-1] == "due_count"
    # a per-layer metric that lists only other cells is not this cell's
    assert cell.per_layer == []
    assert cell.reader("due_count")(type("R", (), {
        "due": lambda self: [1, 2, 3]})()) == 3.0
    with pytest.raises(KeyError):
        harness.resolve(tiny, "no.such.cell")


def _run(tiny, seed=2**31 + 3, control=False):
    cell = harness.resolve(tiny, "tiny.short")
    return harness.run_cell(cell, seed, 2.0, False, time.perf_counter(),
                            require_chip=False, log=lambda m: None,
                            control=control)


def test_sound_run_is_correct_and_the_control_is_not(tiny):
    res = _run(tiny, control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s",
                                   "due_count"}
    assert res["metrics"]["due_count"]["value"] == 8.0
    assert list(res)[-1] == "checks"
    # the int8 control, read on the same sample with the run's own counts,
    # goes through the same limits and comes out not correct
    ctl = res["control"]
    assert ctl["correct"] is False
    assert ctl["checks"]["logit_err"]["value"] > TINY_LIMITS["logit_err"]
    assert ctl["checks"]["missing"]["value"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(tiny):
    with faults.planted("token"):
        res = _run(tiny)
    assert not res["correct"]
    assert res["checks"]["logit_err"]["value"] > TINY_LIMITS["logit_err"]


def test_an_estimate_altered_where_it_is_produced_is_not_correct(tiny):
    with faults.planted("log_z"):
        res = _run(tiny)
    assert not res["correct"]
    assert res["checks"]["logz_rel_err"]["value"] > \
        TINY_LIMITS["logz_rel_err"]


@pytest.mark.parametrize("fault", ["wrong_row", "last_candidate"])
def test_a_wrong_choice_of_token_is_not_correct(tiny, fault):
    """The next lane's token and score, or the last retrieved candidate
    with its own score: the logits agree, the choice does not."""
    with faults.planted(fault):
        res = _run(tiny)
    assert not res["correct"]
    assert res["checks"]["greedy_rank"]["value"] > \
        TINY_LIMITS["greedy_rank"]


def test_sample_holds_the_longest_and_is_seeded():
    class R:
        def __init__(self, i, n):
            self.index, self.n_prompt, self.tokens = i, n, [0] * 3
            self.temperature = 0.0 if i % 2 == 0 else 0.8

    done = [R(i, n) for i, n in enumerate([5, 9, 2, 9, 7, 1])]
    a = check.pick_sample(done, 3, seed=11)
    # the longest, then the longest greedy one, then drawn from the seed
    assert [r.index for r in a[:2]] == [1, 4] and len(a) == 3
    assert [r.index for r in a] == \
        [r.index for r in check.pick_sample(done, 3, seed=11)]
    assert [r.index for r in check.pick_sample(done, 1, seed=11)] == [1]
    assert check.pick_sample([], 3, seed=1) == []


def test_reference_int8_control_departs_from_float32():
    """The control's arithmetic differs from the reference's by int8
    rounding, not by a different model."""
    import jax.numpy as jnp
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(64, 32)),
                    jnp.bfloat16)
    y32 = reference.linear(x, w, "f32")
    y8 = reference.linear(x, w, "int8")
    rel = float(jnp.linalg.norm(y8 - y32) / jnp.linalg.norm(y32))
    assert 1e-4 < rel < 0.05


def test_itl_reads_every_gap_that_starts_in_the_window():
    """Gaps are single intervals between a request's tokens; those that
    start outside the window are left out, and one still open when the
    run stopped lasts until the stop."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "itl", os.path.join(BENCH, "metrics", "itl_p95_ms.py"))
    itl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(itl)

    class R:
        def __init__(self, times, done):
            self.token_times, self.done = times, done

    class Run:
        window, stopped = (10.0, 20.0), 30.0
        sent = [R([9.0, 10.5, 11.0, 11.5], 12.0),   # 0.5, 0.5 (9.0 is out)
                R([19.0, 19.2, 21.0], 22.0),        # 0.2, 1.8 (21.0 is out)
                R([15.0], 0.0)]                     # open: 30 - 15
    assert itl.read(Run()) == pytest.approx(
        float(np.percentile([0.5, 0.5, 0.2, 1.8, 15.0], 95)) * 1e3)
    Run.sent = [R([25.0, 26.0], 27.0)]
    assert itl.read(Run()) is None
