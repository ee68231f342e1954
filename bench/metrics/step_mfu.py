"""Model FLOPs of the lane-positions the window advanced, over the
window's seconds times the chip's bf16 peak. Counted from shapes as an
exact model needs them: 2 per trunk parameter for every busy lane, 2 V d
for the head of every lane that emitted a token, and attention over each
lane's live positions -- whatever estimator or kernel served them. Time
the benchmark spent starting or stopping the profiler is not the
window's."""
from bench.roofline import decode_flops


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    a = run.cell.arch
    trunk = 2.0 * a.trunk_params()
    head = 2.0 * a.vocab * a.d
    attn = decode_flops(a, 0, 1)
    flops = sum(s.n_active * trunk + s.n_emitted * head + s.attended * attn
                for s in steps)
    lo, hi = run.window
    seconds = (hi - lo) - sum(s.paused_s for s in steps)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
