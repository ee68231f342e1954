"""Host time per scheduler step: from one step's readback to the next
step's dispatch (the scheduler's own stamps), which holds the open loop's
admissions and the scheduler's bookkeeping, summed over the window's
steps and divided by their count. Time the loop slept with no lane busy,
or spent starting and stopping the profiler, is left out."""


def read(run):
    steps = run.window_steps()
    total, n = 0.0, 0
    for prev, cur in zip(steps, steps[1:]):
        total += (cur.t_dispatch - prev.t_device_done) - cur.idle_s \
            - cur.paused_s
        n += 1
    return total / n * 1e3 if n else None
