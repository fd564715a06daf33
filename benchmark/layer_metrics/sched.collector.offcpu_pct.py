"""Share of the planning loop's wall time its thread was not on a CPU.

layer: scheduler (serve/scheduler.py) · source: program_counter · moves: qps
The loop (plan + cover + group) is Python and numpy, nothing that should
sleep: its wall minus the thread's CPU time (``sched.plan_loop_cpu_us``,
time.thread_time over the loop) is time it wanted to run and did not: the
interpreter lock held by ~130 other threads, the OS."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    wall = _window.stage_seconds(ctx, ("plan", "cover", "group"))
    cpu_us = _window.counter_delta(ctx, "sched.plan_loop_cpu_us")
    if not wall or cpu_us is None:
        return None
    return 100.0 * (1.0 - cpu_us / 1e6 / wall)
