"""Batched dispatches the scheduler launched per collector cycle over the
window.

layer: scheduler (serve/scheduler.py) · source: program_counter · moves: qps
Observations the timer ``sched.stage.launch`` gained (one a dispatch) over
those ``sched.stage.plan`` gained (one a cycle), ``before`` → ``after``. A
cycle plans all its requests and then groups them by the bytes of their
windows and residual parameters: a mix with one key launches once a cycle,
one whose requests carry k keys up to k times, each with a cover of its own.
A program without the cycle record reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    launches = _window.timer_delta(ctx, "sched.stage.launch")
    cycles = _window.timer_delta(ctx, "sched.stage.plan")
    if launches is None or cycles is None or cycles[0] <= 0:
        return None
    return launches[0] / cycles[0]
