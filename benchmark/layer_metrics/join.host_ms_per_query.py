"""Milliseconds of a join outside the kernel, per join request over the
window: plan, cover, gate, the f64 refine of the uncertain pairs, the answer.

layer: planner, cover (index/planner.py, index/prune.py,
filter/geom_batch.py) · source: program_counter · moves: p50_ms
Seconds the timer ``query.join`` gained less those ``join.device`` gained,
over the observations ``query.join`` gained, ``before`` → ``after``. Host
time on the request's thread, the wait for the interpreter lock in it. A
program without either timer reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    whole = _window.timer_delta(ctx, "query.join")
    device = _window.timer_delta(ctx, "join.device")
    if whole is None or device is None or whole[0] <= 0:
        return None
    return 1000.0 * (whole[1] - device[1]) / whole[0]
