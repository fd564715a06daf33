"""Share of the window the collector thread spent waiting for requests.

layer: scheduler (serve/scheduler.py) · source: program_counter · moves: qps
``sched.stage.idle`` (blocked in queue.get() with nothing queued) plus
``sched.stage.window`` (first request until the batch closed). High: the
bottleneck is above the scheduler. Low: the collector is the serial thread
every request waits for."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    return _window.stage_pct(ctx, ("idle", "window"))
