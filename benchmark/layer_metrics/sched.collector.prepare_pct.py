"""Share of the window the collector spent between planning and the device.

layer: scheduler (serve/scheduler.py) · source: program_counter · moves: qps
``sched.stage.group`` (group keys and the planning loop's own checks),
``union`` (np.unique over the batch's blocks), ``prepare`` (padding and
the host→device puts) and ``launch`` (the dispatch call)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    return _window.stage_pct(ctx, ("group", "union", "prepare", "launch"))
