"""Share of the window the collector thread spent on candidate-block covers.

layer: planner, cover (index/prune.py, curves/) · source: program_counter
moves: qps
``sched.stage.cover``: the cover-cache look-up and, on a miss, the range
decomposition of every planned request."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    return _window.stage_pct(ctx, ("cover",))
