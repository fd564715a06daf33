"""Share of the window the collector thread spent planning.

layer: planner, cover (index/planner.py) · source: program_counter
moves: qps
``sched.stage.plan``: strategy selection and the auths fold of every
plan-cache miss, one request after another."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    return _window.stage_pct(ctx, ("plan",))
