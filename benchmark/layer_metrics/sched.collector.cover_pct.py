"""Share of the window the collector thread spent on candidate-block covers.

layer: planner, cover (index/prune.py, curves/) · source: program_counter
moves: qps
``sched.stage.cover``: one cover a group of the cycle (``_cover_group``: the
range decomposition of all the group's boxes together and their candidate
blocks); a lone repeated plan keeps its own and costs nothing here."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    return _window.stage_pct(ctx, ("cover",))
