"""Share of the window's plans that were bound, not planned.

layer: planner, cover (index/bind.py at serve/scheduler.py
``_plan_request``) · source: program_counter · moves: qps
Counters ``sched.plan.bound`` (a request whose box, interval and constants
were bound into the plan its filter shape made the first time) and
``sched.plan.full`` (a request the planner planned: ``_plan``), ``before``
→ ``after``: 100 × bound / (bound + full). A request served by the exact
key of the plan cache ticks neither. A program without the counters, as
one from before the bind, reads None; so does a window that planned
nothing."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    bound = _window.counter_delta(ctx, "sched.plan.bound")
    full = _window.counter_delta(ctx, "sched.plan.full")
    if bound is None and full is None:
        return None
    total = (bound or 0) + (full or 0)
    return 100.0 * (bound or 0) / total if total > 0 else None
