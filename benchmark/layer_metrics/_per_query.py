"""A stage's milliseconds per request over the window: shared by the readers
of the extent refine and of the select route. No metric of its own (no entry
in BENCHMARK.json names it). A program without the stage's timer, or a
window without a request, reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def stage_ms(ctx: dict, stage: str, per: str = "query.count"):
    """Seconds the timer ``stage`` gained over the observations the timer
    ``per`` gained, ``before`` → ``after``, in milliseconds."""
    gained = _window.timer_delta(ctx, stage)
    requests = _window.timer_delta(ctx, per)
    if gained is None or requests is None or requests[0] <= 0:
        return None
    return 1000.0 * gained[1] / requests[0]
