"""Mean milliseconds a request waited from submit until its batch closed.

layer: scheduler (serve/scheduler.py) · source: program_counter
moves: p50_ms
Timer ``queue_wait``: none of its neighbours' planning is in it. A request
that arrives while the collector is busy with the batch before waits that
batch out here."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    return _window.mean_ms(ctx, "queue_wait")
