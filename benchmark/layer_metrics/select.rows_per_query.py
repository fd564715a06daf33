"""Features a select answered with, per request over the window.

layer: REST (web/server.py) · source: program_counter · moves: p50_ms
Counter ``http.features.rows`` (rows of every ``GET /features`` result as
the route hands them to the GeoJSON export) over the observations
``query.features`` gained, ``before`` → ``after``: what the traffic asked
the serializer for. A program without the counter reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    rows = _window.counter_delta(ctx, "http.features.rows")
    requests = _window.timer_delta(ctx, "query.features")
    if rows is None or requests is None or requests[0] <= 0:
        return None
    return rows / requests[0]
