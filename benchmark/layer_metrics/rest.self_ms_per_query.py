"""REST's own milliseconds per count request over the window.

layer: REST (web/server.py) · source: program_counter · moves: p50_ms
The span ``http.request.count`` runs from the parsed request line to the
flushed response; the scheduler's ``query.count`` nests under it. Their
difference is URL and query parsing, routing, the deadline, priority and
tenant look-ups, JSON encoding and the socket write. Accept and thread start
happen before the span and stay unseen."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    http = _window.timer_delta(ctx, "http.request.count")
    inner = _window.timer_delta(ctx, "query.count")
    if http is None or inner is None or http[0] <= 0:
        return None
    return 1000.0 * (http[1] - inner[1]) / http[0]
