"""A request's milliseconds outside the server: the client's mean latency
less the server's mean from accept to the flushed response.

layer: REST (web/server.py) · source: program_counter · moves: p50_ms
The client's side is the mean send-to-parsed-body latency of the window's good
requests (``ctx["requests"]``); the server's is ``rest.accept_ms_per_query``
plus the mean of the ``http.request.*`` timer that gained the most
observations in the window (the cell's own route). What is left is the
connect and the listen backlog before ``accept()``, the bytes on loopback and
the client's own work on the answer, its thread's turns at the lock among
them. A program without the timers reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402

PREFIX = "http.request."


def read(ctx: dict):
    lat = [done - sent for sent, done, good in ctx["requests"] if good]
    accept_ms = _window.mean_ms(ctx, "http.accept")
    routes = [name for name in ctx["after"]["/metrics"]["timers"]
              if name.startswith(PREFIX)]
    gained = {name: _window.timer_delta(ctx, name) for name in routes}
    route = max(gained, key=lambda n: gained[n][0], default=None)
    if not lat or accept_ms is None or route is None \
            or gained[route][0] <= 0:
        return None
    return 1000.0 * sum(lat) / len(lat) - accept_ms \
        - _window.mean_ms(ctx, route)
