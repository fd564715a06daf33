"""REST's milliseconds per select around the store's query: GeoJSON of the
rows and the response.

layer: REST (web/server.py, io/export.py) · source: program_counter
moves: p50_ms
The flat timer ``http.request.types`` runs from the parsed request line to
the flushed response of ``GET /types/<t>/features``; the store's root
``query.features`` (plan, device select, hydration of the rows) nests in its
interval. Their difference over the window, per request, is the GeoJSON
export of the 14-attribute record (a Python loop a feature), its re-parse,
and ``http.respond`` (JSON encoding and the socket write). The ``serialize``
span is not in it: only a query without hints makes one, and this route's
``limit`` hydrates inside ``query.features``. A program without either
timer reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    http = _window.timer_delta(ctx, "http.request.types")
    inner = _window.timer_delta(ctx, "query.features")
    if http is None or inner is None or http[0] <= 0:
        return None
    return 1000.0 * (http[1] - inner[1]) / http[0]
