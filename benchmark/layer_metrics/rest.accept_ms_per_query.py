"""A request's milliseconds from the listener's accept to its parsed request.

layer: REST (web/server.py) · source: program_counter · moves: p50_ms
The flat timer ``http.accept`` runs from the instant ``accept()`` returned on
the listener thread (``BacklogHTTPServer.process_request``) to the parsed
request line and headers, just before ``http.request.<family>`` opens: the
handler thread's creation and start, its first turn at the interpreter lock
and the header read. Its window's seconds over its observations; a program
without the timer reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    return _window.mean_ms(ctx, "http.accept")
