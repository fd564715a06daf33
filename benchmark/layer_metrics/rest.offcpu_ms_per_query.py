"""A request's milliseconds on the server that its handler thread wanted a
CPU and did not have one: the interpreter lock, the OS.

layer: REST (web/server.py) · source: program_counter · moves: p50_ms
Each request adds to the counter ``http.offcpu_us`` its wall time from accept
to the flushed response, less its thread's CPU time and less its waits by
design (``trace.waiting``: a scheduled result up to the instant it was
resolved, the device's read-backs, a request body). The window's gain over
the requests ``http.accept`` observed in it; a program without the counter
or the timer reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    off_us = _window.counter_delta(ctx, "http.offcpu_us")
    accepted = _window.timer_delta(ctx, "http.accept")
    if off_us is None or accepted is None or accepted[0] <= 0:
        return None
    return off_us / accepted[0] / 1000.0
