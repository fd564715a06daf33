"""Milliseconds a select spent on its device program, launch to the result
read back, per select request over the window.

layer: fused program (index/compiled.py) · source: program_counter
moves: p50_ms
Seconds the timers ``device_scan`` + ``device_wait`` gained
(``trace.device_fetch``: the host's enqueue of the dispatch, then the wait
until its result is ready; in this cell every fetch is the fused select)
over the observations ``query.features`` gained, ``before`` → ``after``. It
is host time on the request's thread, so it holds the device's work and the
thread's wait for the interpreter lock once the result is ready, and reads
above the device time a select of the trace's breakdown. A program without
either timer, or a window without a select, reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _per_query  # noqa: E402


def read(ctx: dict):
    scan = _per_query.stage_ms(ctx, "device_scan", per="query.features")
    wait = _per_query.stage_ms(ctx, "device_wait", per="query.features")
    if scan is None or wait is None:
        return None
    return scan + wait
