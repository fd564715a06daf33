"""Milliseconds a join spent in the grouped point-in-polygon kernel, launch
to the per-pair results read back, per join request over the window.

layer: join kernel (index/scan.py) · source: program_counter · moves: p50_ms
Seconds the timer ``join.device`` gained (the span of that name under the
store's root ``query.join``: host time on the request's thread from the
first launch to the last read-back's return, so it holds the wait for the
device, for the joins of other requests queued on it and for the interpreter
lock) over the observations ``query.join`` gained, ``before`` → ``after``. A
program without the span reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _per_query  # noqa: E402


def read(ctx: dict):
    return _per_query.stage_ms(ctx, "join.device", per="query.join")
