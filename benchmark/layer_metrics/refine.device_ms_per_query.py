"""Milliseconds a polygon count spent in the pool kernel, launch to the
classification read back, per count request over the window.

layer: staged kernels (index/scan.py) · source: program_counter
moves: p50_ms
Seconds the timer ``refine.device`` gained (the span of that name under the
request's ``scan`` leaf: host time from the launch to the read-back's
return, so it holds the wait for the device and for the interpreter lock)
over the observations ``query.count`` gained, ``before`` → ``after``. A
program without the span reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _per_query  # noqa: E402


def read(ctx: dict):
    return _per_query.stage_ms(ctx, "refine.device")
