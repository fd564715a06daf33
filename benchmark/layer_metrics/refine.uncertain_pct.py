"""Candidate ways the pool kernel left uncertain, in percent.

layer: staged kernels (index/scan.py) · source: program_counter
moves: p50_ms
Counters ``refine.ways_uncertain`` over ``refine.ways_candidate``,
``before`` → ``after``: of the ways whose envelope met the polygon's, those
the device could neither count nor drop in f32 and the host refined in f64.
A program without the pool has no such counters and reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    uncertain = _window.counter_delta(ctx, "refine.ways_uncertain")
    candidates = _window.counter_delta(ctx, "refine.ways_candidate")
    if uncertain is None or not candidates:
        return None
    return 100.0 * uncertain / candidates
