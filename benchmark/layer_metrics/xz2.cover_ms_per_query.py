"""Milliseconds of XZ2 range decomposition per count request over the window.

layer: planner, cover (curves/xz.py, index/prune.py) · source: program_counter
moves: p50_ms
Seconds the timer ``range_decompose`` gained (a lone plan's cover:
``XZSFC.ranges_arrays``, a level of the tree a numpy step since PR 31, and
the ranges' search in the sorted codes, on the completer thread) over the
observations ``query.count`` gained, ``before`` → ``after``. A program
without the timer reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _per_query  # noqa: E402


def read(ctx: dict):
    return _per_query.stage_ms(ctx, "range_decompose")
