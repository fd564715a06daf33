"""(Point, polygon) pairs the join's kernel left uncertain, in percent.

layer: join kernel (index/scan.py) · source: program_counter · moves: p50_ms
Counters ``join.pairs_uncertain`` over ``join.point_pairs``, ``before`` →
``after``: of the pairs the kernel classified (the rows that pass the filter
× the polygons their tile was paired with), those the f32 certainty band
could call neither inside nor outside and the host settled in f64. A program
without the counters reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    uncertain = _window.counter_delta(ctx, "join.pairs_uncertain")
    pairs = _window.counter_delta(ctx, "join.point_pairs")
    if uncertain is None or not pairs:
        return None
    return 100.0 * uncertain / pairs
