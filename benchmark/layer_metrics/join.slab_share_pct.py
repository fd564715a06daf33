"""Of the point × edge tests the join's kernel ran, the share its pairs' own
slabs need, in percent: what the rest reads is the bucket's pad.

layer: join kernel (index/scan.py, index/device.py's ``__segy__``) · source:
program_counter · moves: qps
Counters ``join.slab_tests`` (rows of a tile × the segments of its pair's
span, summed over the pairs the kernel ran) over ``join.edge_tests`` (rows of
a tile × the width of the bucket its pair ran in), ``before`` → ``after``. A
read in whole chunks from the one that holds the span's first segment, and
the rounding up to a bucket's width, are the difference. A program without
the counters reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    slab = _window.counter_delta(ctx, "join.slab_tests")
    tests = _window.counter_delta(ctx, "join.edge_tests")
    if slab is None or not tests:
        return None
    return 100.0 * slab / tests
