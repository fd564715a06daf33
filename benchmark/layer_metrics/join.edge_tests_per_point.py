"""Point × edge tests the join's kernel ran for every row it gathered: what
the gate lets through.

layer: join kernel (index/prune.py's gate, index/scan.py) · source:
program_counter · moves: qps
Counters ``join.edge_tests`` (rows of a tile × the segments its pair reads
from the pool, the bucket's pad too, summed over the pairs the kernel ran)
over ``join.points_scanned`` (rows of the tiles it gathered), ``before`` →
``after``. A brute force reads the polygon table's segments here (~200,000);
a gate that paired every tile with the polygons that hold its points alone
would read a polygon or two's. A program without the counters reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    tests = _window.counter_delta(ctx, "join.edge_tests")
    points = _window.counter_delta(ctx, "join.points_scanned")
    if tests is None or not points:
        return None
    return tests / points
