"""Host refine milliseconds per count request over the window.

layer: planner, cover (index/planner.py, filter/geom_batch.py)
source: program_counter · moves: p50_ms
Seconds the timer ``refine`` gained (the exact f64 refine of the ways the
pool kernel left uncertain, on the completer thread; ``rows`` of the span =
those ways) over the observations ``query.count`` gained, ``before`` →
``after``. Beside ``refine.uncertain_pct`` it says whether the host refines
the sliver or every candidate: a store without the pool feeds the same timer
with the whole candidate set. A program without the timer reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _per_query  # noqa: E402


def read(ctx: dict):
    return _per_query.stage_ms(ctx, "refine")
