"""Blocks of 4,096 rows the fused program gathered, per select request over
the window.

layer: fused program (index/compiled.py) · source: program_counter
moves: qps
Counter ``fused.blocks_gathered`` (for every fused dispatch read back, the
block capacity of the branch that served it: the first rung of the program's
ladder that holds the blocks its gate kept alive, or the table's blocks
where the whole table was masked) over the observations ``query.features``
gained, ``before`` → ``after``. The gather and the compaction of a select
cost device time in proportion to it; the counter ``fused.blocks_alive``
beside it says how many the gate kept. A program without the counter, as
every one whose pruned branch has one capacity, reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    blocks = _window.counter_delta(ctx, "fused.blocks_gathered")
    requests = _window.timer_delta(ctx, "query.features")
    if blocks is None or requests is None or requests[0] <= 0:
        return None
    return blocks / requests[0]
