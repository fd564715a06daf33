"""The two readers PR 29 added for the fused program, against made pages and
the parent's.

``select.device_ms_per_query`` reads two timers the parent has too, so the
parent's side reads a number; ``select.blocks_gathered_per_query`` reads the
counter ``fused.blocks_gathered``, which only a program with the ladder of
block capacities has, so the parent's pages (``parent_pages.json``: what the
commit before PR 28's program said of itself under three selects) read None.
Neither raises on a page that lacks what it reads. Run by hand, as the rest
of this directory (a second):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_select_device_readers.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

SELECT = "gdelt-z3-10m.select-c8"
DEVICE_MS = "select.device_ms_per_query"
BLOCKS = "select.blocks_gathered_per_query"
BENCH = run.load_json(os.path.dirname(HERE), "BENCHMARK.json")
with open(os.path.join(HERE, "tests", "parent_pages.json")) as f:
    PARENT = json.load(f)


def read(name: str, ctx: dict):
    return run.load_module("layer_metrics", name).read(ctx)


def made_ctx(timers0: dict, timers1: dict, counters0: dict,
             counters1: dict) -> dict:
    """A ctx whose ``/metrics`` pages hold these timers ({name: (count,
    total_s)}) and counters at the window's edges."""
    def page(timers, counters):
        return {"/metrics": {
            "timers": {k: {"count": c, "total_s": s}
                       for k, (c, s) in timers.items()},
            "counters": dict(counters)}}
    return {"seconds": 51.0, "before": page(timers0, counters0),
            "after": page(timers1, counters1)}


BEFORE = {"device_scan": (10, 0.010), "device_wait": (10, 0.440),
          "query.features": (10, 0.9)}
AFTER = {"device_scan": (110, 0.060), "device_wait": (110, 1.190),
         "query.features": (110, 4.0)}


def test_both_are_entries_of_the_select_cell():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert entries[DEVICE_MS]["moves"] == "p50_ms"
    assert entries[BLOCKS]["moves"] == "qps"
    for name in (DEVICE_MS, BLOCKS):
        assert entries[name]["workloads"] == [SELECT]
        assert entries[name]["layer"] == "fused program"
        assert entries[name]["source"] == "program_counter"


def test_made_pages_read_the_window_s_gain_per_select():
    ctx = made_ctx(BEFORE, AFTER, {"fused.blocks_gathered": 10_240},
                   {"fused.blocks_gathered": 10_240 + 100 * 112})
    # (0.050 + 0.750) s over 100 selects; 11,200 blocks over 100 selects
    assert read(DEVICE_MS, ctx) == pytest.approx(8.0)
    assert read(BLOCKS, ctx) == pytest.approx(112.0)


@pytest.mark.parametrize("absent", ["device_scan", "device_wait"])
def test_one_timer_absent_reads_none(absent):
    after = {k: v for k, v in AFTER.items() if k != absent}
    assert read(DEVICE_MS, made_ctx(BEFORE, after, {}, {})) is None


@pytest.mark.parametrize("after", [
    {k: v for k, v in AFTER.items() if k != "query.features"},
    dict(AFTER, **{"query.features": BEFORE["query.features"]})],
    ids=["no such timer", "no observation inside the window"])
def test_no_select_in_the_window_reads_none(after):
    ctx = made_ctx(BEFORE, after, {"fused.blocks_gathered": 0},
                   {"fused.blocks_gathered": 512})
    assert read(DEVICE_MS, ctx) is None
    assert read(BLOCKS, ctx) is None


def test_counter_absent_reads_none_for_the_blocks_alone():
    ctx = made_ctx(BEFORE, AFTER, {"fused.queries": 10},
                   {"fused.queries": 110})
    assert read(BLOCKS, ctx) is None
    assert read(DEVICE_MS, ctx) == pytest.approx(8.0)


def test_the_parent_s_pages():
    """What the parent's server said under three selects: the timers are
    there, the counter is not."""
    pages = PARENT[SELECT]
    assert "fused.blocks_gathered" not in pages["after"]["/metrics"][
        "counters"]
    ctx = {"seconds": 51.0, "before": pages["before"],
           "after": pages["after"]}
    assert read(BLOCKS, ctx) is None
    value = read(DEVICE_MS, ctx)
    assert isinstance(value, float) and value > 0.0
    # the other cell's pages hold no select at all: None, nothing raised
    osm = PARENT["osm-xz2-10m.intersects-c8"]
    ctx = {"seconds": 51.0, "before": osm["before"], "after": osm["after"]}
    assert read(BLOCKS, ctx) is None and read(DEVICE_MS, ctx) is None
