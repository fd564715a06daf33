"""``select.whole_columns_pct``, the GeoJSON writer's reader of the share of
columns it encoded as a whole, on hand-made pages: the share where the
program counts both ``export.geojson.*`` counters, None where it does not (a
program from before the writer) or where the window wrote no GeoJSON. A
second on the CPU; by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_select_columns_reader.py -q
"""

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

NAME = "select.whole_columns_pct"
CELL = "gdelt-z3-10m.select-c8"


def make_ctx():
    """A window in which 300 exports wrote 4,200 columns whole and 60 value
    by value."""
    before = {"timers": {}, "counters": {
        "export.geojson.columns_whole": 1400,
        "export.geojson.columns_by_value": 20}}
    after = {"timers": {}, "counters": {
        "export.geojson.columns_whole": 5600,
        "export.geojson.columns_by_value": 80}}
    return {"seconds": 50.0, "before": {"/metrics": before},
            "after": {"/metrics": after}}


def read(ctx):
    return run.load_module("layer_metrics", NAME).read(ctx)


def test_reader_computes_the_share():
    assert read(make_ctx()) == pytest.approx(100 * 4200 / 4260, rel=1e-12)


def test_a_program_without_the_counters_reads_none():
    ctx = make_ctx()
    for page in ("before", "after"):
        ctx[page]["/metrics"]["counters"].clear()
    assert read(ctx) is None


def test_a_window_without_geojson_reads_none():
    ctx = make_ctx()
    ctx["after"] = copy.deepcopy(ctx["before"])
    assert read(ctx) is None


def test_benchmark_json_names_the_reader_for_the_select_cell():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry["workloads"] == [CELL]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "higher", "program_counter", "REST",
                                "qps")
    assert os.path.exists(os.path.join(HERE, "layer_metrics", NAME + ".py"))
