"""``join.slab_share_pct``, the join kernel's reader of what share of its
tests the pairs' own slabs need, on hand-made pages: the ratio where the
program counts ``join.slab_tests``, None where it does not (a program from
before the counter) or where the window ran no join. A second on the CPU; by
hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_join_slab_reader.py -q
"""

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

NAME = "join.slab_share_pct"
CELL = "gdelt-countries-10m.join-c4"


def make_ctx():
    """A window whose joins ran 1.2e12 edge tests, 8.4e11 of them the tests
    their pairs' slabs need."""
    before = {"timers": {}, "counters": {"join.edge_tests": 3.0e11,
                                         "join.slab_tests": 2.0e11}}
    after = {"timers": {}, "counters": {"join.edge_tests": 1.5e12,
                                        "join.slab_tests": 1.04e12}}
    return {"seconds": 50.0, "before": {"/metrics": before},
            "after": {"/metrics": after}}


def read(ctx):
    return run.load_module("layer_metrics", NAME).read(ctx)


def test_reader_computes_the_share():
    assert read(make_ctx()) == pytest.approx(100 * 8.4e11 / 1.2e12,
                                             rel=1e-12)


def test_a_program_without_the_counter_reads_none():
    ctx = make_ctx()
    for page in ("before", "after"):
        del ctx[page]["/metrics"]["counters"]["join.slab_tests"]
    assert read(ctx) is None


def test_a_window_without_a_join_reads_none():
    ctx = make_ctx()
    ctx["after"] = copy.deepcopy(ctx["before"])
    assert read(ctx) is None


def test_benchmark_json_names_the_reader_for_the_join_cell():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry["workloads"] == [CELL]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "higher", "program_counter",
                                "join kernel", "qps")
    assert os.path.exists(os.path.join(HERE, "layer_metrics", NAME + ".py"))
