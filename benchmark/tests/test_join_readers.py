"""The readers PR 32 adds, the five of the served join and
``sched.dispatches_per_cycle``, on hand-made pages: what each computes, and
None where there is nothing to read (a program from before the join, a
window without one, a run without a trace). A second on the CPU; by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_join_readers.py -q
"""

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

JOIN = ("join.device_ms_per_query", "join.host_ms_per_query",
        "join.edge_tests_per_point", "join.uncertain_pct",
        "join_pip_roofline")
CELL = "gdelt-countries-10m.join-c4"
WINDOWS = "gdelt-z3-10m.count-windows-c64"


def timer(count, total_s):
    return {"count": count, "total_s": total_s}


def make_ctx():
    """A 50 s window of 200 joins: 150 s in the kernel's span and 170 s in
    the store's root over the four clients; 4.0e8 rows gathered, 1.2e12 edge
    tests, 8.0e9 pairs classified of which 4.0e6 stayed uncertain, 6.0e10
    segments read; the device busy 2.7 s of a 3 s slice. And 100 collector
    cycles that launched 700 dispatches."""
    before = {"timers": {"query.join": timer(50, 40.0),
                         "join.device": timer(50, 35.0),
                         "sched.stage.plan": timer(10, 1.0),
                         "sched.stage.launch": timer(30, 1.0)},
              "counters": {"join.points_scanned": 1.0e8,
                           "join.edge_tests": 3.0e11,
                           "join.point_pairs": 2.0e9,
                           "join.pairs_uncertain": 1.0e6,
                           "join.segments_read": 1.0e10}}
    after = {"timers": {"query.join": timer(250, 210.0),
                        "join.device": timer(250, 185.0),
                        "sched.stage.plan": timer(110, 9.0),
                        "sched.stage.launch": timer(730, 3.0)},
             "counters": {"join.points_scanned": 5.0e8,
                          "join.edge_tests": 1.5e12,
                          "join.point_pairs": 1.0e10,
                          "join.pairs_uncertain": 5.0e6,
                          "join.segments_read": 7.0e10}}
    config = run.load_json(run.ROOT, "benchmark", "configs",
                           "gdelt-countries-10m.json")
    return {"seconds": 50.0, "config": config,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"busy_s": 2.7, "window_s": 3.0},
            "before": {"/metrics": before}, "after": {"/metrics": after}}


def read(name, ctx):
    return run.load_module("layer_metrics", name).read(ctx)


# bytes the window's joins needed: 4.0e8 rows x 20 B + 6.0e10 segments x 16 B
NEED = 4.0e8 * 20 + 6.0e10 * 16


@pytest.mark.parametrize("name,want", [
    ("join.device_ms_per_query", 1000 * 150.0 / 200),
    ("join.host_ms_per_query", 1000 * (170.0 - 150.0) / 200),
    ("join.edge_tests_per_point", 1.2e12 / 4.0e8),
    ("join.uncertain_pct", 100 * 4.0e6 / 8.0e9),
    ("join_pip_roofline", 100 * (NEED / 50.0 / 819e9) / 0.9),
    ("sched.dispatches_per_cycle", 700 / 100),
])
def test_reader_computes(name, want):
    assert read(name, make_ctx()) == pytest.approx(want, rel=1e-12)


def test_the_roofline_counts_the_configurations_bytes():
    mod = run.load_module("layer_metrics", "join_pip_roofline")
    cfg = make_ctx()["config"]
    assert mod.bytes_needed(10, 3, cfg["predicate_plane_bytes"],
                            cfg["refine_plane_bytes"]) == 10 * 20 + 3 * 16
    assert 0 < read("join_pip_roofline", make_ctx()) <= 100


@pytest.mark.parametrize("name", JOIN + ("sched.dispatches_per_cycle",))
def test_a_program_without_the_names_reads_none(name):
    ctx = make_ctx()
    for page in ("before", "after"):
        ctx[page]["/metrics"] = {"timers": {"query.count": timer(5, 1.0)},
                                 "counters": {}}
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", JOIN + ("sched.dispatches_per_cycle",))
def test_a_window_without_a_request_reads_none(name):
    ctx = make_ctx()
    ctx["after"] = copy.deepcopy(ctx["before"])
    assert read(name, ctx) is None


def test_the_roofline_needs_a_trace_and_the_configurations_bytes():
    ctx = make_ctx()
    ctx["trace"] = None
    assert read("join_pip_roofline", ctx) is None
    ctx = make_ctx()
    del ctx["config"]["refine_plane_bytes"]
    assert read("join_pip_roofline", ctx) is None


def test_benchmark_json_names_the_readers_for_their_cells():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in JOIN:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           name + ".py"))
    assert by_name["sched.dispatches_per_cycle"]["workloads"] == [WINDOWS]
    assert by_name["join_pip_roofline"]["source"] == "device_trace"
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == cells[WINDOWS]["chips"] == 1
    json.dumps(bench)
