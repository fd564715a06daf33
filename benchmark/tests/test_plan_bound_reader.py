"""``sched.plan.bound_pct`` (PR 35) on a parent-shaped run and on made pages.

The driver runs the parent commit with this PR's ``benchmark/`` laid over it,
traced too: on the parent's pages (``parent_pages.json``: no ``sched.plan.*``
counter) the reader has to read None and raise nothing. A second on the CPU;
needs no chip:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_plan_bound_reader.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

NAME = "sched.plan.bound_pct"
with open(os.path.join(HERE, "tests", "parent_pages.json")) as f:
    PARENT = json.load(f)


def read(ctx):
    return run.load_module("layer_metrics", NAME).read(ctx)


def pages(before: dict, after: dict) -> dict:
    return {"seconds": 51.0,
            "before": {"/metrics": {"timers": {}, "counters": before}},
            "after": {"/metrics": {"timers": {}, "counters": after}}}


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_the_parent_has_no_counters_and_reads_none(cell):
    counters = PARENT[cell]["after"]["/metrics"]["counters"]
    assert not any(k.startswith("sched.plan.") for k in counters)
    assert read({"seconds": 51.0, **PARENT[cell]}) is None


@pytest.mark.parametrize("before,after,want", [
    # warm-up planned the shape once and bound 900; the window bound 13,986
    # and planned 14 (an IN list of another size, say)
    ({"sched.plan.bound": 900, "sched.plan.full": 1},
     {"sched.plan.bound": 14_886, "sched.plan.full": 15,
      "sched.plan.bind_failed": 14}, 99.9),
    # a cell of singles: every request planned
    ({"sched.plan.full": 10}, {"sched.plan.full": 3_410}, 0.0),
    # counters that appear inside the window count from 0
    ({}, {"sched.plan.bound": 3, "sched.plan.full": 1}, 75.0),
])
def test_share_of_the_windows_plans(before, after, want):
    assert read(pages(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({"sched.plan.bound": 5, "sched.plan.full": 1},
     {"sched.plan.bound": 5, "sched.plan.full": 1}),   # exact-key hits only
    ({}, {"other": 3}),
])
def test_nothing_planned_reads_none(before, after):
    assert read(pages(before, after)) is None


def test_benchmark_json_lists_it_for_the_two_count_cells():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "planner, cover",
        "moves": "qps",
        "workloads": ["gdelt-z3-10m.count-c64",
                      "gdelt-z3-10m.count-windows-c64"]}
    assert bench["per_layer"][-1] is entry     # appended, nothing moved
