"""The readers of the scheduler's dispatch-cycle record on a hand-made
``ctx``: what each computes, and None where there is nothing to read (a
program from before the record, tracing off, no dispatch in the slice).
A second on the CPU; needs no chip."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

NEW = ("rest.self_ms_per_query", "sched.queue_wait_ms",
       "sched.collector.idle_pct", "sched.collector.plan_pct",
       "sched.collector.cover_pct", "sched.collector.prepare_pct",
       "sched.collector.offcpu_pct", "sched.resolve_ms_per_dispatch",
       "sched.inflight_pct")


def timer(count, total_s):
    return {"count": count, "total_s": total_s}


def make_ctx():
    """A 10 s window of 70 cycles: the collector waits 1 s, plans 4 s,
    covers 3 s, groups 0.5 s and prepares 1.3 s; 0.2 s is its bookkeeping."""
    stages0 = {f"sched.stage.{n}": timer(10, 1.0) for n in (
        "idle", "window", "plan", "cover", "group", "union", "prepare",
        "launch", "resolve")}
    gained = {"idle": 0.75, "window": 0.25, "plan": 4.0, "cover": 3.0,
              "group": 0.5, "union": 0.25, "prepare": 0.75, "launch": 0.3,
              "resolve": 0.35}
    stages1 = {f"sched.stage.{n}": timer(80, 1.0 + s)
               for n, s in gained.items()}
    before = {"timers": {**stages0, "http.request.count": timer(100, 30.0),
                         "query.count": timer(100, 29.5),
                         "queue_wait": timer(100, 12.0)},
              "counters": {"sched.plan_loop_cpu_us": 1_000_000}}
    after = {"timers": {**stages1, "http.request.count": timer(2100, 630.0),
                        "query.count": timer(2100, 623.5),
                        "queue_wait": timer(2100, 272.0)},
             "counters": {"sched.plan_loop_cpu_us": 4_000_000}}
    events = [{"launch_ms": 1000.0, "ready_ms": 1100.0},
              {"launch_ms": 1050.0, "ready_ms": 1200.0},   # overlaps the first
              {"launch_ms": 2000.0, "ready_ms": 2100.0},
              {"launch_ms": 2020.0, "ready_ms": 2050.0}]   # inside the third
    return {"seconds": 10.0, "slice": (1.0, 4.0), "batch_events": events,
            "before": {"/metrics": before}, "after": {"/metrics": after}}


def read(name, ctx):
    return run.load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("name,want", [
    ("rest.self_ms_per_query", 1000 * (600.0 - 594.0) / 2000),
    ("sched.queue_wait_ms", 1000 * 260.0 / 2000),
    ("sched.collector.idle_pct", 10.0),
    ("sched.collector.plan_pct", 40.0),
    ("sched.collector.cover_pct", 30.0),
    ("sched.collector.prepare_pct", 18.0),
    ("sched.collector.offcpu_pct", 100 * (1 - 3.0 / 7.5)),
    ("sched.resolve_ms_per_dispatch", 1000 * 0.35 / 70),
    ("sched.inflight_pct", 100 * 0.3 / 3.0),
])
def test_reader_on_a_made_window(name, want):
    assert read(name, make_ctx()) == pytest.approx(want)


def test_the_collector_shares_leave_its_bookkeeping():
    ctx = make_ctx()
    total = sum(read(f"sched.collector.{n}_pct", ctx)
                for n in ("idle", "plan", "cover", "prepare"))
    assert total == pytest.approx(98.0)   # 0.2 s of 10 unaccounted


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(name):
    """A program without the cycle record (the parent commit), or with
    tracing and the flight recorder off."""
    ctx = make_ctx()
    for snap in ("before", "after"):
        ctx[snap]["/metrics"] = {"timers": {"query.count": timer(5, 1.0)},
                                 "counters": {}}
    ctx["batch_events"] = [{"ts_ms": 1, "rows_scanned": 4096}]
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_a_window_without_a_dispatch_is_none(name):
    ctx = make_ctx()
    ctx["after"] = ctx["before"]
    ctx["batch_events"] = []
    assert read(name, ctx) is None


def test_every_new_reader_has_its_entry():
    bench = json.load(open(os.path.join(os.path.dirname(HERE),
                                        "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert m["source"] == "program_counter"
        assert "gdelt-z3-10m.count-c64" in m["workloads"]
        assert m["moves"] in ("qps", "p50_ms")
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           name + ".py"))
