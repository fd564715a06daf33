"""What PR 28 added to the benchmark, held to a parent-shaped run and to
answers that are wrong.

The driver runs the parent commit with this PR's ``benchmark/`` laid over it,
traced too: a reader that raises there ends that run with exit code 1 and the
PR with it (PR 27: ``run_failed``, parent side, traced run). ``parent_pages.json``
is what the parent's server said of itself (``/healthz``, ``/metrics``,
``/scheduler`` before and after) under three requests of each new cell's
traffic, recorded by running the parent with these files over it: no
``refine.*`` counter, no ``refine.device`` timer, no ``http.features.rows``.
Every new reader has to return None or a number on it and raise nothing, and
None where what it reads is what this PR adds to the program.

The operations: ``correct`` reads false for the osm cell under ``--control
float32``, and for both cells where an answer is altered where the client
parses it; a select cut at its limit has no reference and counts as wrong.
The select's float32 control needs millions of rows to meet a box's edge in
a whole run (PERF.md has its readings on the chip): here it is shown on a
made event beside an edge, with no server. Run by hand, as the rest of this
directory (about three minutes):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_new_readers.py -q
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402
from test_rehearsal import rehearse  # noqa: E402

OSM, SELECT = "osm-xz2-10m.intersects-c8", "gdelt-z3-10m.select-c8"
BENCH = run.load_json(os.path.dirname(HERE), "BENCHMARK.json")
OLD = {"gdelt-z3-10m.count-c64"}
NEW_READERS = [m for m in BENCH["per_layer"]
               if not OLD & set(m["workloads"])]
# what reads only what this PR adds to the program: None on any parent
ADDED = {"intersects_pool_roofline", "refine.device_ms_per_query",
         "refine.uncertain_pct", "select.rows_per_query"}
with open(os.path.join(HERE, "tests", "parent_pages.json")) as f:
    PARENT = json.load(f)


def parent_ctx(cell_name: str, pages: dict) -> dict:
    cell, config, traffic = run.find_cell(BENCH, cell_name)
    return {"cell": cell, "config": config, "traffic": traffic,
            "peaks": run.load_json(HERE, "peaks.json")["TPU v5 lite"],
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "seconds": 51.0, "before": pages["before"],
            "after": pages["after"], "closed": pages["after"],
            "memory": {}, "memory_peak_bytes": 0, "trace": None,
            "slice": (1.0, 4.0), "batch_events": [],
            "requests": [(0.1, 0.2, True)]}


# what BENCHMARK.json lists cell by cell (PR 34): an entry may be added to a
# cell by any PR; one that is here may not lose its cell or its reader
SCHED = {"sched.batch_mean", "sched.queue_wait_ms",
         "sched.collector.idle_pct", "sched.collector.plan_pct",
         "sched.collector.cover_pct", "sched.collector.prepare_pct",
         "sched.collector.offcpu_pct", "sched.resolve_ms_per_dispatch",
         "sched.inflight_pct"}
LISTED = {
    "gdelt-z3-10m.count-c64": SCHED | {
        "count_multi_blocks_roofline", "device.busy_ms_per_query",
        "device.idle_pct", "device.hbm_peak_gb", "rest.self_ms_per_query"},
    OSM: ADDED - {"select.rows_per_query"} | {
        "refine.host_ms_per_query", "xz2.cover_ms_per_query"},
    SELECT: {"select.serialize_ms_per_query", "select.rows_per_query",
             "select.device_ms_per_query",
             "select.blocks_gathered_per_query"},
    "gdelt-countries-10m.join-c4": {
        "join.device_ms_per_query", "join.host_ms_per_query",
        "join.edge_tests_per_point", "join.uncertain_pct",
        "join_pip_roofline"},
    "gdelt-z3-10m.count-windows-c64": SCHED | {"sched.dispatches_per_cycle"},
}


def test_every_cell_of_the_benchmark_is_held():
    assert {c["name"] for c in BENCH["workloads"]} >= set(LISTED)


@pytest.mark.parametrize("cell", sorted(LISTED))
def test_the_new_readers_are_the_ones_the_issue_names(cell):
    """Every entry the benchmark listed for the cell is still listed for it,
    under a reader of its own that run.py can load."""
    listed = {m["name"] for m in BENCH["per_layer"] if cell in m["workloads"]}
    assert LISTED[cell] <= listed, sorted(LISTED[cell] - listed)
    for name in sorted(LISTED[cell]):
        assert callable(run.load_module("layer_metrics", name).read), name


@pytest.mark.parametrize("recorded", sorted(PARENT))
@pytest.mark.parametrize("metric", NEW_READERS, ids=lambda m: m["name"])
def test_reader_on_a_parent_shaped_ctx(metric, recorded):
    """Each reader on its own cell's ctx with the pages either run of the
    parent left: nothing raises, and what the parent lacks reads None."""
    ctx = parent_ctx(metric["workloads"][0], PARENT[recorded])
    value = run.load_module("layer_metrics", metric["name"]).read(ctx)
    assert value is None or isinstance(value, float)
    if metric["name"] in ADDED:
        assert value is None
    # a traced parent whose slice saw the device changes nothing of that
    ctx["trace"] = {"busy_s": 0.5, "window_s": 3.0, "idle_share": 0.83}
    again = run.load_module("layer_metrics", metric["name"]).read(ctx)
    assert (again is None) == (value is None)


@pytest.mark.parametrize("metric", NEW_READERS, ids=lambda m: m["name"])
def test_reader_on_empty_pages_reads_none(metric):
    pages = {p: {"timers": {}, "counters": {}} if p == "/metrics" else {}
             for p in run.SNAPSHOTS}
    ctx = parent_ctx(metric["workloads"][0],
                     {"before": pages, "after": pages})
    assert run.load_module("layer_metrics", metric["name"]).read(ctx) is None


def test_the_roofline_reads_the_counter_over_the_busy_share():
    pages = json.loads(json.dumps(PARENT[OSM]))
    pages["after"]["/metrics"]["counters"]["refine.segments_tested"] = \
        51 * 819_000_000          # a second of the peak's bytes, at 16 B
    ctx = parent_ctx(OSM, pages)
    ctx["trace"] = {"busy_s": 1.5, "window_s": 3.0, "idle_share": 0.5}
    got = run.load_module("layer_metrics", "intersects_pool_roofline").read(ctx)
    assert got == pytest.approx(100.0 * (16 / 1000) / 0.5)


@pytest.mark.parametrize("cell", [OSM, SELECT])
def test_traced_rehearsal_reads_the_new_metrics(capsys, cell):
    line = rehearse(capsys, cell, trace=1, rows=300000, seconds=4)
    assert line["correct"] is True and line["failed"] == 0
    got = line["rehearsal"]
    if cell == OSM:
        assert 0.0 <= got["refine.uncertain_pct"]["value"] < 5.0
        for name in ("refine.device_ms_per_query", "xz2.cover_ms_per_query",
                     "refine.host_ms_per_query"):
            assert got[name]["value"] > 0.0
        # no TPU plane on the CPU: the roofline's reader returns nothing
        assert "intersects_pool_roofline" not in got
    else:
        assert got["select.serialize_ms_per_query"]["value"] > 0.0
        assert got["select.rows_per_query"]["value"] > 0.0


@pytest.mark.parametrize("cell", [OSM, SELECT])
def test_altered_answer_is_seen(capsys, monkeypatch, cell):
    """Every 7th answer loses what it held: a count one short, a feature
    collection without its first feature."""
    real = run.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind == "ops":
            ans, calls = mod.answer, [0]

            def answer(body):
                calls[0] += 1
                if calls[0] % 7 == 0:
                    body = dict(body)
                    if "features" in body:
                        body["features"] = body["features"][1:]
                    else:
                        body["count"] = body["count"] + 1
                return ans(body)
            mod.answer = answer
        return mod

    monkeypatch.setattr(run, "load_module", load)
    line = rehearse(capsys, cell, rows=300000)
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0


def test_a_select_cut_at_its_limit_counts_as_wrong(capsys, monkeypatch):
    """With a limit of 2 the boxes that hold two events or more come back
    cut: no reference stands for a cut set, so those answers are wrong."""
    real = run.find_cell

    def find(bench, workload):
        cell, config, traffic = real(bench, workload)
        traffic = dict(traffic, params=dict(traffic["params"], limit=2))
        return cell, config, traffic

    monkeypatch.setattr(run, "find_cell", find)
    line = rehearse(capsys, SELECT, rows=300000)
    assert line["correct"] is False
    assert 0 < line["compared"]["wrong_answers"]["value"] < line["checked"]


def test_select_answer_refuses_what_is_no_whole_collection():
    op = run.load_module("ops", "select_box")
    feat = {"type": "Feature", "id": "0",
            "geometry": {"type": "Point", "coordinates": [1.5, 2.5]},
            "properties": {"GLOBALEVENTID": "900000007", "NumMentions": 3,
                           "dtg": "2020-01-06T00:00:00.000Z"}}
    whole = {"type": "FeatureCollection", "features": [feat]}
    assert op.answer(whole) == (
        ("900000007", 1.5, 2.5, "2020-01-06T00:00:00.000Z", 3),)
    assert op.answer(dict(whole, approximate=True)) is None
    assert op.answer({"count": 1}) is None
    assert op.answer({"type": "FeatureCollection"}) is None
    for lost in ("GLOBALEVENTID", "NumMentions", "dtg"):
        props = {k: v for k, v in feat["properties"].items() if k != lost}
        assert op.answer(dict(whole, features=[dict(feat, properties=props)])
                         ) is None
    assert op.answer(dict(whole, features=[dict(feat, geometry=None)])) is None


def test_select_float32_control_differs_on_a_made_event():
    """An event 1e-7 degrees outside a box's east edge at lon 100 is inside
    it in float32 (whose spacing there is 7.6e-6 degrees)."""
    op = run.load_module("ops", "select_box")
    data = run.load_module("data", "gdelt_events")
    _, _, traffic = run.find_cell(BENCH, SELECT)
    corpus = data.make_corpus(1000, 5)
    t = int(np.datetime64("2020-01-06T00:00:00", "ms").astype(np.int64))
    corpus["x"][0], corpus["y"][0], corpus["dtg"][0] = 100.5000001, 45.25, t
    args = ((99.5, 44.75, 100.5, 45.75), traffic["params"]["limit"])
    ref, low = data.Reference(corpus), data.controls(corpus)["float32"]
    exact = op.expected(ref, traffic["params"], args)
    lower = op.expected(low, traffic["params"], args)
    assert "900000000" not in {e[0] for e in exact}
    assert ("900000000", 100.5000001, 45.25,
            "2020-01-06T00:00:00.000Z", int(corpus["NumMentions"][0])) in lower


def test_osm_control_in_the_programs_place_reads_not_correct(capsys):
    line = rehearse(capsys, OSM, more=("--control", "float32"), seconds=8,
                    rows=1_000_000)
    assert line["control"] == "float32"
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"]["wrong_answers"]["value"] > 0
