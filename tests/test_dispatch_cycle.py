"""The dispatch cycle timed from inside the scheduler (serve/scheduler.py
``_Cycle`` / ``_Dispatch``), spans with a start (trace.py), the REST span
(web/server.py) and kernel names in lowered modules (index/scan.py)."""

import ast
import json
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import config, trace
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.durability import faults
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.metrics import REGISTRY
from geomesa_tpu.obs.flight import RECORDER
from geomesa_tpu.trace import RING

DURING = "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
COLLECTOR = ("idle", "window", "plan", "cover", "group", "union", "prepare",
             "launch")
COMPLETER = ("pickup", "delta", "ready_wait", "resolve")


def _mk_store(n=50_000, seed=11):
    rng = np.random.default_rng(seed)
    ds = TpuDataStore()
    ds.create_schema(
        "t", "v:Int,dtg:Date,*geom:Point;geomesa.z3.interval=week")
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    ds.load("t", FeatureTable.build(ds.get_schema("t"), {
        "v": rng.integers(0, 100, n).astype(np.int32),
        "dtg": base + rng.integers(0, 30 * 86400000, n),
        "geom": (rng.uniform(-60, 60, n), rng.uniform(-40, 40, n))}))
    return ds


def _query(i: int) -> str:
    return (f"BBOX(geom, {-30 + 0.25 * i}, {-20 + 0.125 * i}, {-24 + 0.25 * i}, "
            f"{-16 + 0.125 * i}) AND {DURING} AND v > 5")


def _timers() -> dict:
    return REGISTRY.snapshot()["timers"]


def _delta(after: dict, before: dict, name: str, key: str):
    return after.get(name, {}).get(key, 0) - before.get(name, {}).get(key, 0)


@pytest.fixture(scope="module")
def wave():
    """Two waves of 64 concurrent, distinct counts through one scheduler:
    the store, the answers, the wave's batch events and the registry and
    scheduler deltas around it."""
    ds = _mk_store(n=200_000)   # large enough for the pruned kernel
    sched = ds.scheduler()
    t_warm = time.time() * 1000
    sched.count("t", _query(199))   # the first program's compile stays out
    deadline = time.time() + 10     # its cycle is recorded after it resolved
    while not RECORDER.recent(kind="batch", since_ms=t_warm) \
            and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)
    t_lo = time.time() * 1000
    before, stats0 = REGISTRY.snapshot(), sched.stats()
    got = {}

    def one(i):
        got[i] = sched.count("t", _query(i))

    for w in range(2):
        ts = [threading.Thread(target=one, args=(64 * w + i,))
              for i in range(64)]
        [t.start() for t in ts]
        [t.join(timeout=60) for t in ts]
        assert not any(t.is_alive() for t in ts)
    deadline = time.time() + 10   # a cycle is observed after it resolved
    while time.time() < deadline:
        events = RECORDER.recent(kind="batch", since_ms=t_lo, limit=1000)
        if sum(e["batch_size"] for e in events) == 128:
            break
        time.sleep(0.02)
    time.sleep(0.05)
    out = {"ds": ds, "got": got, "events": events, "before": before,
           "after": REGISTRY.snapshot(), "stats0": stats0,
           "stats1": sched.stats()}
    yield out
    sched.shutdown()


def test_wave_answers_are_exact(wave):
    ds = wave["ds"]
    assert len(wave["got"]) == 128
    for i in (0, 63, 100, 127):
        assert wave["got"][i] == ds.planner("t").count(_query(i))


def test_every_batch_event_holds_all_stages(wave):
    events = wave["events"]
    assert events and sum(e["batch_size"] for e in events) == 128
    for e in events:
        assert set(e["stages"]) == set(COLLECTOR + COMPLETER), e
        for start, dur in e["stages"].values():
            assert dur >= 0 and start > 1e12   # epoch ms
        for key in ("launch_ms", "ready_ms", "plan_loop_cpu_ms",
                    "plan_misses", "cover_misses", "cover_boxes",
                    "cover_ranges", "union_tier", "tier",
                    "first_call", "queue_depth", "threads", "cycle_size"):
            assert key in e, key
        assert e["kernel"] == "count_multi_blocks.point_boxes"
        assert e["union_tier"] >= 8 and e["tier"] >= e["batch_size"]
        assert e["plan_misses"] <= e["cycle_size"]
        # one decomposition for all the boxes of the dispatch, under one
        # scan's range budget however many they are (two week bins here)
        assert e["cover_boxes"] == e["batch_size"]
        assert 0 < e["cover_ranges"] <= 2 * 2 * config.SCAN_RANGES_TARGET.get()


@pytest.mark.parametrize("thread", ["collector", "completer"])
def test_stages_of_one_thread_do_not_overlap(wave, thread):
    names = COLLECTOR if thread == "collector" else COMPLETER
    for e in wave["events"]:
        spans = sorted((e["stages"][n][0], e["stages"][n][0]
                        + e["stages"][n][1], n) for n in names)
        for (_, end, a), (start, _, b) in zip(spans, spans[1:]):
            assert start >= end - 0.002, (a, b, e["stages"])  # ms rounding


def test_launch_before_ready_and_on_the_recorders_clock(wave):
    for e in wave["events"]:
        assert e["launch_ms"] <= e["ready_ms"]
        assert e["stages"]["launch"][0] == e["launch_ms"]
        # anchor + offset lines up with the ts_ms the recorder stamped
        # (time.time() when the event was recorded, after resolve)
        assert -5 <= e["ts_ms"] - e["ready_ms"] < 2000


def test_collector_stages_make_the_cycle_wall(wave):
    """Σ collector stages = idle start → launch end, ± 2 %: what is left is
    the loop's own bookkeeping (_account, the dispatch object)."""
    wall = staged = 0.0
    for e in wave["events"]:
        if e["cycle_size"] != e["batch_size"]:
            continue   # a cycle of several groups: each event has its own
        s = e["stages"]
        wall += s["launch"][0] + s["launch"][1] - s["idle"][0]
        staged += sum(s[n][1] for n in COLLECTOR)
    assert wall > 0
    assert abs(staged - wall) / wall < 0.02, (staged, wall)


def test_stage_timers_one_observation_a_cycle_or_dispatch(wave):
    before, after = wave["before"]["timers"], wave["after"]["timers"]
    cycles = wave["stats1"]["batches"] - wave["stats0"]["batches"]
    for n in COLLECTOR[:5]:   # the cycle's own: idle … group
        assert _delta(after, before, f"sched.stage.{n}", "count") == cycles
    for n in COLLECTOR[5:] + COMPLETER:   # one a dispatch
        assert _delta(after, before, f"sched.stage.{n}", "count") \
            == len(wave["events"])
    # the events and the timers tell the same seconds
    for n in ("plan", "cover", "prepare", "ready_wait", "resolve"):
        from_events = sum(e["stages"][n][1] for e in wave["events"]
                          if n not in COLLECTOR[:5]
                          or e is _first_of_cycle(wave, e))
        timer = 1000 * _delta(after, before, f"sched.stage.{n}", "total_s")
        assert abs(from_events - timer) <= 0.02 * max(timer, 1.0), n


def _first_of_cycle(wave, e):
    """A cycle of several groups repeats its collector stages on each
    group's event: count it once."""
    same = [x for x in wave["events"]
            if x["stages"]["idle"][0] == e["stages"]["idle"][0]]
    return min(same, key=lambda x: x["batch_id"])


def test_plan_loop_cpu_counter_and_wall(wave):
    c0, c1 = wave["before"]["counters"], wave["after"]["counters"]
    cpu_us = c1["sched.plan_loop_cpu_us"] - c0.get("sched.plan_loop_cpu_us", 0)
    before, after = wave["before"]["timers"], wave["after"]["timers"]
    loop_s = sum(_delta(after, before, f"sched.stage.{n}", "total_s")
                 for n in ("plan", "cover", "group"))
    assert cpu_us > 0 and loop_s > 0
    # CPU time of one thread cannot pass its wall time (clock granularity)
    assert cpu_us / 1e6 <= loop_s * 1.05 + 0.02


def test_range_decompose_counts_group_covers_and_plan_plan_misses(wave):
    """On the scheduled path the `plan` timer counts plan-cache misses and
    `range_decompose` the group covers: one a dispatch, not one a request."""
    before, after = wave["before"]["timers"], wave["after"]["timers"]
    s0, s1 = wave["stats0"], wave["stats1"]
    plan_misses = s1["plan_cache"]["misses"] - s0["plan_cache"]["misses"]
    covers = s1["group_covers"] - s0["group_covers"]
    assert plan_misses == 128
    assert _delta(after, before, "plan", "count") == plan_misses
    assert covers == len(wave["events"]) < 128
    assert _delta(after, before, "range_decompose", "count") == covers
    firsts = [e for e in wave["events"] if e is _first_of_cycle(wave, e)]
    assert sum(e["plan_misses"] for e in firsts) == plan_misses
    assert sum(e["cover_misses"] for e in firsts) == covers
    assert s1["cover_boxes_mean"] > 1.0
    # the cover's seconds are the cycle's `cover` stage
    cover_s = _delta(after, before, "sched.stage.cover", "total_s")
    decomposed_s = _delta(after, before, "range_decompose", "total_s")
    assert abs(cover_s - decomposed_s) <= 0.02 * max(cover_s, 1e-3)


def test_queue_wait_ends_when_the_batch_closed(wave):
    """No request's queue wait holds its neighbours' planning: the requests
    of one batch closed at one instant, before any of them was planned, and
    launched at one instant, after all of them were."""
    sched = wave["ds"].scheduler()
    reqs = [sched.submit("t", _query(200 + i)) for i in range(32)]
    [r.result(timeout=60) for r in reqs]
    by_batch = {}
    for r in reqs:
        assert r.queue_wait_s == (r.t_closed - r.t_submit) / 1e9
        by_batch.setdefault(r.batch_id, []).append(r)
    assert None not in by_batch and len(by_batch) < 32
    for grp in by_batch.values():
        assert len({r.t_closed for r in grp}) == 1
        assert len({r.t_launch for r in grp}) == 1
        for r in grp:
            assert r.t_submit <= r.t_closed <= r.t_plan[0] <= r.t_plan[1] \
                <= r.t_launch
            assert r.scan_s > 0


def test_dead_series_are_gone(wave):
    snap = wave["after"]
    names = set(snap["counters"]) | set(snap["histograms"]) \
        | set(snap["timers"])
    assert "scheduler.fused_size" not in names
    assert not [n for n in names if n.startswith("scheduler.flush.")]
    assert "scheduler.batch_size" in snap["histograms"]
    assert wave["stats1"]["flush_reasons"]


def test_request_trace_partitions_its_latency(wave):
    t = next(t for t in RING.recent(400) if t["name"] == "query.count"
             and "scan" in t["stages_ms"])
    kids = {c["name"]: c for c in t["root"]["children"]}
    assert list(kids)[:4] == ["submit", "queue_wait", "batch_host", "scan"]
    assert "wake" in kids
    # one after the other on one clock
    order = [kids[n] for n in ("submit", "queue_wait", "batch_host", "scan",
                               "wake")]
    for a, b in zip(order, order[1:]):
        assert abs(a["start_ms"] + a["duration_ms"] - b["start_ms"]) < 0.5
    assert kids["scan"]["attrs"]["batch_id"].isdigit()
    nested = [c["name"] for c in kids["batch_host"].get("children", ())]
    assert nested and nested[0] == "plan"
    assert sum(c["self_ms"] for c in t["root"]["children"]) \
        >= 0.95 * t["duration_ms"]


# -- slow_cycles --------------------------------------------------------------


def test_slow_cycle_is_kept_whole():
    ds = _mk_store(n=5_000, seed=2)
    sched = ds.scheduler()
    try:
        sched.count("t", _query(1))
        assert sched.stats()["slow_cycles"] == []
        faults.arm_serve_delay("sched.dispatch", seconds=1.2, n=1)
        try:
            n = sched.count("t", _query(2))
        finally:
            faults.reset()
        assert n == ds.planner("t").count(_query(2))
        slow = sched.stats()["slow_cycles"]
        assert len(slow) == 1
        c = slow[0]
        assert c["stages"]["launch"][1] >= 1200.0
        assert set(c["stages"]) == set(COLLECTOR + COMPLETER)
        assert c["queue_depth"] == 0 and c["threads"] >= 3
        json.dumps(c)   # what GET /scheduler serves
        # only the last eight are kept
        sched._slow_cycles = [dict(c, batch_id=i) for i in range(8)]
        faults.arm_serve_delay("sched.dispatch", seconds=1.05, n=1)
        try:
            sched.count("t", _query(3))
        finally:
            faults.reset()
        kept = sched.stats()["slow_cycles"]
        assert len(kept) == 8 and kept[0]["batch_id"] == 1
    finally:
        sched.shutdown()


# -- REST ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    from geomesa_tpu import web
    ds = _mk_store(n=20_000, seed=4)
    httpd = web.serve(ds, host="127.0.0.1", port=0, background=True)
    yield ds, httpd.server_address[1]
    httpd.shutdown()
    httpd.server_close()
    if ds._scheduler is not None:
        ds._scheduler.shutdown()


def _get(port: int, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return json.loads(r.read())


def test_http_request_is_covered_by_stage_self_times(served):
    ds, port = served
    q = urllib.parse.quote(_query(7))
    _get(port, f"/types/t/count?cql={q}")       # compile outside
    q = urllib.parse.quote(_query(8))
    want = ds.planner("t").count(_query(8))
    n_events = len(RECORDER.recent(limit=100000))
    # a request of some length, as on a loaded server: what the root keeps
    # to itself (REST's routing, a fixed ~0.2 ms) is then well under 5 %
    faults.arm_serve_delay("sched.device_wait", seconds=0.05, n=1)
    try:
        assert _get(port, f"/types/t/count?cql={q}")["count"] == want
    finally:
        faults.reset()
    deadline = time.time() + 5
    while time.time() < deadline:   # the root closes after the response
        roots = [t for t in list(RING._ring)
                 if t.name == "http.request.count"]
        if len(roots) >= 2:
            break
        time.sleep(0.01)
    t = roots[-1]
    assert t.coverage() >= 0.95, t.to_dict()
    names = [c.name for c in t.root.children]
    assert names == ["query.count", "http.respond"]
    assert t.root.attrs["scheduled"]
    # one flight event for the request (the scheduler's), one for its batch:
    # the REST root derives no second one
    kinds = [e["kind"] for e in RECORDER.recent(limit=100000)][
        :len(RECORDER.recent(limit=100000)) - n_events]
    assert sorted(kinds) == ["batch", "count.scheduled"], kinds
    d = t.to_dict()["root"]
    assert d["start_ms"] == 0.0
    assert d["children"][1]["start_ms"] >= d["children"][0]["start_ms"]


def test_http_timers_by_route_family(served):
    _, port = served
    before = _timers()
    q = urllib.parse.quote(_query(9))
    _get(port, f"/types/t/count?cql={q}")
    _get(port, "/scheduler")
    _get(port, "/events?kind=batch&limit=1")
    time.sleep(0.1)
    after = _timers()
    assert _delta(after, before, "http.request.count", "count") == 1
    assert _delta(after, before, "http.request.scheduler", "count") == 1
    assert _delta(after, before, "http.request.events", "count") == 1
    assert _delta(after, before, "http.respond", "count") >= 3
    # REST's own time: the route's span minus the scheduler's under it
    rest = _delta(after, before, "http.request.count", "total_s") \
        - _delta(after, before, "query.count", "total_s")
    assert 0 < rest < 1.0


def test_served_surfaces_show_the_cycle(served):
    _, port = served
    q = urllib.parse.quote(_query(10))
    _get(port, f"/types/t/count?cql={q}")
    ev = _get(port, "/events?kind=batch&limit=1")["events"][0]
    for key in ("stages", "launch_ms", "ready_ms", "plan_loop_cpu_ms",
                "first_call"):
        assert key in ev
    assert "slow_cycles" in _get(port, "/scheduler")
    tr = _get(port, "/traces?limit=5")["traces"]
    root = next(t for t in tr if t["name"] == "http.request.count")["root"]
    assert all("start_ms" in c for c in root["children"])
    m = _get(port, "/metrics")
    for name in ("http.request.count", "http.respond", "sched.stage.idle",
                 "sched.stage.resolve", "queue_wait", "batch_host", "wake"):
        assert name in m["timers"], name
    assert "sched.plan_loop_cpu_us" in m["counters"]


# -- trace.py -----------------------------------------------------------------


def test_spans_hold_a_start_on_one_clock():
    with trace.trace("outer") as t:
        time.sleep(0.002)
        with trace.span("a"):
            time.sleep(0.002)
        t1 = time.perf_counter_ns()
        node = trace.record("b", "span", 0.001, t1)
        trace.record("c", "span", 0.0005, parent=node)
        trace.record("no_end", "span", 0.0)
    d = t.to_dict()["root"]
    assert d["start_ms"] == 0.0
    a, b, no_end = d["children"]
    assert a["start_ms"] >= 2.0
    assert abs(b["start_ms"] - ((t1 - t.root.start_ns) / 1e6 - 1.0)) < 0.01
    assert b["children"][0]["name"] == "c"
    assert "start_ms" not in no_end and "start_ms" not in b["children"][0]
    # the anchor puts the span clock on the wall clock
    assert abs(trace.epoch_ms(time.perf_counter_ns())
               - time.time() * 1000) < 50
    assert abs(trace.epoch_ms(t.root.start_ns) - t.ts_ms) < 50


def test_record_without_a_trace_feeds_the_registry():
    before = _timers().get("cycle_test.flat", {}).get("count", 0)
    assert trace.record("cycle_test.flat", "span", 0.001) is None
    assert _timers()["cycle_test.flat"]["count"] == before + 1


def test_pending_span_trees_are_bounded_and_keep_their_exemplars():
    """Without a reader the registry folds its backlog of closed span trees
    itself, past ``_PENDING_MAX``; a retained trace folded that way still
    becomes its bucket's exemplar (the sampler is settled first)."""
    from geomesa_tpu import obs
    obs.install()
    REGISTRY.snapshot()
    ids = []
    for _ in range(3 * REGISTRY._PENDING_MAX):
        try:
            with trace.trace("cycle_test.bounded") as t:
                ids.append(t.trace_id)
                raise ValueError("kept by the tail sampler")
        except ValueError:
            pass
        assert len(REGISTRY._pending) <= REGISTRY._PENDING_MAX + 1
    folded_inline = set(ids[:2 * REGISTRY._PENDING_MAX])
    with REGISTRY._lock:
        kept = {tid for tid, _ in
                REGISTRY._exemplars.get("cycle_test.bounded", {}).values()}
    assert kept & folded_inline
    assert _timers()["cycle_test.bounded"]["count"] == len(ids)


def test_trace_module_imports_no_jax_at_import_time():
    tree = ast.parse(open(trace.__file__).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    mods = [a.name for n in top if isinstance(n, ast.Import) for a in n.names] \
        + [n.module for n in top if isinstance(n, ast.ImportFrom)]
    assert not [m for m in mods if m and m.split(".")[0] == "jax"]
    with trace.annotation("sched.test", batch_id=1):   # imported on use
        pass


# -- kernel names -------------------------------------------------------------


@pytest.mark.parametrize("mode", ["count_multi_blocks", "count_blocks"])
def test_lowered_module_is_named_for_its_kernel(mode):
    import jax
    import jax.numpy as jnp
    from geomesa_tpu.index import prune
    from geomesa_tpu.index.scan import program_name
    ds = _mk_store(n=4_096, seed=6)
    kern = ds.planner("t").indexes[0].kernels
    plan = ds.planner("t").plan(_query(0))
    n_boxes = 4 if mode == "count_multi_blocks" else 1
    fn = kern._get(mode, plan.primary_kind, plan.windows is not None,
                   plan.residual_device[0], plan.residual_device[2],
                   n_boxes, plan.windows.shape[0],
                   (8, prune.BLOCK_SIZE, 0))
    jitted = fn
    while not hasattr(jitted, "lower"):   # inside the probe's closure
        jitted = next(c.cell_contents for c in jitted.__closure__
                      if callable(c.cell_contents)
                      and hasattr(c.cell_contents, "__name__"))
    boxes = jnp.zeros((n_boxes, 8), jnp.int32)
    rp = [jnp.asarray(p) for p in plan.residual_device[1]]
    lowered = jitted.lower(kern.cols, boxes, jnp.asarray(plan.windows), rp,
                           jnp.zeros(8, jnp.int32))
    want = program_name(f"{mode}.{plan.primary_kind}")
    assert want == f"{mode}_point_boxes"
    assert f"jit_{want}" in lowered.as_text()[:400]
    assert isinstance(jax.block_until_ready(jitted(
        kern.cols, boxes, jnp.asarray(plan.windows), rp,
        jnp.full(8, -1, jnp.int32))), jax.Array)
