"""Micro-batching query scheduler (serve/scheduler.py): coalescing
correctness, plan caching, group covers, generation invalidation, trace integration,
kernel-cache bounding, and the web serving path."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.filter import ir


def _mk_store(n=50_000, seed=3, expiry=None):
    rng = np.random.default_rng(seed)
    ds = TpuDataStore()
    spec = "v:Int,name:String,dtg:Date,*geom:Point;geomesa.z3.interval=week"
    if expiry:  # user-data entries are comma-separated after the ';'
        spec += f",geomesa.feature.expiry={expiry}"
    ds.create_schema("t", spec)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    ds.load("t", FeatureTable.build(ds.get_schema("t"), {
        "v": rng.integers(0, 100, n).astype(np.int32),
        "name": rng.choice(["a", "b", "c"], n).astype(object),
        "dtg": base + rng.integers(0, 30 * 86400000, n),
        "geom": (rng.uniform(-60, 60, n), rng.uniform(-40, 40, n))}))
    return ds


DURING = "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"


def _queries(k=16):
    return [f"BBOX(geom, {-10 + i}, {5 + 0.5 * i}, {10 + i}, "
            f"{25 + 0.5 * i}) AND {DURING}" for i in range(k)]


@pytest.fixture(scope="module")
def store():
    ds = _mk_store()
    yield ds
    if ds._scheduler is not None:
        ds._scheduler.shutdown()


# -- coalescing correctness ---------------------------------------------------


def test_count_many_matches_individual_counts(store):
    qs = _queries(16)
    ref = [store.count("t", q) for q in qs]
    got = store.count_many("t", qs)
    assert got == ref
    st = store.scheduler().stats()
    assert st["fused"] > 0  # the batch really fused, not 16 singles


def test_submitted_together_actually_batch(store):
    sched = store.scheduler()
    before = sched._n_batches
    reqs = [sched.submit("t", q) for q in _queries(12)]
    got = [r.result(timeout=30) for r in reqs]
    assert all(isinstance(n, int) for n in got)
    # 12 compatible queries submitted back-to-back take far fewer batches
    assert sched._n_batches - before <= 4
    assert any(r.batched and r.batch_size > 1 for r in reqs)


def test_mixed_batchable_and_fallback(store):
    """Non-fusable shapes (OR→union plans, fid lookups, INCLUDE) ride the
    same submission and still answer exactly."""
    t = store.tables["t"]
    fid = str(t.fids[5])
    qs = [_queries(4)[0],
          f"BBOX(geom, -10, 5, 10, 25) OR BBOX(geom, 30, 5, 50, 25)",
          "INCLUDE",
          "v < 50"]
    ref = [store.count("t", q) for q in qs]
    assert store.count_many("t", qs) == ref
    assert store.scheduler().count("t", ir.FidFilter((fid,))) == 1


def test_concurrent_clients_coalesce_and_agree(store):
    sched = store.scheduler()
    q = _queries(1)[0]
    ref = store.count("t", q)
    outs, errs = [], []

    def client():
        try:
            for _ in range(4):
                outs.append(sched.count("t", q))
        except Exception as e:  # pragma: no cover - failure detail
            errs.append(e)

    ts = [threading.Thread(target=client) for _ in range(16)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not errs
    assert outs and all(o == ref for o in outs)


def test_count_future_async_api(store):
    q = _queries(2)[1]
    req = store.count_future("t", q)
    assert req.result(timeout=30) == store.count("t", q)
    assert req.future.done()


# -- plan cache, covers -------------------------------------------------------


def _traces_of(q):
    """The ring's traces of scheduled counts of ``q``, oldest first: found
    by the request's own attribute, not by their place in the ring (another
    thread's trace may land between two of them)."""
    from geomesa_tpu.trace import RING
    return sorted((t for t in RING.recent(256)
                   if t["root"].get("attrs", {}).get("scheduled")
                   and t["root"]["attrs"].get("filter") == q),
                  key=lambda t: t["id"])


def _span(node, name):
    if node["name"] == name:
        return node
    for c in node.get("children", ()):
        found = _span(c, name)
        if found is not None:
            return found
    return None


def test_plan_cache_hit_skips_plan_stage_in_trace(store):
    sched = store.scheduler()
    q = "BBOX(geom, -3, -3, 17, 17) AND " + DURING
    n1 = sched.count("t", q)
    n2 = sched.count("t", q)
    assert n1 == n2
    first, second = _traces_of(q)
    assert "plan" in first["stages_ms"], "cold query must show a plan stage"
    assert "plan" not in second["stages_ms"], \
        "plan-cache hit must skip the plan stage entirely"
    assert "queue_wait" in second["stages_ms"]
    assert "scan" in second["stages_ms"]


def test_bound_request_has_a_bound_plan_span_and_counts(store):
    """A filter that misses the exact key and meets its shape's template:
    a ``plan`` span with ``bound=True`` (it times the bind), a tick of
    ``sched.plan.bound``; the shape's first request has the span without
    the attribute and ticks ``sched.plan.full``; an exact-key hit has no
    ``plan`` span and ticks neither."""
    from geomesa_tpu.metrics import REGISTRY
    sched = store.scheduler()
    qs = [f"BBOX(geom, {-5 - i}, -3, 17, 17) AND {DURING} AND v < {70 + i}"
          " AND name = 'a'" for i in range(3)]

    def ticks():
        c = REGISTRY.snapshot()["counters"]
        return [c.get("sched.plan." + k, 0)
                for k in ("bound", "full", "bind_failed")]

    seen = [ticks()]
    for q in (qs[0], qs[1], qs[2], qs[1]):
        assert sched.count("t", q) == store.count("t", q)
        seen.append(ticks())
    steps = [[b - a for a, b in zip(x, y)] for x, y in zip(seen, seen[1:])]
    assert steps == [[0, 1, 0], [1, 0, 0], [1, 0, 0], [0, 0, 0]]
    (full,), (bound, hit), (bound2,) = (_traces_of(q) for q in qs)
    assert "bound" not in _span(full["root"], "plan").get("attrs", {})
    for t in (bound, bound2):
        assert _span(t["root"], "plan")["attrs"]["bound"] == "True"
        assert _span(t["root"], "batch_host") is not None
    assert _span(hit["root"], "plan") is None
    assert "plan" not in hit["stages_ms"] and "scan" in hit["stages_ms"]


def test_stats_carry_the_plan_counters(store):
    sched = store.scheduler()
    before = sched.stats()["plan"]
    assert set(before) == {"bound", "full", "bind_failed"}
    qs = [f"BBOX(geom, -20, {-9 - i}, 3, 12) AND {DURING} AND v >= {i}"
          for i in range(6)]
    assert store.count_many("t", qs) == [store.count("t", q) for q in qs]
    after = sched.stats()["plan"]
    moved = {k: after[k] - before[k] for k in after}
    assert moved["bound"] + moved["full"] == 6
    assert moved["bind_failed"] == 0 and moved["full"] >= 1
    # the template look-up is no request's own: the cache's tallies are
    # the exact key's alone
    assert sched.plans.stats()["misses"] >= after["bound"] + after["full"]


def test_lone_repeat_keeps_its_cover_on_the_cached_plan(store):
    """A group of one covers through the plan object the plan cache holds:
    the same query again, alone, decomposes nothing."""
    sched = store.scheduler()
    q = "BBOX(geom, -8, -1, 12, 19) AND " + DURING
    first = sched.submit("t", q)
    n1 = first.result(timeout=30)
    covers = sched.stats()["group_covers"]
    assert first.plan.blocks is not False
    again = sched.submit("t", q)
    assert again.result(timeout=30) == n1 == store.count("t", q)
    if not again.result_cache_hit:
        assert again.plan is first.plan and again.batch_id != first.batch_id
    assert sched.stats()["group_covers"] == covers


def test_a_group_is_covered_once_for_all_its_boxes(store):
    sched = store.scheduler()
    st0 = sched.stats()
    qs = [f"BBOX(geom, {-40 + 2 * i}, {-30 + i}, {-37 + 2 * i}, {-27 + i}) "
          f"AND {DURING} AND v < 90" for i in range(24)]
    reqs = [sched.submit("t", q) for q in qs]
    got = [r.result(timeout=30) for r in reqs]
    assert got == [store.count("t", q) for q in qs]
    st1 = sched.stats()
    batches = {r.batch_id for r in reqs}
    assert None not in batches
    # one cover a dispatch, and none kept on a member's plan
    assert st1["group_covers"] - st0["group_covers"] == len(batches) < 24
    assert st1["cover_boxes_mean"] > 1.0
    assert all(r.plan.blocks is False for r in reqs if r.batch_size > 1)
    assert "cover_cache" not in st1


def test_a_cover_fault_fails_its_group_only(store, monkeypatch):
    sched = store.scheduler()
    qs = [f"BBOX(geom, {-33 + i}, -12, {-31 + i}, -9) AND {DURING}"
          for i in range(3)]
    index = store.planner("t").plan(qs[0]).index

    def boom(self, boxes, intervals):
        raise RuntimeError("cover fault")

    monkeypatch.setattr(type(index), "cover_blocks", boom)
    reqs = [sched.submit("t", q) for q in qs]
    single = sched.submit("t", "v < 50")
    assert single.result(timeout=30) == store.count("t", "v < 50")
    for r in reqs:
        with pytest.raises(RuntimeError, match="cover fault"):
            r.result(timeout=30)
    monkeypatch.undo()
    assert sched.healthy()
    assert sched.count("t", qs[0] + " AND v < 99") == \
        store.count("t", qs[0] + " AND v < 99")


def test_generation_invalidates_on_ingest(store):
    sched = store.scheduler()
    q = "BBOX(geom, 1, 1, 2, 2) AND " + DURING
    gen0 = store.generation("t")
    n0 = sched.count("t", q)
    base = np.datetime64("2020-01-06T00:00:00", "ms").astype(np.int64)
    with store.get_writer("t") as w:
        w.write(v=1, name="a", dtg=int(base), geom=(1.5, 1.5))
    assert store.generation("t") > gen0
    assert sched.count("t", q) == n0 + 1, \
        "stale cached plan served after an ingest"
    # and the flush (delta → main index merge) bumps again
    gen1 = store.generation("t")
    store.flush("t")
    assert store.generation("t") > gen1
    assert sched.count("t", q) == n0 + 1


def test_generation_invalidates_on_remove_and_update(store):
    sched = store.scheduler()
    q = "v = 7"
    n0 = sched.count("t", q)
    removed = store.remove_features("t", "v = 7")
    assert removed == n0
    assert sched.count("t", q) == 0
    changed = store.update_features("t", "v = 8", {"v": 7})
    assert sched.count("t", q) == changed


def test_generation_invalidates_on_age_off():
    import time as _time
    rng = np.random.default_rng(11)
    n = 5000
    ds = TpuDataStore()
    ds.create_schema("t", "v:Int,dtg:Date,*geom:Point;"
                          "geomesa.feature.expiry=dtg(30 days)")
    now = int(_time.time() * 1000)
    # recent rows: inside TTL at write time, so they land
    ds.load("t", FeatureTable.build(ds.get_schema("t"), {
        "v": rng.integers(0, 100, n).astype(np.int32),
        "dtg": now - rng.integers(0, 10 * 86400000, n),
        "geom": (rng.uniform(-60, 60, n), rng.uniform(-40, 40, n))}))
    try:
        sched = ds.scheduler()
        q = "BBOX(geom, -60, -40, 60, 40)"
        n0 = sched.count("t", q)
        assert n0 == n
        # advance the clock far enough that every row's TTL lapsed
        dropped = ds.age_off("t", now_ms=now + 40 * 86400000)
        assert dropped == n
        assert sched.count("t", q) == 0, \
            "stale cached plan served after age-off"
    finally:
        if ds._scheduler is not None:
            ds._scheduler.shutdown()


def test_plan_cache_bounded():
    from geomesa_tpu.serve.scheduler import LruCache
    c = LruCache(4, "test.cache")
    for i in range(10):
        c.put(("k", i), i)
    assert c.stats()["size"] == 4
    from geomesa_tpu.serve.scheduler import _MISS
    assert c.get(("k", 0)) is _MISS
    assert c.get(("k", 9)) == 9


# -- adaptive window / instrumentation ---------------------------------------


def test_adaptive_window_stays_bounded_and_stats_populate(store):
    sched = store.scheduler()
    for q in _queries(6):
        sched.count("t", q)  # serial singles: window should shrink
    st = sched.stats()
    assert sched._min_window_us <= st["window_us"] <= st["window_us_max"]
    assert st["queries"] >= 6 and st["batches"] >= 1
    assert sum(st["flush_reasons"].values()) == st["batches"]
    assert sum(st["batch_size_hist"].values()) == st["batches"]
    from geomesa_tpu.metrics import REGISTRY
    snap = REGISTRY.snapshot()
    assert snap["histograms"]["scheduler.batch_size"]["count"] >= 1
    assert "scheduler.queue_depth" in snap["gauges"]
    prom = REGISTRY.to_prometheus()
    assert "geomesa_tpu_scheduler_batch_size" in prom


def test_parse_and_guard_errors_surface(store):
    sched = store.scheduler()
    with pytest.raises(Exception):
        sched.count("t", "THIS IS NOT CQL (")
    with pytest.raises(ValueError):
        sched.submit("no_such_type", "INCLUDE")


# -- kernel LRU bound ---------------------------------------------------------


def test_scan_kernel_cache_bounded_and_correct(store):
    planner = store.planner("t")
    idx = next(i for i in planner.indexes if hasattr(i, "kernels"))
    kern = idx.kernels
    q = "BBOX(geom, -10, 5, 10, 25) AND " + DURING
    ref = planner.count(q)
    config.KERNEL_CACHE.set(2)
    try:
        # many distinct residual structures cycle through a 2-entry cache
        for v in range(6):
            planner.count(f"BBOX(geom, -10, 5, 10, 25) AND v < {v} AND "
                          f"v <> {v + 40 + v}" if v % 2 else
                          f"BBOX(geom, -10, 5, 10, 25) AND v >= {v}")
            assert len(kern._jitted) <= 2
        # an evicted signature recompiles and still answers exactly
        assert planner.count(q) == ref
    finally:
        config.KERNEL_CACHE.unset()
    from geomesa_tpu.metrics import REGISTRY
    assert REGISTRY.snapshot()["gauges"].get("kernels.compiled", 0) >= 1


# -- the web serving path -----------------------------------------------------


def test_web_count_coalesces(store):
    from geomesa_tpu.web import serve
    httpd = serve(store, port=0, background=True)
    try:
        port = httpd.server_address[1]
        base = f"http://127.0.0.1:{port}"

        def get(path):
            with urllib.request.urlopen(base + path) as r:
                return json.loads(r.read())

        q = "BBOX(geom,%20-10,%205,%2010,%2025)"
        ref = store.count("t", "BBOX(geom, -10, 5, 10, 25)")
        outs = []

        def client():
            outs.append(get(f"/types/t/count?cql={q}")["count"])

        ts = [threading.Thread(target=client) for _ in range(12)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert all(o == ref for o in outs)
        st = get("/scheduler")
        assert st["queries"] >= 12
        assert "batch_size_hist" in st and "plan_cache" in st
        assert set(st["plan"]) == {"bound", "full", "bind_failed"}
        assert st["plan"]["bound"] + st["plan"]["full"] >= 1
    finally:
        httpd.shutdown()


def test_web_count_scheduler_disabled_param():
    ds = _mk_store(n=2000, seed=9)
    ds.params["scheduler"] = False
    try:
        assert ds.count_coalesced("t", "INCLUDE") == 2000
        assert ds._scheduler is None  # direct path: no scheduler spun up
    finally:
        if ds._scheduler is not None:
            ds._scheduler.shutdown()


# -- bare-planner binding (the bench harness shape) ---------------------------


def test_planner_binding(store):
    from geomesa_tpu.serve.scheduler import PlannerBinding, QueryScheduler
    planner = store.planner("t")
    sched = QueryScheduler(PlannerBinding({"t": planner}), flush_size=8)
    try:
        qs = _queries(8)
        assert sched.count_many("t", qs) == [planner.count(q) for q in qs]
    finally:
        sched.shutdown()
