"""A count is planned once a filter shape (serve/scheduler.py
``_plan_request``, index/bind.py): the plan the scheduler binds from its
shape's template equals the planner's, drift goes to the planner, and the
served answers are exact."""

import numpy as np
import pytest

from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.filter import ir
from geomesa_tpu.filter.parser import parse_ecql
from geomesa_tpu.index import bind
from geomesa_tpu.index.guards import QueryInterceptor
from geomesa_tpu.metrics import REGISTRY
from geomesa_tpu.serve import scheduler as _sched
from geomesa_tpu.serve.scheduler import QueryScheduler, StoreBinding

N = 40_000
BASE = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
DAY = 86_400_000
SPEC = ("code:String:index=true,v:Integer,w:Double,g:Float,dtg:Date,"
        "*geom:Point;geomesa.z3.interval=week")
AUTHS = (None, ["admin"])


def _columns(n, seed):
    rng = np.random.default_rng(seed)
    return {
        "code": rng.choice(["010", "020", "043", "190"], n).astype(object),
        "v": rng.geometric(0.2, n).astype(np.int32),
        "w": rng.uniform(0, 100, n),
        "g": rng.integers(0, 50, n).astype(np.float32),
        "dtg": BASE + rng.integers(0, 30 * DAY, n),
        "geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n))}


@pytest.fixture(scope="module")
def world():
    """A point type with visibilities, three indexes (z3, the attribute
    index on ``code``, the full scan) and the stats sketches, so ``_plan``
    prices its candidates as it does for ``gdelt-z3-10m``; and an extent
    type, where INTERSECTS leaves a host residual."""
    ds = TpuDataStore()
    ds.create_schema("t", SPEC)
    cols = _columns(N, 11)
    rng = np.random.default_rng(12)
    vis = rng.choice(["", "admin", "admin&ops", "user|ops"], N,
                     p=[0.4, 0.3, 0.2, 0.1])
    ds.load("t", FeatureTable.build(ds.get_schema("t"), cols,
                                    visibilities=vis))
    ds.create_schema("ways", "name:String,dtg:Date,*geom:LineString;"
                             "geomesa.indices=xz2")
    m = 2_000
    x0, y0 = rng.uniform(-60, 60, m), rng.uniform(-40, 40, m)
    lines = [f"LINESTRING({float(x0[i])!r} {float(y0[i])!r}, "
             f"{float(x0[i]) + 0.5!r} {float(y0[i]) + 0.3!r})"
             for i in range(m)]
    ds.load("ways", FeatureTable.build(ds.get_schema("ways"), {
        "name": rng.choice(["a", "b"], m).astype(object),
        "dtg": BASE + rng.integers(0, 30 * DAY, m),
        "geom": lines}))
    yield ds, cols, vis
    if ds._scheduler is not None:
        ds._scheduler.shutdown()


def _iso(ms):
    return str(np.datetime64(int(ms), "ms")) + "Z"


def _box(rng):
    cx, cy = float(rng.uniform(-150, 150)), float(rng.uniform(-70, 70))
    hw, hh = (float(v) for v in rng.uniform(0.5, 12, 2))
    return f"BBOX(geom, {cx - hw!r}, {cy - hh!r}, {cx + hw!r}, {cy + hh!r})"


def _during(rng):
    lo = BASE + int(rng.integers(0, 20)) * DAY + int(rng.integers(0, DAY))
    return (f"dtg DURING {_iso(lo)}/"
            f"{_iso(lo + int(rng.integers(1, 9)) * DAY)}")


SHAPES = {
    "bbox": lambda r: _box(r),
    "bbox_during": lambda r: f"{_box(r)} AND {_during(r)}",
    "cmp_gt_Integer": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                                 f"v > {int(r.integers(1, 12))}"),
    "cmp_ge_Integer": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                                 f"v >= {int(r.integers(1, 12))}"),
    "cmp_lt_Integer": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                                 f"v < {int(r.integers(1, 12))}"),
    "cmp_eq_Integer": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                                 f"v = {int(r.integers(1, 12))}"),
    "cmp_gt_Float": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                               f"g > {float(r.integers(1, 40))!r}"),
    "cmp_lt_Float": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                               f"g < {float(r.integers(1, 40)) + 0.5!r}"),
    "in_Integer": lambda r: (
        f"{_box(r)} AND {_during(r)} AND v IN "
        f"({', '.join(str(int(x)) for x in r.integers(1, 30, 3))})"),
    "two_cmps": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                           f"v > {int(r.integers(1, 6))} AND "
                           f"g <= {float(r.integers(5, 40))!r}"),
}
# a Double lives on the device as a rounded f32: its comparison is the
# host's, the shape is not groupable and every request is planned in full
HOST_SHAPES = {
    "cmp_gt_Double": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                                f"w > {float(r.uniform(1, 90))!r}"),
    "cmp_ge_Double": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                                f"w >= {float(r.uniform(1, 90))!r}"),
    "cmp_lt_Double": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                                f"w < {float(r.uniform(1, 90))!r}"),
    "cmp_eq_Double": lambda r: (f"{_box(r)} AND {_during(r)} AND "
                                f"w = {float(r.uniform(1, 90))!r}"),
}
SEEDS = (1, 2, 3, 4)


def _same_array(a, b):
    if a is None or b is None:
        return a is None and b is None
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


def _template_for(ds, first, auths):
    """What ``_plan_request`` keeps of a shape's first plan."""
    planner = ds.planner("t")
    f0 = parse_ecql(first)
    base = planner._plan(f0)
    folded = planner._apply_auths(base, auths)
    assert _sched._groupable(folded)
    tmpl = bind.PlanTemplate.of(base, folded)
    assert tmpl is not None
    return planner, tmpl


def _assert_same_plan(got, ref, served=False):
    """``served``: a plan that ran alone carries its cover (``blocks`` and
    the cover's stats in ``explain``), which no fresh plan has."""
    assert got.index is ref.index
    assert got.primary_kind == ref.primary_kind
    assert _same_array(got.boxes_loose, ref.boxes_loose)
    assert _same_array(got.windows, ref.windows)
    assert (got.residual_device is None) == (ref.residual_device is None)
    if ref.residual_device is not None:
        assert got.residual_device[0] == ref.residual_device[0]
        assert len(got.residual_device[1]) == len(ref.residual_device[1])
        for a, b in zip(got.residual_device[1], ref.residual_device[1]):
            assert isinstance(a, np.ndarray) and _same_array(a, b)
    assert got.residual_host is None and ref.residual_host is None
    assert got.candidate_slices is None
    assert served or got.blocks is False
    assert got.cost == ref.cost and not got.empty
    assert got.full_filter == ref.full_filter
    assert got.explain["boxes"] == ref.explain["boxes"]
    assert got.explain["intervals"] == ref.explain["intervals"]
    assert got.explain["residual_device"] == ref.explain["residual_device"]
    assert {k: got.explain[k] for k in ref.explain} == ref.explain
    assert served or got.explain == ref.explain
    assert _sched._group_key(got) == _sched._group_key(ref)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("auths", AUTHS, ids=["noauths", "admin"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_bound_plan_equals_the_planners(world, shape, auths, seed):
    ds, _, _ = world
    rng = np.random.default_rng([seed, sorted(SHAPES).index(shape)])
    make = SHAPES[shape]
    planner, tmpl = _template_for(ds, make(rng), auths)
    index = tmpl.binder.index
    for _ in range(6):
        f = parse_ecql(make(rng))
        got = tmpl.bind(f)
        assert got is not None, str(f)
        ref = planner._apply_auths(planner._plan(f), auths)
        if ref.index is not index:
            # `_plan` priced another index cheaper for these values; a bound
            # plan keeps its shape's: compare on that index
            ref = planner._apply_auths(index.plan(f), auths)
        _assert_same_plan(got, ref)
        cover_got = index.cover_blocks(got.explain["boxes"],
                                       index.cover_intervals(got))
        cover_ref = index.cover_blocks(ref.explain["boxes"],
                                       index.cover_intervals(ref))
        assert _same_array(cover_got[0], cover_ref[0])
        assert cover_got[1] == cover_ref[1]


@pytest.fixture()
def sched(world):
    """A scheduler of its own over the store: its counters start at 0."""
    ds, _, _ = world
    s = QueryScheduler(StoreBinding(ds), result_cache=0)
    yield s
    s.shutdown()


def _plan_counts(s):
    return dict(s.stats()["plan"])


@pytest.mark.parametrize("auths", AUTHS, ids=["noauths", "admin"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_scheduler_binds_from_the_second_request_on(world, sched, shape,
                                                    auths):
    """Through ``_plan_request`` itself: the first request of a shape is
    planned and leaves the template, every later one is bound, and the
    plan a request carries equals the planner's."""
    ds, _, _ = world
    rng = np.random.default_rng([7, sorted(SHAPES).index(shape)])
    qs = [SHAPES[shape](rng) for _ in range(5)]
    first = sched.submit("t", qs[0], auths=auths)
    want0 = ds.count("t", qs[0], auths=auths)
    assert first.result(timeout=60) == want0
    assert not first.plan_bound
    assert _plan_counts(sched) == {"bound": 0, "full": 1, "bind_failed": 0}
    reqs = [sched.submit("t", q, auths=auths) for q in qs[1:]]
    got = [r.result(timeout=60) for r in reqs]
    assert got == [ds.count("t", q, auths=auths) for q in qs[1:]]
    assert _plan_counts(sched) == {"bound": 4, "full": 1, "bind_failed": 0}
    planner = ds.planner("t")
    for r in reqs:
        assert r.plan_bound and r.plan_cache_hit is False
        ref = planner._apply_auths(r.plan.index.plan(r.f_ir), auths)
        _assert_same_plan(r.plan, ref, served=True)
    # the exact key still serves a repeated filter the same plan object
    again = sched.submit("t", qs[2], auths=auths)
    assert again.result(timeout=60) == got[1]
    assert again.plan is reqs[1].plan and again.plan_cache_hit
    assert not again.plan_bound
    assert _plan_counts(sched)["bound"] == 4


@pytest.mark.parametrize("shape", sorted(HOST_SHAPES))
def test_a_double_comparison_is_the_hosts_and_is_planned_every_time(
        world, sched, shape):
    ds, _, _ = world
    rng = np.random.default_rng([9, sorted(HOST_SHAPES).index(shape)])
    qs = [HOST_SHAPES[shape](rng) for _ in range(3)]
    assert [sched.count("t", q) for q in qs] == [ds.count("t", q)
                                                 for q in qs]
    assert _plan_counts(sched) == {"bound": 0, "full": 3, "bind_failed": 0}


# -- drift goes to `_plan` ----------------------------------------------------

WEEK = "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
WARM = f"BBOX(geom, -10, -10, 10, 10) AND {WEEK} AND v > 3"


def _warm(s, q=WARM, type_name="t"):
    """Leave ``q``'s shape a template (where it is groupable)."""
    s.count(type_name, q)
    return _plan_counts(s)


DRIFTS = {
    # (the shape's first filter, a later one of the shape that does not bind)
    "antimeridian": (
        f"BBOX(geom, 150, -10, 170, 10) AND {WEEK}",
        f"BBOX(geom, 170, -10, -170, 10) AND {WEEK}"),
    "disjoint_bboxes": (
        f"BBOX(geom, -10, -10, 10, 10) AND BBOX(geom, 0, 0, 20, 20) "
        f"AND {WEEK}",
        f"BBOX(geom, -10, -10, 10, 10) AND BBOX(geom, 30, 30, 40, 40) "
        f"AND {WEEK}"),
    "during_without_overlap": (
        f"BBOX(geom, -10, -10, 10, 10) AND {WEEK} AND "
        "dtg DURING 2020-01-08T00:00:00Z/2020-01-20T00:00:00Z",
        f"BBOX(geom, -10, -10, 10, 10) AND {WEEK} AND "
        "dtg DURING 2020-01-14T00:00:00Z/2020-01-20T00:00:00Z"),
    "float_literal_on_an_Integer": (
        WARM, f"BBOX(geom, -20, -10, 10, 10) AND {WEEK} AND v > 3.5"),
    "in_list_of_fewer_known_codes": (
        f"BBOX(geom, -10, -10, 10, 10) AND {WEEK} AND "
        "code IN ('010', '020', '043')",
        f"BBOX(geom, -10, -10, 10, 10) AND {WEEK} AND "
        "code IN ('010', 'nope', 'never')"),
}


@pytest.mark.parametrize("case", sorted(DRIFTS))
def test_values_that_do_not_bind_are_planned_in_full(world, sched, case):
    ds, _, _ = world
    first, drifted = DRIFTS[case]
    c0 = _warm(sched, first)
    assert c0 == {"bound": 0, "full": 1, "bind_failed": 0}
    r = sched.submit("t", drifted)
    assert r.result(timeout=60) == ds.count("t", drifted)
    assert not r.plan_bound
    assert _plan_counts(sched) == {"bound": 0, "full": 2, "bind_failed": 1}
    # the shape still binds the values that do
    again = sched.submit("t", first.replace(", 10)", ", 11)", 1))
    again.result(timeout=60)
    assert again.plan_bound


def test_an_in_list_crossing_a_power_of_two_is_another_shape(world, sched):
    ds, _, _ = world
    q4 = f"BBOX(geom, -10, -10, 10, 10) AND {WEEK} AND v IN (1, 2, 3, 4)"
    q5 = f"BBOX(geom, -10, -10, 10, 10) AND {WEEK} AND v IN (1, 2, 3, 4, 5)"
    q3 = f"BBOX(geom, -12, -10, 10, 10) AND {WEEK} AND v IN (6, 7, 8)"
    assert bind.shape_key(parse_ecql(q4)) != bind.shape_key(parse_ecql(q5))
    assert bind.shape_key(parse_ecql(q4)) == bind.shape_key(parse_ecql(q3))
    for q in (q4, q5, q3):
        assert sched.count("t", q) == ds.count("t", q)
    # q5 met no template and left its own; q3 bound into q4's
    assert _plan_counts(sched) == {"bound": 1, "full": 2, "bind_failed": 0}


NEVER_BOUND = {
    "or": ("t", "BBOX(geom, -10, -10, 10, 10) OR BBOX(geom, 30, 5, 50, 25)",
           "BBOX(geom, -12, -10, 10, 10) OR BBOX(geom, 31, 5, 50, 25)"),
    "attribute_index": ("t", "code = '043'", "code = '190'"),
    "host_residual": (
        "ways", "INTERSECTS(geom, POLYGON((0 0, 10 0, 10 10, 0 10, 0 0)))",
        "INTERSECTS(geom, POLYGON((1 1, 12 0, 10 10, 0 10, 1 1)))"),
    "two_boxes": (
        "t", f"BBOX(geom, 170, -10, -170, 10) AND {WEEK}",
        f"BBOX(geom, 171, -10, -171, 10) AND {WEEK}"),
    "include": ("t", "INCLUDE", "INCLUDE"),
}


@pytest.mark.parametrize("case", sorted(NEVER_BOUND))
def test_a_shape_the_collector_would_not_group_is_planned_every_time(
        world, sched, case):
    ds, _, _ = world
    type_name, q1, q2 = NEVER_BOUND[case]
    want = [ds.count(type_name, q) for q in (q1, q2)]
    reqs = []
    for q in (q1, q2):
        reqs.append(sched.submit(type_name, q))
        reqs[-1].result(timeout=60)
    assert [r.result() for r in reqs] == want
    assert not any(r.plan_bound for r in reqs)
    full = 1 if q1 == q2 else 2   # the exact key serves a repeated filter
    assert _plan_counts(sched) == {"bound": 0, "full": full,
                                   "bind_failed": 0}
    if case != "include":
        assert not reqs[0].batched or reqs[0].batch_size == 1


def test_a_fid_filter_has_no_shape_and_is_planned(world, sched):
    ds, _, _ = world
    fids = [str(f) for f in ds.tables["t"].fids[:3]]
    for fid in fids:
        assert sched.count("t", ir.FidFilter((fid,))) == 1
    assert _plan_counts(sched) == {"bound": 0, "full": 3, "bind_failed": 0}


class _Veto(QueryInterceptor):
    def guard(self, plan, f, sft):
        boxes = plan.explain.get("boxes") or ()
        return "too wide" if any(b[2] - b[0] > 50 for b in boxes) else None


def test_a_planner_with_an_interceptor_is_never_bound(world, sched):
    """A guard may veto by value: the template is by-passed from the moment
    the planner has one, also for a shape that already had its template."""
    ds, _, _ = world
    _warm(sched)
    bound = sched.submit("t", WARM.replace("-10, -10", "-9, -10"))
    bound.result(timeout=60)
    assert bound.plan_bound
    ds.add_interceptor("t", _Veto())
    try:
        ok = sched.submit("t", WARM.replace("-10, -10", "-8, -10"))
        assert ok.result(timeout=60) == ds.count(
            "t", WARM.replace("-10, -10", "-8, -10"))
        assert not ok.plan_bound
        wide = sched.submit("t", WARM.replace("-10, -10", "-80, -10"))
        with pytest.raises(Exception, match="too wide"):
            wide.result(timeout=60)
    finally:
        ds._interceptors["t"].clear()
    assert _plan_counts(sched) == {"bound": 1, "full": 3, "bind_failed": 0}


def test_no_template_outlives_a_write(world):
    """A write moves the generation: the next request of the shape meets
    no template, is planned, leaves a new one, and the acknowledged rows
    are in the answer."""
    ds = TpuDataStore()
    ds.create_schema("t", SPEC)
    ds.load("t", FeatureTable.build(ds.get_schema("t"), _columns(8_000, 21)))
    s = ds.scheduler()
    try:
        qs = [f"BBOX(geom, {-60 - i}, -50, 60, 50) AND {WEEK} AND v > 2"
              for i in range(4)]
        a, b = (s.submit("t", q) for q in qs[:2])
        na, nb = a.result(timeout=60), b.result(timeout=60)
        assert not a.plan_bound and b.plan_bound
        extra = _columns(3_000, 22)
        extra["dtg"] = np.full(3_000, BASE + 6 * DAY)     # inside WEEK
        extra["geom"] = (np.zeros(3_000), np.zeros(3_000))
        extra["v"] = np.full(3_000, 9, dtype=np.int32)
        ds.load("t", FeatureTable.build(ds.get_schema("t"), extra))
        c = s.submit("t", qs[2])
        nc = c.result(timeout=60)
        assert c.generation != a.generation
        assert not c.plan_bound, "the old generation's template was used"
        d = s.submit("t", qs[3])
        assert d.result(timeout=60) == ds.count("t", qs[3])
        assert d.plan_bound and d.plan.index is c.plan.index
        assert nc == ds.count("t", qs[2]) and nc >= nb + 3_000 > na
        assert s.stats()["plan"] == {"bound": 2, "full": 2,
                                     "bind_failed": 0}
    finally:
        s.shutdown()


# -- served exactness ---------------------------------------------------------


def test_256_distinct_counts_are_exact_and_bound(world, sched):
    """Box x week x threshold, all distinct, submitted together: every
    answer equals numpy over the raw columns; all but the shape's first
    few are bound (the first cycle's requests meet no template yet)."""
    ds, cols, _ = world
    rng = np.random.default_rng(77)
    x, y = cols["geom"]
    qs, want = [], []
    for i in range(256):
        cx, cy = float(rng.uniform(-150, 150)), float(rng.uniform(-70, 70))
        hw, hh = (float(v) for v in rng.uniform(2, 30, 2))
        box = (cx - hw, cy - hh, cx + hw, cy + hh)
        lo = BASE + int(rng.integers(0, 4)) * 7 * DAY
        thr = int(rng.choice([2, 5, 10]))
        qs.append(f"BBOX(geom, {box[0]!r}, {box[1]!r}, {box[2]!r}, "
                  f"{box[3]!r}) AND dtg DURING {_iso(lo)}/"
                  f"{_iso(lo + 7 * DAY)} AND v > {thr}")
        want.append(int(np.sum(
            (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
            & (cols["dtg"] > lo) & (cols["dtg"] < lo + 7 * DAY)
            & (cols["v"] > thr))))
    assert len(set(qs)) == 256
    c0 = REGISTRY.snapshot()["counters"]
    _warm(sched, f"BBOX(geom, -1, -1, 1, 1) AND {WEEK} AND v > 1")
    reqs = [sched.submit("t", q) for q in qs]
    assert [r.result(timeout=120) for r in reqs] == want
    counts = _plan_counts(sched)
    assert counts["bound"] >= 250 and counts["bind_failed"] == 0
    assert counts["bound"] + counts["full"] == 257
    assert any(r.batched and r.batch_size > 1 for r in reqs)
    c1 = REGISTRY.snapshot()["counters"]
    assert c1["sched.plan.bound"] - c0.get("sched.plan.bound", 0) \
        == counts["bound"]
    assert c1["sched.plan.full"] - c0.get("sched.plan.full", 0) \
        == counts["full"]
