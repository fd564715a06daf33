"""Stats subsystem tests: sketch correctness, merge, serialization, DSL,
estimation, and cost-based planning (SURVEY.md §2.5 parity)."""

import numpy as np
import pytest

from geomesa_tpu import stats as st
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.stats.dsl import observe_table, parse_stat


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="module")
def store(rng):
    n = 20_000
    ds = TpuDataStore()
    ds.create_schema("pts", "name:String,val:Int,score:Double,dtg:Date,*geom:Point")
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    table = FeatureTable.build(ds.get_schema("pts"), {
        "name": rng.choice(["alpha", "beta", "gamma", "delta"], n, p=[0.5, 0.3, 0.15, 0.05]),
        "val": rng.integers(0, 1000, n).astype(np.int32),
        "score": rng.normal(50, 10, n),
        "dtg": base + rng.integers(0, 28 * 86400000, n),
        # clustered points so spatial selectivity is non-uniform
        "geom": (np.clip(rng.normal(10, 30, n), -180, 180),
                 np.clip(rng.normal(20, 15, n), -90, 90)),
    })
    ds.load("pts", table)
    return ds


# -- sketches ----------------------------------------------------------------


def test_count_and_merge():
    a, b = st.CountStat(), st.CountStat()
    a.observe(np.arange(10))
    b.observe(5)
    a += b
    assert a.count == 15
    assert st.from_dict(a.to_dict()).count == 15


def test_minmax_numeric(rng):
    vals = rng.integers(-500, 500, 5000)
    mm = st.MinMaxStat("v")
    mm.observe(vals)
    assert mm.min == vals.min() and mm.max == vals.max()
    # HLL cardinality within 10% of the true unique count
    true = len(np.unique(vals))
    assert abs(mm.cardinality - true) / true < 0.1


def test_minmax_strings_and_merge():
    a, b = st.MinMaxStat("s"), st.MinMaxStat("s")
    a.observe(np.array(["kiwi", "apple"], dtype=object))
    b.observe(np.array(["zebra", "mango"], dtype=object))
    a += b
    assert a.min == "apple" and a.max == "zebra"
    rt = st.from_dict(a.to_dict())
    assert rt.min == "apple" and rt.max == "zebra"


def test_enumeration_exact(rng):
    vals = rng.choice(["x", "y", "z"], 1000, p=[0.6, 0.3, 0.1])
    e = st.EnumerationStat("a")
    e.observe(vals)
    assert e.counts == {v: int(c) for v, c in
                        zip(*np.unique(vals, return_counts=True))}


def test_topk(rng):
    # heavy hitters survive; zipf-ish tail
    vals = np.concatenate([
        np.repeat("big", 5000), np.repeat("mid", 1000),
        rng.choice([f"t{i}" for i in range(500)], 2000)])
    rng.shuffle(vals)
    tk = st.TopKStat("a")
    for chunk in np.array_split(vals, 7):
        tk.observe(chunk)
    top = tk.topk(2)
    assert top[0][0] == "big" and top[1][0] == "mid"
    assert top[0][1] >= 5000  # space-saving overestimates, never under


def test_frequency_countmin(rng):
    vals = np.concatenate([np.repeat(7, 3000), rng.integers(100, 10000, 10000)])
    fr = st.FrequencyStat("a")
    fr.observe(vals)
    est = fr.estimate(7)
    assert est >= 3000            # count-min never underestimates
    assert est <= 3000 + 200      # and the overshoot is bounded at this width
    halves = np.array_split(vals, 2)
    f1, f2 = st.FrequencyStat("a"), st.FrequencyStat("a")
    f1.observe(halves[0])
    f2.observe(halves[1])
    f1 += f2
    assert f1.estimate(7) == est  # merge == bulk (deterministic hashing)


def test_histogram_mass(rng):
    vals = rng.uniform(0, 100, 20000)
    h = st.HistogramStat("a", 50, 0, 100)
    h.observe(vals)
    assert int(h.counts.sum()) == 20000
    mass = h.mass_between(25, 75)
    assert abs(mass - 10000) < 300
    rt = st.from_dict(h.to_dict())
    assert np.array_equal(rt.counts, h.counts)


def test_z2histogram_box_mass(rng):
    x = rng.uniform(-180, 180, 30000)
    y = rng.uniform(-90, 90, 30000)
    z = st.Z2HistogramStat("geom", 5)
    z.observe(x, y)
    true = int(np.sum((x >= -30) & (x <= 30) & (y >= -20) & (y <= 20)))
    est = z.mass_in_box(-30, -20, 30, 20)
    assert abs(est - true) / true < 0.1


def test_z3histogram_windows(rng):
    from geomesa_tpu.curves.binnedtime import TimePeriod, max_offset, time_to_binned_time
    period = TimePeriod.parse("week")
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    ms = base + rng.integers(0, 28 * 86400000, 20000)
    bins, offs = time_to_binned_time(ms, period)
    zh = st.Z3HistogramStat("dtg", "week")
    zh.observe(bins, offs, max_offset(period))
    assert zh.total == 20000
    lo = base + 7 * 86400000
    hi = base + 14 * 86400000
    true = int(np.sum((ms >= lo) & (ms <= hi)))
    blo, olo = time_to_binned_time(np.int64(lo), period)
    bhi, ohi = time_to_binned_time(np.int64(hi), period)
    est = zh.mass_in_windows([(int(blo), int(olo), int(bhi), int(ohi))],
                             max_offset(period))
    assert abs(est - true) / true < 0.1


def test_descriptive_stats(rng):
    a = rng.normal(10, 2, 5000)
    b = 3 * a + rng.normal(0, 1, 5000)
    d = st.DescriptiveStat(["a", "b"])
    halves = [(a[:2500], b[:2500]), (a[2500:], b[2500:])]
    d1, d2 = st.DescriptiveStat(["a", "b"]), st.DescriptiveStat(["a", "b"])
    d1.observe(*halves[0])
    d2.observe(*halves[1])
    d1 += d2
    d.observe(a, b)
    np.testing.assert_allclose(d.mean, [a.mean(), b.mean()], rtol=1e-9)
    np.testing.assert_allclose(d.covariance, np.cov(a, b), rtol=1e-6)
    np.testing.assert_allclose(d1.mean, d.mean, rtol=1e-9)


def test_groupby(rng):
    g = st.GroupByStat("cat", "Count()")
    g.observe(np.array(["a", "b", "a", "a"], dtype=object))
    g.observe(np.array(["b"], dtype=object))
    assert g.groups["a"].count == 3 and g.groups["b"].count == 2
    rt = st.from_dict(g.to_dict())
    assert rt.groups["a"].count == 3


# -- DSL ---------------------------------------------------------------------


def test_dsl_roundtrip():
    specs = ['Count()', 'MinMax("dtg")', 'Enumeration("name")', 'TopK("name")',
             'Frequency("name",12)', 'Histogram("val",20,0.0,100.0)',
             'Z2Histogram("geom",5)', 'Z3Histogram("dtg","week")',
             'DescriptiveStats("a","b")', 'GroupBy("cat",Count())']
    for spec in specs:
        stat = parse_stat(spec)
        assert parse_stat(stat.spec()).kind == stat.kind
    seq = parse_stat("Count();MinMax('val')")
    assert seq.kind == "seq" and len(seq.stats) == 2


def test_observe_table(store):
    table = store.tables["pts"]
    seq = parse_stat('Count();MinMax("val");Enumeration("name")')
    observe_table(seq, table)
    assert seq.stats[0].count == len(table)
    vals = np.asarray(table.columns["val"])
    assert seq.stats[1].min == int(vals.min())
    assert sum(seq.stats[2].counts.values()) == len(table)


def test_observe_table_dictionary_column_equals_per_row(store):
    """String columns observe each distinct value once, weighted by its
    code count: the sketch state must equal the per-row observation."""
    table = store.tables["pts"]
    col = table.columns["name"]
    raw = np.asarray(col.vocab, dtype=object)[col.codes]
    for spec in ('MinMax("name")', 'Frequency("name",12)', 'TopK("name")'):
        fast, slow = parse_stat(spec), parse_stat(spec)
        observe_table(fast, table)
        slow.observe(raw)
        assert fast.to_dict() == slow.to_dict(), spec


# -- GeoMesaStats API + estimation -------------------------------------------


def test_store_stats_api(store):
    s = store.stats("pts")
    n = len(store.tables["pts"])
    assert s.get_count() == n
    assert s.get_count(exact=True) == n
    xmin, ymin, xmax, ymax = s.get_bounds()
    x, y = store.tables["pts"].geometry().point_xy()
    assert (xmin, ymax) == (x.min(), y.max())
    mm = s.get_min_max("val")
    assert mm.min == int(np.min(store.tables["pts"].columns["val"]))
    tk = s.get_top_k("name")
    assert tk.topk(1)[0][0] == "alpha"


def test_estimated_count_close(store):
    s = store.stats("pts")
    ecql = "BBOX(geom, -20, 5, 40, 35)"
    est = s.get_count(ecql)
    exact = s.get_count(ecql, exact=True)
    assert exact > 0
    assert abs(est - exact) / exact < 0.25  # grid-resolution error envelope


def test_estimated_spatiotemporal(store):
    s = store.stats("pts")
    ecql = ("BBOX(geom, -20, 5, 40, 35) AND "
            "dtg DURING 2020-01-07T00:00:00Z/2020-01-14T00:00:00Z")
    est = s.get_count(ecql)
    exact = s.get_count(ecql, exact=True)
    assert exact > 0
    assert abs(est - exact) / exact < 0.35  # independence assumption + grids


def test_exact_stat_scan_filtered(store):
    s = store.stats("pts")
    e = s.run_stat('Enumeration("name")', "val < 100")
    exact = store.count("pts", "val < 100")
    assert sum(e.counts.values()) == exact


def test_histogram_api(store):
    s = store.stats("pts")
    h = s.get_histogram("val", bins=10)
    assert int(h.counts.sum()) == len(store.tables["pts"])


def test_cost_based_decider_runs(store):
    # stats present → pricing path executes and still picks the z3 index
    plan = store.planner("pts").plan(
        "BBOX(geom, -20, 5, 40, 35) AND "
        "dtg DURING 2020-01-07T00:00:00Z/2020-01-14T00:00:00Z")
    assert plan.index.name == "z3"


def test_one_sided_dtg_estimate_fast(store):
    # open-ended interval → astronomically wide bin span; must not iterate it
    import time
    s = store.stats("pts")
    t0 = time.perf_counter()
    est = s.get_count("dtg > 2020-01-07T00:00:00Z")
    assert time.perf_counter() - t0 < 2.0
    exact = s.get_count("dtg > 2020-01-07T00:00:00Z", exact=True)
    assert abs(est - exact) / exact < 0.15


def test_remove_and_recreate_schema():
    ds = TpuDataStore()
    ds.create_schema("t", "val:Int,*geom:Point")
    ds.load("t", FeatureTable.build(ds.get_schema("t"),
                                    {"val": [1], "geom": ([0.0], [0.0])}))
    ds.remove_schema("t")
    ds.create_schema("t", "other:Int,*geom:Point")
    ds.load("t", FeatureTable.build(ds.get_schema("t"),
                                    {"other": [2], "geom": ([1.0], [1.0])}))
    assert ds.stats("t").get_min_max("other").min == 2


def test_histogram_on_string_returns_none(store):
    assert store.stats("pts").get_histogram("name") is None


def test_groupby_seq_substat(store):
    g = parse_stat('GroupBy("name",Count();MinMax("val"))')
    observe_table(g, store.tables["pts"])
    total = sum(sub.stats[0].count for sub in g.groups.values())
    assert total == len(store.tables["pts"])
    assert all(sub.stats[1].min >= 0 for sub in g.groups.values())


def test_stats_persistence_roundtrip(store):
    from geomesa_tpu.stats.store import GeoMesaStats
    s = store.stats("pts")
    d = s.to_dict()
    rt = GeoMesaStats.from_dict(store.get_schema("pts"), d, planner=s.planner)
    assert rt.total == s.total
    assert rt.get_bounds() == s.get_bounds()
