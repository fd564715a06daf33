"""Aggregating-scan tests: BIN encoding, device stats scan, sampling, hints
dispatch (SURVEY.md §2.4 iterators parity)."""

import numpy as np
import pytest

from geomesa_tpu.aggregates.bin import BIN_DTYPE, BIN_LABEL_DTYPE, decode_bin
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features.table import FeatureTable


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(9)
    n = 10000
    base = np.datetime64("2022-01-01T00:00:00", "ms").astype(np.int64)
    return {
        "track": rng.choice(["t1", "t2", "t3", "t4"], n).astype(object),
        "val": rng.integers(0, 100, n).astype(np.int32),
        "dtg": base + rng.integers(0, 7 * 86400000, n),
        "x": rng.uniform(-90, 90, n),
        "y": rng.uniform(-45, 45, n),
    }


@pytest.fixture(scope="module")
def store(data):
    ds = TpuDataStore()
    ds.create_schema("tr", "track:String,val:Int,dtg:Date,*geom:Point")
    ds.load("tr", FeatureTable.build(ds.get_schema("tr"), {
        "track": data["track"], "val": data["val"], "dtg": data["dtg"],
        "geom": (data["x"], data["y"])}))
    return ds


ECQL = "BBOX(geom, -50, -20, 50, 30) AND val < 60"


def _ref_mask(data):
    return ((data["x"] >= -50) & (data["x"] <= 50)
            & (data["y"] >= -20) & (data["y"] <= 30) & (data["val"] < 60))


# -- BIN ---------------------------------------------------------------------


def test_bin_records(store, data):
    recs = store.query("tr", ECQL, hints={"bin": {"track": "track"}})
    ref = _ref_mask(data)
    assert recs.dtype == BIN_DTYPE
    assert len(recs) == int(ref.sum())
    assert recs.dtype.itemsize == 16
    # lat/lon round-trip through f32
    np.testing.assert_allclose(np.sort(recs["lon"]),
                               np.sort(data["x"][ref].astype(np.float32)))
    # same track value -> same id
    ids_by_track = {}
    rows = np.nonzero(ref)[0]
    for rid, tr in zip(recs["track"], data["track"][rows]):
        ids_by_track.setdefault(tr, set()).add(rid)
    assert all(len(s) == 1 for s in ids_by_track.values())
    assert len(set.union(*ids_by_track.values())) == len(ids_by_track)


def test_bin_labelled_sorted(store, data):
    recs = store.query("tr", ECQL, hints={
        "bin": {"track": "track", "label": "val", "sort": True}})
    assert recs.dtype == BIN_LABEL_DTYPE and recs.dtype.itemsize == 24
    assert np.all(np.diff(recs["dtg"]) >= 0)
    wire = recs.tobytes()
    back = decode_bin(wire, labelled=True)
    assert np.array_equal(back, recs)


# -- device stats scan -------------------------------------------------------


def test_stats_hint_count_histogram(store, data):
    ref = _ref_mask(data)
    seq = store.query("tr", ECQL, hints={
        "stats": 'Count();Histogram("val",10,0,100);Enumeration("track")'})
    assert seq.stats[0].count == int(ref.sum())
    # histogram: only vals < 60 -> top 4 bins empty
    assert int(seq.stats[1].counts.sum()) == int(ref.sum())
    assert np.all(seq.stats[1].counts[6:] == 0)
    uniq, cnt = np.unique(data["track"][ref], return_counts=True)
    assert seq.stats[2].counts == {v: int(c) for v, c in zip(uniq, cnt)}


def test_stats_hint_z2_and_groupby(store, data):
    ref = _ref_mask(data)
    seq = store.query("tr", ECQL, hints={
        "stats": 'Z2Histogram("geom",5);GroupBy("track",Count())'})
    assert int(seq.stats[0].counts.sum()) == int(ref.sum())
    uniq, cnt = np.unique(data["track"][ref], return_counts=True)
    assert {k: v.count for k, v in seq.stats[1].groups.items()} == \
        {v: int(c) for v, c in zip(uniq, cnt)}


def test_stats_mixed_device_host(store, data):
    # MinMax takes the host path, Count the device path — same spec string
    ref = _ref_mask(data)
    seq = store.query("tr", ECQL, hints={"stats": 'Count();MinMax("val")'})
    assert seq.stats[0].count == int(ref.sum())
    assert seq.stats[1].max == int(data["val"][ref].max())


def test_device_stats_match_host_full_table(store, data):
    seq = store.query("tr", "INCLUDE", hints={"stats": 'Count();Enumeration("track")'})
    assert seq.stats[0].count == len(data["val"])
    assert sum(seq.stats[1].counts.values()) == len(data["val"])


# -- sampling ----------------------------------------------------------------


def test_sampling(store, data):
    full = store.query("tr", ECQL)
    s = store.query("tr", ECQL, hints={"sample": 10})
    assert len(s.indices) == int(np.ceil(full.count / 10))
    assert np.all(np.isin(s.indices, full.indices))


def test_sampling_by_track(store, data):
    s = store.query("tr", ECQL, hints={"sample": {"n": 50, "by": "track"}})
    # every track that matched must survive the per-group sampling
    ref = _ref_mask(data)
    tracks_in = set(np.unique(data["track"][ref]))
    got = set(s.table.column("track").vocab[c] for c in s.table.column("track").codes)
    assert got == tracks_in


def test_density_respects_attribute_index_plan():
    # when the attribute index wins planning, the attr predicate lives in
    # candidate_slices — density must NOT take a device mask missing it
    ds = TpuDataStore()
    ds.create_schema("dd", "track:String:index=true,dtg:Date,*geom:Point")
    rng = np.random.default_rng(1)
    n = 1000
    base = np.datetime64("2022-01-01", "ms").astype(np.int64)
    tr = rng.choice(["a", "b"], n).astype(object)
    ds.load("dd", FeatureTable.build(ds.get_schema("dd"), {
        "track": tr, "dtg": base + rng.integers(0, 86400000, n),
        "geom": (rng.uniform(-10, 10, n), rng.uniform(-10, 10, n))}))
    q = "track = 'a' AND BBOX(geom, -10, -10, 10, 10)"
    d = ds.query("dd", q, hints={"density": {"bbox": (-10, -10, 10, 10),
                                             "width": 16, "height": 16}})
    assert float(d.weights.sum()) == ds.count("dd", q) == int(np.sum(tr == "a"))


def test_unknown_hint_raises(store):
    with pytest.raises(ValueError):
        store.query("tr", "INCLUDE", hints={"bogus": 1})


def test_prepare_density_matches_oneshot(store, data):
    """Prepared density == one-shot density, and repeated calls reuse the
    staged plan (the r2 bench re-planned per call at ~1s/query)."""
    from geomesa_tpu.aggregates.density import density, prepare_density
    planner = store.planner("tr")
    bbox = (-60, -30, 60, 30)
    f = "BBOX(geom, -60, -30, 60, 30)"
    pd = prepare_density(planner, f, bbox, 32, 16)
    g1 = pd()
    g2 = density(planner, f, bbox, 32, 16)
    np.testing.assert_allclose(g1.weights, g2.weights)
    assert hasattr(pd, "dispatch")  # async device path was chosen
    # pipelined dispatches agree with blocking
    outs = [pd.dispatch() for _ in range(4)]
    for o in outs:
        np.testing.assert_allclose(np.asarray(o), g1.weights)


_MASS_FILTERS = {
    "bbox": ("BBOX(geom, -50, -20, 50, 30)", lambda d: True),
    "bbox_during": (
        "BBOX(geom, -50, -20, 50, 30) AND dtg DURING "
        "2022-01-02T00:00:00Z/2022-01-05T00:00:00Z",
        lambda d: (d["dtg"] > np.datetime64("2022-01-02", "ms").astype(np.int64))
        & (d["dtg"] < np.datetime64("2022-01-05", "ms").astype(np.int64))),
    "bbox_residual": ("BBOX(geom, -50, -20, 50, 30) AND val < 60",
                      lambda d: d["val"] < 60),
}


@pytest.mark.parametrize("case", sorted(_MASS_FILTERS))
def test_density_mass_is_the_count_of_the_same_filter(store, data, case):
    """Every row a filter matches lands in exactly one cell of a grid that
    holds the filter's box, and no other row lands in any: the grid's mass
    is the count of the same filter (and the brute-force count)."""
    from geomesa_tpu.aggregates.density import prepare_density
    ecql, rest = _MASS_FILTERS[case]
    want = int(np.sum((data["x"] >= -50) & (data["x"] <= 50)
                      & (data["y"] >= -20) & (data["y"] <= 30) & rest(data)))
    assert want > 0
    grid = prepare_density(store.planner("tr"), ecql,
                           (-60, -30, 60, 40), 64, 32)()
    assert int(grid.weights.sum(dtype=np.float64)) == want
    assert store.count("tr", ecql) == want


def test_density_pruned_blocks_path(monkeypatch):
    """Range-pruned density (block gather + scatter) matches the host grid."""
    from geomesa_tpu.index import prune
    monkeypatch.setattr(prune, "BLOCK_SIZE", 256)
    monkeypatch.setattr(prune, "PRUNE_MAX_FRACTION", 1.0)
    import numpy as np
    from geomesa_tpu.aggregates.density import density, _host_density
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.features.table import FeatureTable
    rng = np.random.default_rng(23)
    n = 40_000
    x = np.clip(rng.normal(0, 30, n), -180, 180)
    y = np.clip(rng.normal(0, 15, n), -90, 90)
    w = rng.uniform(0, 2, n)
    ds = TpuDataStore()
    ds.create_schema("dp", "w:Double,*geom:Point")
    ds.load("dp", FeatureTable.build(ds.get_schema("dp"),
                                     {"w": w, "geom": (x, y)}))
    planner = ds.planner("dp")
    f = "BBOX(geom, -20, -10, 20, 10)"
    bbox = (-20.0, -10.0, 20.0, 10.0)
    plan = planner.plan(f)
    assert planner._pruned_blocks(plan) is not None  # pruned path engaged
    g = density(planner, f, bbox, 64, 32)
    ref = _host_density(planner, f, planner.plan(f), bbox, 64, 32, None, None)
    # f32 snap vs f64 snap can disagree for points within float error of a
    # cell edge; compare masses and near-equality of the grid
    assert abs(g.weights.sum() - ref.weights.sum()) <= 2
    assert np.sum(np.abs(g.weights - ref.weights)) <= 4


def test_density_weight_attr_not_on_device_uses_host(store, data):
    """A weight attribute with no usable numeric device column must take the
    exact host path, not silently weight by 1.0 (or by dict codes)."""
    from geomesa_tpu.aggregates.density import prepare_density
    planner = store.planner("tr")
    # no weight -> device path
    run = prepare_density(planner, "INCLUDE", (-30, -30, 30, 30), 8, 8,
                          weight_attr=None)
    assert hasattr(run, "dispatch")
    # 'dtg' has no device column (bin/off planes carry it) -> host path
    run2 = prepare_density(planner, "INCLUDE", (-30, -30, 30, 30), 8, 8,
                           weight_attr="dtg")
    assert not hasattr(run2, "dispatch")
    # 'track' is a String column (device dict codes are NOT weights) -> host
    run3 = prepare_density(planner, "INCLUDE", (-30, -30, 30, 30), 8, 8,
                           weight_attr="track")
    assert not hasattr(run3, "dispatch")
