"""Range-pruned scan execution: exactness parity vs the full-table scan and
brute force, plus the touched-fraction contract (a selective query must scan
a small fraction of rows — the ≙ of the reference's ≤2000-range scans)."""

import numpy as np
import pytest

from geomesa_tpu.features.geometry import LINESTRING, GeometryArray
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.index import prune
from geomesa_tpu.index.planner import QueryPlanner
from geomesa_tpu.index.spatial import XZ2Index, XZ3Index, Z2Index, Z3Index


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    # tiny blocks + relaxed fraction gate: at unit-test scale the per-block
    # row count amplifies the scanned fraction (the cover's candidate-row
    # slop is scale-free — pinned below — but block granularity is not), so
    # the 25% gate that protects real tables would decline here
    monkeypatch.setattr(prune, "BLOCK_SIZE", 256)
    monkeypatch.setattr(prune, "PRUNE_MAX_FRACTION", 1.0)


def _z3_setup(n=60_000, seed=5):
    rng = np.random.default_rng(seed)
    x = np.clip(rng.normal(0, 60, n), -180, 180)
    y = np.clip(rng.normal(0, 30, n), -90, 90)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    dtg = base + rng.integers(0, 30 * 86400000, n)
    sft = SimpleFeatureType.from_spec(
        "t", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
    table = FeatureTable.build(sft, {"dtg": dtg, "geom": (x, y)})
    return sft, table, x, y, dtg


Q = ("BBOX(geom, -10, 30, 10, 45) AND "
     "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")


def _brute(x, y, dtg):
    lo = np.datetime64("2020-01-05", "ms").astype(np.int64)
    hi = np.datetime64("2020-01-12", "ms").astype(np.int64)
    return (x >= -10) & (x <= 10) & (y >= 30) & (y <= 45) & (dtg > lo) & (dtg < hi)


def test_z3_pruned_parity_and_fraction():
    sft, table, x, y, dtg = _z3_setup()
    idx = Z3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])

    plan = planner.plan(Q)
    blocks = planner._pruned_blocks(plan)
    assert blocks is not None and len(blocks) > 0, "pruning did not engage"
    frac = plan.explain["candidate_rows"] / len(table)
    assert frac < 0.02, f"cover slop: {frac:.1%} candidate rows"

    rows = planner.select_indices(Q, plan=plan)
    expected = np.flatnonzero(_brute(x, y, dtg))
    np.testing.assert_array_equal(rows, expected)
    assert planner.count(Q) == len(expected)

    # prepared (async) pruned count agrees
    pq = planner.prepare(Q)
    assert pq.count() == len(expected)
    assert int(pq.count_async()) == len(expected)


def test_explain_counts_the_blocks_the_scan_reads():
    """``explain`` of a pruned plan reports the scan as it runs: the number
    of candidate blocks is the number gathered, the scanned rows are those
    blocks' rows, and every matching row lies in one of them."""
    sft, table, x, y, dtg = _z3_setup()
    idx = Z3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    plan = planner.plan(Q)
    blocks = planner._pruned_blocks(plan)
    assert plan.explain["candidate_blocks"] == len(blocks) > 0
    assert plan.explain["scanned_rows"] == len(blocks) * prune.BLOCK_SIZE
    assert plan.explain["scanned_fraction"] == round(
        len(blocks) * prune.BLOCK_SIZE / len(table), 5)
    matched = _brute(x, y, dtg)
    assert 0 < matched.sum() <= plan.explain["candidate_rows"]
    positions = np.flatnonzero(matched[idx.perm])   # in sorted order
    assert np.isin(positions // prune.BLOCK_SIZE, blocks).all()


def test_z3_pruned_vs_full_scan(monkeypatch):
    sft, table, x, y, dtg = _z3_setup(seed=9)
    idx = Z3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    pruned = planner.select_indices(Q)
    monkeypatch.setenv("GEOMESA_TPU_PRUNE", "0")
    full = planner.select_indices(Q)
    np.testing.assert_array_equal(pruned, full)


def test_z3_spatial_only_pruning():
    """A bbox-only query on a temporal index must still prune (the
    unconstrained-interval sentinel is NOT a temporal constraint)."""
    sft, table, x, y, dtg = _z3_setup()
    idx = Z3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    q = "BBOX(geom, -5, 32, 5, 40)"
    plan = planner.plan(q)
    blocks = planner._pruned_blocks(plan)
    assert blocks is not None and len(blocks) > 0, "spatial-only did not prune"
    rows = planner.select_indices(q, plan=plan)
    expected = np.flatnonzero((x >= -5) & (x <= 5) & (y >= 32) & (y <= 40))
    np.testing.assert_array_equal(rows, expected)


def test_z2_pruned_parity():
    rng = np.random.default_rng(3)
    n = 50_000
    x = np.clip(rng.normal(0, 50, n), -180, 180)
    y = np.clip(rng.normal(0, 25, n), -90, 90)
    sft = SimpleFeatureType.from_spec("p", "*geom:Point")
    table = FeatureTable.build(sft, {"geom": (x, y)})
    idx = Z2Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    q = "BBOX(geom, -8, 20, 12, 40)"
    plan = planner.plan(q)
    blocks = planner._pruned_blocks(plan)
    assert blocks is not None and len(blocks) > 0
    rows = planner.select_indices(q, plan=plan)
    expected = np.flatnonzero((x >= -8) & (x <= 12) & (y >= 20) & (y <= 40))
    np.testing.assert_array_equal(rows, expected)


def test_xz2_pruned_parity():
    rng = np.random.default_rng(11)
    n = 40_000
    lx = rng.uniform(-170, 160, n)
    ly = rng.uniform(-80, 75, n)
    shapes = [(LINESTRING, [[lx[i], ly[i]],
                            [lx[i] + 0.5, ly[i] + 0.4]]) for i in range(n)]
    sft = SimpleFeatureType.from_spec("l", "*geom:LineString")
    table = FeatureTable.build(sft, {"geom": GeometryArray.from_shapes(shapes)})
    idx = XZ2Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    q = "BBOX(geom, -10, 20, 10, 40)"
    plan = planner.plan(q)
    blocks = planner._pruned_blocks(plan)
    assert blocks is not None and len(blocks) > 0
    assert plan.explain["candidate_rows"] / len(table) < 0.10
    rows = planner.select_indices(q, plan=plan)
    # envelope-overlap semantics for extents
    expected = np.flatnonzero((lx <= 10) & (lx + 0.5 >= -10)
                              & (ly <= 40) & (ly + 0.4 >= 20))
    np.testing.assert_array_equal(rows, expected)


def test_xz3_pruned_parity():
    rng = np.random.default_rng(13)
    n = 40_000
    lx = rng.uniform(-170, 160, n)
    ly = rng.uniform(-80, 75, n)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    dtg = base + rng.integers(0, 30 * 86400000, n)
    shapes = [(LINESTRING, [[lx[i], ly[i]],
                            [lx[i] + 0.5, ly[i] + 0.4]]) for i in range(n)]
    sft = SimpleFeatureType.from_spec(
        "l3", "dtg:Date,*geom:LineString;geomesa.z3.interval=week")
    table = FeatureTable.build(
        sft, {"dtg": dtg, "geom": GeometryArray.from_shapes(shapes)})
    idx = XZ3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    q = ("BBOX(geom, -10, 20, 10, 40) AND "
         "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
    plan = planner.plan(q)
    blocks = planner._pruned_blocks(plan)
    assert blocks is not None and len(blocks) > 0
    rows = planner.select_indices(q, plan=plan)
    lo = np.datetime64("2020-01-05", "ms").astype(np.int64)
    hi = np.datetime64("2020-01-12", "ms").astype(np.int64)
    expected = np.flatnonzero((lx <= 10) & (lx + 0.5 >= -10)
                              & (ly <= 40) & (ly + 0.4 >= 20)
                              & (dtg > lo) & (dtg < hi))
    np.testing.assert_array_equal(rows, expected)


def test_empty_cover_is_exact():
    """A bbox far from all data: pruning yields zero blocks, count 0."""
    sft, table, x, y, dtg = _z3_setup(n=30_000)
    idx = Z3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    # x is clipped normal(0,60): nothing within a tiny box at a specific spot
    q = ("BBOX(geom, 179.99, -89.99, 179.995, -89.985) AND "
         "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
    expected = int(np.sum((x >= 179.99) & (x <= 179.995)
                          & (y >= -89.99) & (y <= -89.985)))
    assert planner.count(q) == expected
    pq = planner.prepare(q)
    assert pq.count() == expected


def test_wide_query_declines_pruning():
    """A whole-world bbox must keep the fused full-table scan."""
    sft, table, x, y, dtg = _z3_setup(n=30_000)
    idx = Z3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    plan = planner.plan("BBOX(geom, -180, -90, 180, 90)")
    assert planner._pruned_blocks(plan) is None
    assert planner.count("BBOX(geom, -180, -90, 180, 90)") == len(x)


def test_fraction_gate_declines(monkeypatch):
    """With the production fraction gate, a broad query (high candidate
    fraction at this block granularity) falls back to the full scan."""
    monkeypatch.setattr(prune, "PRUNE_MAX_FRACTION", 0.25)
    sft, table, x, y, dtg = _z3_setup(n=30_000)
    idx = Z3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    plan = planner.plan("BBOX(geom, -90, -45, 90, 45)")
    assert planner._pruned_blocks(plan) is None
    rows = planner.select_indices("BBOX(geom, -90, -45, 90, 45)")
    expected = np.flatnonzero((x >= -90) & (x <= 90) & (y >= -45) & (y <= 45))
    np.testing.assert_array_equal(rows, expected)


def test_counts_multi_blocks_parity():
    """Batched per-box counts over union candidate blocks == individual
    pruned counts."""
    sft, table, x, y, dtg = _z3_setup()
    idx = Z3Index(sft, table)
    planner = QueryPlanner(sft, table, [idx])
    qs = [f"BBOX(geom, {-10+i}, {30+i}, {10+i}, {45+i}) AND "
          "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
          for i in range(5)]
    plans = [planner.plan(q) for q in qs]
    blist = [planner._pruned_blocks(p) for p in plans]
    assert all(b is not None for b in blist)
    union = np.unique(np.concatenate([b for b in blist if len(b)]))
    boxes = np.concatenate([p.boxes_loose[:1] for p in plans], axis=0)
    counts = idx.kernels.counts_multi_blocks(
        "point_boxes", boxes, plans[0].windows, plans[0].residual_device,
        union, prune.BLOCK_SIZE)
    singles = [planner.count(q) for q in qs]
    np.testing.assert_array_equal(counts, singles)
