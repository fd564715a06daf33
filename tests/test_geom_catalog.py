"""Geometry function catalog (geom/): kernels, push-down, joins.

Three contracts, one suite:

  * parity — every st_* kernel agrees with the f64 host oracle on a
    randomized mixed corpus (degenerate rings, dateline-adjacent shapes,
    empty row sets included): boolean predicates pin EXACT (banded f32
    classify + host refine of the uncertain sliver), scalars pin within
    their documented forward-error bounds. ``parity_report`` axes all 0.
  * push-down — function queries produce identical counts/selections
    through the fused single-dispatch program, the staged planner path,
    and the host evaluator (toggling FUSED_QUERY / GEOM_KERNELS), with
    eligible Func residuals costing ONE device round per cold query.
  * distribution — the 2-process CPU dryrun's join battery and st_*
    function counts come back byte-equal to the single-process oracle,
    plus the workload plane's ``funcs`` dimension counting each function
    once per query (no call-site double-count).
"""

import json

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.features import geometry as geo
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.filter.evaluate import evaluate
from geomesa_tpu.filter.parser import parse_ecql
from geomesa_tpu.geom import catalog, oracle
from geomesa_tpu.index import compiled as fused
from geomesa_tpu.index.planner import QueryPlanner
from geomesa_tpu.index.scan import ROUNDS
from geomesa_tpu.index.spatial import Z3Index


def _unshadow_block_size():
    from geomesa_tpu.index import prune
    vars(prune).pop("BLOCK_SIZE", None)


# -- mixed corpus: the parity torture set ------------------------------------


def _mixed_shapes(rng, n=160):
    """Points, rings, lines — including degenerate (zero-area) rings,
    collinear runs, and dateline-adjacent coordinates."""
    shapes = []
    for i in range(n):
        kind = i % 8
        cx = float(rng.uniform(-175, 175))
        cy = float(rng.uniform(-85, 85))
        if kind == 0:
            shapes.append((geo.POINT, [cx, cy]))
        elif kind == 1:  # dateline-adjacent point
            shapes.append((geo.POINT, [float(rng.uniform(179.0, 180.0))
                                       * (1 if i % 2 else -1), cy]))
        elif kind == 2:  # convex-ish polygon
            k = int(rng.integers(4, 9))
            ang = np.sort(rng.uniform(0, 2 * np.pi, k))
            r = rng.uniform(0.5, 4.0, k)
            ring = [[cx + float(r[j] * np.cos(ang[j])),
                     cy + float(r[j] * np.sin(ang[j]))] for j in range(k)]
            ring.append(ring[0])
            shapes.append((geo.POLYGON, [ring]))
        elif kind == 3:  # degenerate ring: zero-area sliver
            ring = [[cx, cy], [cx + 2.0, cy], [cx, cy]]
            ring.append(ring[0])
            shapes.append((geo.POLYGON, [ring]))
        elif kind == 4:  # axis-aligned box near the dateline
            w, h = float(rng.uniform(0.1, 2)), float(rng.uniform(0.1, 2))
            x0 = float(rng.uniform(176.0, 178.0)) * (1 if i % 2 else -1)
            x1, y0 = x0 + w * (0.1 if x0 > 0 else 1.0), cy
            ring = [[x0, y0], [x1, y0], [x1, y0 + h], [x0, y0 + h],
                    [x0, y0]]
            shapes.append((geo.POLYGON, [ring]))
        elif kind == 5:  # linestring
            k = int(rng.integers(2, 6))
            pts = [[cx + float(rng.uniform(-3, 3)),
                    cy + float(rng.uniform(-3, 3))] for _ in range(k)]
            shapes.append((geo.LINESTRING, pts))
        elif kind == 6:  # collinear linestring (degenerate hull)
            shapes.append((geo.LINESTRING,
                           [[cx + j * 0.5, cy + j * 0.25]
                            for j in range(4)]))
        else:  # tiny triangle
            ring = [[cx, cy], [cx + 0.01, cy], [cx, cy + 0.01], [cx, cy]]
            shapes.append((geo.POLYGON, [ring]))
    return shapes


LITERAL = (geo.POLYGON, [[[-30.0, -20.0], [30.0, -20.0], [30.0, 25.0],
                          [-30.0, 25.0], [-30.0, -20.0]]])


@pytest.mark.parametrize("seed", [3, 11, 29])
def test_kernel_vs_oracle_parity_pins_zero(seed):
    rng = np.random.default_rng(seed)
    arr = geo.GeometryArray.from_shapes(_mixed_shapes(rng))
    rows = np.arange(len(arr), dtype=np.int64)
    rep = catalog.parity_report(arr, rows, LITERAL)
    assert all(v == 0 for v in rep.values()), rep


def test_parity_on_empty_row_set():
    arr = geo.GeometryArray.from_shapes(_mixed_shapes(
        np.random.default_rng(0), 16))
    rep = catalog.parity_report(arr, np.array([], dtype=np.int64), LITERAL)
    assert all(v == 0 for v in rep.values()), rep


def test_buffer_bound_is_documented_and_holds():
    """st_buffer's approximation contract: the octagon circumscribes the
    true d-disk (contains it) and overshoots the radius by at most the
    documented sec(pi/8) - 1 ≈ 8.24%."""
    rng = np.random.default_rng(5)
    arr = geo.GeometryArray.from_shapes(_mixed_shapes(rng, 64))
    rows = np.arange(len(arr), dtype=np.int64)
    d = 0.25
    for shp in catalog.kernel_buffers(arr, rows, d):
        assert shp is not None
    assert abs(oracle.BUFFER_OVERSHOOT - (1.0 / np.cos(np.pi / 8) - 1.0)) \
        < 1e-12
    offs = oracle.octagon_offsets(d)
    radii = np.hypot(offs[:, 0], offs[:, 1])
    # vertices at the circumradius, edge midpoints at >= d: contains disk
    assert np.allclose(radii, d * oracle.BUFFER_SEC)
    mids = (offs + np.roll(offs, 1, axis=0)) / 2.0
    assert np.all(np.hypot(mids[:, 0], mids[:, 1]) >= d - 1e-12)


# -- three-way parity: fused / staged / host ---------------------------------


@pytest.fixture(scope="module")
def world():
    _unshadow_block_size()
    config.PRUNE_BLOCK.set(512)
    try:
        rng = np.random.default_rng(7)
        n = 6000
        sft = SimpleFeatureType.from_spec(
            "gc", "name:String,val:Int,dtg:Date,*geom:Point;"
            "geomesa.z3.interval=week")
        base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
        table = FeatureTable.build(sft, {
            "name": rng.choice(["a", "b", "c"], n),
            "val": rng.integers(0, 100, n).astype(np.int32),
            "dtg": base + rng.integers(0, 30 * 86400000, n),
            "geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n))})
        planner = QueryPlanner(sft, table, [Z3Index(sft, table)])
    finally:
        config.PRUNE_BLOCK.unset()
    return planner, table


@pytest.fixture(autouse=True)
def _fused_on():
    _unshadow_block_size()
    config.PRUNE_BLOCK.set(512)
    config.FUSED_QUERY.set(True)
    yield
    config.PRUNE_BLOCK.unset()
    config.FUSED_QUERY.unset()
    config.GEOM_KERNELS.unset()


FUNC_QUERIES = [
    "st_distance(geom, POINT(10 10)) < 15",
    "st_distance(geom, POINT(-120 40)) <= 8",
    "st_contains(POLYGON((-40 -30, 20 -30, 20 20, -40 20, -40 -30)), geom)",
    "st_intersects(geom, POLYGON((0 0, 60 0, 30 50, 0 0)))",
    "st_distance(geom, POINT(10 10)) < 25 AND val < 50",
    "st_area(st_buffer(geom, 2.0)) > 10",
    "st_length(st_convexHull(st_buffer(geom, 1.0))) > 5",
]


def _three_way(planner, table, q):
    """count/select through fused, staged-with-kernels, staged-host —
    all three must agree exactly."""
    host = evaluate(parse_ecql(q), table)
    outs = {}
    for label, (fq, gk) in {"fused": (True, True),
                            "staged": (False, True),
                            "host": (False, False)}.items():
        config.FUSED_QUERY.set(fq)
        config.GEOM_KERNELS.set(gk)
        try:
            outs[label] = (planner.count(q), planner.select_indices(q))
        finally:
            config.FUSED_QUERY.set(True)
            config.GEOM_KERNELS.unset()
    for label, (c, s) in outs.items():
        assert c == int(host.sum()), (q, label, c, int(host.sum()))
        assert np.array_equal(s, np.flatnonzero(host)), (q, label)


@pytest.mark.parametrize("q", FUNC_QUERIES)
def test_func_query_three_way_parity(q, world):
    planner, table = world
    _three_way(planner, table, q)


def test_eligible_func_residual_fuses_single_dispatch(world):
    """dispatches-per-cold-query 1.0: an eligible Func residual executes
    INSIDE the fused program — one device round, no fallback."""
    planner, table = world
    shape = "st_distance(geom, POINT({x} 10)) < 9"
    planner.prepare(shape.format(x=12)).count()   # register the recipe
    f0 = fused.STATS["fallbacks"]
    snap = ROUNDS.snapshot()
    n = planner.prepare(shape.format(x=-31.5)).count()
    assert ROUNDS.rounds_since(snap) == 1
    assert fused.STATS["fallbacks"] == f0
    host = evaluate(parse_ecql(shape.format(x=-31.5)), table)
    assert n == int(host.sum())


def test_ineligible_func_counts_fallback_and_stays_exact(world):
    """A Func shape the fused lowering can't serve (nested FuncExpr in the
    residual) falls back staged, counted in STATS.fallbacks, exact."""
    planner, table = world
    q = "BBOX(geom, -60, -40, 60, 40) AND st_area(st_buffer(geom, 2.0)) > 10"
    f0 = fused.STATS["fallbacks"]
    c = planner.count(q)
    assert fused.STATS["fallbacks"] > f0
    assert c == int(evaluate(parse_ecql(q), table).sum())


def test_union_select_and_density_lowering(world):
    """Satellite: Or-of-covers plans lower to ONE fused dispatch for
    select and density, byte-equal to the staged path / host grid."""
    planner, table = world
    q = ("BBOX(geom, -60, -40, -10, 10) AND val < 70"
         " OR BBOX(geom, 20, -10, 70, 45) AND val >= 30")
    host = evaluate(parse_ecql(q), table)
    rows = planner.select_indices(q)
    assert np.array_equal(rows, np.flatnonzero(host))

    from geomesa_tpu.aggregates.density import host_grid, prepare_density
    bbox = (-180.0, -90.0, 180.0, 90.0)
    g = prepare_density(planner, q, bbox, 64, 32)()
    expect = host_grid(table, np.flatnonzero(host), bbox, 64, 32)
    assert np.array_equal(g.weights, expect)


# -- surfaces ----------------------------------------------------------------


def test_projection_columns_wkt_and_scalars(world):
    planner, table = world
    from geomesa_tpu.geom.functions import projection_columns
    rows = np.arange(8)
    cols = projection_columns(
        table, rows,
        "st_centroid(geom) AS c, st_distance(geom, POINT(0 0)) AS d, val")
    assert list(cols) == ["c", "d", "val"]
    assert all(w.startswith("POINT") for w in cols["c"])
    x, y = table.column("geom").point_xy()
    want = np.hypot(x[rows], y[rows])
    assert np.allclose(cols["d"], want, atol=2e-3)
    assert cols["val"] == list(np.asarray(table.column("val"))[rows])


def test_jsonquery_func_ops_match_ecql(world):
    planner, table = world
    from geomesa_tpu.web.jsonquery import parse_json_query
    sft = planner.sft
    jq = {"geometry": {"$stDistance": {
        "$geometry": {"type": "Point", "coordinates": [10, 10]},
        "$lt": 15}}}
    f = parse_json_query(json.dumps(jq), sft)
    want = evaluate(parse_ecql("st_distance(geom, POINT(10 10)) < 15"),
                    table)
    assert np.array_equal(evaluate(f, table), want)
    jq2 = {"geometry": {"$stContains": {"$geometry": {
        "type": "Polygon",
        "coordinates": [[[-40, -30], [20, -30], [20, 20], [-40, 20],
                         [-40, -30]]]}}}}
    f2 = parse_json_query(json.dumps(jq2), sft)
    want2 = evaluate(parse_ecql(
        "st_contains(POLYGON((-40 -30, 20 -30, 20 20, -40 20, -40 -30)),"
        " geom)"), table)
    assert np.array_equal(evaluate(f2, table), want2)


# -- workload plane: the funcs dimension -------------------------------------


def test_workload_funcs_dimension_no_double_count():
    """One query touching st_distance twice and st_centroid once counts
    each function ONCE (funcs_of dedups at IR level), and distinct st_*
    shapes hash to distinct plan entries."""
    from geomesa_tpu.filter import ir
    f = parse_ecql("st_distance(geom, POINT(0 0)) < 5 AND "
                   "st_distance(st_centroid(geom), POINT(1 1)) < 9")
    assert ir.funcs_of(f) == ("st_centroid", "st_distance")

    from geomesa_tpu.obs.workload import WorkloadAnalytics
    w = WorkloadAnalytics(meter=False)
    for i, q in enumerate([
            "st_distance(geom, POINT(0 0)) < 5",
            "st_distance(geom, POINT(0 0)) < 5",
            "st_contains(POLYGON((0 0, 1 0, 1 1, 0 1, 0 0)), geom)"]):
        w._fold_event({"ts_ms": 1000.0 + i,
                       "plan_hash": f"p{hash(q) & 0xffff}",
                       "funcs": list(ir.funcs_of(parse_ecql(q)))})
    hs = w.hot_set()
    funcs = {e["key"]: e["count"] for e in hs["funcs"]}
    assert funcs == {"st_distance": 2, "st_contains": 1}, funcs
    plans = [e["key"] for e in hs["plans"]]
    assert len(set(plans)) == 2


def test_workload_funcs_state_roundtrip():
    from geomesa_tpu.obs.workload import (WorkloadAnalytics, merge_states)
    w = WorkloadAnalytics(meter=False)
    w._fold_event({"ts_ms": 1.0, "funcs": ["st_area"]})
    st = w.export_state()
    merged = merge_states([st, st])
    view = WorkloadAnalytics.from_state(merged)
    funcs = {e["key"]: e["count"] for e in view.hot_set()["funcs"]}
    assert funcs == {"st_area": 2}


# -- the 2-process join drill ------------------------------------------------


def test_join_single_process_oracle_matches_host():
    """spatial_join under an inactive runtime IS the oracle: counts and
    pair fid lists match a direct host evaluation of the same predicate."""
    from geomesa_tpu.cluster.dryrun import (JOIN_POLYGONS, build_local,
                                            inactive_runtime)
    from geomesa_tpu.geom.join import spatial_join

    rt = inactive_runtime()
    _, planner, scan, fids_sorted, _ = build_local(rt, 3000, 11)
    res = spatial_join(planner, JOIN_POLYGONS, "st_contains",
                       runtime=rt, fids=fids_sorted)
    for j, poly in enumerate(JOIN_POLYGONS):
        host = evaluate(parse_ecql(f"st_contains({poly}, geom)"),
                        planner.table)
        assert res.counts[j] == int(host.sum())
        assert len(res.pairs[j]) == res.counts[j]
    assert res.rows_global == 3000


@pytest.fixture(scope="module")
def join_dryrun():
    from geomesa_tpu.cluster.dryrun import run_dryrun
    report = run_dryrun(num_processes=2, n=4000, seed=13,
                        timeout_s=300, web=False)
    assert report["exit_codes"] == [0, 0], json.dumps(
        {k: report[k] for k in ("exit_codes", "checks", "work_dir")},
        indent=1)
    return report


def test_two_process_join_byte_equal_to_oracle(join_dryrun):
    """The acceptance drill: both ranks' join battery (psum counts +
    rank-order-merged pairs) and st_* function counts byte-equal the
    single-process oracle."""
    ch = join_dryrun["checks"]
    assert ch["join_equal"], json.dumps(ch, indent=1)
    assert ch["func_counts_equal"], json.dumps(ch, indent=1)
    oracle_join = join_dryrun["ranks"][0]["battery"]["join"]
    for op in ("st_contains", "st_intersects"):
        st = oracle_join[op]
        assert st["rows_global"] == 4000
        assert [len(p) for p in st["pairs"]] == \
            [min(c, 200) for c in st["counts"]]


def test_two_process_join_used_collectives(join_dryrun):
    """The workers actually went through the mesh: psum rounds counted on
    every rank, every rank held a strict subset of the corpus, and both
    join ops report the two processes they ran across."""
    for r in join_dryrun["ranks"]:
        assert r["psum_rounds"] > 0
        assert 0 < r["local_rows"] < 4000
        meta = r["battery"]["join_meta"]
        assert {op: m["num_processes"] for op, m in meta.items()} == {
            "st_contains": 2, "st_intersects": 2}
