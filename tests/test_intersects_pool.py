"""INTERSECTS(geom, POLYGON) over the segment pool: the kernel's certain hits
and misses agree with f64, only the uncertain sliver refines on the host, and
the served count equals the benchmark's plain reference on OSM's grid."""

import importlib.util
import json
import os
import urllib.request
from urllib.parse import quote

import numpy as np
import pytest

from geomesa_tpu.features import geometry as geo
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.filter import geom_batch
from geomesa_tpu.filter.geom_numpy import literal_segments
from geomesa_tpu.filter.parser import parse_ecql
from geomesa_tpu.index import prune, scan
from geomesa_tpu.index.planner import QueryPlanner
from geomesa_tpu.index.spatial import XZ2Index, XZ3Index
from geomesa_tpu.metrics import REGISTRY

POLY = "POLYGON ((-12 30, 10 28, 14 44, -2 50, -12 30))"
Q = f"INTERSECTS(geom, {POLY})"
OSM_SCHEMA = ("user:String,tags:String,dtg:Date,*geom:Geometry:srid=4326;"
              "geomesa.indices=xz2")
COUNTERS = ("refine.segments_tested", "refine.ways_candidate",
            "refine.ways_uncertain", "refine.overflow_fallbacks")
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(prune, "BLOCK_SIZE", 256)
    monkeypatch.setattr(prune, "PRUNE_MAX_FRACTION", 1.0)


def _bench_module(kind, name):
    """benchmark/<kind>/<name>.py, loaded by path as run.py loads it."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def osm():
    return _bench_module("data", "osm_ways")


def _counters():
    got = REGISTRY.snapshot()["counters"]
    return {k: got.get(k, 0) for k in COUNTERS}


def _gained(before):
    return {k: v - before[k] for k, v in _counters().items()}


def _planner(garr, spec="*geom:Geometry", cls=XZ2Index, extra=None):
    sft = SimpleFeatureType.from_spec("l", spec)
    table = FeatureTable.build(sft, {"geom": garr, **(extra or {})})
    idx = cls(sft, table)
    return QueryPlanner(sft, table, [idx]), idx


def _one_segment(n=40_000, seed=2):
    rng = np.random.default_rng(seed)
    x0 = rng.uniform(-60, 60, n)
    y0 = rng.uniform(0, 70, n)
    coords = np.empty((2 * n, 2))
    coords[0::2, 0], coords[0::2, 1] = x0, y0
    coords[1::2, 0] = x0 + rng.uniform(-2, 2, n)
    coords[1::2, 1] = y0 + rng.uniform(-2, 2, n)
    return geo.GeometryArray.linestrings(coords)


def _under_one_block():
    return _one_segment(n=100, seed=4)


def _mixed_vertex_counts():
    rng = np.random.default_rng(7)
    shapes = [(geo.LINESTRING, [[0, 0], [1, 1], [2, 0]])] * 100
    shapes += [(geo.LINESTRING,
                [[rng.uniform(-50, 50), rng.uniform(-50, 50)],
                 [rng.uniform(-50, 50), rng.uniform(-50, 50)]])
               for _ in range(5000)]
    return geo.GeometryArray.from_shapes(shapes)


def _walks(n=6000, seed=11):
    """Ways of 2 to 2,000 vertices, and one longer than a pool tile."""
    rng = np.random.default_rng(seed)
    nodes = np.minimum(2 + np.floor(rng.lognormal(1.4, 1.0, n)),
                       2000).astype(np.int64)
    nodes[:3] = (2000, 2, scan.POOL_TILE + 1500)
    off = np.zeros(n + 1, np.int64)
    np.cumsum(nodes, out=off[1:])
    steps = rng.normal(0, 0.05, (off[-1], 2))
    steps[off[:-1]] = np.stack([rng.uniform(-40, 40, n),
                                rng.uniform(10, 60, n)], axis=1)
    c = np.cumsum(steps, axis=0)
    coords = c - np.repeat(c[off[:-1]] - steps[off[:-1]], nodes, axis=0)
    return geo.GeometryArray.linestrings(coords, off)


def _mixed_types(n=6000, seed=13):
    """Lines, closed polygons (some wide enough to hold the query polygon,
    one with a hole around it), multi-part lines and polygons, points."""
    rng = np.random.default_rng(seed)
    shapes = []
    for i in range(n):
        c = np.array([rng.uniform(-60, 60), rng.uniform(0, 70)])
        w, h = rng.uniform(0.2, 3.0, 2)
        box = [c, c + [w, 0], c + [w, h], c + [0, h], c]
        box = [list(map(float, p)) for p in box]
        far = [[p[0] + 25.0, p[1] + 9.0] for p in box]
        kind = i % 6
        if kind == 0:
            shapes.append((geo.POLYGON, [box]))
        elif kind == 1:
            shapes.append((geo.MULTIPOLYGON, [[box], [far]]))
        elif kind == 2:
            shapes.append((geo.MULTILINESTRING, [box[:3], far[:2]]))
        elif kind == 3:
            shapes.append((geo.MULTIPOINT, [box[0], far[0]]))
        else:
            k = int(rng.integers(2, 40))
            pts = c + np.cumsum(rng.normal(0, 0.3, (k, 2)), axis=0)
            shapes.append((geo.LINESTRING, pts.tolist()))
    shapes += [
        # holds the whole query polygon: no boundary of the two meets
        (geo.POLYGON, [[[-30, 10], [30, 10], [30, 65], [-30, 65],
                        [-30, 10]]]),
        # the same with a hole the query polygon lies in: disjoint
        (geo.POLYGON, [[[-31, 9], [31, 9], [31, 66], [-31, 66], [-31, 9]],
                       [[-20, 20], [20, 20], [20, 60], [-20, 60],
                        [-20, 20]]]),
        # two parts, one inside the query polygon, one far off
        (geo.MULTIPOLYGON, [[[[0, 40], [1, 40], [1, 41], [0, 40]]],
                            [[[50, 0], [51, 0], [51, 1], [50, 0]]]]),
        # two parts, both outside, bbox over the polygon
        (geo.MULTILINESTRING, [[[-40, 5], [-39, 6]], [[40, 60], [41, 61]]]),
    ]
    return geo.GeometryArray.from_shapes(shapes)


def _brute(garr, q=Q, rows=None):
    fir = parse_ecql(q)
    rows = np.arange(len(garr)) if rows is None else rows
    return geom_batch.batch_intersects(garr, rows, fir.geometry)


@pytest.mark.parametrize("layer", [_one_segment, _under_one_block,
                                   _mixed_vertex_counts, _walks,
                                   _mixed_types], ids=lambda f: f.__name__)
def test_band_count_matches_exact(layer):
    """One path for every extent layer: the one-segment layer is the pool's
    smallest case, long ways and mixed geometry types the general."""
    garr = layer()
    planner, idx = _planner(garr)
    assert idx.seg_off is not None
    before = _counters()
    plan = planner.plan(Q)
    fast = planner._band_intersects_count(plan)
    assert fast is not None, "band path did not engage"
    want = int(_brute(garr).sum())
    assert fast == want > 0
    got = _gained(before)
    assert got["refine.ways_candidate"] >= want
    assert got["refine.segments_tested"] >= got["refine.ways_candidate"]
    assert got["refine.overflow_fallbacks"] == 0
    # the public count() takes the same value
    assert planner.count(Q) == fast


@pytest.mark.parametrize("layer", [_one_segment, _walks, _mixed_types],
                         ids=lambda f: f.__name__)
def test_certain_and_uncertain_cover_every_way_and_certain_agrees_with_f64(
        layer):
    """Block by block: the ways the kernel left uncertain are handed back,
    and among the others its certain hits are exactly the f64 hits, so a
    certain miss is an f64 miss."""
    garr = layer()
    planner, idx = _planner(garr)
    plan = planner.plan(Q)
    edges = literal_segments(plan.residual_host.geometry).astype(np.float32)
    box = (-12.0, 28.0, 14.0, 50.0)
    bb = garr.bboxes()[idx.perm]
    overlaps = ((bb[:, 0] <= box[2]) & (bb[:, 2] >= box[0])
                & (bb[:, 1] <= box[3]) & (bb[:, 3] >= box[1]))
    truth = _brute(garr)[idx.perm]
    bsz, seen_unc = prune.BLOCK_SIZE, 0
    for b in range(-(-len(garr) // bsz)):
        certain, unc, facts = idx.kernels.intersects_band_blocks(
            plan.primary_kind, plan.boxes_loose, plan.windows,
            plan.residual_device, edges, np.array([b], dtype=np.int32), bsz,
            idx.seg_off)
        rows = np.arange(b * bsz, min((b + 1) * bsz, len(garr)))
        assert facts["candidate_ways"] == int(overlaps[rows].sum())
        assert np.all((unc >= rows[0]) & (unc <= rows[-1]))
        assert np.all(overlaps[unc])
        settled = np.setdiff1d(rows[overlaps[rows]], unc)
        assert certain == int(truth[settled].sum())
        seen_unc += len(unc)
    assert seen_unc < 0.05 * overlaps.sum() + 20


def test_band_way_longer_than_a_tile_is_split_not_declined():
    garr = _walks()
    planner, idx = _planner(garr)
    longest = int(np.argmax(np.diff(idx.seg_off)))
    assert idx.seg_off[longest + 1] - idx.seg_off[longest] > scan.POOL_TILE
    # a polygon around the long way alone: its row, and whatever shares it
    xy = garr.feature_coords(int(idx.perm[longest]))
    x0, y0 = xy.min(axis=0) - 0.01
    x1, y1 = xy.max(axis=0) + 0.01
    q = (f"INTERSECTS(geom, POLYGON (({x0} {y0}, {x1} {y0}, {x1} {y1}, "
         f"{x0} {y1}, {x0} {y0})))")
    plan = planner.plan(q)
    assert planner._band_intersects_count(plan) == int(
        _brute(garr, q).sum()) > 0


def test_band_block_list_past_one_launch_goes_out_in_several(monkeypatch):
    monkeypatch.setattr(scan, "BAND_MAX_BLOCKS", 8)
    garr = _one_segment()
    planner, idx = _planner(garr)
    plan = planner.plan(Q)
    blocks = planner._pruned_blocks(plan)
    launches = list(scan.band_launches(blocks, prune.BLOCK_SIZE, idx.seg_off,
                                       len(garr)))
    assert len(blocks) > 8 and len(launches) > 1
    assert max(t[0] for *_, t in launches) == 8
    assert planner._band_intersects_count(plan) == int(_brute(garr).sum())


def test_band_xz3_and_a_device_residual_share_the_path():
    garr = _one_segment(n=8000)
    rng = np.random.default_rng(3)
    dtg = np.datetime64("2020-01-01", "ms").astype(np.int64) \
        + rng.integers(0, 20 * 86_400_000, len(garr))
    val = rng.integers(0, 10, len(garr)).astype(np.int32)
    planner, idx = _planner(garr, "val:Integer,dtg:Date,*geom:LineString",
                            XZ3Index, {"dtg": dtg, "val": val})
    q = (f"{Q} AND val > 4 AND dtg DURING "
         f"2020-01-03T00:00:00Z/2020-01-12T00:00:00Z")
    plan = planner.plan(q)
    fast = planner._band_intersects_count(plan)
    assert fast is not None
    t0, t1 = (np.datetime64(t, "ms").astype(np.int64)
              for t in ("2020-01-03", "2020-01-12"))
    want = _brute(garr) & (val > 4) & (dtg > t0) & (dtg < t1)
    assert fast == int(want.sum()) == planner.count(q)


def _boundary_layer():
    """Segments touching the polygon exactly, among random ones."""
    # polygon edge from (-12,30) to (10,28): midpoint lies on the edge
    mid = ((-12 + 10) / 2, (30 + 28) / 2)
    crafted = [
        # endpoint exactly ON an edge midpoint, rest outside
        [[mid[0], mid[1]], [mid[0], mid[1] - 5.0]],
        # endpoint exactly on a polygon vertex
        [[-12.0, 30.0], [-20.0, 20.0]],
        # collinear overlap with an edge segment
        [[-12.0, 30.0], [10.0, 28.0]],
        # fully inside
        [[0.0, 40.0], [1.0, 41.0]],
        # fully outside, near-ish
        [[30.0, 30.0], [31.0, 31.0]],
    ]
    rng = np.random.default_rng(5)
    # pad with random segments so the table crosses the pruning size gate
    n = 10_000
    x0 = rng.uniform(-60, 60, n)
    y0 = rng.uniform(0, 70, n)
    pads = [[[x0[i], y0[i]], [x0[i] + 0.5, y0[i] + 0.5]] for i in range(n)]
    return geo.GeometryArray.from_shapes(
        [(geo.LINESTRING, s) for s in crafted + pads])


def test_band_boundary_cases_route_to_host():
    """Segments touching the polygon exactly (vertex-on-edge, endpoint-on-
    vertex, collinear overlap) classify as uncertain and the host refine
    keeps the count exact."""
    garr = _boundary_layer()
    planner, _ = _planner(garr)
    before = _counters()
    plan = planner.plan(Q)
    fast = planner._band_intersects_count(plan)
    assert fast is not None
    assert fast == int(_brute(garr).sum())
    assert _gained(before)["refine.ways_uncertain"] >= 3
    # the first four crafted segments all intersect; the fifth does not
    assert list(_brute(garr, rows=np.arange(5))) == [
        True, True, True, True, False]


def test_unc_cap_overflow_falls_back_and_counts_exactly(monkeypatch):
    garr = _boundary_layer()        # three ways at the least are uncertain
    planner, idx = _planner(garr)
    real = idx.kernels.intersects_band_blocks
    monkeypatch.setattr(
        idx.kernels, "intersects_band_blocks",
        lambda *a, **kw: real(*a, unc_cap=2, **kw))
    before = _counters()
    plan = planner.plan(Q)
    assert planner._band_intersects_count(plan) is None
    assert _gained(before)["refine.overflow_fallbacks"] == 1
    # the caller refines every candidate on the host instead
    assert planner.count(Q) == int(_brute(garr).sum())
    assert _gained(before)["refine.overflow_fallbacks"] == 2


# -- OSM's grid: the benchmark's reference, through the served path ----------


def _ring_cql(ring) -> str:
    wkt = ", ".join(f"{x / 1e7:.7f} {y / 1e7:.7f}"
                    for x, y in list(ring) + [ring[0]])
    return f"INTERSECTS(geom, POLYGON(({wkt})))"


def _with_ways(corpus, ways, closed=()):
    """The corpus with hand-made ways (lists of grid vertices) appended;
    those whose number is in ``closed`` are buildings."""
    out = dict(corpus)
    xs = [np.asarray(w, dtype=np.int32) for w in ways]
    out["xi"] = np.concatenate([corpus["xi"]] + [w[:, 0] for w in xs])
    out["yi"] = np.concatenate([corpus["yi"]] + [w[:, 1] for w in xs])
    out["off"] = np.concatenate([corpus["off"], corpus["off"][-1]
                                 + np.cumsum([len(w) for w in xs])])
    k = len(ways)
    out["closed"] = np.concatenate(
        [corpus["closed"], [i in closed for i in range(k)]])
    out["dtg"] = np.concatenate([corpus["dtg"], corpus["dtg"][:k]])
    for name in ("user", "tags"):
        codes, vocab = corpus[name]
        out[name] = (np.concatenate([codes, codes[:k]]), vocab)
    return out


class Served:
    """A store of the benchmark's OSM corpus behind web.serve."""

    def __init__(self, osm, corpus):
        from geomesa_tpu import web
        from geomesa_tpu.datastore import DataStoreFinder
        self.ds = DataStoreFinder.get_data_store(type="tpu")
        osm.load(self.ds, corpus, "osm", OSM_SCHEMA)
        self.httpd = web.serve(self.ds, host="127.0.0.1", port=0,
                               background=True)
        self.port = self.httpd.server_address[1]

    def get(self, path):
        url = f"http://127.0.0.1:{self.port}{path}"
        with urllib.request.urlopen(url) as r:
            return json.loads(r.read())

    def post(self, path, doc):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", method="POST",
            data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read())

    def count(self, ring):
        return self.get("/types/osm/count?cql=" + quote(_ring_cql(ring)))

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.ds.close()


H = 1_500_000                     # the made polygon: 0.3 degrees across
MADE = {  # name → (way from the polygon's centre, is a building, intersects)
    "crossing_once": (lambda cx, cy: [(cx, cy), (cx + 3 * H, cy + 11)],
                      False, 1),
    "crossing_2000_nodes": (lambda cx, cy: np.stack(
        [cx - 2 * H + np.arange(2000) * 2000,
         cy + (np.arange(2000) % 2) * 3000], axis=1), False, 1),
    "touching_a_vertex": (lambda cx, cy: [(cx - H, cy - H),
                                          (cx - 2 * H, cy - 2 * H)],
                          False, 1),
    "touching_an_edge": (lambda cx, cy: [(cx - H, cy), (cx - 2 * H, cy + 7)],
                         False, 1),
    "one_cell_short_of_the_edge": (
        lambda cx, cy: [(cx - H - 1, cy), (cx - 2 * H, cy + 7)], False, 0),
    "contained": (lambda cx, cy: [(cx, cy), (cx + 10, cy + 10)], False, 1),
    "contained_building": (lambda cx, cy: [
        (cx, cy), (cx + 900, cy), (cx + 900, cy + 700), (cx, cy + 700),
        (cx, cy)], True, 1),
    "containing_building": (lambda cx, cy: [
        (cx - 3 * H, cy - 3 * H), (cx + 3 * H, cy - 3 * H),
        (cx + 3 * H, cy + 3 * H), (cx - 3 * H, cy + 3 * H),
        (cx - 3 * H, cy - 3 * H)], True, 1),
    "disjoint_bbox_overlapping": (
        lambda cx, cy: [(cx + H - 10, cy + H), (cx + H, cy + H - 10)],
        False, 0),
    "disjoint": (lambda cx, cy: [(cx + 3 * H, cy), (cx + 3 * H + 9, cy + 9)],
                 False, 0),
}


@pytest.fixture(scope="module")
def served(osm):
    """50,000 ways, the made ways after them, and what 200 seeded polygons
    of the cell's own traffic count over REST beside the reference."""
    corpus = osm.make_corpus(50_000, 77)
    cx, cy = np.rint(corpus["centers"][0] * 1e7).astype(np.int64)
    names = list(MADE)
    corpus = _with_ways(
        corpus, [MADE[k][0](cx, cy) for k in names],
        closed=[i for i, k in enumerate(names) if MADE[k][1]])
    s = Served(osm, corpus)
    s.corpus, s.ref, s.names = corpus, osm.Reference(corpus), names
    s.ring = [(cx - H, cy - H), (cx + H, cy - H // 2), (cx + H // 2, cy + H),
              (cx - H, cy + H // 2)]
    yield s
    s.close()


def test_served_counts_equal_the_reference_for_200_polygons(served):
    op = _bench_module("ops", "count_intersects")
    with open(os.path.join(BENCH, "traffic", "intersects-c8.json")) as f:
        params = json.load(f)["params"]
    params = dict(params, zipf_s=3.0)     # mostly the first clusters
    rings = op.rings(params, served.corpus["centers"],
                     np.random.default_rng(20261002), 200)
    before = served.get("/metrics")["counters"]
    wrong, matched = [], []
    for ring in rings:
        body = served.count(ring)
        want = served.ref.count_intersects(ring)
        matched.append(want)
        if body != {"count": want}:
            wrong.append((ring, body, want))
    assert not wrong, wrong[:3]
    assert sum(m > 0 for m in matched) > 100 and max(matched) > 300
    after = served.get("/metrics")["counters"]
    gained = {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}
    assert gained["refine.ways_candidate"] > sum(matched) * 0.9
    assert gained["refine.overflow_fallbacks"] == 0
    assert gained["refine.ways_uncertain"] \
        < 0.05 * gained["refine.ways_candidate"]


def test_served_made_polygon_counts_every_named_case(served):
    assert served.count(served.ring) == {
        "count": served.ref.count_intersects(served.ring)}


@pytest.mark.parametrize("case", list(MADE))
def test_made_way(served, case):
    """Each made way alone against the made polygon: the reference, the f64
    refine and the pool kernel (a count over the way's own rows) agree."""
    k = len(served.corpus["off"]) - 1 - len(MADE) + served.names.index(case)
    want = MADE[case][2]
    planner = served.ds.planners["osm"]
    geometry = parse_ecql(_ring_cql(served.ring)).geometry
    assert int(geom_batch.batch_intersects(
        planner.table.geometry(), np.array([k]), geometry)[0]) == want
    # the reference on a corpus of this way alone
    off = served.corpus["off"]
    alone = {"off": np.array([0, off[k + 1] - off[k]]),
             "xi": served.corpus["xi"][off[k]: off[k + 1]],
             "yi": served.corpus["yi"][off[k]: off[k + 1]],
             "closed": served.corpus["closed"][k: k + 1]}
    ref = type(served.ref)(alone)
    assert ref.count_intersects(served.ring) == want
    # the kernel: the block that holds the way, minus its other rows
    idx = planner.indexes[0]
    pos = int(np.flatnonzero(idx.perm == k)[0])
    bsz = prune.BLOCK_SIZE
    plan = planner.plan(_ring_cql(served.ring))
    certain, unc, _ = idx.kernels.intersects_band_blocks(
        plan.primary_kind, plan.boxes_loose, plan.windows,
        plan.residual_device, literal_segments(geometry).astype(np.float32),
        np.array([pos // bsz], dtype=np.int32), bsz, idx.seg_off)
    rows = np.arange(pos // bsz * bsz, min((pos // bsz + 1) * bsz,
                                           len(idx.perm)))
    truth = geom_batch.batch_intersects(
        planner.table.geometry(), idx.perm[rows], geometry)
    settled = np.setdiff1d(rows, unc)
    assert certain == int(truth[np.isin(rows, settled)].sum())
    if pos in settled:
        assert bool(truth[rows == pos][0]) == bool(want)


def test_a_single_leaves_its_spans_under_scan_and_feeds_the_timers(served):
    before = served.get("/metrics")["timers"]
    # a polygon no test has sent: its plan is in no cache, so its cover runs
    served.count([(x + 3, y + 5) for x, y in served.ring])
    tree = max((t for t in served.get("/traces?limit=5")["traces"]
                if t["name"] == "http.request.count"), key=lambda t: t["id"])
    found = {}

    def walk(node, path):
        found[node["name"]] = (path, node)
        for c in node.get("children", ()):
            walk(c, path + [node["name"]])

    walk(tree["root"], [])
    path, dev = found["refine.device"]
    assert path[-1] == "scan" and "query.count" in path
    assert {"blocks", "ways", "segments", "edges", "certain",
            "uncertain"} <= set(dev["attrs"])
    assert int(dev["attrs"]["edges"]) == 4
    assert int(dev["attrs"]["segments"]) >= int(dev["attrs"]["ways"]) > 0
    assert found["range_decompose"][0][-1] == "scan"
    assert found["refine"][0][-1] == "scan"
    assert found["device_wait"][0][-1] == "refine.device"
    after = served.get("/metrics")["timers"]
    for name in ("refine.device", "refine", "range_decompose"):
        assert after[name]["count"] \
            - before.get(name, {"count": 0})["count"] == 1, name


def test_an_append_is_in_the_next_count(served):
    cx, cy = served.ring[0]
    ring = [(cx - 40 * H, cy), (cx - 39 * H, cy), (cx - 39 * H, cy + H),
            (cx - 40 * H, cy + H)]          # empty so far, ~6 degrees west
    assert served.count(ring) == {"count": 0}
    x, y = (cx - 39.5 * H) / 1e7, (cy + H / 2) / 1e7
    line = {"type": "LineString",
            "coordinates": [[round(x, 3), round(y, 3)],
                            [round(x, 3) + 0.01, round(y, 3) + 0.01]]}
    box = {"type": "Polygon", "coordinates": [[
        [round(x, 3), round(y, 3)], [round(x, 3) + 0.02, round(y, 3)],
        [round(x, 3) + 0.02, round(y, 3) + 0.02], [round(x, 3), round(y, 3)]]]}
    props = {"user": "mapper00001", "tags": "{}",
             "dtg": "2020-01-01T00:00:00"}
    ack = served.post("/types/osm/features", {
        "type": "FeatureCollection", "features": [
            {"type": "Feature", "id": "w1", "geometry": line,
             "properties": props},
            {"type": "Feature", "id": "w2", "geometry": box,
             "properties": props}]})
    assert ack == {"ingested": 2}
    assert served.count(ring) == {"count": 2}
    # and once the delta has merged into the index, pool and all
    served.post("/types/osm/flush", {})
    assert served.count(ring) == {"count": 2}


def test_float32_control_differs_on_a_made_case(osm):
    """A vertex one grid cell outside an edge is on it in float32."""
    corpus = osm.make_corpus(500, 5)
    cx, cy = 1_000_000_000, 450_000_000        # lon 100, lat 45
    ring = [(cx, cy), (cx + 2_000_000, cy), (cx + 2_000_000, cy + 2_000_000),
            (cx, cy + 2_000_000)]
    corpus = _with_ways(corpus, [
        [(cx - 1, cy + 700_000), (cx - 900_000, cy + 700_123)]])
    ref, low = osm.Reference(corpus), osm.controls(corpus)["float32"]
    assert ref.count_intersects(ring) == 0
    assert low.count_intersects(ring) == 1
    s = Served(osm, corpus)
    try:
        assert s.ds.planners["osm"].count(_ring_cql(ring)) == 0
    finally:
        s.close()


def test_a_vertex_beside_an_edge_is_not_on_it(osm):
    """The way and the polygon of one wrong answer in 12,600 (PR 27, seed
    550808905): the way's last vertex lies 7e-11 degrees outside an edge
    (integer cross product -2046 of an edge of 3.1e6 cells), which the host
    refine's "on the boundary" band of 1e-12 of the edge's length took for a
    touch."""
    xs = [-1066081951, -1066084242, -1066086053, -1066087080, -1066088401,
          -1066090072, -1066090540, -1066091702, -1066093049, -1066093595,
          -1066096647, -1066098269, -1066098250]
    ys = [195340420, 195345219, 195346635, 195347608, 195348131, 195349050,
          195349605, 195351252, 195352624, 195355818, 195359498, 195361634,
          195362137]
    ring = [(-1065879174, 195801998), (-1068744280, 197035809),
            (-1070135010, 194243499), (-1067269904, 193009689)]
    # the same way two cells further in crosses the edge
    corpus = _with_ways(osm.make_corpus(500, 5), [
        list(zip(xs, ys)), [(x - 2, y) for x, y in zip(xs, ys)]])
    ref = osm.Reference(corpus)
    want = ref.count_intersects(ring)
    n = len(corpus["off"]) - 1
    s = Served(osm, corpus)
    try:
        planner = s.ds.planners["osm"]
        hit = geom_batch.batch_intersects(
            planner.table.geometry(), np.array([n - 2, n - 1]),
            parse_ecql(_ring_cql(ring)).geometry)
        assert list(hit) == [False, True]
        before = _counters()
        plan = planner.plan(_ring_cql(ring))
        assert planner._band_intersects_count(plan) == want
        assert _gained(before)["refine.ways_uncertain"] >= 2
    finally:
        s.close()
