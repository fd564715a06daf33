"""The served spatial join (``ds.join``, ``GET /types/{t}/join``) against the
benchmark's plain reference, ``benchmark/data/gdelt_countries.py:Reference``,
on small seeded corpora: two edge buckets and the gate both occur, events sit
on edges, on vertices and within 1e-6 degrees of a boundary, the uncertain
list overflows, and writes to either type are in the next join."""

import importlib.util
import json
import os
import urllib.parse
import urllib.request

import numpy as np
import pytest

from geomesa_tpu.datastore import DataStoreFinder
from geomesa_tpu.features.geometry import POLYGON, GeometryArray
from geomesa_tpu.features.table import FeatureTable, StringColumn
from geomesa_tpu.index import scan
from geomesa_tpu.metrics import REGISTRY
from geomesa_tpu.web import serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "gdelt_countries_for_tests",
    os.path.join(ROOT, "benchmark", "data", "gdelt_countries.py"))
gc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gc)

POINTS = ("NumMentions:Integer,NumArticles:Integer,dtg:Date,"
          "*geom:Point:srid=4326;geomesa.z3.interval=week")
POLYGONS = "name:String,*geom:Polygon:srid=4326;geomesa.indices=xz2"
BASE = int(np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64))
DAY = 86_400_000
STATS = "count,sum(NumMentions),sum(NumArticles)"
# a window inside the corpus's 30 days, and no filter at all
WINDOW = (BASE + 3 * DAY + 1234, BASE + 12 * DAY + 567)


def _iso(ms):
    return str(np.datetime64(int(ms), "ms")) + "Z"


def _rings(n, rng):
    """``n`` star-shaped rings of 16-600 vertices on a 1/64 degree grid (so
    that a vertex, and the midpoint of an edge, are exact in f64 and in the
    cross products of both the program and the reference), closed."""
    off, xy = [0], []
    for i in range(n):
        k = int(rng.integers(16, 601)) if i else 600   # both edge buckets
        angle = (np.arange(k) + rng.uniform(0.2, 0.8, k)) * (2 * np.pi / k)
        r = rng.uniform(2, 8) * rng.uniform(0.7, 1.0, k)
        c = rng.uniform([-12, -8], [12, 8])
        ring = np.round(np.stack([c[0] + r * np.cos(angle),
                                  c[1] + r * np.sin(angle)], 1) * 32) / 32
        ring = ring[np.r_[True, np.any(np.diff(ring, axis=0) != 0, axis=1)]]
        xy.append(np.vstack([ring, ring[:1]]))
        off.append(off[-1] + len(ring) + 1)
    return {"off": np.asarray(off, dtype=np.int64), "xy": np.concatenate(xy),
            "names": [f"country{i:03d}" for i in range(n)]}


def _corpus(rows, n_polygons, seed):
    """Events over the polygons' neighbourhood, the last of them placed: on
    vertices, on the midpoints of edges, and 1e-6 degrees off a midpoint to
    either side of its edge."""
    rng = np.random.default_rng(seed)
    pol = _rings(n_polygons, rng)
    x, y = rng.uniform(-22, 22, rows), rng.uniform(-17, 17, rows)
    a = pol["xy"]
    firsts = rng.integers(0, len(a) - 1, 120)
    firsts = firsts[~np.isin(firsts + 1, pol["off"])]   # not across two rings
    mid = (a[firsts] + a[firsts + 1]) / 2
    d = a[firsts + 1] - a[firsts]
    beside = 1e-6 * np.stack([-d[:, 1], d[:, 0]], 1) / np.hypot(*d.T)[:, None]
    placed = np.concatenate([a[firsts], mid, mid + beside, mid - beside])
    x[-len(placed):], y[-len(placed):] = placed[:, 0], placed[:, 1]
    mentions = rng.geometric(0.18, rows).astype(np.int32)
    dtg = BASE + rng.integers(0, 30 * DAY, rows)
    # events in the seconds WINDOW's ends fall in, at, before and after the
    # ends, in the middle of the first polygon
    ends = np.array([d + t for t in WINDOW for d in (-1, 0, 1, 300, -200)])
    dtg[: len(ends)] = ends
    x[: len(ends)], y[: len(ends)] = a[: pol["off"][1] - 1].mean(axis=0)
    return {"x": x, "y": y, "dtg": dtg,
            "NumMentions": mentions,
            "NumArticles": mentions + rng.integers(0, 3, rows,
                                                   dtype=np.int32),
            "polygons": pol}


def _polygon_table(sft, pol):
    level = np.arange(len(pol["off"]), dtype=np.int64)
    return FeatureTable.build(sft, {
        "name": StringColumn.encode(pol["names"]),
        "geom": GeometryArray(np.full(len(pol["names"]), POLYGON, np.int8),
                              level, level, pol["off"], pol["xy"])},
        fids=pol["names"])


def _point_table(sft, c, rows=slice(None)):
    return FeatureTable.build(sft, {
        "NumMentions": c["NumMentions"][rows],
        "NumArticles": c["NumArticles"][rows], "dtg": c["dtg"][rows],
        "geom": (c["x"][rows], c["y"][rows])})


def _store(c):
    ds = DataStoreFinder.get_data_store(type="tpu")
    ds.load("countries", _polygon_table(
        ds.create_schema("countries", POLYGONS), c["polygons"]))
    ds.load("gdelt", _point_table(ds.create_schema("gdelt", POINTS), c))
    return ds


def _expected(c, window, boundary=True, gt=None):
    """Every polygon's row by the reference; ``gt``: NumMentions > gt too."""
    if gt is not None:
        keep = c["NumMentions"] > gt
        c = dict(c, **{k: c[k][keep] for k in
                       ("x", "y", "dtg", "NumMentions", "NumArticles")})
    ref = gc.Reference(c)
    lo, hi = window or (BASE - 1, BASE + 31 * DAY)
    return [(ref.names[i],) + ref.join(lo, hi, i, boundary)
            for i in range(len(ref.names))]


def _rows(body):
    assert body["polygons"] == len(body["rows"])
    return [(r["fid"], r["count"], r["sum"]["NumMentions"],
             r["sum"]["NumArticles"]) for r in body["rows"]]


def _counters():
    return {k: v for k, v in REGISTRY.snapshot()["counters"].items()
            if k.startswith("join.")}


def _gained(before, name):
    return _counters().get(name, 0) - before.get(name, 0)


@pytest.fixture(scope="module")
def corpus():
    return _corpus(6000, 12, seed=11)


@pytest.fixture(scope="module")
def store(corpus):
    ds = _store(corpus)
    yield ds
    ds.close()


@pytest.fixture(scope="module")
def port(store):
    httpd = serve(store, port=0, background=True)
    yield httpd.server_address[1]
    httpd.shutdown()
    httpd.server_close()


def _cql(window, gt):
    terms = []
    if window:
        terms.append(f"dtg DURING {_iso(window[0])}/{_iso(window[1])}")
    if gt is not None:
        terms.append(f"NumMentions > {gt}")
    return " AND ".join(terms) or "INCLUDE"


CASES = [("st_intersects", None, None), ("st_intersects", WINDOW, None),
         ("st_intersects", WINDOW, 5), ("st_contains", None, None),
         ("st_contains", WINDOW, 5)]


@pytest.mark.parametrize("op,window,gt", CASES)
def test_join_equals_the_reference(store, corpus, op, window, gt):
    before = _counters()
    body = store.join("gdelt", "countries", op, _cql(window, gt), STATS)
    assert body["op"] == op
    assert _rows(body) == _expected(corpus, window, op == "st_intersects", gt)
    # the placed events are the f32 band's: the uncertain path was taken
    assert _gained(before, "join.pairs_uncertain") > 0
    assert _gained(before, "join.overflow_fallbacks") == 0
    assert _gained(before, "join.launches") == 1


@pytest.mark.parametrize("op,window,gt", CASES)
def test_route_equals_the_reference(port, corpus, op, window, gt):
    url = (f"http://127.0.0.1:{port}/types/gdelt/join?with=countries&op={op}"
           f"&stats={urllib.parse.quote(STATS)}"
           f"&cql={urllib.parse.quote(_cql(window, gt))}")
    with urllib.request.urlopen(url) as resp:
        body = json.loads(resp.read())
    assert _rows(body) == _expected(corpus, window, op == "st_intersects", gt)
    assert [r["name"] for r in body["rows"]] == corpus["polygons"]["names"]


def test_boundary_events_tell_the_two_ops_apart(store, corpus):
    on = _rows(store.join("gdelt", "countries", "st_intersects", "INCLUDE",
                          STATS))
    off = _rows(store.join("gdelt", "countries", "st_contains", "INCLUDE",
                           STATS))
    assert sum(r[1] for r in on) > sum(r[1] for r in off)
    assert all(a[1] >= b[1] for a, b in zip(on, off))


@pytest.mark.parametrize("bad", [
    "op=st_within", "stats=mean(NumMentions)", "stats=sum(nope)",
    "with=nope", "with=gdelt"])
def test_route_refuses_what_it_cannot_join(port, bad):
    query = {"with": "countries", "op": "st_intersects", "stats": "count"}
    key, value = bad.split("=")
    query[key] = value
    url = (f"http://127.0.0.1:{port}/types/gdelt/join?"
           + urllib.parse.urlencode(query))
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(url)
    assert err.value.code == 400
    assert json.loads(err.value.read())["kind"] == "bad_request"


def test_uncertain_overflow_falls_back_exactly(store, corpus, monkeypatch):
    monkeypatch.setattr(scan, "JOIN_UNC_CAP", 4)
    before = _counters()
    body = store.join("gdelt", "countries", "st_intersects", _cql(WINDOW, None),
                      STATS)
    assert _rows(body) == _expected(corpus, WINDOW)
    assert _gained(before, "join.overflow_fallbacks") == 1


def test_window_ends_inside_a_second_are_exact(store, corpus, monkeypatch):
    """The device keeps a week's times in seconds: the rows of the seconds
    the window's ends fall in are the host's, and when they are more than
    the kernel lists, the whole join is."""
    want = _expected(corpus, WINDOW)
    inside = sum(WINDOW[0] < t < WINDOW[1] for t in corpus["dtg"][:10])
    assert 0 < inside < 10
    body = store.join("gdelt", "countries", "st_intersects",
                      _cql(WINDOW, None), STATS)
    assert _rows(body) == want
    monkeypatch.setattr(scan, "JOIN_TIME_CAP", 1)
    before = _counters()
    body = store.join("gdelt", "countries", "st_intersects",
                      _cql(WINDOW, None), STATS)
    assert _rows(body) == want
    assert _gained(before, "join.pairs_matched") == sum(r[1] for r in want)


def test_a_window_that_matches_nothing(store, corpus):
    body = store.join("gdelt", "countries", "st_intersects",
                      "dtg DURING 2031-01-01T00:00:00Z/2031-01-02T00:00:00Z",
                      STATS)
    assert _rows(body) == [(n, 0, 0, 0) for n in corpus["polygons"]["names"]]


def test_an_empty_polygon_side():
    c = _corpus(500, 1, seed=3)
    ds = DataStoreFinder.get_data_store(type="tpu")
    ds.create_schema("countries", POLYGONS)
    ds.load("gdelt", _point_table(ds.create_schema("gdelt", POINTS), c))
    assert ds.join("gdelt", "countries") == {
        "op": "st_intersects", "polygons": 0, "rows": []}
    ds.close()


def test_appends_to_either_type_are_in_the_next_join():
    c = _corpus(5000, 9, seed=5)
    pol = c["polygons"]
    ds = DataStoreFinder.get_data_store(type="tpu")
    first8 = {"off": pol["off"][:9], "xy": pol["xy"][: pol["off"][8]],
              "names": pol["names"][:8]}
    psft = ds.create_schema("countries", POLYGONS)
    ds.load("countries", _polygon_table(psft, first8))
    sft = ds.create_schema("gdelt", POINTS)
    ds.load("gdelt", _point_table(sft, c, slice(0, 4000)))
    # 1,000 events more: they land in the delta tier, no rebuild
    ds.load("gdelt", _point_table(sft, c, slice(4000, 5000)))
    assert ds.deltas["gdelt"] is not None
    want = _expected(c, WINDOW)
    got = _rows(ds.join("gdelt", "countries", "st_intersects",
                        _cql(WINDOW, None), STATS))
    assert got == want[:8]
    # the ninth polygon
    last = {"off": pol["off"][8:] - pol["off"][8],
            "xy": pol["xy"][pol["off"][8]:], "names": pol["names"][8:]}
    ds.load("countries", _polygon_table(psft, last))
    got = _rows(ds.join("gdelt", "countries", "st_intersects",
                        _cql(WINDOW, None), STATS))
    assert got == want
    ds.close()


@pytest.mark.parametrize("n_polygons", [8, 64])
def test_launches_do_not_grow_with_the_polygons(n_polygons):
    c = _corpus(3000, n_polygons, seed=7)
    ds = _store(c)
    before = _counters()
    body = ds.join("gdelt", "countries", "st_intersects", _cql(WINDOW, None),
                   STATS)
    assert _rows(body) == _expected(c, WINDOW)
    assert _gained(before, "join.launches") == 1
    assert _gained(before, "join.block_polygon_pairs") > n_polygons
    ds.close()


def _gate(ds):
    """(polygon pool, tile envelopes, the gate's pairs, rows a tile) of an
    unfiltered join of ``ds``'s gdelt with its countries."""
    from geomesa_tpu.index import prune
    planner = ds.planners["gdelt"]
    pool = next(i for i in ds.planners["countries"].indexes
                if i.name == "xz2")
    bsz = min(prune.BLOCK_SIZE, len(planner.table))
    tile = min(scan.JOIN_TILE, bsz)
    env = planner.indexes[0].join_envelopes(bsz, tile)
    blocks = prune.gate_blocks(env, None, None, None)
    return pool, env, prune.gate_slabs(
        env, blocks, pool.polygon_envelopes(), pool.seg_ykey, pool.seg_rise,
        scan.SEG_STEP), tile


def _combs(rows, seed):
    """Events over six combs: a zigzag of k teeth over a base, every edge
    of the zigzag as tall as the teeth and the sides taller, so that a tile
    that meets a comb's envelope meets all its k + 3 edges: slabs of six
    sizes, from under a chunk to some hundreds of segments. The last events
    sit on vertices and on the midpoints of edges."""
    rng = np.random.default_rng(seed)
    off, xy = [0], []
    for c, k in enumerate((10, 37, 85, 175, 350, 700)):
        x0 = -21.0 + 7 * c
        i = np.arange(k + 1)
        ring = np.vstack([np.stack([x0 + i * (6 / k),
                                    np.where(i % 2 == 0, 6.0, -4.0)], 1),
                          [[x0 + 6, -6.0], [x0, -6.0]]])
        ring = np.round(ring * 1024) / 1024
        xy.append(np.vstack([ring, ring[:1]]))
        off.append(off[-1] + len(ring) + 1)
    a = np.concatenate(xy)
    off = np.asarray(off, dtype=np.int64)
    x, y = rng.uniform(-22, 22, rows), rng.uniform(-8, 8, rows)
    firsts = rng.integers(0, len(a) - 1, 150)
    firsts = firsts[~np.isin(firsts + 1, off)]
    placed = np.concatenate([a[firsts], (a[firsts] + a[firsts + 1]) / 2])
    x[-len(placed):], y[-len(placed):] = placed[:, 0], placed[:, 1]
    mentions = rng.geometric(0.18, rows).astype(np.int32)
    return {"x": x, "y": y, "dtg": BASE + rng.integers(0, 30 * DAY, rows),
            "NumMentions": mentions,
            "NumArticles": mentions + rng.integers(0, 3, rows,
                                                   dtype=np.int32),
            "polygons": {"off": off, "xy": a,
                         "names": [f"comb{c}" for c in range(len(off) - 1)]}}


def _in_f64(c, boundary):
    """Every polygon's (name, count, sums) by geom_batch.points_in_polygons
    over the f64 coordinates."""
    from geomesa_tpu.filter import geom_batch
    pol = c["polygons"]
    n = len(pol["names"])
    level = np.arange(n + 1, dtype=np.int64)
    i, p = geom_batch.points_in_polygons(
        c["x"], c["y"], GeometryArray(np.full(n, POLYGON, np.int8), level,
                                      level, pol["off"], pol["xy"]),
        boundary)
    return [(pol["names"][k], int(np.sum(p == k)),
             int(c["NumMentions"][i[p == k]].astype(np.int64).sum()),
             int(c["NumArticles"][i[p == k]].astype(np.int64).sum()))
            for k in range(n)]


@pytest.fixture(scope="module")
def combs():
    return _combs(6000, seed=17)


@pytest.fixture(scope="module")
def comb_store(combs):
    ds = _store(combs)
    yield ds
    ds.close()


@pytest.mark.parametrize("op", ["st_intersects", "st_contains"])
def test_slabs_of_many_widths_join_exactly(comb_store, combs, op):
    _, _, pairs, _ = _gate(comb_store)
    assert len(np.unique(np.searchsorted(scan.JOIN_WIDTHS,
                                         pairs[:, 3]))) >= 4
    before = _counters()
    body = comb_store.join("gdelt", "countries", op, "INCLUDE", STATS)
    assert _rows(body) == _in_f64(combs, op == "st_intersects")
    assert _gained(before, "join.launches") == 1
    assert _gained(before, "join.overflow_fallbacks") == 0


@pytest.mark.parametrize("which", ["store", "comb_store"])
def test_a_pair_runs_in_the_smallest_width_over_what_it_reads(
        request, monkeypatch, which):
    ds = request.getfixturevalue(which)
    _, _, pairs, _ = _gate(ds)
    widths = np.asarray(scan.JOIN_WIDTHS)
    read, span = pairs[:, 3], pairs[:, 3] - pairs[:, 2]
    width = widths[np.searchsorted(widths, read)]
    # its span fits the width, and it reads less than a step before it
    assert np.all(span <= width)
    assert np.all((pairs[:, 2] >= 0) & (pairs[:, 2] < scan.SEG_STEP))
    # the ladder: whole chunks up to the pool's pad, a step x1.5 (x2 first)
    assert np.all(widths % scan.SEG_CHUNK == 0)
    assert widths[0] == scan.SEG_CHUNK and widths[-1] == scan.POOL_TILE
    assert np.all(width <= np.maximum(2 * scan.SEG_CHUNK, 1.5 * read))
    # what the kernel was sent: each bucket the pairs that read more than
    # the width below it and at most its own
    sent, real = [], scan._fetch

    def spy(fn, *args):
        if np.shape(args[-1]) == (len(widths), 2):
            sent.append((np.asarray(args[-2]), np.asarray(args[-1])))
        return real(fn, *args)

    monkeypatch.setattr(scan, "_fetch", spy)
    ds.join("gdelt", "countries", "st_intersects", "INCLUDE", "count")
    (got, spans), = sent
    assert np.array_equal(spans[:, 1], np.bincount(
        np.searchsorted(widths, read), minlength=len(widths)))
    for b, (first, count) in enumerate(spans):
        r = got[first: first + count, 3]
        assert np.all(r <= widths[b]) and (b == 0 or np.all(r > widths[b - 1]))


@pytest.mark.parametrize("which", ["store", "comb_store"])
def test_the_y_sorted_plane_starts_a_chunk_every_step(request, which):
    """``__segy__``: chunk q is the y-sorted pool from segment q * SEG_STEP
    on, so the chunks a pair reads, every SEG_CHUNK / SEG_STEP-th from its
    first, are its polygon's segments in one run; each polygon's are its
    pool's, ordered by their lower end."""
    ds = request.getfixturevalue(which)
    pool, _, pairs, _ = _gate(ds)
    segy = np.asarray(pool.device.columns[scan.SEGY])
    seg = np.asarray(pool.device.columns[scan.SEG])
    step, chunk = scan.SEG_STEP, scan.SEG_CHUNK
    assert segy.shape[1:] == (4, chunk)
    flat = segy[:, :, :step].transpose(1, 0, 2).reshape(4, -1)
    assert flat.shape[1] >= seg.shape[1]
    at = step * np.arange(len(segy))[:, None] + np.arange(chunk)
    flat_padded = np.pad(flat, ((0, 0), (0, at.max() + 1 - flat.shape[1])))
    assert np.array_equal(segy, flat_padded[:, at].transpose(1, 0, 2))
    for a, b in zip(pool.seg_off[:-1], pool.seg_off[1:]):
        assert sorted(map(tuple, seg[:, a:b].T)) \
            == sorted(map(tuple, flat[:, a:b].T))
        assert np.all(np.diff(np.minimum(flat[1, a:b], flat[3, a:b])) >= 0)
    # what a pair reads: its first chunk, then every chunk / step-th
    for t, first, lo, hi, _ in pairs[:: max(1, len(pairs) // 200)]:
        rows = first + (chunk // step) * np.arange(-(-hi // chunk))
        got = segy[rows].transpose(1, 0, 2).reshape(4, -1)[:, lo:hi]
        assert np.array_equal(got, flat[:, first * step + lo:
                                        first * step + hi])


@pytest.mark.parametrize("which", ["store", "comb_store"])
def test_slab_tests_count_what_the_pairs_own_slabs_need(request, which):
    ds = request.getfixturevalue(which)
    _, _, pairs, tile = _gate(ds)
    before = _counters()
    ds.join("gdelt", "countries", "st_intersects", "INCLUDE", "count")
    assert _gained(before, "join.slab_tests") \
        == int((pairs[:, 3] - pairs[:, 2]).sum()) * tile
    assert _gained(before, "join.edge_tests") == int(np.take(
        scan.JOIN_WIDTHS, np.searchsorted(scan.JOIN_WIDTHS,
                                          pairs[:, 3])).sum()) * tile


def test_the_gate_drops_pairs_and_cuts_slabs_of_two_buckets(store):
    pool, env, pairs, _ = _gate(store)
    seg_n = np.diff(pool.seg_off)
    tiles = env["xmin"].size
    assert 0 < len(pairs) < tiles * len(seg_n)
    # a slab is a span of its polygon's segments, mostly a part of them
    first = pairs[:, 1] * scan.SEG_STEP
    assert np.all(first + pairs[:, 2] >= pool.seg_off[pairs[:, 4]])
    assert np.all(first + pairs[:, 3] <= pool.seg_off[pairs[:, 4] + 1])
    assert (pairs[:, 3] - pairs[:, 2]).sum() \
        < 0.5 * seg_n[pairs[:, 4]].sum()
    assert len(np.unique(np.searchsorted(scan.JOIN_WIDTHS,
                                         pairs[:, 3]))) >= 2
    before = _counters()
    store.join("gdelt", "countries", "st_intersects", "INCLUDE", "count")
    assert _gained(before, "join.block_polygon_pairs") == len(pairs)


def test_partial_sums_are_exact_for_any_int32(monkeypatch):
    """The kernel sums a stat in two 16-bit halves a (tile, polygon) pair and
    the host widens them: JOIN_TILE rows of at most 2^16 a half cannot
    overflow int32 whatever the values, here near its ends."""
    assert scan.JOIN_TILE * 0xFFFF < 2 ** 31
    c = _corpus(2000, 8, seed=13)
    rng = np.random.default_rng(1)
    c["NumMentions"] = rng.choice(
        np.array([2 ** 31 - 1, -2 ** 31, 2 ** 31 - 7, 12345, -1],
                 dtype=np.int32), len(c["x"]))
    c["NumArticles"] = np.full(len(c["x"]), 2 ** 31 - 1, dtype=np.int32)
    ds = _store(c)
    body = ds.join("gdelt", "countries", "st_intersects", "INCLUDE", STATS)
    assert _rows(body) == _expected(c, None)
    assert max(r[3] for r in _rows(body)) > 2 ** 31
    ds.close()
