"""REST / GeoJSON API (≙ geomesa-web servlets + geomesa-geojson JSON API)."""

import json
import urllib.request

import numpy as np
import pytest

from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.web import serve


@pytest.fixture(scope="module")
def server():
    rng = np.random.default_rng(3)
    n = 5000
    x = rng.uniform(-20, 20, n)
    y = rng.uniform(-20, 20, n)
    base = np.datetime64("2024-05-01T00:00:00", "ms").astype(np.int64)
    ds = TpuDataStore()
    ds.create_schema("w", "name:String,v:Int,dtg:Date,*geom:Point")
    ds.load("w", FeatureTable.build(ds.get_schema("w"), {
        "name": rng.choice(["a", "b"], n), "v": rng.integers(0, 100, n).astype(np.int32),
        "dtg": base + rng.integers(0, 86400000, n), "geom": (x, y)}))
    httpd = serve(ds, port=0, background=True)
    port = httpd.server_address[1]
    yield f"http://127.0.0.1:{port}", ds, x, y
    httpd.shutdown()


def _get(url):
    with urllib.request.urlopen(url) as r:
        return r.status, json.loads(r.read())


def test_types_listing(server):
    base, ds, x, y = server
    status, body = _get(f"{base}/types")
    assert status == 200 and body["types"] == ["w"]
    status, body = _get(f"{base}/types/w")
    assert body["count"] == 5000
    assert any(a["name"] == "geom" for a in body["attributes"])


def test_count_and_explain(server):
    base, ds, x, y = server
    q = "BBOX(geom, -5, -5, 5, 5)"
    status, body = _get(f"{base}/types/w/count?cql={urllib.parse.quote(q)}")
    ref = int(np.sum((x >= -5) & (x <= 5) & (y >= -5) & (y <= 5)))
    assert body["count"] == ref
    status, body = _get(f"{base}/types/w/explain?cql={urllib.parse.quote(q)}")
    assert status == 200 and "index" in body


def test_features_geojson(server):
    base, ds, x, y = server
    q = urllib.parse.quote("BBOX(geom, -5, -5, 5, 5)")
    status, fc = _get(f"{base}/types/w/features?cql={q}&limit=10&sort=-v")
    assert status == 200
    assert fc["type"] == "FeatureCollection" and len(fc["features"]) == 10
    vs = [f["properties"]["v"] for f in fc["features"]]
    assert vs == sorted(vs, reverse=True)
    g = fc["features"][0]["geometry"]
    assert g["type"] == "Point" and -5 <= g["coordinates"][0] <= 5


def _columns_whole():
    from geomesa_tpu.metrics import REGISTRY
    return REGISTRY.snapshot()["counters"].get(
        "export.geojson.columns_whole", 0)


@pytest.mark.parametrize("hints", [{"limit": "25"},
                                   {"limit": "40", "sort": "-v"},
                                   {"crs": "EPSG:3857"}])
def test_features_served_as_the_reference_writes_them(server, hints):
    """The route's bytes parse to the document the per-feature reference
    writes for the same query, under the JSON type and their own length;
    every column of the schema is encoded as a whole column."""
    from test_geojson_writer import reference
    base, ds, x, y = server
    q = "BBOX(geom, -5, -5, 5, 5)"
    before = _columns_whole()
    with urllib.request.urlopen(
            f"{base}/types/w/features?cql={urllib.parse.quote(q)}&"
            + urllib.parse.urlencode(hints)) as r:
        body = r.read()
        assert r.headers["Content-Type"] == "application/json"
        assert int(r.headers["Content-Length"]) == len(body)
    assert _columns_whole() - before == 4
    hints = {k: int(v) if k == "limit" else v for k, v in hints.items()}
    want = reference(ds.query("w", q, hints=hints).table)
    assert json.dumps(json.loads(body)) == json.dumps(json.loads(want))
    assert len(json.loads(body)["features"]) > 0


def test_dict_routes_write_the_bytes_they_wrote_before(server):
    """A count, a join and /metrics still go out as ``json.dumps`` of the
    route's dict, byte for byte."""
    from geomesa_tpu.web.server import GeoJsonApi
    base, ds, x, y = server
    q = {"cql": ["BBOX(geom, -5, -5, 5, 5)"]}
    with urllib.request.urlopen(
            f"{base}/types/w/count?{urllib.parse.urlencode(q, True)}") as r:
        body = r.read()
    assert body == json.dumps(
        GeoJsonApi(ds).handle("GET", "/types/w/count", q)[1]).encode()
    with urllib.request.urlopen(f"{base}/metrics") as r:
        body = r.read()
        assert r.headers["Content-Type"] == "application/json"
    assert body == json.dumps(json.loads(body)).encode()

    js = TpuDataStore()
    js.create_schema("p", "v:Integer,dtg:Date,*geom:Point;"
                          "geomesa.z3.interval=week")
    js.load("p", FeatureTable.build(js.get_schema("p"), {
        "v": np.arange(len(x), dtype=np.int32),
        "dtg": np.full(len(x), 1_700_000_000_000, np.int64),
        "geom": (x, y)}))
    js.create_schema("c", "name:String,*geom:Polygon;geomesa.indices=xz2")
    js.load("c", FeatureTable.build(js.get_schema("c"), {
        "name": ["west", "east"],
        "geom": ["POLYGON ((-10 -10, 0 -10, 0 10, -10 10, -10 -10))",
                 "POLYGON ((0 -10, 10 -10, 10 10, 0 10, 0 -10))"]}))
    httpd = serve(js, port=0, background=True)
    try:
        jq = {"with": ["c"], "stats": ["count,sum(v)"]}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.server_address[1]}/types/p/join?"
                + urllib.parse.urlencode(jq, True)) as r:
            body = r.read()
    finally:
        httpd.shutdown()
    doc = GeoJsonApi(js).handle("GET", "/types/p/join", jq)[1]
    assert [row["count"] for row in doc["rows"]] == [
        int(np.sum((x >= lo) & (x <= hi) & (y >= -10) & (y <= 10)))
        for lo, hi in ((-10, 0), (0, 10))]
    assert body == json.dumps(doc).encode()


def test_post_ingest_roundtrip(server):
    base, ds, x, y = server
    fc = {"type": "FeatureCollection", "features": [
        {"type": "Feature", "geometry": {"type": "Point",
                                         "coordinates": [101.5, 3.25]},
         "properties": {"name": "posted", "v": 7,
                        "dtg": "2024-05-02T12:00:00"}},
    ]}
    req = urllib.request.Request(
        f"{base}/types/w/features", data=json.dumps(fc).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req) as r:
        assert json.loads(r.read())["ingested"] == 1
    status, body = _get(f"{base}/types/w/count?cql=" +
                        urllib.parse.quote("name = 'posted'"))
    assert body["count"] == 1


def test_metrics_and_config(server):
    base, ds, x, y = server
    status, m = _get(f"{base}/metrics")
    assert status == 200 and "counters" in m
    assert "gauges" in m and "timers" in m
    status, c = _get(f"{base}/config")
    assert "GEOMESA_TPU_PRUNE" in c


def test_metrics_prometheus_exposition(server):
    import re
    base, ds, x, y = server
    # exercise the traced count path so query.count has a histogram
    for _ in range(3):
        _get(f"{base}/types/w/count?cql=" +
             urllib.parse.quote("BBOX(geom, -5, -5, 5, 5)"))
    with urllib.request.urlopen(f"{base}/metrics?format=prometheus") as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    assert "NaN" not in text
    # sample lines may carry an OpenMetrics exemplar suffix on histogram
    # buckets backed by a tail-retained trace (# {trace_id="N"} value)
    line_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.eE+-]+"
        r"( # \{[^}]*\} -?[0-9.eE+-]+)?$")
    for line in text.strip().split("\n"):
        if not line.startswith("#"):
            assert line_re.match(line), line
    for q in ("0.5", "0.9", "0.99"):
        assert f'geomesa_tpu_query_count_seconds{{quantile="{q}"}}' in text


def test_traces_endpoint_recent_first_bounded(server):
    base, ds, x, y = server
    from geomesa_tpu.trace import RING
    RING.clear()
    for i in range(4):
        _get(f"{base}/types/w/count?cql=" +
             urllib.parse.quote(f"BBOX(geom, -{i + 1}, -5, 5, 5)"))
    status, body = _get(f"{base}/traces")
    assert status == 200
    ids = [t["id"] for t in body["traces"]]
    assert len(ids) == 4 and ids == sorted(ids, reverse=True)
    status, body = _get(f"{base}/traces?limit=2")
    assert len(body["traces"]) == 2
    assert body["traces"][0]["id"] == ids[0]  # still newest first


def test_healthz(server):
    base, ds, x, y = server
    status, body = _get(f"{base}/healthz")
    assert status == 200
    assert body["status"] == "ok" and body["devices"] >= 1
    assert body["types"] == 1
    # what hardware answers: the CPU under the test harness
    import jax
    assert body["backend"] == jax.default_backend() == "cpu"
    assert body["device_kind"] == jax.local_devices()[0].device_kind


def test_bad_cql_is_400(server):
    base, ds, x, y = server
    try:
        urllib.request.urlopen(f"{base}/types/w/count?cql=NONSENSE(((")
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_concurrent_ingest_and_query_stress():
    """Writers POSTing features while readers GET counts: every response
    must be a consistent snapshot — counts monotonically non-decreasing
    (append-only workload), never an error, and the final count exact.
    Exercises the store's writer-lock + snapshot discipline end to end
    through the REST thread pool (ThreadingHTTPServer)."""
    import threading

    rng = np.random.default_rng(17)
    n0 = 20000
    ds = TpuDataStore()
    ds.create_schema("c", "v:Int,dtg:Date,*geom:Point")
    base = np.datetime64("2024-05-01T00:00:00", "ms").astype(np.int64)
    ds.load("c", FeatureTable.build(ds.get_schema("c"), {
        "v": rng.integers(0, 100, n0).astype(np.int32),
        "dtg": base + rng.integers(0, 86400000, n0),
        "geom": (rng.uniform(-20, 20, n0), rng.uniform(-20, 20, n0))}))
    httpd = serve(ds, port=0, background=True)
    port = httpd.server_address[1]
    url = f"http://127.0.0.1:{port}"
    errors = []
    counts = []
    n_writers, per_writer, batch = 4, 12, 7

    def writer(wid):
        try:
            for i in range(per_writer):
                fc = {"type": "FeatureCollection", "features": [
                    {"type": "Feature",
                     "geometry": {"type": "Point",
                                  "coordinates": [float(wid), float(i % 10)]},
                     "properties": {"v": wid, "dtg": "2024-05-01T12:00:00Z"}}
                    for _ in range(batch)]}
                req = urllib.request.Request(
                    f"{url}/types/c/features", method="POST",
                    data=json.dumps(fc).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req) as r:
                    assert r.status == 200
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(("writer", wid, repr(e)))

    def reader(rid):
        try:
            got = []
            for _ in range(40):
                with urllib.request.urlopen(f"{url}/types/c/count") as r:
                    assert r.status == 200
                    got.append(json.loads(r.read())["count"])
            counts.append(got)
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(("reader", rid, repr(e)))

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(n_writers)]
    threads += [threading.Thread(target=reader, args=(r,)) for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    httpd.shutdown()
    assert not errors, errors
    # consistent snapshots: append-only counts never go backwards per reader
    for got in counts:
        assert got == sorted(got), got
        assert all(g >= n0 for g in got)
    expected = n0 + n_writers * per_writer * batch
    assert ds.count("c", "INCLUDE") == expected
    # the delta path (not a full rebuild per batch) absorbed the writes
    assert ds.count("c", f"BBOX(geom, -0.5, -0.5, {n_writers}.5, 10.5)") \
        >= n_writers * per_writer * batch


# -- JSON query DSL (≙ GeoJsonQuery language) --------------------------------


def test_json_query_parser_shapes():
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.filter import ir
    from geomesa_tpu.web.jsonquery import parse_json_query

    sft = SimpleFeatureType.from_spec("t", "name:String,v:Int,dtg:Date,"
                                           "*geom:Point")
    f = parse_json_query("{}", sft)
    assert isinstance(f, ir.Include)
    f = parse_json_query('{"name": "bar"}', sft)
    assert f == ir.Cmp("=", "name", "bar")
    f = parse_json_query('{"v": {"$lt": 10}, "name": "a"}', sft)
    assert isinstance(f, ir.And) and len(f.children) == 2
    f = parse_json_query('{"$or": [{"name": "a"}, {"v": 10}]}', sft)
    assert isinstance(f, ir.Or)
    f = parse_json_query('{"$.v": {"$in": [1, 2, 3]}}', sft)
    assert f == ir.In("v", (1, 2, 3))
    # "geometry" maps to the default geometry attribute
    f = parse_json_query('{"geometry": {"$bbox": [-10, -5, 10, 5]}}', sft)
    assert f == ir.BBox("geom", -10, -5, 10, 5)
    f = parse_json_query(
        '{"geometry": {"$intersects": {"$geometry": '
        '{"type": "Point", "coordinates": [30, 10]}}}}', sft)
    assert isinstance(f, ir.Intersects) and f.attr == "geom"
    f = parse_json_query(
        '{"geometry": {"$dwithin": {"$geometry": '
        '{"type": "Point", "coordinates": [0, 0]}, '
        '"$dist": 111320, "$unit": "meters"}}}', sft)
    assert isinstance(f, ir.Dwithin)
    assert f.distance == pytest.approx(1.0)  # 111.32 km ~ 1 degree
    for bad in ('{"v": {"$frob": 3}}', '[1]',
                '{"geometry": {"$bbox": [1, 2]}}',
                '{"geometry": {"$intersects": {"nope": 1}}}'):
        with pytest.raises(ValueError):
            parse_json_query(bad, sft)


def test_json_query_over_rest(server):
    base, ds, x, y = server
    q = urllib.parse.quote(
        '{"geometry": {"$bbox": [-5, -5, 5, 5]}, "v": {"$lt": 50}}')
    status, body = _get(f"{base}/types/w/count?q={q}")
    assert status == 200
    v = np.asarray(ds.tables["w"].columns["v"])
    ref = int(np.sum((x >= -5) & (x <= 5) & (y >= -5) & (y <= 5) & (v < 50)))
    assert body["count"] == ref
    # features endpoint honors the same q
    status, fc = _get(f"{base}/types/w/features?q={q}&limit=5")
    assert status == 200 and len(fc["features"]) == min(5, ref)
    # $or of two names
    q2 = urllib.parse.quote('{"$or": [{"name": "a"}, {"name": "b"}]}')
    status, body = _get(f"{base}/types/w/count?q={q2}")
    assert body["count"] == 5000
    # malformed query -> 400, not a server error
    try:
        status, body = _get(f"{base}/types/w/count?q=" + urllib.parse.quote(
            '{"v": {"$nope": 1}}'))
    except urllib.error.HTTPError as e:
        status, body = e.code, json.loads(e.read())
    assert status == 400 and "error" in body
