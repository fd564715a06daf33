#!/usr/bin/env python3
"""chip_smoke.py — does the served path still start, and answer right, on the chip?

    python chip_smoke.py [--rows N] [--seed S]

One process (it spawns nothing, so it is the only holder of the chip) that

1. refuses to run unless ``jax.default_backend() == "tpu"``;
2. builds a GDELT-shaped point corpus from ``--seed`` (README quick-start
   schema, 64 clustered centres), 100,000,000 rows by default;
3. loads it through ``DataStoreFinder → create_schema → ds.load`` and serves
   it with ``web.serve(ds, background=True)`` — what ``geomesa-tpu serve`` runs;
4. sends the REST requests a client would (count, 64 concurrent counts,
   features, polygon count, write then read-back) plus a density and a KNN
   through the library, and compares every answer with a plain numpy
   evaluation over the raw f64/int64 host columns, outside any timed region;
5. asserts what would otherwise hide the device: planes on a TPU device,
   bytes in use, no fused fallback, nothing degraded, breaker closed, no 5xx,
   native encoder loaded.

Phase seconds are printed as plain facts, not metrics. Any failed check or
exception exits non-zero; only a fully passing run prints the last line
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import urlencode

import numpy as np

DEFAULT_ROWS = 100_000_000
SPEC = "name:String,val:Int,dtg:Date,*geom:Point;geomesa.z3.interval=week"
TYPE = "gdelt"
DURING = "2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
T_LO = np.datetime64("2020-01-05T00:00:00", "ms").astype(np.int64)
T_HI = np.datetime64("2020-01-12T00:00:00", "ms").astype(np.int64)
VAL_GT = 10
BIG_BOX = (-10.0, 30.0, 30.0, 55.0)
HTTP_TIMEOUT_S = 900
EARTH_R_M = 6371008.8


# -- corpus -------------------------------------------------------------------


def make_corpus(rows: int, seed: int) -> dict:
    """Raw host columns: 64 clustered centres over 30 days plus the two
    quick-start attributes. ``name`` is made in bulk as
    dictionary codes + vocab, the form FeatureTable stores it in."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform([-120, -40], [140, 60], size=(64, 2))
    which = rng.integers(0, 64, rows, dtype=np.int8)
    x = np.clip(centers[which, 0] + rng.normal(0, 8, rows), -180, 180)
    y = np.clip(centers[which, 1] + rng.normal(0, 6, rows), -90, 90)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    dtg = base + rng.integers(0, 30 * 86_400_000, rows)
    val = rng.integers(0, 100, rows, dtype=np.int32)
    vocab = [f"actor{i:02d}" for i in range(64)]
    codes = rng.integers(0, len(vocab), rows, dtype=np.int32)
    return {"x": x, "y": y, "dtg": dtg, "val": val, "name_codes": codes,
            "name_vocab": vocab, "centers": centers}


# -- the plain reference (numpy over the raw columns) -------------------------


class Reference:
    """Straightforward evaluation of the same predicates, independent of the
    package: f64 compares on lon/lat, int64 compares on epoch millis."""

    def __init__(self, corpus: dict):
        self.x, self.y = corpus["x"], corpus["y"]
        self.dtg, self.val = corpus["dtg"], corpus["val"]
        # DURING is exclusive at both ends; rows passing time ∧ val are the
        # candidates every count below shares
        self.during = np.flatnonzero((self.dtg > T_LO) & (self.dtg < T_HI))
        self.during_val = self.during[self.val[self.during] > VAL_GT]

    def _in_box(self, rows, box):
        x, y = self.x[rows], self.y[rows]
        return rows[(x >= box[0]) & (x <= box[2])
                    & (y >= box[1]) & (y <= box[3])]

    def rows_box_during_val(self, box) -> np.ndarray:
        return self._in_box(self.during_val, box)

    def rows_box_during(self, box) -> np.ndarray:
        return self._in_box(self.during, box)

    def rows_polygon_during(self, ring) -> np.ndarray:
        """Even-odd crossing rule in f64 over the envelope's candidates."""
        ring = np.asarray(ring, dtype=np.float64)
        env = (ring[:, 0].min(), ring[:, 1].min(),
               ring[:, 0].max(), ring[:, 1].max())
        rows = self._in_box(self.during, env)
        px, py = self.x[rows], self.y[rows]
        inside = np.zeros(len(rows), dtype=bool)
        for (x1, y1), (x2, y2) in zip(ring[:-1], ring[1:]):
            if y1 == y2:
                continue
            cond = (y1 > py) != (y2 > py)
            xint = x1 + (py - y1) * (x2 - x1) / (y2 - y1)
            inside ^= cond & (px < xint)
        return rows[inside]

    def knn(self, qx: float, qy: float, k: int):
        rad = np.pi / 180.0
        la1, la2 = self.y * rad, qy * rad
        a = np.sin((la2 - la1) / 2) ** 2 + np.cos(la1) * np.cos(la2) \
            * np.sin((qx - self.x) * rad / 2) ** 2
        d = 2 * EARTH_R_M * np.arcsin(np.sqrt(np.clip(a, 0.0, 1.0)))
        part = np.argpartition(d, k - 1)[:k]
        order = part[np.argsort(d[part], kind="stable")]
        return order, d[order]


# -- the run ------------------------------------------------------------------


class Smoke:
    def __init__(self):
        self.failures: list = []
        self.phases: dict = {}
        self.statuses: list = []

    def check(self, ok: bool, what: str) -> None:
        print(("ok    " if ok else "FAIL  ") + what, flush=True)
        if not ok:
            self.failures.append(what)

    def timed(self, name: str, fn):
        t0 = time.perf_counter()
        out = fn()
        self.phases[name] = round(time.perf_counter() - t0, 3)
        print(f"phase {name}: {self.phases[name]} s", flush=True)
        return out

    def http(self, method: str, url: str, body: bytes = None) -> dict:
        """One request; a non-2xx raises (urllib) and ends the run."""
        req = urllib.request.Request(url, data=body, method=method)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S) as r:
            self.statuses.append(r.status)
            return json.loads(r.read())


def cql_box(box, with_val: bool = True) -> str:
    f = (f"BBOX(geom, {box[0]!r}, {box[1]!r}, {box[2]!r}, {box[3]!r}) "
         f"AND dtg DURING {DURING}")
    return f + (f" AND val > {VAL_GT}" if with_val else "")


def _url(base: str, path: str, **query) -> str:
    return f"{base}{path}" + (f"?{urlencode(query)}" if query else "")


def load_store(smoke: Smoke, corpus: dict):
    """DataStoreFinder → create_schema → FeatureTable.build → ds.load."""
    from geomesa_tpu.datastore import DataStoreFinder
    from geomesa_tpu.features.table import FeatureTable, StringColumn

    ds = DataStoreFinder.get_data_store(type="tpu")
    sft = ds.create_schema(TYPE, SPEC)
    table = smoke.timed("table_build", lambda: FeatureTable.build(sft, {
        "name": StringColumn(corpus["name_codes"], corpus["name_vocab"]),
        "val": corpus["val"], "dtg": corpus["dtg"],
        "geom": (corpus["x"], corpus["y"])}))
    smoke.timed("index_build", lambda: ds.load(TYPE, table))
    for idx in ds.planners[TYPE].indexes:
        stages = getattr(idx, "build_stages", None)
        if stages:
            print(f"build_stages {idx.name}: {json.dumps(stages)}",
                  flush=True)
    return ds


def serve_and_compare(smoke: Smoke, ds, corpus: dict, ref: Reference) -> None:
    """Start the REST server the CLI starts, send the requests, compare."""
    from geomesa_tpu import web

    httpd = web.serve(ds, host="127.0.0.1", port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        _requests(smoke, ds, corpus, ref, base)
    finally:
        httpd.shutdown()
        httpd.server_close()


def _requests(smoke: Smoke, ds, corpus: dict, ref: Reference,
              base: str) -> None:
    import jax

    centers = corpus["centers"]

    # /healthz says what hardware answers (its process-wide counters are
    # read again at the end; the checks are on what THIS run added)
    h = h0 = smoke.http("GET", _url(base, "/healthz"))
    m0 = smoke.http("GET", _url(base, "/metrics"))
    smoke.check(h["backend"] == jax.default_backend()
                and h["device_kind"] == jax.devices()[0].device_kind
                and h["devices"] == len(jax.local_devices()),
                f"/healthz hardware: backend={h['backend']} "
                f"device_kind={h['device_kind']} devices={h['devices']}")

    # where the index build went, as the server reports it
    recent = smoke.http("GET", _url(base, "/progress"))["progress"]["recent"]
    stages = {p["phase"]: round(p["duration_ms"] / 1000, 2)
              for p in reversed(recent) if p.get("type") == TYPE}
    rest = round(smoke.phases["index_build"] - sum(stages.values()), 2)
    print(f"build phases (GET /progress): {json.dumps(stages)}; rest of "
          f"index_build (full-scan planes, stats battery): {rest} s",
          flush=True)

    # 1. one count, bbox + DURING + val > 10: cold (with compile), then warm
    url = _url(base, f"/types/{TYPE}/count", cql=cql_box(BIG_BOX))
    cold = smoke.timed("first_query_with_compile",
                       lambda: smoke.http("GET", url))
    warm = smoke.timed("same_query_warm", lambda: smoke.http("GET", url))
    want = len(ref.rows_box_during_val(BIG_BOX))
    for tag, got in (("cold", cold), ("warm", warm)):
        smoke.check(got.get("count") == want and not got.get("approximate"),
                    f"REST count {tag}: got {got} want {want}")
    # the same filter through the library: the fused count program
    # (compiled.try_count) — the scheduler batches REST counts instead
    got = smoke.timed("library_count", lambda: ds.count(TYPE, cql_box(BIG_BOX)))
    smoke.check(got == want, f"library count: got {got} want {want}")

    # 2. 64 concurrent counts over distinct boxes: the batched dispatch
    rng = np.random.default_rng(7)
    boxes = []
    for i in range(64):
        cx, cy = centers[i]
        w, hgt = rng.uniform(0.5, 6.0, 2)
        boxes.append((float(cx - w), float(max(-90.0, cy - hgt)),
                      float(cx + w), float(min(90.0, cy + hgt))))
    sched0 = smoke.http("GET", _url(base, "/scheduler"))
    gate = threading.Barrier(len(boxes))

    def one(box):
        gate.wait()
        return smoke.http("GET", _url(base, f"/types/{TYPE}/count",
                                      cql=cql_box(box)))

    def burst():
        with ThreadPoolExecutor(max_workers=len(boxes)) as pool:
            return list(pool.map(one, boxes))

    answers = smoke.timed("concurrent_64_counts", burst)
    sched1 = smoke.http("GET", _url(base, "/scheduler"))
    wants = [len(ref.rows_box_during_val(b)) for b in boxes]
    bad = [(b, a, w) for b, a, w in zip(boxes, answers, wants)
           if a.get("count") != w or a.get("approximate")]
    smoke.check(not bad, f"64 concurrent REST counts exact "
                         f"(total {sum(wants)}; mismatches {bad[:3]})")
    fused = sched1["fused"] - sched0["fused"]
    print(f"scheduler batches: {json.dumps(sched1['batch_size_hist'])} "
          f"flush_reasons: {json.dumps(sched1['flush_reasons'])}", flush=True)
    # each batched dispatch as the flight recorder saw it
    events = smoke.http("GET", _url(base, "/events", kind="batch",
                                    limit=200))["events"]
    print("batched dispatches [size, rows_scanned, duration_ms, device_ms]: "
          + json.dumps([[e["batch_size"], e["rows_scanned"], e["duration_ms"],
                         e["device_ms"]] for e in reversed(events)]),
          flush=True)
    smoke.check(fused == len(boxes) and sched1["singles"] == sched0["singles"],
                f"all 64 went through the batched dispatch (fused +{fused})")
    smoke.check(max(int(k) for k in sched1["batch_size_hist"]) > 1,
                "at least one dispatch carried more than one query")

    # 3. select: features of a small box, ids equal as sets
    cx, cy = centers[0]
    small = (float(cx - 0.5), float(cy - 0.5), float(cx + 0.5), float(cy + 0.5))
    limit = 50_000
    fc = smoke.timed("features_select", lambda: smoke.http(
        "GET", _url(base, f"/types/{TYPE}/features",
                    cql=cql_box(small, with_val=False), limit=limit)))
    want_rows = ref.rows_box_during(small)
    got_ids = {f["id"] for f in fc["features"]}
    smoke.check(len(want_rows) < limit
                and got_ids == {str(r) for r in want_rows.tolist()},
                f"REST features: got {len(got_ids)} ids want {len(want_rows)}")

    # 4. polygon INTERSECTS count: count_refine + host refine of the sliver
    cx, cy = centers[1]
    ring = [(cx - 5, cy - 3), (cx + 1, cy - 4.5), (cx + 5.5, cy - 1),
            (cx + 2, cy + 0.5), (cx + 4, cy + 4), (cx - 1, cy + 2.5),
            (cx - 4.5, cy + 3.5), (cx - 5, cy - 3)]
    wkt = ", ".join(f"{px!r} {py!r}" for px, py in
                    ((float(a), float(b)) for a, b in ring))
    poly_cql = (f"INTERSECTS(geom, POLYGON(({wkt}))) "
                f"AND dtg DURING {DURING}")
    got = smoke.timed("polygon_count", lambda: smoke.http(
        "GET", _url(base, f"/types/{TYPE}/count", cql=poly_cql)))
    want = len(ref.rows_polygon_during(ring))
    smoke.check(got.get("count") == want and not got.get("approximate"),
                f"REST polygon INTERSECTS count: got {got} want {want}")

    # 5. library: a 512x512 density whose mass is the count ...
    render = (BIG_BOX[0] - 2, BIG_BOX[1] - 2, BIG_BOX[2] + 2, BIG_BOX[3] + 2)
    grid = smoke.timed("density_512", lambda: ds.query(
        TYPE, cql_box(BIG_BOX), hints={"density": {
            "bbox": render, "width": 512, "height": 512}}))
    want = len(ref.rows_box_during_val(BIG_BOX))
    w = np.asarray(grid.weights)
    smoke.check(w.shape == (512, 512) and bool(np.isfinite(w).all())
                and float(w.astype(np.float64).sum()) == float(want),
                f"density 512x512 mass: got {float(w.sum(dtype=np.float64))}"
                f" want {want}")

    # ... and a KNN with k = 10 against a brute-force top-10
    from geomesa_tpu.process.knn import knn
    qx, qy = float(centers[2][0] + 0.37), float(centers[2][1] - 0.21)
    k = 10
    got_rows, got_d = smoke.timed(
        "knn_10", lambda: knn(ds.planner(TYPE), qx, qy, k))
    want_rows, want_d = ref.knn(qx, qy, k)
    smoke.check(len(got_d) == k
                and bool(np.allclose(got_d, want_d, rtol=1e-9, atol=1e-6))
                and set(np.asarray(got_rows).tolist())
                == set(want_rows.tolist()),
                f"knn k={k}: got {np.round(got_d, 3).tolist()} "
                f"want {np.round(want_d, 3).tolist()}")

    # 6. write, then read it back (LSM delta tier): the acknowledged write
    # must be in the next count
    cx, cy = centers[3]
    tiny = (float(cx - 0.05), float(cy - 0.05), float(cx + 0.05), float(cy + 0.05))
    before = len(ref.rows_box_during_val(tiny))
    feats = [{"type": "Feature", "id": f"smoke-{i}",
              "geometry": {"type": "Point",
                           "coordinates": [float(cx + 0.01 * (i - 2)),
                                           float(cy + 0.01 * (i - 2))]},
              "properties": {"name": "actor00", "val": 50 + i,
                             "dtg": "2020-01-08T12:00:00"}}
             for i in range(5)]
    ack = smoke.timed("post_features", lambda: smoke.http(
        "POST", _url(base, f"/types/{TYPE}/features"),
        json.dumps({"type": "FeatureCollection",
                    "features": feats}).encode()))
    got = smoke.timed("count_after_write", lambda: smoke.http(
        "GET", _url(base, f"/types/{TYPE}/count", cql=cql_box(tiny))))
    smoke.check(ack == {"ingested": len(feats)}
                and got.get("count") == before + len(feats)
                and not got.get("approximate"),
                f"write read back: ack {ack}, count {got} "
                f"want {before} + {len(feats)}")

    # nothing degraded, breaker closed, no 5xx — asked over HTTP
    h = smoke.http("GET", _url(base, "/healthz"))
    m = smoke.http("GET", _url(base, "/metrics"))
    degraded = m["counters"].get("scheduler.degraded", 0) \
        - m0["counters"].get("scheduler.degraded", 0)
    breaker = h["overload"].get("breaker", {}).get("state")
    smoke.check(degraded == 0, f"scheduler.degraded == 0 (is {degraded})")
    smoke.check(breaker == "closed", f"breaker closed (is {breaker})")
    fq = {k: v - h0["fused_query"].get(k, 0)
          for k, v in h["fused_query"].items()}
    smoke.check(fq["fallbacks"] == 0 and fq["queries"] > 0,
                f"fused programs served the point shapes: {json.dumps(fq)}")
    compiles = {k[len("kernel."):-len(".compile")]: round(v["total_s"], 2)
                for k, v in m["timers"].items()
                if k.startswith("kernel.") and k.endswith(".compile")}
    print(f"first-call (trace + compile) seconds by kernel.tier: "
          f"{json.dumps(compiles)}", flush=True)
    smoke.check(all(s == 200 for s in smoke.statuses),
                f"{len(smoke.statuses)} HTTP responses, all 200")


def device_checks(smoke: Smoke, ds) -> None:
    """What would otherwise hide the device."""
    from geomesa_tpu import config, native
    from geomesa_tpu.index.device import memory_snapshot

    plane_bytes = 0
    off_device = []
    for idx in ds.planners[TYPE].indexes:
        for name, arr in idx.device.columns.items():
            plane_bytes += int(arr.nbytes)
            if any(d.platform != "tpu" for d in arr.devices()):
                off_device.append(f"{idx.name}.{name}")
    smoke.check(not off_device,
                f"every device column sits on a TPU device {off_device}")
    mem = memory_snapshot()
    print(f"device memory: {json.dumps(mem)}", flush=True)
    in_use = mem.get("bytes_in_use", 0)
    smoke.check(in_use >= plane_bytes,
                f"bytes_in_use {in_use} >= plane bytes {plane_bytes}")
    if not config.NO_NATIVE.get():
        smoke.check(native.available(), "native encoder loaded")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=DEFAULT_ROWS)
    p.add_argument("--seed", type=int, default=1234)
    args = p.parse_args(argv)

    from geomesa_tpu import config
    cache_dir = config.enable_compile_cache()   # before any backend starts
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: refusing to run: jax.default_backend() is "
              f"{jax.default_backend()!r}, not 'tpu'", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}  compile cache: {cache_dir}",
          flush=True)
    print(f"rows: {args.rows}  seed: {args.seed}", flush=True)
    if args.rows != DEFAULT_ROWS:
        print("reduced: " + json.dumps({
            "rows": args.rows, "of": DEFAULT_ROWS, "why": "--rows"}),
            flush=True)

    smoke = Smoke()
    corpus = smoke.timed("corpus_generation",
                         lambda: make_corpus(args.rows, args.seed))
    ds = load_store(smoke, corpus)
    ref = Reference(corpus)
    serve_and_compare(smoke, ds, corpus, ref)
    device_checks(smoke, ds)
    ds.close()

    print("phases: " + json.dumps(smoke.phases), flush=True)
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} check(s) failed:",
              file=sys.stderr)
        for f in smoke.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
