"""N-process CPU-backend distributed dryrun + single-process oracle.

The acceptance surface for the cluster runtime (and the engine behind
the CI ``cluster`` job): spawn N real worker processes
(``JAX_PLATFORMS=cpu``, gloo collectives), have each

  1. deal itself a round-robin slice of a deterministic shared-seed
     corpus (so no process ever materializes the full table),
  2. repartition by Morton key range (cluster/build.py) so it owns one
     contiguous, sorted shard,
  3. build a real local store + index over the shard, assemble the
     ClusterShardedTable global arrays, and run the query battery:
     psum'd bbox+time counts, a psum'd density grid, and ordered-merge
     selects,
  4. start a web surface and auto-register the cluster in the Federator
     (both processes must appear in /fleet with no manual --addr list),

while the parent runs the IDENTICAL battery single-process (the oracle
is the same code path with an inactive runtime — one code path, two
cardinalities). The orchestrator then asserts byte-equality: every
rank's psum count == oracle count, density grids sha-identical, merged
select fids list-identical, and every rank holds strictly less than the
full corpus.

The corpus deliberately contains duplicated (point, time) rows so the
tie-break discipline (original-gid plane through the partition, local
row order in the index) is exercised, not just probable.

The default (non-drill) run additionally exercises the cluster knn
radius exchange against a brute-force oracle and the distributed WRITE
path: a fresh extra corpus routed by Morton key ownership, each process
ingesting only its owned rows, and the post-ingest cluster table proven
byte-equal to the oracle that ingested everything single-process.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from geomesa_tpu.cluster.runtime import ClusterRuntime, runtime

SPEC = "name:String,val:Int,dtg:Date,*geom:Point"
TYPE = "pts"

COUNT_QUERIES = [
    "BBOX(geom, -10, -10, 10, 10)",
    "BBOX(geom, -10, -10, 10, 10) AND dtg DURING "
    "2020-01-05T00:00:00Z/2020-01-20T00:00:00Z",
    "val > 50",
    "INCLUDE",
]
SELECT_QUERIES = [
    "BBOX(geom, -6, -6, 6, 6)",
    "BBOX(geom, 20, 20, 60, 60) AND dtg DURING "
    "2020-01-02T00:00:00Z/2020-01-25T00:00:00Z",
]
DENSITY_QUERY = "BBOX(geom, -90, -45, 90, 45)"
DENSITY_BBOX = (-90.0, -45.0, 90.0, 45.0)
DENSITY_WH = (64, 32)

# geometry-catalog battery: st_* function queries (banded kernels +
# host refine per shard, psum-reduced — ClusterScan.count is device-only
# and cannot host-refine Func residuals) and the point-in-polygon join
FUNC_COUNT_QUERIES = [
    "st_distance(geom, POINT(0 0)) < 25",
    "st_contains(POLYGON((-30 -15, 30 -15, 30 15, -30 15, -30 -15)), geom)",
    "st_intersects(geom, POLYGON((60 10, 120 10, 90 60, 60 10)))",
]
JOIN_POLYGONS = [
    "POLYGON((-20 -20, 20 -20, 20 20, -20 20, -20 -20))",
    "POLYGON((0 0, 40 0, 20 35, 0 0))",
    "POLYGON((100 -30, 160 -30, 160 40, 130 5, 100 40, 100 -30))",
]
JOIN_MAX_PAIRS = 200

# cluster knn battery: (cql, x, y, k) — device-exact plans only (knn
# rejects host residuals). k=7 overlaps the duplicated corpus tail so
# the (distance, gid) tie-break is exercised, not just probable.
KNN_QUERIES = [
    ("INCLUDE", 0.0, 0.0, 5),
    ("BBOX(geom, -60, -60, 60, 60)", 10.0, -5.0, 7),
    ("BBOX(geom, -10, -10, 10, 10) AND dtg DURING "
     "2020-01-05T00:00:00Z/2020-01-20T00:00:00Z", -3.0, 4.0, 6),
]

# write-path stage: the extra corpus is this fraction of the base one
WRITE_EXTRA_DIV = 8


# balance-drill corpus window: a 2-hour dtg span starting on an
# epoch-week boundary keeps every row in ONE z3 time bin, so the
# (bin << 48 | z) partition keys become spatial-major and coarse Morton
# cells map cleanly onto contiguous shard key ranges (the default
# 30-day corpus interleaves time bins and spatial cells straddle shards)
DRILL_START = "2020-01-06T00:00:00"
DRILL_SPAN_MS = 2 * 3600 * 1000


def make_corpus(n: int, seed: int, span_ms: Optional[int] = None,
                start: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Deterministic shared corpus; the tail duplicates head rows
    (same point, same timestamp) to force key ties across processes.
    ``span_ms``/``start`` narrow the dtg window (the balance drill needs
    a single z3 time bin); defaults reproduce the historical corpus."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    base = np.datetime64(start or "2020-01-01T00:00:00",
                         "ms").astype(np.int64)
    span = int(span_ms) if span_ms else 30 * 86400000
    dtg = base + rng.integers(0, span, n)
    name = rng.choice(["a", "b", "c"], n)
    val = rng.integers(0, 100, n).astype(np.int32)
    dup = max(1, n // 64)
    x[-dup:], y[-dup:], dtg[-dup:] = x[:dup], y[:dup], dtg[:dup]
    return {"x": x, "y": y, "dtg": dtg, "name": name, "val": val}


def _partition_keys(sft, table) -> np.ndarray:
    """Morton partition key per row: a MONOTONE coarsening of the z3
    index sort order (bin major, z high bits minor) — rows with equal
    full keys share a partition key, so no key range ever straddles a
    process boundary and the within-shard index sort restores the exact
    global order."""
    from geomesa_tpu.curves.binnedtime import TimePeriod
    from geomesa_tpu.index.spatial import Z3Index, _DeltaKeyShim

    shim = _DeltaKeyShim(sft, table, sft.geometry_attribute.name,
                         sft.dtg_attribute.name,
                         TimePeriod.parse(sft.z3_interval))
    Z3Index._sort_keys(shim)
    bins = np.asarray(shim._bins, dtype=np.int64)
    z = np.asarray(shim._z, dtype=np.int64)
    return (bins << 48) | (z >> 15)


def inactive_runtime() -> ClusterRuntime:
    """A single-process runtime for the oracle path (never touches the
    process-global singleton or jax.distributed)."""
    rt = ClusterRuntime()
    rt.initialized = True
    rt.topology = "flat"
    return rt


def build_local(rt: ClusterRuntime, n: int, seed: int,
                stages: Optional[dict] = None,
                span_ms: Optional[int] = None,
                start: Optional[str] = None):
    """Slice → partition → store/index → global table. Collective when
    the runtime is active; the complete single-process pipeline when
    not (the oracle)."""
    from geomesa_tpu import DataStoreFinder, config
    from geomesa_tpu.cluster.build import cluster_partition
    from geomesa_tpu.cluster.exec import ClusterScan
    from geomesa_tpu.cluster.table import ClusterShardedTable
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.features.table import FeatureTable

    if stages is None:
        stages = {}
    t0 = time.perf_counter()
    corpus = make_corpus(n, seed, span_ms=span_ms, start=start)
    if rt.active():
        ids = np.arange(rt.process_id, n, rt.num_processes, dtype=np.int64)
    else:
        ids = np.arange(n, dtype=np.int64)
    mine = {k: v[ids] for k, v in corpus.items()}
    stages["corpus_s"] = round(time.perf_counter() - t0, 3)

    sft = SimpleFeatureType.from_spec(TYPE, SPEC)
    t0 = time.perf_counter()
    key_table = FeatureTable.build(sft, {
        "name": mine["name"], "val": mine["val"], "dtg": mine["dtg"],
        "geom": (mine["x"], mine["y"])})
    keys = _partition_keys(sft, key_table)
    stages["keys_s"] = round(time.perf_counter() - t0, 3)

    keys_l, part, bounds, stages = cluster_partition(
        rt, keys, {**mine, "gid": ids}, gids=ids, stages=stages)

    t0 = time.perf_counter()
    fids = ["f%09d" % g for g in part["gid"]]
    ds = DataStoreFinder.get_data_store(backend="tpu")
    ds.create_schema(TYPE, SPEC)
    ds.load(TYPE, FeatureTable.build(ds.get_schema(TYPE), {
        "name": part["name"], "val": part["val"].astype(np.int32),
        "dtg": part["dtg"].astype(np.int64),
        "geom": (part["x"], part["y"])}, fids=fids))
    planner = ds.planner(TYPE)
    idx = next(i for i in planner.indexes if i.name == "z3")
    stages["index_build_s"] = round(time.perf_counter() - t0, 3)

    t0 = time.perf_counter()
    host_cols = {k: np.asarray(v) for k, v in idx.device.columns.items()}
    st = ClusterShardedTable.from_local_columns(rt, host_cols,
                                                key_bounds=bounds)
    stages["global_table_s"] = round(time.perf_counter() - t0, 3)
    rt.register_table(TYPE, st.layout.summary())
    if config.SHARDWATCH_ENABLED.get():
        # shard balance observatory: exchange the empirical cell -> shard
        # occupancy map (collective — symmetric because the knob is env-
        # driven and identical across ranks) and install it in the ledger
        from geomesa_tpu.cluster.table import shard_cell_map
        from geomesa_tpu.obs import shardwatch as _shardwatch
        t0 = time.perf_counter()
        cells, key_ranges, shard_rows = shard_cell_map(
            rt, part["x"], part["y"], keys_l)
        _shardwatch.WATCH.set_shard_map(TYPE, cells, key_ranges,
                                        shard_rows)
        stages["shard_map_s"] = round(time.perf_counter() - t0, 3)
    fids_sorted = np.asarray(planner.table.fids)[np.asarray(idx.perm)]
    return ds, planner, ClusterScan(st), fids_sorted, stages


def run_battery(planner, scan, fids_sorted) -> dict:
    """Counts + density + ordered-merge selects; identical output shape
    on every rank AND on the oracle (which is how equality is judged)."""
    out = {"counts": {}, "count_warm_ms": {}, "selects": {}}
    for q in COUNT_QUERIES:
        plan = planner.plan(q)
        c = scan.count(plan)                       # compile + collective
        t0 = time.perf_counter()
        c2 = scan.count(plan)
        out["count_warm_ms"][q] = round(
            (time.perf_counter() - t0) * 1000.0, 3)
        assert c == c2
        out["counts"][q] = int(c)
    plan = planner.plan(DENSITY_QUERY)
    grid = scan.density(plan, DENSITY_BBOX, *DENSITY_WH)
    t0 = time.perf_counter()
    grid = scan.density(plan, DENSITY_BBOX, *DENSITY_WH)
    out["density_warm_ms"] = round((time.perf_counter() - t0) * 1000.0, 3)
    g32 = np.ascontiguousarray(np.asarray(grid, dtype=np.float32))
    out["density_sha"] = hashlib.sha256(g32.tobytes()).hexdigest()
    out["density_sum"] = float(g32.sum())
    for q in SELECT_QUERIES:
        plan = planner.plan(q)
        t0 = time.perf_counter()
        merged = scan.select_merged(plan, {"fid": fids_sorted})
        out.setdefault("select_ms", {})[q] = round(
            (time.perf_counter() - t0) * 1000.0, 3)
        out["selects"][q] = merged["fid"]

    # geometry catalog: st_* function counts + the sharded spatial join
    # (same code path on the oracle — inactive runtime collapses the
    # psum/merge, so equality judges the distribution, not the kernels)
    from geomesa_tpu.geom.join import func_counts, join_battery
    rt = getattr(scan, "runtime", None)
    t0 = time.perf_counter()
    out["func_counts"] = func_counts(planner, FUNC_COUNT_QUERIES,
                                     runtime=rt)
    jb = join_battery(planner, JOIN_POLYGONS, runtime=rt,
                      fids=fids_sorted, max_pairs=JOIN_MAX_PAIRS)
    out["join"] = jb["stable"]
    out["join_meta"] = jb["meta"]
    out["geom_ms"] = round((time.perf_counter() - t0) * 1000.0, 3)
    return out


# -- cluster knn + the distributed write path ---------------------------------


def _knn_key(q: str, x: float, y: float, k: int) -> str:
    return f"{q}|{x},{y},k={k}"


def run_knn(planner, scan) -> dict:
    """The bounded-radius-exchange battery: every query's (ids, dists)
    plus the number of collective rounds it took (the dryrun asserts
    rounds are counted and stay under the cap)."""
    from geomesa_tpu.cluster.exec import KNN_STATS
    out: dict = {"results": {}, "rounds": {}}
    for q, x, y, k in KNN_QUERIES:
        plan = planner.plan(q)
        before = KNN_STATS["rounds_total"]
        ids, d = scan.knn(plan, x, y, k)
        out["results"][_knn_key(q, x, y, k)] = {
            "ids": [int(i) for i in ids],
            "d": [float(v) for v in np.asarray(d, dtype=np.float32)]}
        out["rounds"][_knn_key(q, x, y, k)] = \
            KNN_STATS["rounds_total"] - before
    out["stats"] = dict(KNN_STATS)
    return out


def oracle_knn(planner, scan) -> dict:
    """The brute-force oracle: no top-k machinery at all — f64 haversine
    over EVERY masked row, (distance, gid) lexsort, take k. What the
    radius exchange must match byte-for-byte."""
    from geomesa_tpu.process.geo import haversine_m
    gx, gy = scan.sharded.host_xy
    out = {}
    for q, x, y, k in KNN_QUERIES:
        idx = np.flatnonzero(scan.mask(planner.plan(q)))
        d = haversine_m(np.asarray(gx)[idx].astype(np.float64),
                        np.asarray(gy)[idx].astype(np.float64),
                        float(x), float(y))
        top = np.lexsort((idx, d))[:k]
        out[_knn_key(q, x, y, k)] = {
            "ids": [int(i) for i in idx[top]],
            "d": [float(v) for v in d[top].astype(np.float32)]}
    return out


def _extra_table(sft, extra: Dict[str, np.ndarray], ids: np.ndarray):
    from geomesa_tpu.features.table import FeatureTable
    return FeatureTable.build(sft, {
        "name": extra["name"][ids],
        "val": extra["val"][ids].astype(np.int32),
        "dtg": extra["dtg"][ids].astype(np.int64),
        "geom": (extra["x"][ids], extra["y"][ids])},
        fids=["e%09d" % g for g in ids])


def run_post_battery(planner, scan, fids_sorted) -> dict:
    """Post-ingest exactness battery (counts + density sha + merged
    selects): byte-equality against the oracle's post-ingest run IS the
    'writes landed on the owning cell' proof — a row on the wrong shard
    breaks rank-order merge, a lost row breaks every count."""
    out: dict = {"counts": {}, "selects": {}}
    for q in COUNT_QUERIES:
        out["counts"][q] = int(scan.count(planner.plan(q)))
    grid = scan.density(planner.plan(DENSITY_QUERY), DENSITY_BBOX,
                        *DENSITY_WH)
    g32 = np.ascontiguousarray(np.asarray(grid, dtype=np.float32))
    out["density_sha"] = hashlib.sha256(g32.tobytes()).hexdigest()
    for q in SELECT_QUERIES:
        out["selects"][q] = scan.select_merged(
            planner.plan(q), {"fid": fids_sorted})["fid"]
    return out


def run_write_path(rt: ClusterRuntime, ds, scan, n: int, seed: int,
                   span_ms: Optional[int] = None,
                   start: Optional[str] = None) -> dict:
    """The distributed durable write path: a fresh extra corpus routes
    by Morton key ownership (ShardCells over the layout's key ranges),
    each process ingests ONLY its owned rows, the cluster table
    reassembles, and the post-ingest battery must be byte-equal to the
    oracle that ingested everything single-process."""
    from geomesa_tpu.cluster.cells import ShardCells
    from geomesa_tpu.cluster.exec import ClusterScan
    from geomesa_tpu.cluster.table import ClusterShardedTable
    from geomesa_tpu.features.table import FeatureTable

    t0 = time.perf_counter()
    n_extra = max(64, n // WRITE_EXTRA_DIV)
    extra = make_corpus(n_extra, seed + 1, span_ms=span_ms, start=start)
    sft = ds.get_schema(TYPE)
    keys = _partition_keys(sft, FeatureTable.build(sft, {
        "name": extra["name"], "val": extra["val"].astype(np.int32),
        "dtg": extra["dtg"].astype(np.int64),
        "geom": (extra["x"], extra["y"])}))
    if rt.active() and scan.layout.key_ranges:
        owners = ShardCells.from_key_ranges(
            scan.layout.key_ranges).route(keys)
        mine = np.flatnonzero(owners == rt.process_id)
    else:
        mine = np.arange(n_extra, dtype=np.int64)
    if len(mine):
        ds.load(TYPE, _extra_table(sft, extra, mine))

    planner = ds.planner(TYPE)        # flush: extras merge into the index
    idx = next(i for i in planner.indexes if i.name == "z3")
    host_cols = {k: np.asarray(v) for k, v in idx.device.columns.items()}
    post_keys = _partition_keys(sft, planner.table)
    st = ClusterShardedTable.from_local_columns(
        rt, host_cols,
        key_bounds=(int(post_keys.min()), int(post_keys.max())))
    scan2 = ClusterScan(st)
    fids_sorted = np.asarray(planner.table.fids)[np.asarray(idx.perm)]
    post = run_post_battery(planner, scan2, fids_sorted)
    return {
        "n_extra": int(n_extra),
        "ingested": int(len(mine)),
        "owned_sha": hashlib.sha256(
            np.asarray(mine, dtype=np.int64).tobytes()).hexdigest(),
        "post": post,
        "key_range": st.layout.key_ranges[rt.process_id]
            if st.layout.key_ranges else None,
        "wall_s": round(time.perf_counter() - t0, 3),
    }


def _expected_routing(key_ranges, n: int, seed: int) -> List[dict]:
    """What ownership routing SHOULD do, recomputed independently by the
    orchestrator from each rank's reported key range."""
    from geomesa_tpu.cluster.cells import ShardCells
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.features.table import FeatureTable

    n_extra = max(64, n // WRITE_EXTRA_DIV)
    extra = make_corpus(n_extra, seed + 1)
    sft = SimpleFeatureType.from_spec(TYPE, SPEC)
    keys = _partition_keys(sft, FeatureTable.build(sft, {
        "name": extra["name"], "val": extra["val"].astype(np.int32),
        "dtg": extra["dtg"].astype(np.int64),
        "geom": (extra["x"], extra["y"])}))
    owners = ShardCells.from_key_ranges(key_ranges).route(keys)
    out = []
    for p in range(len(key_ranges)):
        mine = np.flatnonzero(owners == p)
        out.append({"ingested": int(len(mine)),
                    "owned_sha": hashlib.sha256(
                        np.asarray(mine, dtype=np.int64).tobytes())
                        .hexdigest()})
    return out


# -- the balance drill --------------------------------------------------------


def _drill_cells(cells: Dict[str, dict], shard: str, k: int = 16,
                 min_rows: int = 8, min_share: float = 0.9) -> List[str]:
    """Cells owned (>= ``min_share`` of their rows) by ``shard`` with
    enough rows to be meaningful, densest first — the drill's target
    set (clean ownership keeps the expected attribution unambiguous)."""
    owned = []
    for cell, owners in cells.items():
        rows = {s: int(o["rows"]) for s, o in owners.items()}
        tot = sum(rows.values())
        if tot >= min_rows and rows.get(shard, 0) / tot >= min_share:
            owned.append((cell, tot))
    owned.sort(key=lambda t: (-t[1], t[0]))
    return [c for c, _ in owned[:k]]


def run_drill(rt: ClusterRuntime, mode: str, seed: int,
              n_events: Optional[int] = None) -> dict:
    """The balance drill: rank 0 synthesizes a query-event storm through
    the observability plane's own input surface (flight record → workload
    tee → shardwatch ledger), then every rank reports its ledger verdict.

    ``skew`` is a Zipf storm (s=1.3) over cells owned by the LAST shard
    — rank 0 emits the events, so the ledger must attribute load across
    a rank boundary to name the victim. ``uniform`` spreads the same
    event count evenly over every shard's cells (the two-sided control:
    balance ≈ 1.0, zero incidents)."""
    from geomesa_tpu.obs import flight as _flight
    from geomesa_tpu.obs import shardwatch as _shardwatch
    from geomesa_tpu.obs.doctor import DOCTOR

    n_events = int(n_events if n_events is not None else os.environ.get(
        "GEOMESA_TPU_DRYRUN_DRILL_EVENTS", "600"))
    smap = (_shardwatch.WATCH.export_state()["maps"] or {}).get(TYPE) \
        or {}
    cells = smap.get("cells") or {}
    shards = sorted(smap.get("key_ranges") or {})
    victim = shards[-1] if shards else "0"
    out: dict = {"mode": mode, "victim": victim, "events": 0}
    if rt.process_id == 0 and cells:
        rng = np.random.default_rng(seed + 1000)
        now_ms = int(time.time() * 1000)
        if mode == "skew":
            pool = _drill_cells(cells, victim)
            w = 1.0 / np.arange(1, len(pool) + 1, dtype=np.float64) ** 1.3
        else:
            # equal weight PER SHARD (not per cell) so the control stays
            # balanced even when shards differ in qualifying-cell count
            pool, wl = [], []
            for s in shards:
                owned = _drill_cells(cells, s)
                pool.extend(owned)
                wl.extend([1.0 / max(1, len(owned))] * len(owned))
            w = np.asarray(wl, dtype=np.float64)
        if len(pool):
            w = w / w.sum()
            picks = rng.choice(len(pool), size=n_events, p=w)
            for j, i in enumerate(picks):
                cell = pool[int(i)]
                rows = sum(int(o["rows"]) for o in cells[cell].values())
                _flight.RECORDER.record({
                    "ts_ms": now_ms, "kind": "query", "type": TYPE,
                    "plan_hash": f"drill:{cell}", "cell": cell,
                    "priority": "interactive",
                    "tenant": f"drill{j % 3}",
                    "duration_ms": 2.0, "rows_scanned": rows,
                    "rows_matched": rows, "device_ms": 0.4})
            out["events"] = int(n_events)
            out["pool_cells"] = len(pool)
    out["balance"] = _shardwatch.WATCH.balance()
    res = DOCTOR.evaluate()
    out["alerts"] = [a for a in res.get("alerts", [])
                     if a["rule"] in ("shard_imbalance",
                                      "collective_straggler")]
    out["imbalance_incidents"] = [
        {"rule": i.get("rule"), "cause": i.get("cause"),
         "suspect": i.get("suspect"), "status": i.get("status")}
        for i in res.get("incidents", [])
        if i.get("rule") == "shard_imbalance"]
    return out


# -- worker entry (one process of the cluster) --------------------------------


def worker_main(out_path: str) -> int:
    n = int(os.environ.get("GEOMESA_TPU_DRYRUN_N", "20000"))
    seed = int(os.environ.get("GEOMESA_TPU_DRYRUN_SEED", "7"))
    with_web = os.environ.get("GEOMESA_TPU_DRYRUN_WEB", "1") != "0"
    drill = os.environ.get("GEOMESA_TPU_DRYRUN_DRILL", "").strip().lower()
    span_ms = os.environ.get("GEOMESA_TPU_DRYRUN_SPAN_MS")
    start = os.environ.get("GEOMESA_TPU_DRYRUN_START") or None
    t_start = time.perf_counter()
    rt = runtime()
    stages: dict = {}
    ds, planner, scan, fids_sorted, stages = build_local(
        rt, n, seed, stages,
        span_ms=int(span_ms) if span_ms else None, start=start)
    battery = run_battery(planner, scan, fids_sorted)
    drill_report = run_drill(rt, drill, seed) if drill else None
    # knn + the distributed write path ride the default dryrun; the
    # drill variant keeps its historical (cfg13-scored) shape
    knn_report = run_knn(planner, scan) if not drill else None
    write_report = run_write_path(rt, ds, scan, n, seed) \
        if not drill else None

    fleet = None
    balance_http = None
    if with_web:
        from geomesa_tpu.web import serve
        httpd = serve(ds, port=0, background=True)
        port = httpd.server_address[1]
        nodes = rt.register_web(port)            # collective: all bound
        if nodes:
            import urllib.request
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/fleet", timeout=30) as r:
                fleet = json.loads(r.read().decode())
            if drill:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/cluster/balance",
                        timeout=30) as r:
                    balance_http = json.loads(r.read().decode())
                if rt.process_id == 0 and drill_report is not None:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/fleet/balance",
                            timeout=30) as r:
                        drill_report["fleet_balance"] = json.loads(
                            r.read().decode())

    report = {
        "process_id": rt.process_id,
        "num_processes": rt.num_processes,
        "local_rows": scan.sharded.local_rows(),
        "n_global": scan.sharded.n,
        "key_range": scan.layout.key_ranges[rt.process_id]
            if scan.layout.key_ranges else None,
        "psum_rounds": rt.psum_rounds,
        "cluster": rt.state(),
        "battery": battery,
        "stages": stages,
        "fleet": fleet,
        "knn": knn_report,
        "write": write_report,
        "drill": drill_report,
        "balance_http": balance_http,
        "wall_s": round(time.perf_counter() - t_start, 3),
    }
    with open(out_path, "w") as f:
        json.dump(report, f)
    rt.barrier("dryrun-done")
    return 0


# -- orchestrator -------------------------------------------------------------


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_dryrun(num_processes: int = 2, n: int = 20000, seed: int = 7,
               timeout_s: float = 420.0, local_devices: int = 2,
               out_dir: Optional[str] = None, web: bool = True,
               drill: Optional[str] = None) -> dict:
    """Spawn the N-process dryrun, compute the oracle in-process, and
    return the merged report with exactness checks + timings. ``drill``
    ("skew" | "uniform") additionally runs the shard-balance drill on the
    single-z3-bin corpus window (see ``DRILL_START``)."""
    if drill and drill not in ("skew", "uniform"):
        raise ValueError(f"unknown drill mode: {drill!r}")
    t_start = time.perf_counter()
    work = out_dir or tempfile.mkdtemp(prefix="geomesa_cluster_dryrun_")
    os.makedirs(work, exist_ok=True)
    span_ms = DRILL_SPAN_MS if drill else None
    start = DRILL_START if drill else None

    coord = f"127.0.0.1:{_free_port()}"
    procs: List[subprocess.Popen] = []
    outs = []
    for p in range(num_processes):
        out_path = os.path.join(work, f"rank{p}.json")
        outs.append(out_path)
        env = os.environ.copy()
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS":
                f"--xla_force_host_platform_device_count={local_devices}",
            "GEOMESA_TPU_CLUSTER": "1",
            "GEOMESA_TPU_CLUSTER_COORDINATOR": coord,
            "GEOMESA_TPU_CLUSTER_NUM_PROCESSES": str(num_processes),
            "GEOMESA_TPU_CLUSTER_PROCESS_ID": str(p),
            "GEOMESA_TPU_NODE_ID": f"proc{p}",
            "GEOMESA_TPU_DRYRUN_N": str(n),
            "GEOMESA_TPU_DRYRUN_SEED": str(seed),
            "GEOMESA_TPU_DRYRUN_WEB": "1" if web else "0",
        })
        if drill:
            env.update({
                "GEOMESA_TPU_DRYRUN_DRILL": drill,
                "GEOMESA_TPU_DRYRUN_START": DRILL_START,
                "GEOMESA_TPU_DRYRUN_SPAN_MS": str(DRILL_SPAN_MS),
            })
        with open(os.path.join(work, f"rank{p}.log"), "w") as log:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "geomesa_tpu.cluster.dryrun",
                 "--worker", "--out", out_path],
                stdout=log, stderr=subprocess.STDOUT, env=env))

    # oracle while the workers run: same battery, inactive runtime
    # (same corpus window as the workers so equality still holds)
    rt0 = inactive_runtime()
    ds0, planner, scan, fids_sorted, ostages = build_local(
        rt0, n, seed, span_ms=span_ms, start=start)
    oracle = run_battery(planner, scan, fids_sorted)
    if not drill:
        oracle["knn_brute"] = oracle_knn(planner, scan)
        oracle["write"] = run_write_path(rt0, ds0, scan, n, seed)

    deadline = time.monotonic() + timeout_s
    rcs = [None] * num_processes
    while time.monotonic() < deadline and any(r is None for r in rcs):
        for i, pr in enumerate(procs):
            if rcs[i] is None:
                rcs[i] = pr.poll()
        time.sleep(0.2)
    for pr in procs:
        if pr.poll() is None:
            pr.kill()
    rcs = [pr.poll() for pr in procs]

    ranks = []
    for path in outs:
        try:
            with open(path) as f:
                ranks.append(json.load(f))
        except Exception:
            ranks.append(None)

    checks = _check(oracle, ranks, n, num_processes, web, drill,
                    seed=seed)
    report = {
        "ok": all(checks.values()) and all(rc == 0 for rc in rcs),
        "num_processes": num_processes,
        "n": n,
        "drill": drill,
        "exit_codes": rcs,
        "checks": checks,
        "oracle": {k: oracle[k] for k in
                   ("counts", "density_sha", "density_sum")},
        "ranks": ranks,
        "oracle_stages": ostages,
        "work_dir": work,
        "wall_s": round(time.perf_counter() - t_start, 3),
    }
    with open(os.path.join(work, "dryrun_report.json"), "w") as f:
        json.dump(report, f, indent=2)
    return report


def _check(oracle: dict, ranks: List[Optional[dict]], n: int,
           num_processes: int, web: bool,
           drill: Optional[str] = None,
           seed: int = 7) -> Dict[str, bool]:
    live = [r for r in ranks if r is not None]
    checks = {"all_ranks_reported": len(live) == num_processes}
    if not checks["all_ranks_reported"]:
        return checks
    checks["counts_equal"] = all(
        r["battery"]["counts"] == oracle["counts"] for r in live)
    checks["density_equal"] = all(
        r["battery"]["density_sha"] == oracle["density_sha"] for r in live)
    checks["selects_equal"] = all(
        r["battery"]["selects"] == oracle["selects"] for r in live)
    checks["func_counts_equal"] = all(
        r["battery"].get("func_counts") == oracle["func_counts"]
        for r in live)
    checks["join_equal"] = all(
        r["battery"].get("join") == oracle["join"] for r in live)
    checks["shards_strict_subset"] = all(
        0 < r["local_rows"] < n for r in live) and \
        sum(r["local_rows"] for r in live) == n
    kr = [r["key_range"] for r in sorted(live,
                                         key=lambda r: r["process_id"])]
    checks["key_ranges_ordered"] = (
        all(k is not None for k in kr)
        and all(kr[i][1] <= kr[i + 1][0] for i in range(len(kr) - 1)))
    checks["psum_rounds_counted"] = all(
        r["psum_rounds"] > 0 for r in live)
    if web:
        def _fleet_ok(r):
            nodes = (r["fleet"] or {}).get("nodes") or {}
            return (len(nodes) == num_processes
                    and all(v.get("ok") for v in nodes.values()))
        checks["fleet_registered"] = all(_fleet_ok(r) for r in live)
    if drill:
        # every rank ran the drill and rank 0's ledger was active
        # (scoring against the pinned bars lives in tests/test_shardwatch.py)
        checks["drill_reported"] = all(
            (r.get("drill") or {}).get("mode") == drill for r in live)
        r0 = next((r for r in live if r["process_id"] == 0), None)
        checks["drill_ledger_active"] = bool(
            r0 and ((r0.get("drill") or {}).get("balance")
                    or {}).get("active"))
    else:
        from geomesa_tpu import config
        # cluster knn: every rank's radius exchange byte-equals the
        # brute-force oracle, with the collective rounds counted and
        # under the cap (exactly 2 per exact query)
        brute = oracle.get("knn_brute")
        checks["knn_exact"] = all(
            (r.get("knn") or {}).get("results") == brute for r in live)
        cap = max(2, int(config.CELL_KNN_MAX_ROUNDS.get()))
        checks["knn_rounds_bounded"] = all(
            (r.get("knn") or {}).get("rounds")
            and all(0 < v <= cap
                    for v in r["knn"]["rounds"].values())
            for r in live)
        # write path: each rank ingested EXACTLY the rows ownership
        # routing assigns it (recomputed independently here), and the
        # post-ingest cluster table byte-equals the oracle that
        # ingested everything single-process
        expected = _expected_routing(kr, n, seed) \
            if checks["key_ranges_ordered"] else None
        by_pid = {r["process_id"]: (r.get("write") or {}) for r in live}
        checks["write_landed_on_owner"] = bool(expected) and all(
            by_pid.get(p, {}).get("ingested") == e["ingested"]
            and by_pid.get(p, {}).get("owned_sha") == e["owned_sha"]
            for p, e in enumerate(expected))
        n_extra = max(64, n // WRITE_EXTRA_DIV)
        checks["write_strict_subset"] = (
            sum(w.get("ingested", 0) for w in by_pid.values()) == n_extra
            and all(w.get("ingested", 0) < n_extra
                    for w in by_pid.values()))
        checks["write_post_equal"] = all(
            (r.get("write") or {}).get("post") == oracle["write"]["post"]
            for r in live)
    return checks


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="N-process CPU cluster dryrun vs single-process oracle")
    ap.add_argument("--worker", action="store_true",
                    help="internal: run as one spawned cluster process")
    ap.add_argument("--out", default="dryrun_report.json")
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--timeout-s", type=float, default=420.0)
    ap.add_argument("--no-web", action="store_true")
    ap.add_argument("--drill", choices=["skew", "uniform"], default=None,
                    help="run the shard-balance drill (Zipf storm on one "
                         "shard's key range, or the uniform control)")
    args = ap.parse_args(argv)
    if args.worker:
        return worker_main(args.out)
    report = run_dryrun(args.procs, args.n, args.seed,
                        timeout_s=args.timeout_s, web=not args.no_web,
                        drill=args.drill)
    print(json.dumps({k: report[k] for k in
                      ("ok", "checks", "wall_s", "work_dir")}, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
