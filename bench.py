"""Benchmark harness — prints ONE JSON line for the driver.

Covers the five BASELINE.md configs:

  0. CPU reference (GeoCQEngine moral slot): a grid-bucket-indexed in-memory
     store (CpuGridIndex below) over 1M points, bbox count — the honest
     indexed-CPU comparator BASELINE.md config 0 names, not a full-scan.
  1. Z3 index (headline): GDELT-like corpus (default 100M pts), bbox+time
     count. Reports the range-pruned scan (cover -> candidate blocks ->
     device gather) and the full-mask scan; blocking p50 (includes one
     device->host round trip — the RTT is MEASURED and reported separately,
     cfg1_rtt_p50_ms), pipelined per-query latency (async dispatches, one
     readback — the sustained-throughput number), index build time, the
     micro-batching scheduler under 64 concurrent client threads
     (cfg1_scheduler_qps / cfg1_scheduler_p50_ms vs cfg1_unbatched_qps —
     the end-to-end serving numbers the batch64 kernel figure feeds), and
     the same query on two CPU comparators: single-core numpy full scan and
     the CpuGridIndex indexed store at full scale.
  2. XZ2 index: st_intersects polygon query over small linestring extents
     (device envelope prefilter + exact host refine), p50.
  3. Spatial join: point-in-polygon counts, points/sec/chip.
  4. Density (512x512, compact/pruned scatter) + KNN (device top-k over
     candidate blocks) — requires config 1 (reported explicitly if missing).
  5. S2 vs Z2 cover calibration (host-only): scanned-rows slop of each
     curve's cover over random boxes, pinning the cost model's S2
     cover_slop (curves/s2.py) against measurement.
  6. WAL ingest overhead: sustained bulk-ingest rows/s through the
     datastore with durability off vs WAL fsync=off/batch/always
     (durability subsystem acceptance: batch within 15% of no-WAL).
  7. Overload behavior: 4x the admission bound of concurrent interactive
     clients against a tightly bounded scheduler — measures the shed rate
     (excess rejected with backpressure, not queued into collapse) and the
     p99 latency of the ADMITTED requests (the property load shedding
     exists to protect).
  8. Workload analytics: a skewed (Zipf) multi-tenant mix of ~200 query
     shapes through the scheduler — measures the hot-set sketch's recall
     of the TRUE top-10 plan hashes against an exact oracle, and the
     wall-clock overhead of the workload plane (enabled at defaults vs
     GEOMESA_TPU_WORKLOAD=0).

Headline metric = config 1 blocking p50 (RTT included; see rtt field).
``vs_baseline`` = indexed-CPU comparator p50 / batch64 per-query (sustained
throughput; ONE fixed definition — see cfg1_vs_baseline_definition, which
names the pipelined fallback if the batch path could not engage). Blocking
and pipelined ratios are reported as their own detail fields.

Scale via GEOMESA_TPU_BENCH_N (default 100M). Subset configs via
GEOMESA_TPU_BENCH_CONFIGS, e.g. "1,3".

Perf watch (ISSUE 6): every run also writes a FLAT machine-stable
``BENCH_summary.json`` — numeric metrics + device/host metadata + the
per-kernel attribution snapshot — the regression gate's input.

  python bench.py --mini                  # CI-sized deterministic run
  python bench.py --mini --check          # compare vs perf/baselines.json;
                                          # exit 3 on confirmed regressions
  python bench.py --mini --update-baseline  # fold this run into baselines

``--check`` flags only past baseline median + k*MAD in each metric's bad
direction (see obs/perfwatch.py), names the responsible kernel by diffing
the attribution snapshots, and writes ``BENCH_report.json``. Two
deterministic fault hooks let the gate prove itself: GEOMESA_TPU_BENCH_
HANDICAP="cfg4_knn:2" stretches a wall metric 2x; GEOMESA_TPU_BENCH_
HANDICAP_KERNEL="topk:2" stretches matching device kernels (the injected
in-kernel slowdown the acceptance test requires the gate to flag AND
attribute).
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# wall-metric handicap spec: "prefix:factor[,prefix:factor...]" — the
# regression gate's deterministic self-test injection
_HANDICAPS: dict = {}


def _parse_handicaps() -> None:
    for part in os.environ.get("GEOMESA_TPU_BENCH_HANDICAP", "").split(","):
        if ":" in part:
            p, f = part.rsplit(":", 1)
            try:
                _HANDICAPS[p.strip()] = float(f)
            except ValueError:
                pass


def _stretch(key) -> float:
    if key:
        for p, f in _HANDICAPS.items():
            if key.startswith(p):
                return f
    return 1.0


def _p50(samples) -> float:
    return float(np.median(np.asarray(samples) * 1000))


def _time_reps(fn, reps: int, key=None):
    fac = _stretch(key)
    lat = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        if fac > 1.0:
            time.sleep(dt * (fac - 1.0))
            dt *= fac
        lat.append(dt)
    return lat


class CpuGridIndex:
    """Single-host indexed CPU comparator (the GeoCQEngine slot,
    /root/reference/geomesa-memory/geomesa-cqengine/.../GeoCQEngine.scala:37):
    rows bucketed by (week-bin, lat/lon grid cell) and sorted by bucket;
    counts answer from per-bucket prefix sums for fully-covered buckets and
    branchless row tests for boundary buckets. This is a *generous* stand-in
    — the JVM original evaluates per-feature JTS predicates on bucket hits."""

    GX, GY = 512, 256
    WEEK_MS = 7 * 86_400_000

    def __init__(self, x, y, dtg_ms):
        self.n = len(x)
        ix = np.minimum(((x + 180.0) * (self.GX / 360.0)).astype(np.int64), self.GX - 1)
        iy = np.minimum(((y + 90.0) * (self.GY / 180.0)).astype(np.int64), self.GY - 1)
        b = dtg_ms // self.WEEK_MS
        self.b0 = int(b.min())
        nb = int(b.max()) - self.b0 + 1
        self.nb = nb
        cell = ((b - self.b0) * (self.GX * self.GY) + iy * self.GX + ix)
        order = np.argsort(cell, kind="stable")
        self.xs = x[order]
        self.ys = y[order]
        self.ts = dtg_ms[order]
        counts = np.bincount(cell, minlength=nb * self.GX * self.GY)
        self.starts = np.concatenate([[0], np.cumsum(counts)])
        self.counts = counts

    def count(self, qx0, qy0, qx1, qy1, lo=None, hi=None) -> int:
        ix0 = max(0, int((qx0 + 180.0) * (self.GX / 360.0)))
        ix1 = min(self.GX - 1, int((qx1 + 180.0) * (self.GX / 360.0)))
        iy0 = max(0, int((qy0 + 90.0) * (self.GY / 180.0)))
        iy1 = min(self.GY - 1, int((qy1 + 90.0) * (self.GY / 180.0)))
        total = 0
        slices = []
        for b in range(self.nb):
            blo = (self.b0 + b) * self.WEEK_MS
            bhi = blo + self.WEEK_MS
            if lo is not None and (bhi <= lo + 1 or blo >= hi):
                continue
            time_full = lo is None or (blo > lo and bhi - 1 < hi)
            iys, ixs = np.meshgrid(np.arange(iy0, iy1 + 1),
                                   np.arange(ix0, ix1 + 1), indexing="ij")
            interior = ((ixs > ix0) & (ixs < ix1) & (iys > iy0) & (iys < iy1))
            cells = b * (self.GX * self.GY) + iys * self.GX + ixs
            if time_full:
                total += int(self.counts[cells[interior]].sum())
                partial = cells[~interior]
            else:
                partial = cells.ravel()
            for c in partial:
                s, e = self.starts[c], self.starts[c + 1]
                if e > s:
                    slices.append((s, e))
        if slices:
            idx = np.concatenate([np.arange(s, e) for s, e in slices])
            xs, ys = self.xs[idx], self.ys[idx]
            m = (xs >= qx0) & (xs <= qx1) & (ys >= qy0) & (ys <= qy1)
            if lo is not None:
                ts = self.ts[idx]
                m &= (ts > lo) & (ts < hi)
            total += int(m.sum())
        return total


def parse_args(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        description="geomesa-tpu benchmark + perf regression gate")
    p.add_argument("--mini", action="store_true",
                   help="CI-sized deterministic run: N=GEOMESA_TPU_BENCH_"
                        "MINI_N, 5 reps, configs 0,1,4 (unless overridden)")
    p.add_argument("--check", action="store_true",
                   help="compare this run against --baseline; exit 3 on "
                        "confirmed regressions")
    p.add_argument("--update-baseline", action="store_true",
                   help="fold this run's summary into --baseline")
    p.add_argument("--baseline",
                   default=os.path.join(REPO, "perf", "baselines.json"))
    p.add_argument("--summary",
                   default=os.path.join(REPO, "BENCH_summary.json"))
    p.add_argument("--report",
                   default=os.path.join(REPO, "BENCH_report.json"))
    p.add_argument("--k", type=float, default=None,
                   help="MAD multiplier for --check (default "
                        "GEOMESA_TPU_PERFWATCH_K)")
    return p.parse_args(argv)


def main(args=None) -> int:
    import jax
    import jax.numpy as jnp

    if args is None:
        args = parse_args()
    _parse_handicaps()
    hk = os.environ.get("GEOMESA_TPU_BENCH_HANDICAP_KERNEL", "")
    if ":" in hk:
        from geomesa_tpu.obs import profiling as _prof
        match, fac = hk.rsplit(":", 1)
        _prof.arm_kernel_handicap(match, float(fac))

    # persistent compile cache: repeated bench runs skip XLA compiles
    from geomesa_tpu import config as _gcfg
    _gcfg.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    # the bench drives planners directly (no datastore), so wire the obs
    # hooks itself — the per-kernel attribution snapshot persisted with
    # each summary is what --check diffs to NAME a regressing kernel
    from geomesa_tpu import obs as _obs
    from geomesa_tpu.metrics import register_device_gauges
    _obs.install()
    register_device_gauges()

    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.features.table import FeatureTable
    from geomesa_tpu.index.planner import QueryPlanner
    from geomesa_tpu.index.spatial import XZ2Index, Z3Index

    n = int(os.environ.get("GEOMESA_TPU_BENCH_N", 100_000_000))
    reps = int(os.environ.get("GEOMESA_TPU_BENCH_REPS", 20))
    default_configs = "0,1,2,3,4,5,6,7,8,9,10"
    if args.mini:
        n = min(n, int(_gcfg.BENCH_MINI_N.get()))
        reps = min(reps, 5)
        # cfg9 rides the mini gate: the serving-layer regressions it pins
        # (cache serve p50, Zipf hit rate, storm isolation) are host-side
        # and CI-sized, unlike the device-bound cfg2/3/5-8 sweeps
        default_configs = "0,1,4,9"
    configs = set(os.environ.get("GEOMESA_TPU_BENCH_CONFIGS",
                                 default_configs).split(","))
    rng = np.random.default_rng(1234)
    detail: dict = {"n_points": n, "device": str(jax.devices()[0]),
                    "host_cores": os.cpu_count()}

    # host<->device link characteristics: every blocking number below
    # includes one of these round trips
    g = jax.jit(lambda s: s + 1)
    s0 = jnp.zeros((), jnp.int32)
    int(g(s0))
    rtt = _time_reps(lambda: int(g(s0)), 12)
    detail["rtt_p50_ms"] = round(_p50(rtt), 2)
    # per-execute overhead floor: K trivial async dispatches + one readback.
    # This bounds ANY pipelined per-query time from below — a pipelined
    # number near it is dispatch-bound, not device-bound.
    def _pipe_floor():
        outs = [g(s0) for _ in range(64)]
        return np.asarray(jnp.stack(outs))
    _pipe_floor()
    detail["dispatch_floor_ms_per_query"] = round(
        min(_time_reps(_pipe_floor, 3)) * 1000 / 64, 3)
    big = np.zeros(8_000_000, np.int32)  # 32MB
    jax.device_put(big[:1024]).block_until_ready()
    t0 = time.perf_counter()
    jax.device_put(big).block_until_ready()
    detail["upload_mbps"] = round(32 / (time.perf_counter() - t0), 1)
    del big

    # GDELT-like synthetic corpus: clustered lon/lat over 30 days
    t0 = time.perf_counter()
    centers = rng.uniform([-120, -40], [140, 60], size=(64, 2))
    which = rng.integers(0, 64, n)
    x = np.clip(centers[which, 0] + rng.normal(0, 8, n), -180, 180)
    y = np.clip(centers[which, 1] + rng.normal(0, 6, n), -90, 90)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    dtg = base + rng.integers(0, 30 * 86400000, n)
    detail["gen_s"] = round(time.perf_counter() - t0, 2)

    qx0, qy0, qx1, qy1 = -10.0, 30.0, 30.0, 55.0
    lo = np.datetime64("2020-01-05", "ms").astype(np.int64)
    hi = np.datetime64("2020-01-12", "ms").astype(np.int64)

    def cpu_query(xs, ys, ts):
        return int(np.sum((xs >= qx0) & (xs <= qx1) & (ys >= qy0) & (ys <= qy1)
                          & (ts > lo) & (ts < hi)))

    # ---- config 0: indexed CPU reference (GeoCQEngine slot), 1M bbox ------
    if "0" in configs:
        m = min(1_000_000, n)
        t0 = time.perf_counter()
        gi = CpuGridIndex(x[:m], y[:m], dtg[:m])
        detail["cfg0_cpu_index_build_s"] = round(time.perf_counter() - t0, 2)
        lat = _time_reps(lambda: gi.count(qx0, qy0, qx1, qy1), max(5, reps))
        detail["cfg0_cpu_1m_bbox_p50_ms"] = round(_p50(lat), 3)
        del gi
        gc.collect()

    headline_p50 = None
    vs_baseline = None
    planner = None

    # ---- config 1: Z3 bbox+time over the full corpus (headline) ----------
    if "1" in configs:
        sft = SimpleFeatureType.from_spec(
            "gdelt", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
        t0 = time.perf_counter()
        table = FeatureTable.build(sft, {"dtg": dtg, "geom": (x, y)})
        detail["cfg1_table_build_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        idx = Z3Index(sft, table)
        jax.block_until_ready(idx.device.columns["xi"])
        detail["cfg1_index_build_s"] = round(time.perf_counter() - t0, 2)
        for k, v in getattr(idx, "build_stages", {}).items():
            detail[f"cfg1_build_{k}"] = v
        t0 = time.perf_counter()
        idx._join_prefetch()  # joins the background host pruning-key sorts
        detail["cfg1_host_keys_s"] = round(time.perf_counter() - t0, 2)
        planner = QueryPlanner(sft, table, [idx])

        # pre-warm the fused single-dispatch programs (cold-shape XLA
        # compiles otherwise land in the first prepared query below)
        from geomesa_tpu.index import compiled as _fused_mod
        t0 = time.perf_counter()
        _fused_mod.warm_programs(idx)
        detail["cfg1_fused_warm_s"] = round(time.perf_counter() - t0, 2)

        ecql = (f"BBOX(geom, {qx0}, {qy0}, {qx1}, {qy1}) AND "
                "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
        t0 = time.perf_counter()
        pq = planner.prepare(ecql)
        detail["cfg1_plan_stage_ms"] = round((time.perf_counter() - t0) * 1000, 2)
        for k in ("candidate_rows", "candidate_blocks", "scanned_fraction"):
            if k in pq.plan.explain:
                detail[f"cfg1_{k}"] = pq.plan.explain[k]

        t0 = time.perf_counter()
        count = pq.count()  # warmup: compiles the pruned scan
        detail["cfg1_warm_s"] = round(time.perf_counter() - t0, 2)
        lat = _time_reps(pq.count, reps, key="cfg1_blocking")
        headline_p50 = _p50(lat)          # blocking: includes one RTT
        detail["cfg1_blocking_p50_ms"] = round(headline_p50, 3)

        # pre-compile the padded-block-count kernel tiers the cold queries
        # will land in (derived from their actual covers, ± one pow2 tier)
        # so a cold query hits a compiled kernel, not a fresh XLA compile.
        # Build/warm-time work — where the reference pays iterator loading.
        from geomesa_tpu.index import prune as _prune_mod
        t0 = time.perf_counter()
        tiers = set()
        for i in (0, 9):
            pl = planner.plan(
                f"BBOX(geom, {qx0 + 0.11 + 0.83 * i}, "
                f"{qy0 - 0.07 - 0.41 * i}, {qx1 + 0.11 + 0.83 * i}, "
                f"{qy1 - 0.07 - 0.41 * i}) AND dtg DURING "
                "2020-01-06T00:00:00Z/2020-01-13T00:00:00Z")
            bl = planner._pruned_blocks(pl)
            if bl is not None and len(bl):
                nbp = max(8, 1 << max(0, len(bl) - 1).bit_length())
                tiers.update({max(8, nbp // 2), nbp, nbp * 2})
        jax.block_until_ready([
            idx.kernels.prepare_count_blocks(
                "point_boxes", pq.plan.boxes_loose, pq.plan.windows,
                pq.plan.residual_device,
                np.arange(nb_t, dtype=np.int32), _prune_mod.BLOCK_SIZE)()
            for nb_t in sorted(tiers)])
        detail["cfg1_tier_warm_s"] = round(time.perf_counter() - t0, 2)

        # cold query: NEVER-seen boxes, prepare (parse/plan/cover/stage) +
        # blocking count, end to end — the honest first-query number the
        # 200ms budget is about. Transfer shapes + scan kernels are warm
        # (per-process, build-time); each rep re-plans + re-covers fresh.
        cold_prep, cold_tot = [], []
        for i in range(10):
            ddx, ddy = 0.11 + 0.83 * i, 0.07 + 0.41 * i
            qc = (f"BBOX(geom, {qx0 + ddx}, {qy0 - ddy}, {qx1 + ddx}, "
                  f"{qy1 - ddy}) AND dtg DURING "
                  "2020-01-06T00:00:00Z/2020-01-13T00:00:00Z")
            t0 = time.perf_counter()
            pqc = planner.prepare(qc)
            t1 = time.perf_counter()
            pqc.count()
            cold_tot.append(time.perf_counter() - t0)
            cold_prep.append(t1 - t0)
        detail["cfg1_cold_prepare_p50_ms"] = round(_p50(cold_prep), 2)
        detail["cfg1_cold_query_p50_ms"] = round(_p50(cold_tot), 2)

        # pipelined: K async dispatches, one stacked readback — amortizes the
        # host<->device RTT; per-query time == sustained throughput
        k = 64

        def pipeline(q):
            outs = [q.count_async() for _ in range(k)]
            return np.asarray(jnp.stack(outs))

        pipeline(pq)
        t0 = time.perf_counter()
        total = pipeline(pq)
        wall = time.perf_counter() - t0
        assert int(total[0]) == count
        pruned_per_query = wall * 1000 / k
        detail["cfg1_pipelined_per_query_ms"] = round(pruned_per_query, 3)
        detail["cfg1_pipelined_qps"] = round(k / wall, 1)

        # batched serving: 64 DISTINCT box-queries, one dispatch against the
        # union of their candidate blocks — the per-dispatch RPC overhead
        # amortizes across the batch, exposing the true per-query device cost
        t0 = time.perf_counter()
        bplans, bblocks, bqueries = [], [], []
        for i in range(64):
            ddx, ddy = (i % 8) * 0.4, (i // 8) * 0.3
            qb = (f"BBOX(geom, {qx0 + ddx}, {qy0 + ddy}, {qx1 + ddx}, "
                  f"{qy1 + ddy}) AND dtg DURING "
                  "2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
            bqueries.append(qb)
            pl = planner.plan(qb)
            bl = planner._pruned_blocks(pl)
            if bl is None:
                break
            bplans.append(pl)
            bblocks.append(bl)
        if len(bplans) == 64:
            from geomesa_tpu.index import prune as _prune
            union = np.unique(np.concatenate(bblocks))
            boxes64 = np.concatenate([p.boxes_loose[:1] for p in bplans])
            detail["cfg1_batch_prep_ms"] = round(
                (time.perf_counter() - t0) * 1000, 1)
            detail["cfg1_batch_union_blocks"] = int(len(union))
            disp = idx.kernels.prepare_counts_multi_blocks(
                "point_boxes", boxes64, bplans[0].windows,
                bplans[0].residual_device, union, _prune.BLOCK_SIZE)
            counts64 = np.asarray(disp())  # warm
            assert int(counts64[0]) == count
            nb_batches = 16
            outs = [disp() for _ in range(nb_batches)]
            jax.block_until_ready(outs)
            t0 = time.perf_counter()
            outs = [disp() for _ in range(nb_batches)]
            jax.block_until_ready(outs)
            per_q = (time.perf_counter() - t0) * 1000 / (nb_batches * 64)
            detail["cfg1_batch64_per_query_ms"] = round(per_q, 4)
            detail["cfg1_batch64_qps"] = round(1000 / per_q, 0)

        # scheduler serving: 64 concurrent client threads against the
        # micro-batching scheduler (serve/scheduler.py — requests coalesce
        # into fused dispatches, plans/covers cache) vs the same threads on
        # the unbatched per-request path (every call plans + dispatches
        # alone). This is the end-to-end serving number the batch64 kernel
        # figure feeds. Skipped under --mini: 64-way thread contention on
        # a small CI host measures the scheduler of the OS, not ours —
        # the batch64 kernel figure above carries the batching signal.
        if len(bplans) == 64 and not args.mini:
            import threading

            from geomesa_tpu.serve.scheduler import (PlannerBinding,
                                                     QueryScheduler)
            # window sized for the client population: 64 synchronous
            # clients resubmit within a few ms of a batch resolving, so an
            # 8ms cap lets batches refill instead of fragmenting
            sched = QueryScheduler(PlannerBinding({"gdelt": planner}),
                                   flush_size=64, window_us=8000)
            n_threads = 64

            def run_clients(fn, reps_c):
                lats: list = []
                llock = threading.Lock()
                barrier = threading.Barrier(n_threads + 1)

                def client(i):
                    q = bqueries[i % len(bqueries)]
                    mine = []
                    barrier.wait()
                    for _ in range(reps_c):
                        tq = time.perf_counter()
                        fn(q)
                        mine.append(time.perf_counter() - tq)
                    with llock:
                        lats.extend(mine)

                ths = [threading.Thread(target=client, args=(i,))
                       for i in range(n_threads)]
                for th in ths:
                    th.start()
                barrier.wait()
                tw = time.perf_counter()
                for th in ths:
                    th.join()
                return lats, time.perf_counter() - tw

            sched.count_many("gdelt", bqueries)  # warm: plans+covers cache
            lat_s, wall_s = run_clients(
                lambda q: sched.count("gdelt", q), 8)
            detail["cfg1_scheduler_qps"] = round(len(lat_s) / wall_s, 1)
            detail["cfg1_scheduler_p50_ms"] = round(_p50(lat_s), 3)
            st = sched.stats()
            detail["cfg1_scheduler_plan_hit_rate"] = \
                st["plan_cache"]["hit_rate"]
            detail["cfg1_scheduler_flush_reasons"] = st["flush_reasons"]
            sched.shutdown()
            for q in bqueries[:4]:
                planner.count(q)  # warm the unbatched comparator path
            lat_u, wall_u = run_clients(lambda q: planner.count(q), 2)
            detail["cfg1_unbatched_qps"] = round(len(lat_u) / wall_u, 1)
            detail["cfg1_unbatched_p50_ms"] = round(_p50(lat_u), 3)
            detail["cfg1_scheduler_vs_unbatched"] = round(
                detail["cfg1_scheduler_qps"]
                / max(detail["cfg1_unbatched_qps"], 1e-9), 2)

            # observability tax at full scale: the same unbatched workload
            # with the whole obs layer (tracing + flight recorder + tail
            # sampling + kernel attribution) muted — the production-size
            # counterpart of the <5% guard in test_perf_budget.py
            from geomesa_tpu import trace as _tr
            with _tr.disabled():
                lat_d, wall_d = run_clients(lambda q: planner.count(q), 2)
            obs_off_qps = len(lat_d) / wall_d
            detail["cfg1_obs_off_qps"] = round(obs_off_qps, 1)
            detail["cfg1_obs_overhead_pct"] = round(
                (obs_off_qps / max(detail["cfg1_unbatched_qps"], 1e-9) - 1)
                * 100, 2)

        # full-mask scan for comparison (same query, pruning disabled)
        os.environ["GEOMESA_TPU_PRUNE"] = "0"
        pq_full = planner.prepare(ecql)
        t0 = time.perf_counter()
        assert pq_full.count() == count
        detail["cfg1_full_warm_s"] = round(time.perf_counter() - t0, 2)
        lat = _time_reps(pq_full.count, max(5, reps // 2))
        detail["cfg1_full_blocking_p50_ms"] = round(_p50(lat), 3)
        pipeline(pq_full)
        t0 = time.perf_counter()
        pipeline(pq_full)
        wall_f = time.perf_counter() - t0
        detail["cfg1_full_pipelined_per_query_ms"] = round(wall_f * 1000 / k, 3)
        bytes_scanned = n * 6 * 4  # xi/xl/yi/yl/bin/off int32 per row
        detail["cfg1_full_scan_gb_per_s"] = round(
            bytes_scanned / (wall_f / k) / 1e9, 1)
        del os.environ["GEOMESA_TPU_PRUNE"]

        # CPU comparators over the identical corpus
        cpu_lat = _time_reps(lambda: cpu_query(x, y, dtg), max(3, reps // 4))
        detail["cfg1_cpu_numpy_fullscan_ms"] = round(_p50(cpu_lat), 1)
        ref = cpu_query(x, y, dtg)
        assert count == ref, f"correctness check failed: {count} != {ref}"
        detail["cfg1_matched"] = count

        t0 = time.perf_counter()
        gi = CpuGridIndex(x, y, dtg)
        detail["cfg1_cpu_index_build_s"] = round(time.perf_counter() - t0, 2)
        assert gi.count(qx0, qy0, qx1, qy1, lo, hi) == ref, "cpu index wrong"
        cpu_idx_lat = _time_reps(
            lambda: gi.count(qx0, qy0, qx1, qy1, lo, hi), max(5, reps // 2))
        cpu_indexed_ms = _p50(cpu_idx_lat)
        detail["cfg1_cpu_indexed_p50_ms"] = round(cpu_indexed_ms, 2)
        del gi
        gc.collect()

        detail["cfg1_vs_indexed_cpu_pipelined"] = round(
            cpu_indexed_ms / pruned_per_query, 2)
        detail["cfg1_vs_indexed_cpu_blocking"] = round(
            cpu_indexed_ms / headline_p50, 2)
        detail["cfg1_vs_numpy_fullscan_pipelined"] = round(
            _p50(cpu_lat) / pruned_per_query, 2)
        if "cfg1_batch64_per_query_ms" in detail:
            detail["cfg1_vs_indexed_cpu_batched"] = round(
                cpu_indexed_ms / detail["cfg1_batch64_per_query_ms"], 1)
        # vs_baseline has ONE fixed definition: indexed-CPU comparator p50 /
        # device per-query cost at sustained throughput (the batched serving
        # kernel — 64 distinct queries per dispatch). The pipelined and
        # blocking ratios are reported as their own fields above; the
        # definition never silently switches between them.
        if "cfg1_vs_indexed_cpu_batched" in detail:
            detail["cfg1_vs_baseline_definition"] = (
                "cpu_indexed_p50_ms / batch64_per_query_ms (sustained "
                "throughput; single-query ratios reported separately)")
            vs_baseline = detail["cfg1_vs_indexed_cpu_batched"]
        else:  # batch path did not engage — fall back, and SAY so
            detail["cfg1_vs_baseline_definition"] = (
                "cpu_indexed_p50_ms / pipelined_per_query_ms (batch64 path "
                "did not engage this run)")
            vs_baseline = detail["cfg1_vs_indexed_cpu_pipelined"]
        detail["cfg1_note"] = (
            "blocking p50 includes one device->host round trip; rtt_p50_ms "
            "and dispatch_floor_ms_per_query are measured above (pipelined "
            "per-query times near the floor are dispatch-bound, not "
            "device-bound). cold_query p50 = prepare+count on never-seen "
            "boxes.")

    # ---- config 2: XZ2 st_intersects over linestring extents -------------
    if "2" in configs:
        n2 = max(100_000, min(n // 20, 5_000_000))
        sft2 = SimpleFeatureType.from_spec("osm", "*geom:LineString")
        lx = rng.uniform(-175, 170, n2)
        ly = rng.uniform(-85, 80, n2)
        dx = rng.uniform(0.01, 2.0, n2)
        dy = rng.uniform(0.01, 2.0, n2)
        from geomesa_tpu.features.geometry import GeometryArray
        t0 = time.perf_counter()
        coords = np.empty((2 * n2, 2), dtype=np.float64)
        coords[0::2, 0] = lx
        coords[0::2, 1] = ly
        coords[1::2, 0] = lx + dx
        coords[1::2, 1] = ly + dy
        garr = GeometryArray.linestrings(coords)
        table2 = FeatureTable.build(sft2, {"geom": garr})
        idx2 = XZ2Index(sft2, table2)
        jax.block_until_ready(idx2.device.columns["bxmin_i"])
        detail["cfg2_build_s"] = round(time.perf_counter() - t0, 2)
        detail["cfg2_n"] = n2
        planner2 = QueryPlanner(sft2, table2, [idx2])
        poly = ("POLYGON ((-12 30, 10 28, 14 44, -2 50, -12 30))")
        q2 = f"INTERSECTS(geom, {poly})"
        pq2 = planner2.prepare(q2)
        c2 = pq2.count()  # warmup (device prefilter + host refine)
        lat2 = _time_reps(pq2.count, max(5, reps // 2))
        detail["cfg2_xz2_intersects_p50_ms"] = round(_p50(lat2), 2)
        detail["cfg2_matched"] = c2
        e2 = planner2.explain(q2)
        detail["cfg2_scan"] = e2.get("scan")
        # CPU envelope-prefilter comparator over same extents (NB: envelope
        # overlap only — weaker than the exact intersects the repo answers)
        bb = garr.bboxes()
        lat2c = _time_reps(lambda: int(np.sum(
            (bb[:, 0] <= 14) & (bb[:, 2] >= -12)
            & (bb[:, 1] <= 50) & (bb[:, 3] >= 28))), 5)
        detail["cfg2_cpu_envelope_ms"] = round(_p50(lat2c), 2)
        # exact CPU comparator: each feature is one segment, the query a
        # convex-free fixed ring — segment intersects polygon iff an
        # endpoint is inside (even-odd ray cast) or it crosses an edge
        # (orientation signs; zero-sign covers boundary touches). This is
        # ground truth for the device-prefilter + host-refine count above,
        # so a mismatch fails the whole run, same as cfg1's assert.
        ring = np.array([(-12.0, 30.0), (10.0, 28.0), (14.0, 44.0),
                         (-2.0, 50.0), (-12.0, 30.0)])

        def exact_intersects_count():
            ax, ay, bx_, by_ = lx, ly, lx + dx, ly + dy
            hit = np.zeros(n2, dtype=bool)
            for qx, qy in ((ax, ay), (bx_, by_)):
                ins = np.zeros(n2, dtype=bool)
                for i in range(len(ring) - 1):
                    (x1, y1), (x2, y2) = ring[i], ring[i + 1]
                    crosses = (y1 > qy) != (y2 > qy)
                    with np.errstate(divide="ignore", invalid="ignore"):
                        xint = x1 + (qy - y1) * (x2 - x1) / (y2 - y1)
                    ins ^= crosses & (qx < xint)
                hit |= ins

            def orient(ox, oy, px_, py_, rx, ry):
                return np.sign((px_ - ox) * (ry - oy)
                               - (py_ - oy) * (rx - ox))

            for i in range(len(ring) - 1):
                (x1, y1), (x2, y2) = ring[i], ring[i + 1]
                o1 = orient(ax, ay, bx_, by_, x1, y1)
                o2 = orient(ax, ay, bx_, by_, x2, y2)
                o3 = orient(x1, y1, x2, y2, ax, ay)
                o4 = orient(x1, y1, x2, y2, bx_, by_)
                hit |= (o1 != o2) & (o3 != o4)
            return int(hit.sum())

        lat2e = _time_reps(exact_intersects_count, max(3, reps // 4))
        detail["cfg2_cpu_exact_ms"] = round(_p50(lat2e), 2)
        exact_ref = exact_intersects_count()
        assert c2 == exact_ref, \
            f"cfg2 correctness check failed: {c2} != {exact_ref}"
        del idx2, planner2, table2, garr
        gc.collect()

    # ---- config 3: point-in-polygon join, pts/sec/chip -------------------
    if "3" in configs:
        from geomesa_tpu.parallel.join import SpatialJoin
        n3 = min(n, 20_000_000)
        px = np.asarray(x[:n3], dtype=np.float32)
        py = np.asarray(y[:n3], dtype=np.float32)
        # real-complexity polygon set (committed artifact): country-scale
        # vertex counts anchored at this corpus's cluster centers — toy
        # 16-gons flattered the join by ~40x fewer edge tests per point
        with open(os.path.join(REPO, "perf",
                               "polygons_complex.json")) as fh:
            _pc = json.load(fh)
        polys = [(int(code), rings) for code, rings in _pc["polygons"]]
        _vc = _pc["vertex_counts"]
        detail["cfg3_poly_vertices_total"] = int(sum(_vc))
        detail["cfg3_poly_vertices_mean"] = round(sum(_vc) / len(_vc), 1)
        detail["cfg3_poly_vertices_max"] = int(max(_vc))
        join = SpatialJoin(polys)
        dx_ = jnp.asarray(px)
        dy_ = jnp.asarray(py)
        jax.block_until_ready([dx_, dy_])
        hits = join.counts(dx_, dy_)  # warmup + correctness smoke
        assert int(hits.sum()) > 0
        lat3 = _time_reps(lambda: join.counts(dx_, dy_), max(5, reps // 2))
        j_ms = _p50(lat3)
        detail["cfg3_join_p50_ms"] = round(j_ms, 2)
        detail["cfg3_join_mpts_per_s_per_chip"] = round(
            n3 / (j_ms / 1000) / 1e6, 1)
        detail["cfg3_n_points"] = n3
        detail["cfg3_n_polygons"] = len(polys)
        del join, dx_, dy_
        gc.collect()

        # extent x extent join (grid partition + device band refine + host
        # f64 uncertain sliver)
        from geomesa_tpu.features.geometry import GeometryArray
        from geomesa_tpu.parallel.extent_join import (candidate_pairs,
                                                      extent_join)
        from geomesa_tpu.parallel.pair_kernel import device_refine
        nj = 200_000
        jx = rng.uniform(-60, 60, nj)
        jy = rng.uniform(-60, 60, nj)
        jc = np.empty((2 * nj, 2))
        jc[0::2, 0], jc[0::2, 1] = jx, jy
        jc[1::2, 0] = jx + rng.uniform(-1, 1, nj)
        jc[1::2, 1] = jy + rng.uniform(-1, 1, nj)
        lines = GeometryArray.linestrings(jc)
        polys_g = GeometryArray.from_shapes(polys)
        t0 = time.perf_counter()
        la, ra = extent_join(lines, polys_g, device="never")
        detail["cfg3_extent_join_host_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        la_d, ra_d = extent_join(lines, polys_g, device="always")
        detail["cfg3_extent_join_device_s"] = round(
            time.perf_counter() - t0, 2)
        assert np.array_equal(la, la_d) and np.array_equal(ra, ra_d)
        detail["cfg3_extent_join_pairs"] = int(len(la))
        detail["cfg3_extent_join_n_lines"] = nj
        # device pair-kernel throughput: candidate pairs refined per second
        # per chip (warm dispatch, excludes the host grid partitioner).
        # The natural candidate set here is small and would be RTT-bound, so
        # the throughput rep tiles it to ~1M pairs — same kernel, same
        # gather-from-geometry-tables serving shape.
        from geomesa_tpu.parallel.pair_kernel import prepare_refine
        cli, crj = candidate_pairs(lines.bboxes(), polys_g.bboxes())
        detail["cfg3_candidate_pairs"] = int(len(cli))
        reps_t = max(1, 1_000_000 // max(1, len(cli)))
        tli = np.tile(cli, reps_t)
        trj = np.tile(crj, reps_t)
        # staged variant: pair vectors + geometry tables resident on device
        # (serving shape; isolates kernel+readback from the per-call upload)
        prep3 = prepare_refine(lines, polys_g, tli, trj)
        if prep3 is None:
            # the device path declines point/oversized geometries (any side
            # over pair_kernel.MAX_SEGMENTS): there is no kernel to time
            detail["cfg3_pair_refine_declined"] = (
                "device pair kernel declined this geometry set "
                "(pair_kernel.MAX_SEGMENTS)")
        else:
            device_refine(lines, polys_g, tli, trj)  # warm/compile
            lat3d = _time_reps(
                lambda: device_refine(lines, polys_g, tli, trj),
                max(5, reps // 2))
            p3d = _p50(lat3d)
            detail["cfg3_pair_refine_p50_ms"] = round(p3d, 2)
            detail["cfg3_pair_refine_mpairs_per_s_per_chip"] = round(
                len(tli) / (p3d / 1000) / 1e6, 2)
            prep3()
            lat3p = _time_reps(prep3, max(5, reps // 2))
            p3p = _p50(lat3p)
            detail["cfg3_pair_refine_staged_p50_ms"] = round(p3p, 2)
            detail["cfg3_pair_refine_staged_mpairs_per_s_per_chip"] = round(
                len(tli) / (p3p / 1000) / 1e6, 2)

    # ---- config 4: density + KNN -----------------------------------------
    if "4" in configs:
        if planner is None:
            detail["cfg4_skipped"] = "config 4 reuses config 1's index; run with 1"
        else:
            from geomesa_tpu.aggregates.density import prepare_density
            ecql = (f"BBOX(geom, {qx0}, {qy0}, {qx1}, {qy1}) AND "
                    "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
            t0 = time.perf_counter()
            drun = prepare_density(planner, ecql, (qx0, qy0, qx1, qy1), 512, 512)
            dg = drun()  # warmup/compile
            detail["cfg4_density_warm_s"] = round(time.perf_counter() - t0, 2)
            lat4 = _time_reps(drun, max(5, reps // 2), key="cfg4_density")
            detail["cfg4_density_512_p50_ms"] = round(_p50(lat4), 2)
            mass = int(dg.weights.sum(dtype=np.float64))
            detail["cfg4_density_mass"] = mass
            # f32 grid-snap vs exact fp62 mask may disagree on an O(1)-point
            # band (~1 f32 ulp) along the bbox edge — bound, don't equate
            ref_mass = detail.get("cfg1_matched", mass)
            assert abs(mass - ref_mass) <= 16, (mass, ref_mass)
            # delivered-grid encoding (device-side pack, DensityScan.scala:95
            # sparse-grid analogue) vs the raw 1MB f32 readback
            pk = getattr(drun, "packed", lambda: None)()
            detail["cfg4_density_pack"] = pk[0] if pk else "raw-f32"
            if pk:
                from geomesa_tpu.aggregates.grid_codec import packed_bytes
                detail["cfg4_density_delivered_kb"] = round(
                    packed_bytes(pk[0], pk[1], 512, 512) / 1024, 1)
                lat_raw = _time_reps(lambda: np.asarray(drun.dispatch()),
                                     max(5, reps // 2))
                detail["cfg4_density_raw_f32_p50_ms"] = round(_p50(lat_raw), 2)
            else:
                detail["cfg4_density_delivered_kb"] = round(512 * 512 * 4 / 1024, 1)
            # dispatch-only (device render cost; no grid readback)
            d0 = drun.dispatch()
            jax.block_until_ready(d0)
            t0 = time.perf_counter()
            outs = [drun.dispatch() for _ in range(16)]
            jax.block_until_ready(outs)
            detail["cfg4_density_dispatch_ms"] = round(
                (time.perf_counter() - t0) * 1000 / 16, 2)

            from geomesa_tpu.process.knn import knn
            t0 = time.perf_counter()
            rows, dists = knn(planner, 2.0, 48.0, 10)
            detail["cfg4_knn_warm_s"] = round(time.perf_counter() - t0, 2)
            fac5 = _stretch("cfg4_knn")
            lat5 = []
            for i in range(max(5, reps // 2)):
                t0 = time.perf_counter()
                rows, dists = knn(planner, 2.0 + 0.03 * i, 48.0, 10)
                dt5 = time.perf_counter() - t0
                if fac5 > 1.0:
                    time.sleep(dt5 * (fac5 - 1.0))
                    dt5 *= fac5
                lat5.append(dt5)
            detail["cfg4_knn10_ms"] = round(_p50(lat5), 1)
            # the host-vs-device split behind the knn number (the cfg4
            # regression postmortem: plan rounds were the cost, not the
            # kernel) — counters accumulate across the reps above
            from geomesa_tpu.metrics import REGISTRY as _reg
            kc = _reg.snapshot()["counters"]
            nq = max(5, reps // 2) + 1
            detail["cfg4_knn_plan_rounds_per_query"] = round(
                kc.get("knn.plan_rounds", 0) / nq, 2)
            detail["cfg4_knn_dispatches_per_query"] = round(
                kc.get("knn.device_dispatches", 0) / nq, 2)
            detail["cfg4_knn_max_m"] = round(float(dists.max()), 1)
            # the expanding-radius fallback (k > device top-k cap) timed at
            # scale — it serves oversized-k requests, so its cost stays
            # visible instead of only the fast path being reported
            t0 = time.perf_counter()
            rows_fb, dists_fb = knn(planner, 2.0, 48.0, 2500)
            detail["cfg4_knn_fallback_k2500_s"] = round(
                time.perf_counter() - t0, 2)
            assert len(rows_fb) == 2500 and np.all(np.diff(dists_fb) >= 0)

    # ---- config 5: S2 vs Z2 cover calibration (host-only) -----------------
    if "5" in configs:
        # scanned_fraction is a pure host quantity (cover -> searchsorted
        # over sorted keys), so this costs no chip time; it pins the cost
        # model's S2 cover_slop against reality (curves/s2.py)
        from geomesa_tpu.curves.s2 import S2SFC, cell_id
        from geomesa_tpu.curves.sfc import Z2SFC

        m = min(2_000_000, n)
        t0 = time.perf_counter()
        s2k = np.sort(cell_id(x[:m], y[:m]))
        z2sfc = Z2SFC()
        z2k = np.sort(z2sfc.index(x[:m], y[:m], lenient=True))
        s2sfc = S2SFC.apply()
        tots = {"s2": 0, "z2": 0, "true": 0}
        rng5 = np.random.default_rng(5)
        for _ in range(24):
            cx, cy = rng5.uniform(-150, 120), rng5.uniform(-55, 45)
            box = (cx, cy, cx + 25.0, cy + 14.0)
            tots["true"] += int(np.sum(
                (x[:m] >= box[0]) & (x[:m] <= box[2])
                & (y[:m] >= box[1]) & (y[:m] <= box[3])))
            for name, keys, rs in (("s2", s2k, s2sfc.ranges([box])),
                                   ("z2", z2k, z2sfc.ranges([box]))):
                lo = np.array([r.lower for r in rs])
                hi = np.array([r.upper for r in rs])
                tots[name] += int(np.sum(
                    np.searchsorted(keys, hi, side="right")
                    - np.searchsorted(keys, lo, side="left")))
        true_rows = max(1, tots["true"])
        detail["cfg5_n"] = m
        detail["cfg5_z2_cover_slop"] = round(tots["z2"] / true_rows, 3)
        detail["cfg5_s2_cover_slop"] = round(tots["s2"] / true_rows, 3)
        detail["cfg5_s2_scanned_fraction"] = round(tots["s2"] / (24 * m), 5)
        detail["cfg5_s"] = round(time.perf_counter() - t0, 2)

    # ---- config 6: WAL ingest overhead (off/batch/always vs no-WAL) -------
    if "6" in configs:
        import shutil
        import tempfile

        from geomesa_tpu.datastore import TpuDataStore

        n6 = min(n, 1_000_000)
        batch_rows = 100_000
        sft6 = SimpleFeatureType.from_spec("ing", "dtg:Date,*geom:Point")
        # pre-built batches: table construction is excluded so the measured
        # cost is the store's ingest path (WAL encode+append+fsync included)
        batches = []
        for b0 in range(0, n6, batch_rows):
            sl = slice(b0, min(b0 + batch_rows, n6))
            batches.append(FeatureTable.build(
                sft6, {"dtg": dtg[sl], "geom": (x[sl], y[sl])},
                fids=[f"i{j}" for j in range(sl.start, sl.stop)]))

        def ingest_qps(policy):
            tmp = tempfile.mkdtemp(prefix="gt-walbench-")
            try:
                if policy is None:
                    st = TpuDataStore()
                else:
                    # snapshot thresholds lifted: this measures the WAL
                    # tax alone (snapshots amortize on their own schedule)
                    st = TpuDataStore.open(tmp, params={
                        "wal.fsync": policy,
                        "snapshot.rows": n6 * 10,
                        "snapshot.wal_bytes": 1 << 40})
                st.create_schema(sft6)
                t0 = time.perf_counter()
                for b in batches:
                    st.load("ing", b)
                if st.durability is not None:
                    st.durability.wal.sync()  # durable before the clock stops
                dt = time.perf_counter() - t0
                st.close()
                return n6 / dt
            finally:
                shutil.rmtree(tmp, ignore_errors=True)

        detail["cfg6_n"] = n6
        # one throwaway run per variant (compile/import/page-cache warmup),
        # then best-of-3: run-level noise (device-upload variance, shared
        # host cores) swings individual runs far
        # more than the WAL tax — the per-policy BEST isolates the
        # systematic cost
        ingest_qps(None)
        ingest_qps("off")
        base = max(ingest_qps(None) for _ in range(3))
        detail["cfg6_ingest_qps_nowal"] = round(base, 0)
        for pol in ("off", "batch", "always"):
            q = max(ingest_qps(pol) for _ in range(3))
            detail[f"cfg6_ingest_qps_wal_{pol}"] = round(q, 0)
            detail[f"cfg6_wal_{pol}_overhead_pct"] = round(
                100.0 * (1.0 - q / base), 1)

    # ---- config 7: overload shed rate + admitted p99 ----------------------
    if "7" in configs:
        import threading

        from geomesa_tpu import config as _cfg
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.serve.resilience.admission import ShedError
        from geomesa_tpu.serve.scheduler import QueryScheduler, StoreBinding

        n7 = min(n, 2_000_000)
        sft7 = SimpleFeatureType.from_spec(
            "ovl", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
        st7 = TpuDataStore()
        st7.create_schema(sft7)
        st7.load("ovl", FeatureTable.build(
            sft7, {"dtg": dtg[:n7], "geom": (x[:n7], y[:n7])}))
        limit7 = 16
        _cfg.ADMIT_INTERACTIVE.set(limit7)
        sched7 = QueryScheduler(StoreBinding(st7), flush_size=8,
                                window_us=300)
        try:
            q7 = (f"BBOX(geom, {qx0}, {qy0}, {qx1}, {qy1}) AND dtg DURING "
                  "2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
            sched7.count("ovl", q7)  # warm: plan + kernels compiled
            n_clients = 4 * limit7              # the 4x saturation burst
            per_client = 8
            lat_ok: list = []
            shed = admitted = 0
            tally = threading.Lock()

            def client(i):
                nonlocal shed, admitted
                for j in range(per_client):
                    t0 = time.perf_counter()
                    try:
                        sched7.count(
                            "ovl", f"BBOX(geom, {qx0 + (i + j) % 7 * 0.1}, "
                                   f"{qy0}, {qx1}, {qy1}) AND dtg DURING "
                                   "2020-01-05T00:00:00Z/"
                                   "2020-01-12T00:00:00Z",
                            timeout=30)
                    except ShedError:
                        with tally:
                            shed += 1
                        continue
                    dt = time.perf_counter() - t0
                    with tally:
                        admitted += 1
                        lat_ok.append(dt)

            ts7 = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
            t0 = time.perf_counter()
            [t.start() for t in ts7]
            [t.join() for t in ts7]
            wall7 = time.perf_counter() - t0
            submitted = n_clients * per_client
            detail["cfg7_n"] = n7
            detail["cfg7_submitted"] = submitted
            detail["cfg7_admitted"] = admitted
            detail["cfg7_overload_shed_rate"] = round(shed / submitted, 3)
            if lat_ok:
                detail["cfg7_overload_admitted_p99_ms"] = round(float(
                    np.percentile(np.asarray(lat_ok) * 1000, 99)), 2)
                detail["cfg7_overload_admitted_p50_ms"] = round(
                    _p50(lat_ok), 2)
            detail["cfg7_overload_qps"] = round(admitted / wall7, 1)
            assert admitted + shed == submitted  # nothing silently dropped
        finally:
            _cfg.ADMIT_INTERACTIVE.unset()
            sched7.shutdown()

    # ---- config 8: workload analytics (hot-set recall + overhead) ---------
    if "8" in configs:
        from geomesa_tpu import config as _cfg
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.filter.parser import parse_ecql
        from geomesa_tpu.obs import workload as _wl
        from geomesa_tpu.obs.flight import plan_hash as _plan_hash
        from geomesa_tpu.serve.scheduler import QueryScheduler, StoreBinding

        n8 = min(n, 1_000_000)
        sft8 = SimpleFeatureType.from_spec(
            "wload", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
        st8 = TpuDataStore()
        st8.create_schema(sft8)
        st8.load("wload", FeatureTable.build(
            sft8, {"dtg": dtg[:n8], "geom": (x[:n8], y[:n8])}))
        sched8 = QueryScheduler(StoreBinding(st8), flush_size=8,
                                window_us=300)
        try:
            # ~200 distinct query shapes (each its own plan hash) drawn
            # Zipf(1.1); 12 tenants drawn from a second skew — the shape
            # the result cache will face, 3x over the 64-slot sketch
            n_shapes, n_tenants, n_draws = 200, 12, 1200
            shapes = [
                f"BBOX(geom, {qx0 + (i % 20) * 0.3:.2f}, "
                f"{qy0 + (i // 20) * 0.3:.2f}, "
                f"{qx1 + (i % 20) * 0.3:.2f}, "
                f"{qy1 + (i // 20) * 0.3:.2f}) AND dtg DURING "
                "2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
                for i in range(n_shapes)]
            wz = 1.0 / (np.arange(n_shapes) + 1) ** 1.1
            draw_s = rng.choice(n_shapes, size=n_draws, p=wz / wz.sum())
            wt = 1.0 / (np.arange(n_tenants) + 1)
            draw_t = rng.choice(n_tenants, size=n_draws, p=wt / wt.sum())
            sched8.count("wload", shapes[0])  # warm: plan + kernels

            def run8() -> float:
                t0 = time.perf_counter()
                for c0 in range(0, n_draws, 32):
                    reqs = [sched8.submit("wload", shapes[draw_s[i]],
                                          tenant=f"tenant{draw_t[i]}")
                            for i in range(c0, min(c0 + 32, n_draws))]
                    for r in reqs:
                        r.result(timeout=60)
                return time.perf_counter() - t0

            # overhead: same burst, workload plane off vs on (defaults).
            # INTERLEAVED minima (the perf-guard estimator): each rep
            # times one off and one on pass back to back so drift hits
            # both arms; min-of-each isolates the intrinsic plane cost
            def _workload_on(on: bool) -> None:
                if on:
                    _cfg.WORKLOAD_ENABLED.unset()
                else:
                    _cfg.WORKLOAD_ENABLED.set(False)
                _wl._enabled_cache[1] = 0

            _workload_on(False)
            run8()  # warm both arms' shared path
            _wl.WORKLOAD.clear()
            t_off = t_on = float("inf")
            for _ in range(3):
                _workload_on(False)
                t_off = min(t_off, run8())
                _workload_on(True)
                t_on = min(t_on, run8())
            detail["cfg8_n"] = n8
            detail["cfg8_submitted"] = n_draws
            detail["cfg8_workload_overhead_pct"] = round(
                100.0 * (t_on / t_off - 1.0), 2)

            # recall: sketch top-10 plan hashes vs the exact oracle (the
            # true per-shape draw counts hashed the way the scheduler
            # hashes them) — 3 identical enabled passes only scale every
            # count equally, so recall is that of one pass
            true8: dict = {}
            for si in draw_s:
                ph = _plan_hash("wload", repr(parse_ecql(shapes[si])),
                                None)
                true8[ph] = true8.get(ph, 0) + 1
            oracle8 = {k for k, _ in sorted(
                true8.items(), key=lambda kv: (-kv[1], kv[0]))[:10]}
            _wl.WORKLOAD.drain()
            got8 = {e["key"] for e in
                    _wl.WORKLOAD.hot_set(k=10)["plans"]}
            detail["cfg8_hotset_recall"] = round(
                len(got8 & oracle8) / 10.0, 2)
            detail["cfg8_hotset_total"] = _wl.WORKLOAD.hot_set()["total"]
        finally:
            _cfg.WORKLOAD_ENABLED.unset()
            _wl._enabled_cache[1] = 0
            sched8.shutdown()

    # ---- config 9: self-optimizing serving (result cache + tenant QoS) ----
    if "9" in configs:
        import threading as _th

        from geomesa_tpu import config as _cfg
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.obs import workload as _wl
        from geomesa_tpu.serve.resilience.admission import ShedError
        from geomesa_tpu.serve.scheduler import QueryScheduler, StoreBinding

        n9 = min(n, 1_000_000)
        sft9 = SimpleFeatureType.from_spec(
            "hotq", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
        st9 = TpuDataStore()
        st9.create_schema(sft9)
        st9.load("hotq", FeatureTable.build(
            sft9, {"dtg": dtg[:n9], "geom": (x[:n9], y[:n9])}))
        sched9 = QueryScheduler(StoreBinding(st9), flush_size=8,
                                window_us=300)
        _wl.WORKLOAD.clear()
        try:
            hot_q = (f"BBOX(geom, {qx0}, {qy0}, {qx1}, {qy1}) AND dtg "
                     "DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")

            # (a) warm hot-query p50 vs the uncached interactive blocking
            # p50 it attacks — same query, same scheduler, cache off/on
            _cfg.RESULT_CACHE_ENABLED.set(False)
            sched9.count("hotq", hot_q)  # warm: plan + kernels
            _cfg.RESULT_CACHE_ENABLED.unset()
            _cfg.RESULT_CACHE_MIN_AT_LEAST.set(0)
            sched9.count("hotq", hot_q)  # insert
            # INTERLEAVED minima (cfg8's discipline): each pass times the
            # uncached and the warm-hit arm back to back, so a GC pause
            # or noisy neighbour lands on both arms instead of poisoning
            # whichever single arm it happened to overlap; the
            # element-wise min across passes isolates each arm's
            # intrinsic cost before the p50
            u9, w9 = [], []
            for _ in range(3):
                _cfg.RESULT_CACHE_ENABLED.set(False)
                u9.append(_time_reps(lambda: sched9.count("hotq", hot_q),
                                     reps, key="cfg9_uncached"))
                _cfg.RESULT_CACHE_ENABLED.unset()
                w9.append(_time_reps(lambda: sched9.count("hotq", hot_q),
                                     reps))
            p9u = _p50(np.stack(u9).min(axis=0))
            p9w = _p50(np.stack(w9).min(axis=0))
            detail["cfg9_n"] = n9
            detail["cfg9_uncached_blocking_p50_ms"] = round(p9u, 3)
            detail["cfg9_warm_hit_p50_ms"] = round(p9w, 4)
            detail["cfg9_warm_speedup"] = round(p9u / p9w, 1)
            assert p9w <= p9u / 5.0, \
                f"warm hit p50 {p9w:.3f}ms not 5x under uncached {p9u:.3f}ms"

            # (b) steady-state hit rate on the cfg8 Zipf mix under the
            # DEFAULT admission floor: pass A teaches the workload plane
            # (cold-rejects while nothing is provably hot), pass B replays
            # the identical draw against the learned hot set
            _cfg.RESULT_CACHE_MIN_AT_LEAST.unset()
            sched9.results.clear()
            n_shapes9 = 200
            n_draws9 = 400 if args.mini else 1200
            shapes9 = [
                f"BBOX(geom, {qx0 + (i % 20) * 0.3:.2f}, "
                f"{qy0 + (i // 20) * 0.3:.2f}, "
                f"{qx1 + (i % 20) * 0.3:.2f}, "
                f"{qy1 + (i // 20) * 0.3:.2f}) AND dtg DURING "
                "2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
                for i in range(n_shapes9)]
            wz9 = 1.0 / (np.arange(n_shapes9) + 1) ** 1.1
            draw9 = rng.choice(n_shapes9, size=n_draws9,
                               p=wz9 / wz9.sum())

            def run9() -> None:
                for c0 in range(0, n_draws9, 32):
                    reqs = [sched9.submit("hotq", shapes9[draw9[i]],
                                          tenant=f"tenant{i % 7}")
                            for i in range(c0, min(c0 + 32, n_draws9))]
                    for r in reqs:
                        r.result(timeout=60)

            run9()  # pass A: learn
            _wl.WORKLOAD.drain()
            s9a = sched9.results.stats()
            run9()  # pass B: replay warm
            s9b = sched9.results.stats()
            hit_rate9 = (s9b["hits"] - s9a["hits"]) / n_draws9
            detail["cfg9_submitted"] = 2 * n_draws9
            detail["cfg9_result_cache_hit_rate"] = round(hit_rate9, 3)
            detail["cfg9_result_cache_size"] = s9b["size"]
            detail["cfg9_result_cache_rejected_cold"] = s9b["rejected_cold"]
            assert hit_rate9 >= 0.5, \
                f"Zipf-head replay hit rate {hit_rate9:.3f} < 0.5"

            # (c) tenant-storm drill: 8 noisy threads flood permanently-cold
            # queries; the victim probes its hot (cached) query. QoS caps
            # the storm's in-flight share, the cache keeps the victim off
            # the contended device — its p99 must hold
            _cfg.RESULT_CACHE_MIN_AT_LEAST.set(0)
            _cfg.ADMIT_INTERACTIVE.set(8)
            sched9.count("hotq", hot_q, tenant="victim")  # re-warm

            def probe9_lat(k) -> np.ndarray:
                lat = []
                for _ in range(k):
                    t0 = time.perf_counter()
                    sched9.count("hotq", hot_q, tenant="victim",
                                 timeout=30)
                    lat.append(time.perf_counter() - t0)
                return np.asarray(lat) * 1000.0

            def probe9(k) -> float:
                return float(np.percentile(probe9_lat(k), 99))

            k9 = 100 if args.mini else 300
            p99_unloaded = probe9(k9)
            stop9 = _th.Event()

            def storm9(tid: int) -> None:
                i = 0
                while not stop9.is_set():
                    try:
                        sched9.count(
                            "hotq",
                            f"BBOX(geom, {qx0 - tid - i * 1e-4:.4f}, "
                            f"{qy0 - 11}, {qx1 + tid}, {qy1}) AND dtg "
                            "DURING 2020-01-05T00:00:00Z/"
                            "2020-01-12T00:00:00Z",
                            tenant="noisy", timeout=30)
                    except ShedError:
                        pass
                    i += 1

            threads9 = [_th.Thread(target=storm9, args=(t,), daemon=True)
                        for t in range(8)]
            [t.start() for t in threads9]
            try:
                time.sleep(0.1)
                # element-wise minimum over three interleaved passes
                # while the storm is live: scheduler hiccups land on
                # independent indices each pass, so min() needs all
                # three to stall at the SAME probe before the p99 moves
                # (~p^3), while QoS starvation — the property pinned
                # here — inflates every index of every pass and survives
                # the minimum untouched. min-of-whole-p99 retries still
                # flaked on loaded hosts: one pass fully inside a noisy
                # window poisons its own p99 and two clean passes can't
                # repair a third's tail
                passes9 = np.stack([probe9_lat(k9) for _ in range(3)])
                p99_storm = float(np.percentile(passes9.min(axis=0), 99))
            finally:
                stop9.set()
                [t.join(timeout=30) for t in threads9]
            qos9 = sched9.admission.stats()["qos"]
            detail["cfg9_victim_unloaded_p99_ms"] = round(p99_unloaded, 3)
            detail["cfg9_victim_storm_p99_ms"] = round(p99_storm, 3)
            detail["cfg9_victim_p99_ratio"] = round(
                p99_storm / p99_unloaded, 2)
            detail["cfg9_storm_qos_shed"] = int(
                qos9["qos_shed"].get("noisy", 0))
            assert detail["cfg9_storm_qos_shed"] > 0, \
                "the storm was never fair-share shed"
            assert "victim" not in qos9["qos_shed"]
            # the acceptance bound, with a 2ms absolute floor: both sides
            # are cache serves, so p99s sit at GIL-jitter scale and the
            # raw ratio is noise-dominated — the drill still fails loudly
            # if the victim is pushed anywhere toward device-bound latency
            # (the uncached p50 yardstick is ~50x the floor at paper scale)
            assert p99_storm <= max(2.0 * p99_unloaded, 2.0), \
                (p99_storm, p99_unloaded, p9u)
        finally:
            _cfg.RESULT_CACHE_MIN_AT_LEAST.unset()
            _cfg.RESULT_CACHE_ENABLED.unset()
            _cfg.ADMIT_INTERACTIVE.unset()
            sched9.shutdown()

    if "10" in configs:
        import threading as _th

        from geomesa_tpu import config as _cfg
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.obs.flight import RECORDER as _flight10
        from geomesa_tpu.obs.profiling import PROGRESS as _progress10

        # a floor of 600k rows: below it the full rebuild is so cheap on
        # host that the merge-vs-full ratio measures python overhead, not
        # the O(n) vs O(delta) asymmetry the gate pins
        n10 = max(min(n, 1_000_000), 600_000)
        if n10 <= n:
            x10, y10, dtg10 = x[:n10], y[:n10], dtg[:n10]
        else:
            x10 = rng.uniform(-180, 180, n10)
            y10 = rng.uniform(-90, 90, n10)
            base10 = np.datetime64("2020-01-01T00:00:00",
                                   "ms").astype(np.int64)
            dtg10 = base10 + rng.integers(0, 30 * 86400000, n10)
        n_base10 = int(n10 * 0.97)
        n_delta10 = n10 - n_base10  # ~3% delta flush (the ≤10% regime)
        spec10 = "dtg:Date,*geom:Point;geomesa.z3.interval=week"
        q10 = (f"BBOX(geom, {qx0}, {qy0}, {qx1}, {qy1}) AND dtg "
               "DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")

        try:
            # keep the delta pending so the flush below is the timed one
            _cfg.LSM_MAX_FRACTION.set(1.0)
            _cfg.MERGE_BUILD.set(True)
            _cfg.SHARD_SORT.set(False)  # measured separately in (b)
            st10 = TpuDataStore()
            st10.create_schema("inc", spec10)
            sft10 = st10.get_schema("inc")
            st10.load("inc", FeatureTable.build(
                sft10, {"dtg": dtg10[:n_base10],
                        "geom": (x10[:n_base10], y10[:n_base10])}))
            old10 = st10.planners["inc"].indexes[0]
            icls10 = type(old10)
            st10.load("inc", FeatureTable.build(
                sft10, {"dtg": dtg10[n_base10:],
                        "geom": (x10[n_base10:], y10[n_base10:])}))
            assert st10.deltas["inc"] is not None, "delta flushed early"

            # (a) incremental merge-build vs full rebuild of the primary
            # index over the SAME merged table (2 reps, min — rep one
            # carries jit compiles on both sides)
            merged10 = FeatureTable.concat([st10.tables["inc"],
                                            st10.deltas["inc"]])
            merged10.fids  # materialize once, like a settled table
            icls10(sft10, merged10)                        # warm full
            icls10.merge_from(old10, merged10, n_base10)   # warm merge
            full_b, merge_b = [], []
            for _ in range(2):
                t0 = time.perf_counter()
                icls10(sft10, merged10)
                full_b.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                icls10.merge_from(old10, merged10, n_base10)
                merge_b.append(time.perf_counter() - t0)
            speedup10 = min(full_b) / max(1e-9, min(merge_b))
            detail["cfg10_n"] = n10
            detail["cfg10_delta_fraction"] = round(n_delta10 / n_base10, 3)
            detail["cfg10_full_build_s"] = round(min(full_b), 3)
            detail["cfg10_merge_build_s"] = round(min(merge_b), 3)
            detail["cfg10_incremental_speedup"] = round(speedup10, 1)
            assert speedup10 >= 5.0, \
                (f"incremental merge build {min(merge_b):.3f}s not 5x "
                 f"under full rebuild {min(full_b):.3f}s")
            # the real store flush through the merge path, checked exact
            # against a brute-force host count
            t0 = time.perf_counter()
            st10.flush("inc")
            detail["cfg10_merge_flush_s"] = round(time.perf_counter() - t0,
                                                  3)
            assert st10.count("inc", q10) == cpu_query(x10, y10, dtg10)

            # (b) mesh-sharded sort vs single-device sort (exactness always;
            # the speedup is a perfwatch-gated metric on >=2-device meshes)
            if len(jax.devices()) >= 2:
                from geomesa_tpu.index.spatial import device_sort_perm
                from geomesa_tpu.parallel import dist as _dist
                kb10 = rng.integers(0, 1 << 14, n10).astype(np.int32)
                k110 = rng.integers(0, 1 << 21, n10).astype(np.int32)
                k210 = rng.integers(0, 1 << 21, n10).astype(np.int32)
                planes10 = [kb10, k110, k210]
                _cfg.SHARD_SORT.set(True)
                _cfg.SHARD_SORT_MIN.set(1)

                def _mesh10():
                    return np.asarray(_dist.mesh_sort_perm(
                        [p.copy() for p in planes10]))

                perm_mesh = _mesh10()  # warm (compiles)
                mesh_sort_s = min(_time_reps(_mesh10, 2))
                _cfg.SHARD_SORT.set(False)

                def _single10():
                    return np.asarray(device_sort_perm(planes10))

                perm_single = _single10()  # warm
                single_sort_s = min(_time_reps(_single10, 2))
                ref10 = np.lexsort(tuple(reversed(planes10)))
                assert np.array_equal(perm_mesh, ref10.astype(np.int32))
                assert np.array_equal(perm_single, ref10.astype(np.int32))
                detail["cfg10_shard_sort_devices"] = len(
                    _dist.shard_devices())
                detail["cfg10_single_sort_s"] = round(single_sort_s, 3)
                detail["cfg10_mesh_sort_s"] = round(mesh_sort_s, 3)
                detail["cfg10_shard_sort_speedup"] = round(
                    single_sort_s / max(1e-9, mesh_sort_s), 2)

            # (c) ingest-while-serving: Zipf counts + sustained appends
            # DURING a background build-then-swap reindex; serving p99 must
            # hold within 2x steady-state (no install cliff)
            _cfg.SHARD_SORT.unset()
            n_shapes10 = 40
            shapes10 = [
                f"BBOX(geom, {qx0 + (i % 8) * 0.5:.2f}, "
                f"{qy0 + (i // 8) * 0.5:.2f}, "
                f"{qx1 + (i % 8) * 0.5:.2f}, {qy1 + (i // 8) * 0.5:.2f}) "
                "AND dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
                for i in range(n_shapes10)]
            wz10 = 1.0 / (np.arange(n_shapes10) + 1) ** 1.1
            draw10 = rng.choice(n_shapes10, size=4096, p=wz10 / wz10.sum())
            for s10 in shapes10:
                st10.count("inc", s10)  # warm plans/kernels

            def _probe10(k: int, i0: int = 0) -> list:
                lat = []
                for i in range(k):
                    t0 = time.perf_counter()
                    st10.count("inc", shapes10[draw10[(i0 + i)
                                                      % len(draw10)]])
                    lat.append(time.perf_counter() - t0)
                return lat

            k10 = 150 if args.mini else 400
            stop10 = _th.Event()

            def _ingest10() -> None:
                i = 0
                while not stop10.is_set():
                    st10.load("inc", FeatureTable.build(
                        st10.get_schema("inc"),
                        {"dtg": dtg[:2000], "geom": (x[:2000], y[:2000])}))
                    i += 1
                    time.sleep(0.05)

            def _spawn_ingest10() -> "_th.Thread":
                t = _th.Thread(target=_ingest10, daemon=True)
                t.start()
                return t

            # steady-state is measured WITH the ingest stream running so
            # the gate isolates the reindex build's effect on serving,
            # not the (constant) cost of concurrent appends
            ing10 = _spawn_ingest10()
            try:
                p99_steady = float(np.percentile(
                    np.asarray(_probe10(k10)) * 1000.0, 99))
            finally:
                stop10.set()
                ing10.join(timeout=60)
            # settle the delta accumulated during the steady window and
            # re-warm the shapes on the settled table: a table swap
            # changes the padded kernel shapes, and the first query after
            # one pays a jit compile — that flush-time cost exists with
            # or without reindex, so it must not pollute either window
            st10.flush("inc")
            for s10 in shapes10:
                st10.count("inc", s10)
            st10.reindex("inc")
            # let the worker pass its (no-op: empty delta) entry flush
            # before restarting ingest, so the during-probe window holds
            # one table generation until the swap_install itself
            for _ in range(400):
                if _flight10.recent(limit=None, kind="reindex"):
                    break
                time.sleep(0.005)
            stop10.clear()
            ing10 = _spawn_ingest10()
            lat_during = []
            try:
                while st10._reindex_threads["inc"].is_alive():
                    lat_during.extend(_probe10(20, i0=len(lat_during)))
            finally:
                stop10.set()
                ing10.join(timeout=60)
            st10._reindex_threads["inc"].join(timeout=300)
            rs10 = st10.reindex_status("inc")
            assert rs10["state"] == "installed", rs10
            p99_during = float(np.percentile(
                np.asarray(lat_during) * 1000.0, 99)) \
                if lat_during else p99_steady
            detail["cfg10_reindex_s"] = rs10["seconds"]
            detail["cfg10_reindex_rows"] = rs10["rows"]
            detail["cfg10_serving_p99_steady_ms"] = round(p99_steady, 3)
            detail["cfg10_serving_p99_during_reindex_ms"] = round(
                p99_during, 3)
            detail["cfg10_serving_queries_during_reindex"] = len(lat_during)
            # 2x steady with a 40ms absolute floor: at mini scale both
            # sides sit at host-jitter latencies (~3-5ms) where the probes
            # share cores AND the GIL with the host-side build thread, so
            # the raw ratio is scheduler noise (observed up to ~22ms p99
            # on a loaded host with NO cliff) — a real install cliff
            # (mid-build table swap, cold kernel recompile) measures
            # 200-1000ms and still fails this loudly; the perfwatch
            # baseline on cfg10_serving_p99_during_reindex_ms tracks the
            # finer-grained trend
            assert p99_during <= max(2.0 * p99_steady, 40.0), \
                (p99_during, p99_steady)

            # phase-breakdown artifact (CI uploads it): every recent build/
            # reindex phase with durations + throughput
            phases10 = [e for e in _progress10.snapshot()["recent"]
                        if e.get("op") in ("index_build", "reindex")]
            with open(os.path.join(REPO, "BENCH_reindex_phases.json"),
                      "w") as fh:
                json.dump({"phases": phases10,
                           "reindex_status": rs10}, fh, indent=1)
        finally:
            _cfg.MERGE_BUILD.unset()
            _cfg.LSM_MAX_FRACTION.unset()
            _cfg.SHARD_SORT.unset()
            _cfg.SHARD_SORT_MIN.unset()

    if "11" in configs:
        # cfg11 — fleet soak scoreboard (obs/soakfleet.py): a REAL
        # multi-process fleet (primary + followers + router over
        # localhost WAL shipping) under sustained Zipf traffic, with a
        # chaos half (rolling restart, lag spike, replica kill,
        # promote-failover, reindex churn) and a clean control half.
        # The scoreboard numbers fold into perf/baselines.json; the
        # correctness axes (doctor precision/recall, acked-write loss,
        # follower fingerprints, clean-half incident count) are pinned
        # exact in perfwatch._OVERRIDES so any drift fails --check.
        # Not in the default config lists: it spawns processes and runs
        # ~2 min even at --mini, so it rides the dedicated soak CI job.
        from geomesa_tpu import config as _cfg
        from geomesa_tpu.obs import soakfleet as _soak

        board11 = _soak.run(
            mini=bool(args.mini),
            scoreboard_path=os.path.join(REPO, "SOAK_scoreboard.json"))
        detail.update(_soak.scoreboard_metrics(board11))
        detail["cfg11_soak_wall_s"] = round(sum(
            h.get("duration_s", 0.0)
            for h in (board11.get("halves") or {}).values()), 1)
        # under a stretch handicap (the gate's self-test) the run is
        # deliberately degraded — the scoreboard still records honestly
        # and perfwatch --check is the judge, so no inline assert
        if float(_cfg.SOAK_STRETCH.get()) == 1.0:
            assert board11.get("ok"), \
                {h: v.get("ok") for h, v in
                 (board11.get("halves") or {}).items()}

    if "12" in configs:
        # cfg12 — multi-process cluster dryrun (cluster/dryrun.py): a
        # REAL 2-process jax.distributed fleet over localhost gloo, ONE
        # table sharded by contiguous Morton key-range, psum-reduced
        # counts/density and host-merged selects judged byte-equal
        # against the single-process oracle (same code path, inactive
        # runtime). The exactness axes are pinned exact in
        # perfwatch._OVERRIDES; the warm timings ride the normal
        # statistical gate. Not in the default config lists: it spawns
        # worker processes, so it rides the dedicated cluster CI job.
        from geomesa_tpu.cluster import dryrun as _cdry
        n12 = int(os.environ.get("GEOMESA_TPU_BENCH_CLUSTER_N",
                                 "8000" if args.mini else "20000"))
        rep12 = _cdry.run_dryrun(
            num_processes=2, n=n12,
            out_dir=os.path.join(REPO, "BENCH_cluster_dryrun"))
        ch12 = rep12["checks"]
        detail["cfg12_count_mismatch"] = 0 if ch12.get("counts_equal") else 1
        detail["cfg12_select_mismatch"] = (
            0 if ch12.get("selects_equal") else 1)
        detail["cfg12_density_mismatch"] = (
            0 if ch12.get("density_equal") else 1)
        detail["cfg12_shard_strict_subset"] = (
            1 if ch12.get("shards_strict_subset") else 0)
        live12 = [r for r in rep12["ranks"] if r]
        if live12:
            detail["cfg12_count_warm_ms"] = round(max(
                max(r["battery"]["count_warm_ms"].values())
                for r in live12), 3)
            detail["cfg12_select_ms"] = round(max(
                max(r["battery"]["select_ms"].values())
                for r in live12), 3)
            detail["cfg12_build_s"] = round(max(
                r["stages"].get("index_build_s", 0.0)
                + r["stages"].get("global_table_s", 0.0)
                for r in live12), 3)
        detail["cfg12_dryrun_wall_s"] = rep12["wall_s"]
        # shard-ownership artifact (CI uploads it): who owns which
        # Morton key-range, with how many rows
        with open(os.path.join(REPO, "BENCH_cluster_shards.json"),
                  "w") as fh:
            json.dump({"checks": ch12, "n": n12,
                       "ownership": [
                           {"process": r["process_id"],
                            "rows": r["local_rows"],
                            "key_range": r["key_range"],
                            "psum_rounds": r["psum_rounds"]}
                           for r in sorted(live12,
                                           key=lambda r: r["process_id"])]},
                      fh, indent=1)
        assert rep12["ok"], ch12

    if "13" in configs:
        # cfg13 — shard balance observatory drill (obs/shardwatch.py +
        # cluster/dryrun.py --drill): the SAME 2-process gloo fleet as
        # cfg12, judged two-sided like cfg11. Skew half: rank 0 fires a
        # Zipf storm at cells owned by the OTHER rank's key range — the
        # ledger must put the load ratio over the pinned bar, open
        # exactly one shard_imbalance incident, attribute it to the
        # victim shard, and project split keys inside the victim's key
        # range. Uniform control half: the same event count spread
        # evenly must read near-1.0 balance with ZERO incidents (an
        # observatory that cries wolf fails the gate as hard as one
        # that misses the storm). All six verdict axes are pinned exact
        # in perfwatch._OVERRIDES; the balance scores and wall times
        # ride the statistical gate. Not in the default config lists —
        # it spawns worker processes, so it rides the balance CI job.
        from geomesa_tpu.cluster import dryrun as _cdry
        n13 = int(os.environ.get("GEOMESA_TPU_BENCH_CLUSTER_N",
                                 "8000" if args.mini else "20000"))
        halves13 = {}
        for mode13 in ("skew", "uniform"):
            halves13[mode13] = _cdry.run_dryrun(
                num_processes=2, n=n13, drill=mode13,
                out_dir=os.path.join(REPO, f"BENCH_balance_{mode13}"))
        skew13 = halves13["skew"]
        ctrl13 = halves13["uniform"]

        def _drill13(rep, pid=0):
            r = next((x for x in rep["ranks"]
                      if x and x["process_id"] == pid), None)
            return (r or {}).get("drill") or {}

        dsk = _drill13(skew13)
        dct = _drill13(ctrl13)
        sc_sk = ((dsk.get("balance") or {}).get("types") or {}) \
            .get("pts", {}).get("score", {})
        sc_ct = ((dct.get("balance") or {}).get("types") or {}) \
            .get("pts", {}).get("score", {})
        inc_sk = dsk.get("imbalance_incidents") or []
        victim13 = dsk.get("victim")
        vrange13 = ((dsk.get("balance") or {}).get("types") or {}) \
            .get("pts", {}).get("shards", {}).get(victim13, {}) \
            .get("key_range") or [None, None]
        splits13 = (((dsk.get("balance") or {}).get("types") or {})
                    .get("pts", {}).get("splits") or {}) \
            .get("boundaries") or []
        # the six pinned verdict axes (exact in perfwatch._OVERRIDES)
        detail["cfg13_skew_flagged"] = 1 if sc_sk.get("over_bar") else 0
        detail["cfg13_skew_incidents"] = len(inc_sk)
        detail["cfg13_skew_attributed"] = (
            1 if (len(inc_sk) == 1 and
                  (inc_sk[0].get("suspect") or {}).get("shard")
                  == victim13) else 0)
        detail["cfg13_skew_splits_in_range"] = (
            1 if (splits13 and vrange13[0] is not None and all(
                vrange13[0] < b["key"] <= vrange13[1] + 1
                for b in splits13)) else 0)
        detail["cfg13_control_incidents"] = len(
            dct.get("imbalance_incidents") or [])
        detail["cfg13_control_balanced"] = (
            1 if (sc_ct.get("max_over_mean") or 99.0) <= 1.35 else 0)
        # federation + battery sanity ride along as exact too: the drill
        # corpus must still pass the oracle equality checks, and the
        # fleet-merged verdict must come from BOTH nodes
        fb13 = (dsk.get("fleet_balance") or {})
        detail["cfg13_fleet_federated"] = (
            1 if (len(fb13.get("nodes") or {}) == 2
                  and not fb13.get("partial")) else 0)
        detail["cfg13_dryrun_ok"] = (
            1 if (skew13["ok"] and ctrl13["ok"]) else 0)
        # statistical axes
        detail["cfg13_skew_max_over_mean"] = round(
            float(sc_sk.get("max_over_mean") or 0.0), 4)
        detail["cfg13_control_max_over_mean"] = round(
            float(sc_ct.get("max_over_mean") or 0.0), 4)
        live13 = [r for r in skew13["ranks"] if r]
        if live13:
            detail["cfg13_shard_map_s"] = round(max(
                r["stages"].get("shard_map_s", 0.0) for r in live13), 3)
        detail["cfg13_wall_s"] = round(
            skew13["wall_s"] + ctrl13["wall_s"], 3)
        # balance artifact (CI uploads it): both halves' verdicts with
        # the projected split points for the hot shard
        with open(os.path.join(REPO, "BENCH_balance.json"), "w") as fh:
            json.dump({
                "n": n13,
                "skew": {"checks": skew13["checks"], "drill": dsk},
                "control": {"checks": ctrl13["checks"], "drill": dct},
            }, fh, indent=1)
        assert skew13["ok"], skew13["checks"]
        assert ctrl13["ok"], ctrl13["checks"]

    if "14" in configs:
        # -- 14: single-dispatch cold-query latency (staged vs fused) -------
        # Uncached single queries: each iteration is a bbox the planner has
        # never seen (same *shape*, distinct values), so the staged path
        # pays cover decomposition + candidate uploads + residual compile
        # per query while the fused path binds values into a cached device
        # program and pays exactly ONE host<->device round.
        from geomesa_tpu import config as _cfg
        from geomesa_tpu.index import compiled as _fq
        from geomesa_tpu.index.scan import ROUNDS as _rounds
        t14_start = time.perf_counter()
        # 100k rows at 512-row blocks prunes like 100M at 4096 (same
        # block-count regime the fused qualifier keys on)
        _cfg.PRUNE_BLOCK.set(512)
        _cfg.FUSED_QUERY.set(True)
        try:
            n14 = 100_000
            rng14 = np.random.default_rng(1234)
            cent14 = rng14.uniform([-120, -40], [140, 60], size=(64, 2))
            which14 = rng14.integers(0, 64, n14)
            x14 = np.clip(cent14[which14, 0] + rng14.normal(0, 8, n14),
                          -180, 180)
            y14 = np.clip(cent14[which14, 1] + rng14.normal(0, 6, n14),
                          -90, 90)
            base14 = np.datetime64("2020-01-01T00:00:00",
                                   "ms").astype(np.int64)
            dtg14 = base14 + rng14.integers(0, 120 * 86400000, n14)
            risk14 = rng14.integers(0, 100, n14).astype(np.int32)
            sft14 = SimpleFeatureType.from_spec(
                "gdelt14", "risk:Int,dtg:Date,*geom:Point;"
                "geomesa.z3.interval=week")
            table14 = FeatureTable.build(
                sft14, {"risk": risk14, "dtg": dtg14, "geom": (x14, y14)})
            idx14 = Z3Index(sft14, table14)
            pl14 = QueryPlanner(sft14, table14, [idx14])

            def _q14(i):
                dx, dy = (0.83 * i) % 40.0, (0.41 * i) % 20.0
                x0, y0 = -90 + dx, -12 - dy
                return (f"BBOX(geom, {x0}, {y0}, {x0 + 12}, {y0 + 8})"
                        " AND dtg DURING 2020-01-02T00:00:00Z/"
                        "2020-03-12T00:00:00Z AND risk > 40")

            # warm both tiers so the cold loops measure per-query work,
            # not one-time XLA compiles
            _fq.warm_programs(idx14)
            _cfg.FUSED_QUERY.set(False)
            for i in (90, 91):
                pl14.prepare(_q14(i)).count()
            _cfg.FUSED_QUERY.set(True)
            for i in (92, 93):          # registers the shape recipe
                pl14.prepare(_q14(i)).count()

            # exactness: fused vs the staged oracle on 16 distinct boxes
            mism14 = 0
            for i in range(30, 46):
                fc = pl14.prepare(_q14(i)).count()
                _cfg.FUSED_QUERY.set(False)
                sc = pl14.prepare(_q14(i)).count()
                _cfg.FUSED_QUERY.set(True)
                mism14 += int(fc != sc)

            # staged cold loop: 24 never-before-seen boxes
            _cfg.FUSED_QUERY.set(False)
            snap14 = _rounds.snapshot()
            stag14 = []
            for i in range(24):
                t0 = time.perf_counter()
                pl14.prepare(_q14(i)).count()
                stag14.append(time.perf_counter() - t0)
            stag_disp14 = _rounds.rounds_since(snap14) / 24.0

            # fused cold loop: 48 never-before-seen boxes
            _cfg.FUSED_QUERY.set(True)
            built14 = _fq.STATS["programs_built"]
            snap14 = _rounds.snapshot()
            fuse14 = []
            for i in range(130, 178):
                t0 = time.perf_counter()
                pl14.prepare(_q14(i)).count()
                fuse14.append(time.perf_counter() - t0)
            fuse_disp14 = _rounds.rounds_since(snap14) / 48.0
            recompiles14 = _fq.STATS["programs_built"] - built14

            sp50 = _p50(stag14) * _stretch("cfg14_staged")
            fp50 = _p50(fuse14)
            detail["cfg14_staged_cold_p50_ms"] = round(sp50, 3)
            detail["cfg14_staged_cold_p99_ms"] = round(float(
                np.percentile(np.asarray(stag14) * 1000, 99)), 3)
            detail["cfg14_fused_cold_p50_ms"] = round(fp50, 3)
            detail["cfg14_fused_cold_p99_ms"] = round(float(
                np.percentile(np.asarray(fuse14) * 1000, 99)), 3)
            # _speedup suffix -> higher-is-better for the regression gate
            detail["cfg14_cold_speedup"] = round(sp50 / fp50, 2)
            detail["cfg14_fused_dispatches_per_cold_query"] = fuse_disp14
            detail["cfg14_staged_dispatches_per_cold_query"] = round(
                stag_disp14, 2)
            detail["cfg14_fused_recompiles"] = recompiles14
            detail["cfg14_fused_parity_mismatches"] = mism14
            floor14 = detail.get("dispatch_floor_ms_per_query")
            if floor14:
                detail["cfg14_staged_floor_multiple"] = round(
                    sp50 / floor14, 1)
                detail["cfg14_fused_floor_multiple"] = round(
                    fp50 / floor14, 1)
            detail["cfg14_wall_s"] = round(
                time.perf_counter() - t14_start, 3)
            # cold-query artifact (CI uploads it)
            with open(os.path.join(REPO, "BENCH_fused_cold.json"),
                      "w") as fh:
                json.dump({
                    "n": n14,
                    "staged_cold_ms": [round(t * 1000, 4) for t in stag14],
                    "fused_cold_ms": [round(t * 1000, 4) for t in fuse14],
                    "summary": {k: detail[k] for k in sorted(detail)
                                if k.startswith("cfg14_")},
                }, fh, indent=1)
            assert mism14 == 0, f"fused/staged parity broke: {mism14}"
            assert recompiles14 == 0, \
                f"fused path recompiled {recompiles14}x across one shape"
            assert fuse_disp14 == 1.0, \
                f"fused cold query took {fuse_disp14} rounds, expected 1"
        finally:
            _cfg.FUSED_QUERY.unset()
            _cfg.PRUNE_BLOCK.unset()

    if "15" in configs:
        # -- 15: geometry function catalog (st_* through the filter IR) -----
        # Two halves. (a) Function-query mix: three push-down-eligible
        # st_* shapes (banded radial distance, point-in-polygon contains /
        # intersects) instantiated at never-before-seen literal values —
        # the fused path must serve each cold query in EXACTLY one device
        # round with zero fallbacks and count byte-equal to the full host
        # evaluator (the numpy oracle over all rows), which is also the
        # latency yardstick the >=10x speedup is measured against.
        # (b) Mesh-sharded spatial join: the same 2-process gloo fleet as
        # cfg12 runs the st_* count battery and the contains/intersects
        # join; psum'd counts and rank-order-merged pairs are judged
        # byte-equal against the single-process oracle. The exactness
        # axes are pinned exact in perfwatch._OVERRIDES; latencies and
        # the join candidate throughput ride the statistical gate. Runs
        # on the dedicated geometry CI job (it spawns worker processes).
        from geomesa_tpu import config as _cfg
        from geomesa_tpu.filter.evaluate import evaluate as _ev15
        from geomesa_tpu.filter.parser import parse_ecql as _pe15
        from geomesa_tpu.index import compiled as _fq
        from geomesa_tpu.index.scan import ROUNDS as _rounds
        t15_start = time.perf_counter()
        _cfg.PRUNE_BLOCK.set(512)
        _cfg.FUSED_QUERY.set(True)
        try:
            n15 = 100_000
            rng15 = np.random.default_rng(77)
            base15 = np.datetime64("2020-01-01T00:00:00",
                                   "ms").astype(np.int64)
            sft15 = SimpleFeatureType.from_spec(
                "geom15", "val:Int,dtg:Date,*geom:Point;"
                "geomesa.z3.interval=week")
            table15 = FeatureTable.build(sft15, {
                "val": rng15.integers(0, 100, n15).astype(np.int32),
                "dtg": base15 + rng15.integers(0, 30 * 86400000, n15),
                "geom": (rng15.uniform(-170, 170, n15),
                         rng15.uniform(-80, 80, n15))})
            idx15 = Z3Index(sft15, table15)
            pl15 = QueryPlanner(sft15, table15, [idx15])

            # shape templates: literal VALUES move per query, the vertex
            # count never does (one padded edge table per recipe)
            def _qdist15(i):
                x0 = -150.0 + (7.3 * i) % 300.0
                y0 = -60.0 + (3.1 * i) % 120.0
                return f"st_distance(geom, POINT({x0:.3f} {y0:.3f})) < 9"

            def _qcont15(i):
                x0 = -160.0 + (11.7 * i) % 260.0
                y0 = -70.0 + (5.3 * i) % 100.0
                return (f"st_contains(POLYGON(({x0} {y0}, {x0 + 30} {y0},"
                        f" {x0 + 30} {y0 + 22}, {x0} {y0 + 22},"
                        f" {x0} {y0})), geom)")

            def _qints15(i):
                x0 = -160.0 + (9.1 * i) % 260.0
                y0 = -70.0 + (4.7 * i) % 100.0
                return (f"st_intersects(geom, POLYGON(({x0} {y0},"
                        f" {x0 + 40} {y0}, {x0 + 20} {y0 + 30},"
                        f" {x0} {y0})))")

            shapes15 = (_qdist15, _qcont15, _qints15)
            _fq.warm_programs(idx15)
            for fn15 in shapes15:        # register each shape's recipe
                for i in (900, 901):
                    pl15.prepare(fn15(i)).count()

            # parity + the host yardstick: 12 fresh instances per shape,
            # fused count vs parse+evaluate over ALL rows (no index)
            mism15 = 0
            host15 = []
            for fn15 in shapes15:
                for i in range(300, 312):
                    q15 = fn15(i)
                    fc15 = pl15.prepare(q15).count()
                    t0 = time.perf_counter()
                    hm15 = _ev15(_pe15(q15), table15)
                    host15.append(time.perf_counter() - t0)
                    mism15 += int(fc15 != int(hm15.sum()))

            # fused cold loop: 16 fresh instances per shape, one round
            # and zero fallbacks per query or the push-down is fiction
            fall15 = _fq.STATS["fallbacks"]
            snap15 = _rounds.snapshot()
            fuse15 = []
            for fn15 in shapes15:
                for i in range(500, 516):
                    q15 = fn15(i)
                    t0 = time.perf_counter()
                    pl15.prepare(q15).count()
                    fuse15.append(time.perf_counter() - t0)
            disp15 = _rounds.rounds_since(snap15) / len(fuse15)

            hp50 = _p50(host15) * _stretch("cfg15_host")
            fp50 = _p50(fuse15)
            detail["cfg15_host_eval_p50_ms"] = round(hp50, 3)
            detail["cfg15_host_eval_p99_ms"] = round(float(
                np.percentile(np.asarray(host15) * 1000, 99)), 3)
            detail["cfg15_fused_cold_p50_ms"] = round(fp50, 3)
            detail["cfg15_fused_cold_p99_ms"] = round(float(
                np.percentile(np.asarray(fuse15) * 1000, 99)), 3)
            detail["cfg15_func_speedup"] = round(hp50 / fp50, 2)
            detail["cfg15_fused_dispatches_per_cold_query"] = disp15
            detail["cfg15_fused_fallbacks"] = \
                _fq.STATS["fallbacks"] - fall15
            detail["cfg15_func_parity_mismatches"] = mism15

            # (b) the sharded join, byte-equal across cardinalities
            from geomesa_tpu.cluster import dryrun as _cdry
            nj15 = int(os.environ.get("GEOMESA_TPU_BENCH_CLUSTER_N",
                                      "8000" if args.mini else "20000"))
            rep15 = _cdry.run_dryrun(
                num_processes=2, n=nj15,
                out_dir=os.path.join(REPO, "BENCH_geom_join"))
            ch15 = rep15["checks"]
            detail["cfg15_join_mismatch"] = (
                0 if ch15.get("join_equal") else 1)
            detail["cfg15_func_count_mismatch"] = (
                0 if ch15.get("func_counts_equal") else 1)
            detail["cfg15_join_dryrun_ok"] = 1 if rep15["ok"] else 0
            live15 = [r for r in rep15["ranks"] if r]
            join15 = meta15 = None
            if live15:
                join15 = live15[0]["battery"].get("join") or {}
                meta15 = {op: {
                    "num_processes": live15[0]["battery"]["join_meta"]
                    [op]["num_processes"],
                    # slowest rank bounds the collective
                    "wall_s": max(r["battery"]["join_meta"][op]["wall_s"]
                                  for r in live15),
                } for op in join15}
                # candidate throughput: every (row, polygon) pair is
                # judged, so tested = rows_global x |polygons| per op
                tested15 = sum(
                    j["rows_global"] * j["polygons"]
                    for j in join15.values())
                wallj15 = sum(m["wall_s"] for m in meta15.values())
                if wallj15 > 0:
                    detail["cfg15_join_cand_per_s"] = round(
                        tested15 / wallj15, 1)
                detail["cfg15_join_num_processes"] = max(
                    m["num_processes"] for m in meta15.values())
            detail["cfg15_wall_s"] = round(
                time.perf_counter() - t15_start, 3)
            # geometry artifact (CI uploads it)
            with open(os.path.join(REPO, "BENCH_geom.json"), "w") as fh:
                json.dump({
                    "n": n15,
                    "host_eval_ms": [round(t * 1000, 4) for t in host15],
                    "fused_cold_ms": [round(t * 1000, 4) for t in fuse15],
                    "join": {"n": nj15, "checks": ch15, "meta": meta15,
                             "counts": {op: j["counts"]
                                        for op, j in (join15 or {}).items()}},
                    "summary": {k: detail[k] for k in sorted(detail)
                                if k.startswith("cfg15_")},
                }, fh, indent=1)
            assert mism15 == 0, \
                f"st_* fused/host parity broke: {mism15}"
            assert disp15 == 1.0, \
                f"fused func query took {disp15} rounds, expected 1"
            assert detail["cfg15_fused_fallbacks"] == 0, \
                "eligible st_* residual fell back to the staged path"
            assert rep15["ok"], ch15
        finally:
            _cfg.FUSED_QUERY.unset()
            _cfg.PRUNE_BLOCK.unset()

    if "16" in configs:
        # cfg16 — cluster cell soak scoreboard (obs/soakcells.py): a
        # REAL two-cell subprocess cluster (2 × replicated shard cell +
        # a shard-aware scatter-gather router) under routed writes and
        # reads, judged two-sided like cfg11. Chaos half: in-cell
        # failover inside the budget, mid-ingest ownership handoff,
        # split-brain refusal from BOTH fenced losers, and a fully dark
        # shard that must page exactly one shard_dark incident and flip
        # the partial-result envelope. Clean control half: same routed
        # traffic, ZERO incidents. The correctness axes (acked-write
        # loss, per-cell fingerprints, split-brain refusals, doctor
        # precision/recall, shard_dark firing, envelope honesty) are
        # pinned exact in perfwatch._OVERRIDES so any drift fails
        # --check. Not in the default config lists: it spawns processes
        # and runs minutes even at --mini, so it rides the cluster-v2
        # CI job.
        from geomesa_tpu.obs import soakcells as _soakc

        board16 = _soakc.run(
            mini=bool(args.mini),
            scoreboard_path=os.path.join(REPO,
                                         "SOAKCELLS_scoreboard.json"))
        detail.update(_soakc.scoreboard_metrics(board16))
        detail["cfg16_soak_wall_s"] = round(sum(
            h.get("duration_s", 0.0)
            for h in (board16.get("halves") or {}).values()), 1)
        assert board16.get("ok"), \
            {h: {k: v for k, v in (half.get("checks") or {}).items()
                 if not v}
             for h, half in (board16.get("halves") or {}).items()}

    if "17" in configs:
        # cfg17 — telemetry history plane overhead (obs/history.py +
        # obs/forensics.py): what retention actually costs. Four axes:
        # the cost of ONE sampler tick on a populated registry (every
        # tick lands a fresh finest slot — the worst case), the
        # amortized per-query overhead of riding the pre-drain hook at
        # a realistic scrape cadence (one scrape per 50 queries, fake
        # clock advancing so the throttle behaves as in production),
        # the retained-ring memory bound, and the cost of freezing one
        # memory-only forensic bundle. Host-side and CI-sized like
        # cfg9; not in the default config lists — it rides the history
        # CI job and explicit --update-baseline runs.
        from geomesa_tpu.metrics import MetricsRegistry as _Reg17
        from geomesa_tpu.obs.forensics import ForensicStore as _FS17
        from geomesa_tpu.obs.history import TelemetryHistory as _TH17

        t17_start = time.perf_counter()
        reg17 = _Reg17()

        def _traffic17(i):
            # the registry writes one served query makes
            reg17.inc("scheduler.queries")
            if i % 7 == 0:
                reg17.inc("admission.shed")
            reg17.observe("query.count", 0.0005 * (1 + (i % 5)))
            reg17.set_gauge("replication.lag_ms", float(i % 100))

        clk17 = {"t": 1_000_000.0}
        hist17 = _TH17(clock=lambda: clk17["t"], registry=reg17)
        for i in range(64):
            _traffic17(i)
        hist17.sample_now(clk17["t"])
        ticks17 = []
        for i in range(200):
            _traffic17(i)
            clk17["t"] += 2.0      # fresh finest slot every tick
            t0 = time.perf_counter()
            hist17.sample_now(clk17["t"])
            ticks17.append(time.perf_counter() - t0)
        detail["cfg17_history_tick_us"] = round(_p50(ticks17) * 1000, 1)

        iters17 = 2000

        def _loop17(sample):
            t0 = time.perf_counter()
            for i in range(iters17):
                _traffic17(i)
                if i % 50 == 0:
                    reg17.snapshot()      # the scrape
                    if sample:            # what pre-drain adds to it
                        clk17["t"] += 0.5  # 0.01s/query: sample ~1/4 scrapes
                        hist17.maybe_sample()
            return time.perf_counter() - t0

        _loop17(False)                    # warm both paths
        _loop17(True)
        off17 = min(_loop17(False) for _ in range(3))
        on17 = min(_loop17(True) for _ in range(3))
        # pct is vs the BARE registry-traffic loop — a worst case whose
        # denominator is a few microseconds of work per query; real
        # queries are 1000x that, which is why the <5% guard on the
        # real query path (tests/test_perf_budget.py) holds easily.
        # The amortized absolute cost is the number to watch.
        detail["cfg17_history_overhead_pct"] = round(
            max(0.0, (on17 - off17) / off17 * 100.0), 2)
        detail["cfg17_history_cost_us_per_query"] = round(
            max(0.0, on17 - off17) / iters17 * 1e6, 3)
        detail["cfg17_ring_memory_bytes"] = hist17.memory_bytes()

        fstore17 = _FS17(dir_path="", registry=reg17, history=hist17,
                         clock=lambda: clk17["t"])
        caps17 = []
        for i in range(20):
            t0 = time.perf_counter()
            fstore17.capture({"id": f"bench-{i}", "rule": "slo_trend",
                              "cause": "bench", "severity": "page",
                              "opened_ms": int(clk17["t"] * 1000),
                              "timeline": {"trace_gids": []}})
            caps17.append(time.perf_counter() - t0)
        detail["cfg17_bundle_capture_ms"] = round(_p50(caps17), 3)
        detail["cfg17_wall_s"] = round(time.perf_counter() - t17_start, 3)

    out = {
        "metric": "z3_bbox_time_count_p50_latency_100m",
        "value": round(headline_p50, 3) if headline_p50 is not None else None,
        "unit": "ms",
        "vs_baseline": vs_baseline,
        "detail": detail,
    }
    print(json.dumps(out))

    # -- flat machine-stable summary + the regression gate ------------------
    from geomesa_tpu.cluster.runtime import runtime as _cluster_runtime
    _crt = _cluster_runtime(init=False)
    _cluster_procs = _crt.num_processes if _crt.active() else 1
    _cluster_shard_rows = ({t: s.get("proc_rows")
                            for t, s in _crt.tables.items()}
                           if _crt.active() and _crt.tables else None)
    from geomesa_tpu import trace as _trace_mod
    from geomesa_tpu.obs import attrib as _attrib
    from geomesa_tpu.obs import perfwatch as _pw
    metrics = {k: v for k, v in detail.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    if out["value"] is not None:
        metrics["value"] = out["value"]
    if vs_baseline is not None:
        metrics["vs_baseline"] = vs_baseline
    summary = {
        "schema": _pw.SCHEMA,
        "ts": int(time.time()),
        "meta": {
            "device": detail.get("device"),
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "host_cores": os.cpu_count(),
            "n_points": n,
            "mini": bool(args.mini),
            "configs": sorted(configs),
            "handicaps": dict(_HANDICAPS) or None,
            # fleet attribution: which node produced this run, in which
            # role — perfwatch baselines and federated scrapes are
            # comparable per node, not just per machine class
            "node_id": _trace_mod.node_id(),
            "role": _trace_mod.node_role(),
            # partition-plane honesty: numbers from an N-process cluster
            # member are never comparable to single-process baselines —
            # perfwatch treats a num_processes mismatch as new-baseline
            "num_processes": _cluster_procs,
            "shard_rows": _cluster_shard_rows,
            # join-input complexity (bench honesty: these numbers mean
            # nothing without the polygon set's vertex budget on record)
            "cfg3_polygons": (
                {"count": int(detail.get("cfg3_n_polygons", 0)),
                 "vertices_total": detail["cfg3_poly_vertices_total"],
                 "vertices_mean": detail["cfg3_poly_vertices_mean"],
                 "vertices_max": detail["cfg3_poly_vertices_max"]}
                if "cfg3_poly_vertices_total" in detail else None),
        },
        "metrics": metrics,
        "kernels": _pw.kernel_summary(_attrib.snapshot()),
    }
    with open(args.summary, "w") as fh:
        json.dump(summary, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"# summary -> {args.summary}", file=sys.stderr)

    rc = 0
    if args.update_baseline:
        try:
            baselines = _pw.load_baselines(args.baseline)
        except (FileNotFoundError, ValueError):
            baselines = _pw.empty_baselines()
        _pw.save_baselines(_pw.update_baselines(baselines, summary),
                           args.baseline)
        print(f"# baselines updated -> {args.baseline} "
              f"({baselines.get('runs')} run(s) folded)", file=sys.stderr)
    if args.check:
        try:
            report = _pw.check_summary(summary, args.baseline, k=args.k,
                                       report_path=args.report)
        except FileNotFoundError:
            print(f"# no baselines at {args.baseline} — bootstrap with "
                  "--update-baseline first", file=sys.stderr)
            return 2
        print(_pw.render(report), file=sys.stderr)
        print(f"# report -> {args.report}", file=sys.stderr)
        if not report["ok"]:
            rc = 3
    return rc


if __name__ == "__main__":
    sys.exit(main())
