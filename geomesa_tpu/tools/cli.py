"""Command-line interface.

≙ reference `geomesa-tools` (SURVEY.md §2.11 — tools/Runner.scala:24 command
tree: create-schema / ingest / export / explain / stats-* / delete /
remove-schema). The "catalog" is a checkpoint directory (io.checkpoint);
mutating commands load → act → save.

    geomesa-tpu create-schema -s STORE -f NAME --spec 'dtg:Date,*geom:Point'
    geomesa-tpu ingest        -s STORE -f NAME data.csv [--converter conv.json | --infer]
    geomesa-tpu count         -s STORE -f NAME [-q ECQL]
    geomesa-tpu export        -s STORE -f NAME [-q ECQL] --format csv [-o out.csv]
    geomesa-tpu explain       -s STORE -f NAME -q ECQL
    geomesa-tpu stats         -s STORE -f NAME [--attr A] [--kind histogram|topk|bounds|count|minmax]
    geomesa-tpu delete        -s STORE -f NAME -q ECQL
    geomesa-tpu debug         metrics|traces|trace|events|slo|kernels|scheduler|cache|admission|wal|replication|workload|cluster|balance
                              [--format prometheus] [--slow MS] [--errors]
                              [--kind K] [--addr HOST:PORT ...] [-s STORE -f NAME -q ECQL]
                              [--id TRACE_ID --fleet]   (debug trace: stitched tree)
    geomesa-tpu cluster-dryrun [--procs N] [--n ROWS] [--out DIR] [--no-web]
    geomesa-tpu serve         -s STORE [--durable] [--ship-port P] [--port W]
    geomesa-tpu replica       --dir DIR --follow HOST:PORT [--port W] [--id ID]
    geomesa-tpu router        --endpoint NAME=HOST:PORT ... [--port P]
    geomesa-tpu fleet         status --addr HOST:PORT [--addr ...] [--json]
    geomesa-tpu soak          [--mini] [--scoreboard PATH] [--half chaos|clean]
    geomesa-tpu recover       --dir DURABILITY_DIR
    geomesa-tpu describe / list / remove-schema
"""

from __future__ import annotations

import argparse
import csv as _csv
import json
import os
import sys


def _load(store_dir: str, must_exist: bool = False):
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.io.checkpoint import load_store
    if os.path.exists(os.path.join(store_dir, "catalog.json")):
        return load_store(store_dir)
    if must_exist:
        raise SystemExit(f"No store at {store_dir} (missing catalog.json)")
    return TpuDataStore()


def _save(store, store_dir: str) -> None:
    from geomesa_tpu.io.checkpoint import save_store
    save_store(store, store_dir)


def cmd_create_schema(args):
    store = _load(args.store)
    store.create_schema(args.feature, args.spec)
    _save(store, args.store)
    print(f"Created schema {args.feature!r}")


def cmd_list(args):
    store = _load(args.store, must_exist=True)
    for name in store.get_type_names():
        t = store.tables.get(name)
        print(f"{name}\t{0 if t is None else len(t)} features")


def cmd_describe(args):
    store = _load(args.store, must_exist=True)
    sft = store.get_schema(args.feature)
    for a in sft.attributes:
        star = "*" if a.default else " "
        print(f"{star} {a.name}: {a.type_name} {a.options or ''}")
    if sft.user_data:
        print(f"user-data: {sft.user_data}")


def cmd_ingest(args):
    from geomesa_tpu.convert import (SimpleFeatureConverter,
                                     converter_config_from_inference,
                                     infer_schema)
    store = _load(args.store)
    fmt = args.format or ("json" if args.files[0].endswith((".json", ".jsonl"))
                          else "tsv" if args.files[0].endswith(".tsv")
                          else "csv")
    delim = "\t" if fmt == "tsv" else ","

    if args.converter:
        with open(args.converter) as fh:
            config = json.load(fh)
        sft = store.get_schema(args.feature)
    elif args.infer:
        if fmt == "json":
            raise SystemExit(
                "--infer only supports delimited input; for JSON provide a "
                "--converter config")
        with open(args.files[0], newline="") as fh:
            rows = list(_csv.reader(fh, delimiter=delim))
        if not rows or not rows[0]:
            raise SystemExit(f"Cannot infer a schema from empty {args.files[0]}")
        names, sample = rows[0], rows[1:101]
        spec, transforms = infer_schema(names, sample)
        config = converter_config_from_inference(spec, transforms)
        if args.feature not in store.get_type_names():
            store.create_schema(args.feature, spec)
            print(f"Inferred schema: {spec}")
        sft = store.get_schema(args.feature)
    else:
        raise SystemExit("ingest requires --converter CONF or --infer")

    conv = SimpleFeatureConverter(config, sft)
    total = 0
    for path in args.files:
        if fmt == "json":
            table = conv.convert_json(path)
        else:
            table = conv.convert_delimited(path, delimiter=delim)
        store.load(args.feature, table)
        total += len(table)
    _save(store, args.store)
    msg = f"Ingested {total} features into {args.feature!r}"
    if conv.skipped:
        msg += f" ({conv.skipped} bad records skipped)"
    print(msg)


def cmd_count(args):
    store = _load(args.store, must_exist=True)
    print(store.count(args.feature, args.cql or "INCLUDE"))


def cmd_export(args):
    from geomesa_tpu.io.export import export
    store = _load(args.store, must_exist=True)
    res = store.query(args.feature, args.cql or "INCLUDE")
    table = res.table
    if args.max is not None and len(table) > args.max:
        import numpy as np
        table = table.take(np.arange(args.max))
    if getattr(args, "select", None):
        # geometry-catalog projections: st_* terms through the vmapped
        # kernels, geometry values as WKT — CSV or JSON columns
        from geomesa_tpu.geom.functions import projection_columns
        cols = projection_columns(table, None, args.select)
        if args.format == "json":
            out = json.dumps({"count": len(table), "columns": cols})
        else:
            import csv as _csv
            import io as _io
            buf = _io.StringIO()
            w = _csv.writer(buf)
            w.writerow(list(cols))
            for row in zip(*cols.values()):
                w.writerow(row)
            out = buf.getvalue()
        if args.output:
            with open(args.output, "w") as fh:
                fh.write(out)
            print(f"Exported {len(table)} projected rows to {args.output}")
        else:
            sys.stdout.write(out)
        return
    out = export(table, args.format, args.output)
    if args.output:
        print(f"Exported {len(table)} features to {args.output}")
    else:
        sys.stdout.write(out)


def cmd_explain(args):
    store = _load(args.store, must_exist=True)
    plan = store.explain(args.feature, args.cql)
    print(json.dumps({k: str(v) for k, v in plan.items()}, indent=2))


def cmd_stats(args):
    store = _load(args.store, must_exist=True)
    s = store.stats(args.feature)
    kind = args.kind
    if kind == "count":
        print(s.get_count(args.cql, exact=not args.no_exact))
    elif kind == "bounds":
        print(s.get_bounds())
    elif kind == "minmax":
        mm = s.get_min_max(_require_attr(store, args))
        print(json.dumps(mm.to_json()))
    elif kind == "topk":
        print(json.dumps(s.get_top_k(_require_attr(store, args)).topk(10)))
    elif kind == "histogram":
        h = s.get_histogram(_require_attr(store, args), bins=args.bins, f=args.cql)
        if h is None:
            raise SystemExit(f"{args.attr!r} is not a binnable attribute")
        edges = h.bin_edges()
        width = max(int(c) for c in h.counts) or 1
        for i, c in enumerate(h.counts):
            bar = "#" * max(1 if c else 0, int(40 * int(c) / width))
            print(f"[{edges[i]:>12.2f} .. {edges[i+1]:>12.2f}] {int(c):>9} {bar}")
    else:
        raise SystemExit(f"Unknown stats kind {kind!r}")


def _require_attr(store, args) -> str:
    if not args.attr:
        raise SystemExit(f"stats --kind {args.kind} requires --attr")
    sft = store.get_schema(args.feature)
    try:
        sft.attribute(args.attr)
    except KeyError:
        raise SystemExit(
            f"No attribute {args.attr!r} in {args.feature!r} "
            f"(have {[a.name for a in sft.attributes]})")
    return args.attr


def cmd_delete(args):
    store = _load(args.store, must_exist=True)
    n = store.remove_features(args.feature, args.cql)
    _save(store, args.store)
    print(f"Deleted {n} features")


def cmd_age_off(args):
    """Run the TTL compaction (≙ the reference's age-off maintenance
    command over DtgAgeOffIterator-configured tables)."""
    store = _load(args.store, must_exist=True)
    n = store.age_off(args.feature)
    if n:
        _save(store, args.store)
    print(f"Aged off {n} features")


def cmd_reindex(args):
    """Rebuild a type's device indexes build-then-swap (the maintenance
    analogue of the reference's offline reindex jobs). Runs in the
    foreground here — against a live server use POST /types/{t}/reindex,
    which builds off the serving path and swaps atomically."""
    import json as _json
    store = _load(args.store, must_exist=True)
    st = store.reindex(args.feature, background=False)
    _save(store, args.store)
    print(_json.dumps(st, indent=2, default=str))


def cmd_recover(args):
    """Crash recovery (the runbook command): load the newest valid snapshot
    under the durability dir, replay the WAL suffix past it (truncating a
    torn tail at the first bad CRC), rebuild indexes, then write a fresh
    post-recovery snapshot so the next restart replays nothing."""
    from geomesa_tpu.datastore import TpuDataStore
    d = args.dir or args.store
    if not d:
        raise SystemExit("recover requires --dir (or -s) DURABILITY_DIR")
    store = TpuDataStore.open(d)
    report = store.recovery_report
    out = report.to_dict() if report is not None else {"recovered": False}
    out["rows"] = {t: (0 if store.tables.get(t) is None
                       else len(store.tables[t]))
                   for t in store.get_type_names()}
    out["post_recovery_snapshot"] = store.durability.snapshot()
    store.close()
    print(json.dumps(out, indent=2, default=str))


def cmd_debug(args):
    """Observability surface: dump the process metrics registry, the
    recent-trace ring, the query-scheduler state, or the WAL segment
    inspector (≙ the reference's stats/audit debug commands plus an
    accumulo-style wal-info). With a store + feature + CQL, runs the
    query first so the dump reflects a real execution — the offline way to
    read a trace tree. ``debug scheduler`` drives the warm query THROUGH the
    scheduler (a concurrent burst, so the dump shows real coalescing:
    queue depth, batch-size histogram, flush reasons, cache hit rates).
    ``debug wal -s DIR`` lists every segment's records (seq ranges, kinds,
    torn-tail diagnostics) without opening the store."""
    from geomesa_tpu.metrics import REGISTRY
    from geomesa_tpu.trace import RING
    if args.what == "wal":
        if not args.store:
            raise SystemExit("debug wal requires -s DURABILITY_DIR")
        from geomesa_tpu.durability import wal as _walmod
        out = _walmod.inspect(os.path.join(args.store, "wal"))
        out["journal"] = _walmod.inspect(
            os.path.join(args.store, "journal"), name="journal")["segments"]
        print(json.dumps(out, indent=2))
        return
    store = None
    if args.store:
        store = _load(args.store, must_exist=True)
        if args.feature and args.cql:
            if args.what in ("scheduler", "workload", "cache"):
                ns = store.count_many(args.feature, [args.cql] * 8)
                print(f"# ran 8x count({args.feature!r}, {args.cql!r}) "
                      f"through the scheduler -> {ns[0]}", file=sys.stderr)
            else:
                n = store.count(args.feature, args.cql)
                print(f"# ran count({args.feature!r}, {args.cql!r}) -> {n}",
                      file=sys.stderr)
    if args.what == "metrics":
        if args.format == "prometheus":
            sys.stdout.write(REGISTRY.to_prometheus())
        else:
            print(json.dumps(REGISTRY.snapshot(), indent=2, default=str))
    elif args.what == "admission":
        # the overload runbook surface: live queue depths per priority
        # class, shed/retry/breaker counters, deadline histograms
        out = {}
        if store is not None:
            sched = store.scheduler()
            out["admission"] = sched.admission.stats()
            out["breaker"] = sched.breaker.stats()
            out["queue_depth"] = sched._queue.qsize()
            out["healthy"] = sched.healthy()
        snap = REGISTRY.snapshot_prefixed(
            "admission.", "breaker.", "retry.", "degrade.",
            "scheduler.deadline", "scheduler.degraded",
            "scheduler.worker_deaths", "scheduler.restarts", "deadline.")
        out["metrics"] = {k: v for k, v in snap.items() if v}
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "scheduler":
        out = {}
        if store is not None:
            out = store.scheduler().stats()
        snap = REGISTRY.snapshot()
        # process-wide serving metrics ride along (a store-less dump still
        # shows whatever this process observed)
        out["metrics"] = {
            "counters": {k: v for k, v in snap["counters"].items()
                         if k.startswith("scheduler.")},
            "histograms": {k: v for k, v in snap["histograms"].items()
                           if k.startswith("scheduler.")},
            "gauges": {k: v for k, v in snap["gauges"].items()
                       if k.startswith(("scheduler.", "kernels."))},
        }
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "cache":
        # the hot-result cache: hit/miss/invalidation counters + per-cell
        # warmth (cross-check against `debug workload` hot cells and the
        # doctor's hot_skew suspects). With -s/-f/-q the repeated count
        # warms the cache first, so the dump shows a real hit.
        out = {}
        if store is not None:
            out["result_cache"] = store.scheduler().results.stats()
        snap = REGISTRY.snapshot_prefixed("result_cache.")
        out["metrics"] = {k: v for k, v in snap.items() if v}
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "events":
        # the flight recorder: one wide event per query/count/batch, with
        # the same filters the /events route takes
        from geomesa_tpu.obs.flight import RECORDER
        out = {"recorder": RECORDER.stats(),
               "events": RECORDER.recent(limit=args.limit,
                                         slow_ms=args.slow,
                                         errors=args.errors,
                                         kind=args.kind,
                                         type_name=args.feature,
                                         since_ms=args.since_ms)}
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "timeline":
        # retained metric timelines as ASCII sparklines — this process's
        # history rings, or a RUNNING node's GET /history via --addr
        # (one row per series; --name narrows, --since-ms/--tier slice)
        from geomesa_tpu.obs import history as _history
        if args.addr:
            import urllib.parse
            import urllib.request
            for addr in args.addr:
                base = addr if addr.startswith("http") else f"http://{addr}"
                prefix = f"{addr} " if len(args.addr) > 1 else ""
                try:
                    with urllib.request.urlopen(base + "/history",
                                                timeout=5) as r:
                        summary = json.loads(r.read().decode())["history"]
                    names = summary.get("series") or []
                    if args.name:
                        names = [n for n in names if n == args.name]
                    for n in names:
                        q = f"/history?name={urllib.parse.quote(n)}"
                        if args.since_ms is not None:
                            q += f"&since_ms={args.since_ms}"
                        if args.tier is not None:
                            q += f"&tier={args.tier}"
                        with urllib.request.urlopen(base + q,
                                                    timeout=5) as r:
                            samples = json.loads(
                                r.read().decode())["samples"]
                        print(prefix + _history.render_timeline(n, samples))
                except OSError as e:
                    print(f"{addr}: UNREACHABLE ({e})")
        else:
            h = _history.HISTORY
            h.maybe_sample()    # a fresh CLI read still shows this tick
            names = [args.name] if args.name else h.series_names()
            if not names:
                print("timeline: no retained series yet "
                      "(GEOMESA_TPU_HISTORY off, or nothing sampled)")
            for n in names:
                print(_history.render_timeline(
                    n, h.range(n, since_ms=args.since_ms or 0,
                               tier=args.tier)))
    elif args.what == "replication":
        # fleet runbook surface: role/lag/ship state (from a RUNNING node
        # via --addr, since replication state lives in the serving
        # process), plus this process's replication/router/drill counters
        out = {}
        for addr in (args.addr or []):
            base = addr if addr.startswith("http") else f"http://{addr}"
            import urllib.request
            node = {}
            for path, key in (("/replication", "replication"),
                              ("/healthz", "healthz")):
                try:
                    with urllib.request.urlopen(base + path,
                                                timeout=5) as r:
                        node[key] = json.loads(r.read().decode())
                except OSError as e:
                    node[key] = {"error": str(e)}
            if len(args.addr) == 1:
                out.update(node)  # the established single-node shape
            else:
                out.setdefault("nodes", {})[addr] = node
        snap = REGISTRY.snapshot_prefixed("replication.", "router.",
                                          "drill.")
        out["metrics"] = {k: v for k, v in snap.items() if v}
        gauges = REGISTRY.snapshot()["gauges"]
        out["lag"] = {k: gauges[k] for k in
                      ("replication.lag_seqs", "replication.lag_ms",
                       "replication.followers") if k in gauges}
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "cluster":
        # the partition plane runbook surface: process count, per-process
        # rows, Morton key-range ownership, mesh topology (axes, ICI/DCN
        # shape), psum round counters — this process's runtime, or a
        # RUNNING cluster node's GET /cluster via --addr (fleet parity
        # with `debug replication`)
        out = {}
        if args.addr:
            import urllib.request
            for addr in args.addr:
                base = addr if addr.startswith("http") else f"http://{addr}"
                try:
                    with urllib.request.urlopen(base + "/cluster",
                                                timeout=5) as r:
                        node = json.loads(r.read().decode())
                except OSError as e:
                    node = {"error": str(e)}
                if len(args.addr) == 1:
                    out.update(node)
                else:
                    out.setdefault("nodes", {})[addr] = node
        else:
            from geomesa_tpu.cluster.runtime import runtime as _cluster_rt
            out = _cluster_rt(init=False).state()
        snap = REGISTRY.snapshot_prefixed("cluster.")
        metrics = {k: v for k, v in snap.items() if v}
        if metrics:
            out["metrics"] = metrics
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "balance":
        # the shard balance observatory runbook surface: per-shard load
        # shares joined from hot cells x key-range ownership, imbalance
        # score, projected split points — this process's ledger, or a
        # RUNNING cluster node's GET /cluster/balance via --addr (one
        # addr flattens; several nest per node)
        out = {}
        if args.addr:
            import urllib.request
            for addr in args.addr:
                base = addr if addr.startswith("http") else f"http://{addr}"
                try:
                    with urllib.request.urlopen(base + "/cluster/balance",
                                                timeout=5) as r:
                        node = json.loads(r.read().decode())
                except OSError as e:
                    node = {"error": str(e)}
                if len(args.addr) == 1:
                    out.update(node)
                else:
                    out.setdefault("nodes", {})[addr] = node
        else:
            from geomesa_tpu.obs.shardwatch import WATCH
            out = WATCH.balance()
        snap = REGISTRY.snapshot_prefixed("cluster.collective.")
        metrics = {k: v for k, v in snap.items() if v}
        if metrics:
            out["collective"] = metrics
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "trace":
        # the stitched cross-process tree for one global trace id:
        # collect this process's halves plus every --addr node's
        # GET /traces?id= halves, stitch, render (--fleet implied by any
        # --addr; without addrs it stitches whatever is local)
        from geomesa_tpu.obs import federation as _fed
        if not args.id:
            raise SystemExit("debug trace requires --id GLOBAL_TRACE_ID")
        nodes = {"local": None}
        for i, addr in enumerate(args.addr or []):
            nodes[f"addr{i}"] = addr
        halves = _fed.collect_trace(args.id, nodes)
        st = _fed.stitch(halves)
        print(_fed.render_stitched(st))
        if args.format == "json":
            print(json.dumps({"id": args.id, "stitched": st,
                              "halves": len(halves)}, indent=2,
                             default=str))
    elif args.what == "slo":
        # burn-rate runbook surface: compliance + multi-window burn rates
        # + page/ticket state per objective — this process's engine, or a
        # RUNNING node's GET /slo via --addr (fleet parity with
        # `debug replication` / `debug workload`)
        out = {}
        if args.addr:
            import urllib.request
            for addr in args.addr:
                base = addr if addr.startswith("http") else f"http://{addr}"
                try:
                    with urllib.request.urlopen(base + "/slo",
                                                timeout=5) as r:
                        node = json.loads(r.read().decode())
                except OSError as e:
                    node = {"error": str(e)}
                if len(args.addr) == 1:
                    out.update(node)
                else:
                    out.setdefault("nodes", {})[addr] = node
        else:
            from geomesa_tpu.obs.slo import ENGINE
            out = {"slo": ENGINE.evaluate()}
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "incidents":
        # the doctor's incident ledger: active + recently-resolved, with
        # correlated timelines — local, or a RUNNING node's /incidents
        out = {}
        if args.addr:
            import urllib.request
            for addr in args.addr:
                base = addr if addr.startswith("http") else f"http://{addr}"
                try:
                    with urllib.request.urlopen(base + "/incidents",
                                                timeout=5) as r:
                        node = json.loads(r.read().decode())
                except OSError as e:
                    node = {"error": str(e)}
                if len(args.addr) == 1:
                    out.update(node)
                else:
                    out.setdefault("nodes", {})[addr] = node
        else:
            from geomesa_tpu.obs.doctor import DOCTOR
            out = DOCTOR.incidents()
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "workload":
        # workload intelligence: windowed rollups, heavy-hitter plan
        # hashes/tenants, hot spatial cells — this process's plane, or a
        # RUNNING node's GET /workload via --addr
        out = {}
        if args.addr:
            import urllib.request
            for addr in args.addr:
                base = addr if addr.startswith("http") else f"http://{addr}"
                try:
                    with urllib.request.urlopen(base + "/workload",
                                                timeout=5) as r:
                        node = json.loads(r.read().decode())
                except OSError as e:
                    node = {"error": str(e)}
                if len(args.addr) == 1:
                    out.update(node)
                else:
                    out.setdefault("nodes", {})[addr] = node
        else:
            from geomesa_tpu.obs.workload import WORKLOAD
            out = {"workload": WORKLOAD.summary()}
        print(json.dumps(out, indent=2, default=str))
    elif args.what == "kernels":
        # per-kernel device cost attribution (dispatches, device wait,
        # transfer bytes, compiles, flops/bytes cost model per kernel id
        # + batch tier), headed by the process-wide recompile count and
        # live/peak device memory — the perf-regression postmortem dump
        from geomesa_tpu.index import compiled as _fused
        from geomesa_tpu.index.device import memory_snapshot
        from geomesa_tpu.obs import attrib
        snap = REGISTRY.snapshot()
        print(json.dumps({
            "recompiles": snap["counters"].get("kernels.recompiles", 0),
            "device_memory": memory_snapshot(),
            "kernels": attrib.snapshot(),
            "fused_query": _fused.stats_snapshot(),
        }, indent=2, default=str))
    else:  # traces — filtered through the shared flight-recorder predicate
        from geomesa_tpu.obs.flight import matches
        traces = [t for t in RING.recent(None)
                  if matches(t, slow_ms=args.slow, errors=args.errors,
                             kind=args.kind)]
        print(json.dumps(traces[: args.limit], indent=2))


def cmd_config(args):
    from geomesa_tpu import config as cfg
    for name, d in cfg.describe().items():
        mark = "" if d["value"] == d["default"] else "  (set)"
        print(f"{name} = {d['value']}{mark}\n    {d['doc']}")


def _configure_cell(spec: str, directory):
    """Bind this process to its shard cell (``--cell SHARD=LO:HI``): the
    ingest gate starts refusing out-of-range writes with 409 and the
    cell fence persists its epoch under the durable directory."""
    from geomesa_tpu.cluster import cells as _cells
    topo = _cells.ShardCells.from_specs([spec])
    _cells.CELLS.configure(topology=None, local=topo.cells[0],
                           directory=directory)
    print(json.dumps({"cell": topo.cells[0].summary(),
                      "fence_epoch": _cells.CELLS.fence.epoch
                      if _cells.CELLS.fence else None}), flush=True)


def cmd_serve(args):
    from geomesa_tpu.web import serve
    if args.durable:
        # a durable store dir (WAL + snapshots): recovery runs on open and
        # every mutation is logged — the shape a replicated fleet requires
        from geomesa_tpu.datastore import TpuDataStore
        store = TpuDataStore.open(args.store)
    else:
        store = _load(args.store, must_exist=True)
    if args.cell:
        _configure_cell(args.cell,
                        args.store if args.durable else None)
    if args.ship_port is not None:
        from geomesa_tpu.replication.shipper import LogShipper
        shipper = LogShipper(store, host=args.host, port=args.ship_port)
        print(json.dumps({"shipping": shipper.address,
                          "epoch": shipper.epoch}), flush=True)
    print(f"Serving {args.store} on http://{args.host}:{args.port}",
          flush=True)
    serve(store, host=args.host, port=args.port)


def cmd_replica(args):
    """Run a read replica: open (or create) the local durable copy at
    --dir, follow the primary's log shipper at --follow host:port, and
    optionally serve the read-only REST API on --port. Runs until
    interrupted; `POST /replication/promote` (or a router failover) turns
    it into a primary in place."""
    import time as _time

    from geomesa_tpu.replication.follower import Follower
    from geomesa_tpu.web import serve
    if args.cell:
        _configure_cell(args.cell, args.dir)
    f = Follower(args.dir, args.follow, follower_id=args.id)
    print(json.dumps({"replica": f.id, "dir": args.dir,
                      "following": args.follow}), flush=True)
    try:
        if args.port:
            print(f"Serving replica on http://{args.host}:{args.port}",
                  flush=True)
            serve(f, host=args.host, port=args.port)
        else:
            while not f.dead:
                _time.sleep(0.5)
            raise SystemExit("replica apply loop died")
    finally:
        f.close()


def cmd_router(args):
    """Run the fleet front door: a health/lag-aware read router over the
    named endpoints, serving routed counts WITH cross-process trace
    propagation plus the federated observability plane (GET /fleet,
    /fleet/metrics, /fleet/slo, the /traces?id= stitcher)."""
    from geomesa_tpu import trace as _t
    from geomesa_tpu.obs import federation as _fed
    from geomesa_tpu.serve.router import (HttpEndpoint, ReplicaRouter,
                                          serve_router)
    eps, nodes = [], {}
    for spec in args.endpoint:
        name, sep, addr = spec.partition("=")
        if not sep:
            name, addr = f"n{len(eps)}", spec
        base = addr if addr.startswith("http") else f"http://{addr}"
        eps.append(HttpEndpoint(name, base))
        nodes[name] = base
    topology = None
    if getattr(args, "shard", None):
        from geomesa_tpu.cluster.cells import ShardCells
        topology = ShardCells.from_specs(args.shard)
    router = ReplicaRouter(eps, topology=topology)
    nodes[_t.node_id()] = None  # federate this router's own counters too
    fed = _fed.configure(nodes)
    print(json.dumps({"router": f"http://{args.host}:{args.port}",
                      "endpoints": sorted(nodes)}), flush=True)
    serve_router(router, host=args.host, port=args.port, federator=fed)


def _render_fleet(fl) -> str:
    lines = ["NODE              ROLE        LAG      SEQ            "
             "BREAKER   QUEUE  FENCED  SLO"]
    for name, n in sorted(fl.get("nodes", {}).items()):
        if not n.get("ok"):
            lines.append(f"{name:<17} DOWN        {n.get('error')}")
            continue
        lag = "-" if n.get("lag_ms") is None else f"{n['lag_ms']}ms"
        seq = f"{n.get('applied_seq')}/{n.get('wal_seq')}"
        lines.append(
            f"{name:<17} {str(n.get('role')):<11} {lag:<8} {seq:<14} "
            f"{str(n.get('breaker')):<9} {str(n.get('queue_depth')):<6} "
            f"{str(n.get('fenced')):<7} {n.get('slo')}")
    for k, v in sorted((fl.get("slo") or {}).items()):
        lines.append(f"slo {k}: status={v.get('status')} "
                     f"compliance={v.get('compliance')} "
                     f"good={v.get('good')}/{v.get('total')}")
    e2e = fl.get("repl_e2e_ms")
    if e2e:
        lines.append(f"repl.e2e: count={e2e.get('count')} "
                     f"p50={e2e.get('p50_ms')}ms p99={e2e.get('p99_ms')}ms "
                     f"exemplars={e2e.get('exemplars')}")
    return "\n".join(lines)


def cmd_fleet(args):
    """Fleet status from anywhere: scrape every --addr node's /healthz +
    bucket-exact metrics state, merge client-side, and print the single
    pane of glass (per-node health/lag/seq, fleet SLO burn rates over
    MERGED samples, the replication e2e pipeline histogram)."""
    from geomesa_tpu.obs import federation as _fed
    if args.action != "status":
        raise SystemExit(f"unknown fleet action {args.action!r}")
    if not args.addr:
        raise SystemExit("fleet status requires --addr HOST:PORT "
                         "(repeatable, one per node)")
    fed = _fed.Federator({a: a for a in args.addr})
    fl = fed.fleet()
    if args.json:
        print(json.dumps(fl, indent=2, default=str))
    else:
        print(_render_fleet(fl))


def cmd_soak(args):
    """Run the fleet soak: launch a real primary+replicas+router fleet
    as subprocesses, drive Zipf multi-tenant traffic through the router,
    execute the chaos timeline (unless --half clean), and write the
    scored scoreboard (JSON + markdown). Exits nonzero when any
    scoreboard check fails."""
    from geomesa_tpu.obs import soakfleet
    halves = ("chaos", "clean") if args.half == "both" else (args.half,)
    board = soakfleet.run(mini=args.mini, scoreboard_path=args.scoreboard,
                          base_dir=args.dir, halves=halves)
    print(soakfleet.render_scoreboard(board))
    if not board.get("ok"):
        raise SystemExit(2)


def cmd_soakcells(args):
    """Run the cluster chaos soak: two replicated shard cells plus a
    shard-aware router as subprocesses, shard-routed writes and
    scatter-gather reads, then the cluster chaos timeline (cell
    failover, mid-ingest handoff, split-brain refusal, shard_dark).
    Exits nonzero when any scoreboard check fails."""
    from geomesa_tpu.obs import soakcells
    halves = ("chaos", "clean") if args.half == "both" else (args.half,)
    board = soakcells.run(mini=args.mini, scoreboard_path=args.scoreboard,
                          base_dir=args.dir, halves=halves)
    print(soakcells.render_scoreboard(board))
    if not board.get("ok"):
        raise SystemExit(2)


def cmd_cluster_dryrun(args):
    """The partition-plane soak: spawn --procs CPU worker processes, build
    ONE table sharded across them by contiguous Morton key-range, and check
    that psum-reduced counts/density and host-merged selects are byte-equal
    to the single-process oracle (same code path, inactive runtime). Exits
    nonzero when any exactness check fails."""
    from geomesa_tpu.cluster.dryrun import run_dryrun
    report = run_dryrun(args.procs, args.n, args.seed,
                        timeout_s=args.timeout_s, out_dir=args.out,
                        web=not args.no_web)
    print(json.dumps({k: report[k] for k in
                      ("ok", "checks", "wall_s", "work_dir")}, indent=2))
    if not report["ok"]:
        raise SystemExit(2)


def cmd_doctor(args):
    """The fleet doctor's verdicts: evaluate the anomaly detectors and
    print ONE line per incident — what fired, since when, suspected
    cause, linked trace. Local by default; with --addr it reads each
    RUNNING node's GET /incidents (repeatable, node-attributed)."""
    from geomesa_tpu.obs.doctor import verdict
    nodes = {}
    if args.addr:
        import urllib.request
        for addr in args.addr:
            base = addr if addr.startswith("http") else f"http://{addr}"
            try:
                with urllib.request.urlopen(base + "/incidents",
                                            timeout=5) as r:
                    nodes[addr] = json.loads(r.read().decode())
            except OSError as e:
                nodes[addr] = {"error": str(e)}
    else:
        from geomesa_tpu.obs.doctor import DOCTOR
        nodes["local"] = DOCTOR.incidents()
    if args.json:
        print(json.dumps(nodes if len(nodes) > 1
                         else next(iter(nodes.values())),
                         indent=2, default=str))
        return
    total = 0
    for name, body in sorted(nodes.items()):
        if body.get("error"):
            print(f"{name}: UNREACHABLE ({body['error']})")
            continue
        incidents = body.get("incidents") or []
        for inc in incidents:
            prefix = f"{name}: " if len(nodes) > 1 else ""
            print(prefix + verdict(inc))
        total += len(incidents)
    if total == 0:
        print("doctor: no incidents — all detectors clear")


def cmd_forensics(args):
    """Forensic bundles the doctor froze at incident open: history
    slices around the firing, matching flight events, retained trace
    gids, replication/cell state, workload hot_set. Without --id, lists
    the captured bundles; with --id, prints that incident's bundle.
    --addr reads a RUNNING node's GET /incidents/{id}/bundle instead."""
    if args.addr:
        import urllib.request
        out = {}
        for addr in args.addr:
            base = addr if addr.startswith("http") else f"http://{addr}"
            if not args.id:
                raise SystemExit("forensics --addr requires --id "
                                 "INCIDENT_ID (list ids with "
                                 "`geomesa-tpu doctor --addr ...`)")
            try:
                with urllib.request.urlopen(
                        base + f"/incidents/{args.id}/bundle",
                        timeout=5) as r:
                    node = json.loads(r.read().decode())
            except OSError as e:
                node = {"error": str(e)}
            if len(args.addr) == 1:
                out.update(node)
            else:
                out.setdefault("nodes", {})[addr] = node
        print(json.dumps(out, indent=2, default=str))
        return
    from geomesa_tpu.obs.forensics import FORENSICS
    if args.id:
        bundle = FORENSICS.get(args.id)
        if bundle is None:
            raise SystemExit(f"no forensic bundle for {args.id}")
        print(json.dumps(bundle, indent=2, default=str))
        return
    bundles = FORENSICS.list()
    if not bundles:
        print("forensics: no bundles captured "
              "(the doctor opens them with incidents)")
        return
    for b in bundles:
        print(f"{b['incident_id']:<10} {b.get('rule', '?'):<20} "
              f"captured_ms={b.get('captured_ms')} "
              f"events={b.get('events')} series={b.get('series')} "
              f"cause={b.get('cause')}")


def cmd_remove_schema(args):
    store = _load(args.store, must_exist=True)
    store.remove_schema(args.feature)
    npz = os.path.join(args.store, f"{args.feature}.npz")
    if os.path.exists(npz):
        os.remove(npz)
    _save(store, args.store)
    print(f"Removed schema {args.feature!r}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="geomesa-tpu",
        description="TPU-native spatio-temporal datastore tools")
    sub = p.add_subparsers(dest="command", required=True)

    def common(sp, feature=True):
        sp.add_argument("-s", "--store", required=True,
                        help="store (checkpoint) directory")
        if feature:
            sp.add_argument("-f", "--feature", required=True,
                            help="feature type name")

    sp = sub.add_parser("create-schema", help="register a feature type")
    common(sp)
    sp.add_argument("--spec", required=True, help="SFT spec string")
    sp.set_defaults(fn=cmd_create_schema)

    sp = sub.add_parser("list", help="list feature types")
    common(sp, feature=False)
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser("describe", help="describe a feature type")
    common(sp)
    sp.set_defaults(fn=cmd_describe)

    sp = sub.add_parser("ingest", help="ingest files through a converter")
    common(sp)
    sp.add_argument("files", nargs="+")
    sp.add_argument("--converter", help="converter config JSON file")
    sp.add_argument("--infer", action="store_true",
                    help="infer schema + converter from the data")
    sp.add_argument("--format", choices=("csv", "tsv", "json"))
    sp.set_defaults(fn=cmd_ingest)

    sp = sub.add_parser("count", help="count matching features")
    common(sp)
    sp.add_argument("-q", "--cql", help="ECQL filter")
    sp.set_defaults(fn=cmd_count)

    sp = sub.add_parser("export", help="export matching features")
    common(sp)
    sp.add_argument("-q", "--cql")
    from geomesa_tpu.io.export import FORMATS as _EXPORT_FORMATS
    sp.add_argument("--format", default="csv", choices=_EXPORT_FORMATS,
                    help="|".join(_EXPORT_FORMATS))
    sp.add_argument("-o", "--output")
    sp.add_argument("--max", type=int)
    sp.add_argument("--select",
                    help="projection list, e.g. "
                         "'st_centroid(geom) AS c, val' (st_* terms "
                         "evaluate through the geometry kernels; "
                         "geometry values export as WKT; csv/json only)")
    sp.set_defaults(fn=cmd_export)

    sp = sub.add_parser("explain", help="show the query plan")
    common(sp)
    sp.add_argument("-q", "--cql", required=True)
    sp.set_defaults(fn=cmd_explain)

    sp = sub.add_parser("stats", help="summary statistics")
    common(sp)
    sp.add_argument("--kind", default="count",
                    choices=("count", "bounds", "minmax", "topk", "histogram"))
    sp.add_argument("--attr")
    sp.add_argument("--bins", type=int, default=20)
    sp.add_argument("-q", "--cql")
    sp.add_argument("--no-exact", action="store_true")
    sp.set_defaults(fn=cmd_stats)

    sp = sub.add_parser("delete", help="delete matching features")
    common(sp)
    sp.add_argument("-q", "--cql", required=True)
    sp.set_defaults(fn=cmd_delete)

    sp = sub.add_parser("remove-schema", help="drop a feature type")
    common(sp)
    sp.set_defaults(fn=cmd_remove_schema)

    sp = sub.add_parser(
        "age-off", help="drop features past their geomesa.feature.expiry TTL")
    common(sp)
    sp.set_defaults(fn=cmd_age_off)

    sp = sub.add_parser(
        "reindex",
        help="rebuild a type's device indexes build-then-swap (bumps the "
             "serving-cache generation)")
    common(sp)
    sp.set_defaults(fn=cmd_reindex)

    sp = sub.add_parser("config", help="list system properties")
    sp.set_defaults(fn=cmd_config)

    sp = sub.add_parser(
        "recover",
        help="crash-recover a durable store directory (snapshot + WAL "
             "replay, torn tail truncated) and write a fresh snapshot")
    sp.add_argument("--dir", help="durability directory (as passed to "
                                  "TpuDataStore.open / params['durability'])")
    sp.add_argument("-s", "--store", help="alias for --dir")
    sp.set_defaults(fn=cmd_recover)

    sp = sub.add_parser(
        "debug", help="dump metrics, recent query traces, flight-recorder "
                      "events, SLO burn rates, per-kernel attribution, "
                      "scheduler state, admission/overload state, doctor "
                      "incidents, or the WAL segment inspector")
    sp.add_argument("what", choices=("metrics", "traces", "trace", "events",
                                     "slo", "kernels", "scheduler", "cache",
                                     "admission", "wal", "replication",
                                     "workload", "incidents", "cluster",
                                     "balance", "timeline"))
    sp.add_argument("-s", "--store", help="store to exercise first (optional)")
    sp.add_argument("-f", "--feature", help="feature type for the warm query "
                                            "(also the type filter for "
                                            "`debug events`)")
    sp.add_argument("-q", "--cql", help="ECQL filter for the warm query")
    sp.add_argument("--format", default=None,
                    choices=("json", "prometheus"))
    sp.add_argument("--limit", type=int, default=20,
                    help="max traces/events to print")
    # traces/events filters (the shared flight-recorder predicate)
    sp.add_argument("--slow", type=float, default=None, metavar="MS",
                    help="only records at least this slow")
    sp.add_argument("--errors", action="store_true",
                    help="only failed/shed/cancelled records")
    sp.add_argument("--kind", default=None,
                    help="match record kind / trace name / a span kind "
                         "present in the stage breakdown")
    sp.add_argument("--since-ms", type=float, default=None, dest="since_ms",
                    metavar="EPOCH_MS",
                    help="`debug events`/`debug timeline`: only records/"
                         "samples stamped at/after this wall time — the "
                         "same slice filter a forensic bundle uses")
    sp.add_argument("--name", default=None,
                    help="for `debug timeline`: only this history series")
    sp.add_argument("--tier", type=int, default=None, metavar="SECONDS",
                    help="for `debug timeline`: pick the ring tier by "
                         "interval (default: the finest)")
    sp.add_argument("--addr", action="append", default=None,
                    metavar="HOST:PORT",
                    help="a RUNNING node to query (repeatable). "
                         "`debug replication`: its /replication + "
                         "/healthz; `debug trace --fleet`: every node's "
                         "/traces?id= halves for the stitcher")
    sp.add_argument("--id", default=None, metavar="TRACE_ID",
                    help="for `debug trace`: the global trace id to "
                         "stitch (the `trace` field a routed count / "
                         "flight event / exemplar carries)")
    sp.add_argument("--fleet", action="store_true",
                    help="for `debug trace`: fetch remote halves from "
                         "every --addr node (without it, only this "
                         "process's rings are searched)")
    sp.set_defaults(fn=cmd_debug)

    sp = sub.add_parser("serve", help="REST/GeoJSON API over a store")
    sp.add_argument("-s", "--store", required=True)
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=8765)
    sp.add_argument("--durable", action="store_true",
                    help="treat -s as a durability dir (WAL + snapshots): "
                         "recover on open, log every mutation — required "
                         "for --ship-port")
    sp.add_argument("--ship-port", type=int, default=None, metavar="PORT",
                    help="also start the replication log shipper on this "
                         "port (0 = ephemeral); followers connect with "
                         "`geomesa-tpu replica --follow host:port`")
    sp.add_argument("--cell", default=None, metavar="SHARD=LO:HI",
                    help="bind this node to a shard cell: ingests whose "
                         "routing key falls outside [LO,HI] are refused "
                         "with 409 not_owner; the cell fence epoch "
                         "persists under the durable dir")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser(
        "router",
        help="run the fleet front door: health/lag-aware routed reads "
             "with cross-process trace propagation, plus the federated "
             "observability plane (/fleet, /fleet/metrics, the "
             "/traces?id= stitcher)")
    sp.add_argument("--endpoint", action="append", required=True,
                    metavar="NAME=HOST:PORT",
                    help="one serving node's REST base address "
                         "(repeatable; NAME= optional)")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=8760)
    sp.add_argument("--shard", action="append", default=None,
                    metavar="SHARD=LO:HI=MEMBER[,MEMBER...]",
                    help="one shard cell's key range + member endpoint "
                         "names (repeatable). With a topology the router "
                         "scatter-gathers counts across cells, routes "
                         "writes by key ownership, and serves /shards + "
                         "/handoff")
    sp.set_defaults(fn=cmd_router)

    sp = sub.add_parser(
        "doctor",
        help="fleet doctor verdicts: run the anomaly detectors and print "
             "one line per incident (what fired, since when, suspected "
             "cause, linked trace); --addr reads running nodes")
    sp.add_argument("--addr", action="append", default=None,
                    metavar="HOST:PORT",
                    help="a RUNNING node's REST address (repeatable); "
                         "without it the local process is diagnosed")
    sp.add_argument("--json", action="store_true",
                    help="print the raw incident JSON instead of verdicts")
    sp.set_defaults(fn=cmd_doctor)

    sp = sub.add_parser(
        "forensics",
        help="forensic bundles the doctor froze at incident open "
             "(history slices, matching events, trace gids, workload "
             "hot_set): list bundles, or print one with --id; --addr "
             "reads a running node's /incidents/{id}/bundle")
    sp.add_argument("--id", default=None, metavar="INCIDENT_ID",
                    help="print this incident's bundle (e.g. inc-3)")
    sp.add_argument("--addr", action="append", default=None,
                    metavar="HOST:PORT",
                    help="a RUNNING node's REST address (repeatable); "
                         "requires --id")
    sp.set_defaults(fn=cmd_forensics)

    sp = sub.add_parser(
        "fleet",
        help="fleet-wide status: scrape every --addr node, merge "
             "client-side, print per-node health + fleet SLO burn rates")
    sp.add_argument("action", choices=("status",))
    sp.add_argument("--addr", action="append", metavar="HOST:PORT",
                    help="a fleet node's REST address (repeatable)")
    sp.add_argument("--json", action="store_true",
                    help="print the raw merged JSON instead of the table")
    sp.set_defaults(fn=cmd_fleet)

    sp = sub.add_parser(
        "soak",
        help="chaos-scored fleet soak: spawn primary+replicas+router as "
             "subprocesses, drive Zipf traffic through the router, run "
             "the chaos timeline, score the scoreboard")
    sp.add_argument("--mini", action="store_true",
                    help="CI-sized run (short phases); omit for the "
                         "nightly-length soak")
    sp.add_argument("--scoreboard", default=None, metavar="PATH",
                    help="scoreboard JSON path (default "
                         "SOAK_scoreboard.json; markdown lands beside it)")
    sp.add_argument("--half", choices=("both", "chaos", "clean"),
                    default="both",
                    help="run only one half (default: both)")
    sp.add_argument("--dir", default=None,
                    help="scratch directory for the fleet's durable "
                         "stores (default: a temp dir)")
    sp.set_defaults(fn=cmd_soak)

    sp = sub.add_parser(
        "soakcells",
        help="cluster chaos soak: two replicated shard cells + a "
             "shard-aware router as subprocesses, shard-routed writes, "
             "scatter-gather reads, cell failover / handoff / "
             "split-brain / shard_dark chaos, scored scoreboard")
    sp.add_argument("--mini", action="store_true",
                    help="CI-sized run (short phases)")
    sp.add_argument("--scoreboard", default=None, metavar="PATH",
                    help="scoreboard JSON path (default "
                         "SOAKCELLS_scoreboard.json)")
    sp.add_argument("--half", choices=("both", "chaos", "clean"),
                    default="both",
                    help="run only one half (default: both)")
    sp.add_argument("--dir", default=None,
                    help="scratch directory for the cells' durable "
                         "stores (default: a temp dir)")
    sp.set_defaults(fn=cmd_soakcells)

    sp = sub.add_parser(
        "cluster-dryrun",
        help="2-process CPU cluster dryrun: spawn worker subprocesses, "
             "shard one table across them by Morton key-range, check "
             "psum counts / density / merged selects byte-equal against "
             "the single-process oracle")
    sp.add_argument("--procs", type=int, default=2,
                    help="number of worker processes (default 2)")
    sp.add_argument("--n", type=int, default=20000,
                    help="corpus rows (default 20000)")
    sp.add_argument("--seed", type=int, default=7)
    sp.add_argument("--timeout-s", type=float, default=420.0,
                    help="hard deadline for the worker fleet")
    sp.add_argument("--out", default=None, metavar="DIR",
                    help="directory for rank reports / logs / "
                         "dryrun_report.json (default: a temp dir)")
    sp.add_argument("--no-web", action="store_true",
                    help="skip the per-rank REST server + federation "
                         "registration checks")
    sp.set_defaults(fn=cmd_cluster_dryrun)

    sp = sub.add_parser(
        "replica",
        help="run a read replica: follow a primary's log shipper, apply "
             "shipped WAL frames into a local durable copy, optionally "
             "serve the read-only REST API")
    sp.add_argument("--dir", required=True,
                    help="local durable store directory for this replica")
    sp.add_argument("--follow", required=True, metavar="HOST:PORT",
                    help="the primary's log-shipper address")
    sp.add_argument("--id", default=None, help="stable follower id "
                    "(default: the directory basename)")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=0,
                    help="serve the read-only REST API here (0 = no HTTP)")
    sp.add_argument("--cell", default=None, metavar="SHARD=LO:HI",
                    help="bind this replica to its shard cell (see "
                         "`serve --cell`); on promote it inherits the "
                         "cell's ingest gate + fence")
    sp.set_defaults(fn=cmd_replica)

    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from geomesa_tpu import config
    config.enable_compile_cache()   # before any command touches a backend
    args.fn(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
