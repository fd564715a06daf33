"""DataStore facade — the framework entry point.

≙ GeoTools ``DataStoreFinder`` + ``GeoMesaDataStore``
(/root/reference/geomesa-index-api/.../geotools/GeoMesaDataStore.scala:49,
MetadataBackedDataStore.scala:123). The TPU store keeps GeoMesa's lifecycle:

  create_schema(sft)     — register the type, decide its indexes
  get_writer(type)       — batch feature writer (append); indexes build on
                           flush (bulk sort ≙ bulk ingest; incremental deltas
                           arrive with the live/streaming layer)
  query/count/explain    — plan + execute through QueryPlanner

Backends are factories keyed by params, mirroring the DataStoreFactorySpi
registry; the in-memory/TPU store registers as ``tpu`` (the moral slot of the
reference's in-memory CQEngine store — and the perf comparison target).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from geomesa_tpu import trace as _trace
from geomesa_tpu.features.geometry import GeometryArray
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.features.table import FeatureTable, StringColumn
from geomesa_tpu.filter import ir
from geomesa_tpu.index.api import QueryResult
from geomesa_tpu.index.planner import QueryPlanner, tally_join
from geomesa_tpu.index.spatial import INDEX_CLASSES, FullScanIndex

_INDEX_BY_NAME = {c.name: c for c in INDEX_CLASSES}

# per-process store-incarnation counter: every TpuDataStore instance gets a
# unique epoch (pid + counter) that salts the serving-scheduler cache keys,
# so plans cached for one incarnation are unreachable from any other — even
# one restored with identical generation counters
import itertools as _itertools
import os as _os
import re

_EPOCHS = _itertools.count(1)


def _next_epoch() -> str:
    return f"{_os.getpid():x}d{next(_EPOCHS)}"


class FeatureWriter:
    """Batch appender (≙ GeoMesaFeatureWriter append mode). Collects rows
    host-side; ``flush`` builds the columnar table and (re)builds indexes —
    the precompute-all-mutations-then-write atomicity discipline
    (IndexAdapter.scala:139-150) becomes build-then-swap."""

    def __init__(self, store: "TpuDataStore", type_name: str):
        self.store = store
        self.type_name = type_name
        self.sft = store.schemas[type_name]
        self._rows: List[dict] = []
        self._fids: List[Optional[str]] = []
        self._vis: List[str] = []

    def write(self, fid: Optional[str] = None, vis: str = "",
              **attributes) -> str:
        """``vis``: visibility expression for this feature (≙ the mutation
        visibility of geomesa-security; '' = public)."""
        missing = [a.name for a in self.sft.attributes if a.name not in attributes]
        if missing:
            raise ValueError(f"Missing attributes {missing}")
        self._rows.append(attributes)
        if fid is None:
            fid = f"{self.type_name}.{self.store._fid_counter(self.type_name)}"
        self._fids.append(fid)
        self._vis.append(vis)
        return fid

    def flush(self) -> None:
        if not self._rows:
            return
        data: Dict[str, list] = {a.name: [] for a in self.sft.attributes}
        for row in self._rows:
            for a in self.sft.attributes:
                data[a.name].append(row[a.name])
        cols: Dict[str, object] = {}
        for a in self.sft.attributes:
            cols[a.name] = GeometryArray.from_rows(data[a.name]) \
                if a.is_geometry else data[a.name]
        vis = self._vis if any(self._vis) else None
        batch = FeatureTable.build(self.sft, cols, fids=self._fids,
                                   visibilities=vis)
        self.store._append(self.type_name, batch)
        self._rows, self._fids, self._vis = [], [], []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            self.flush()


class TpuDataStore:
    """In-process TPU-backed datastore.

    Concurrency model (≙ the reference's immutable-plans + concurrent-store
    discipline, SURVEY.md §5): mutators (_append/flush/update_*/remove_*)
    serialize on a store-wide writer lock and follow build-then-swap — new
    tables/planners are constructed fully before any shared reference is
    reassigned, and existing FeatureTable/QueryPlanner objects are never
    mutated in place. Readers never take the lock for query execution; they
    grab one consistent (planner, delta) snapshot via ``_snapshot`` (a brief
    lock acquire, so a mid-flush reader can't pair a pre-flush planner with
    a post-flush delta and under/double-count) and then work purely on the
    captured objects. Exercised by tests/test_web.py's concurrent
    ingest+query stress test through the REST server's thread pool."""

    def __init__(self, params: Optional[dict] = None):
        import threading

        from geomesa_tpu import obs as _obs
        from geomesa_tpu.metrics import register_device_gauges
        register_device_gauges()
        _obs.install()
        self._lock = threading.RLock()
        self.params = params or {}
        self.schemas: Dict[str, SimpleFeatureType] = {}
        self.tables: Dict[str, FeatureTable] = {}
        self.planners: Dict[str, QueryPlanner] = {}
        # LSM delta tier: recent appends held as a small host-side run that
        # queries merge in exactly; flushed into the device-indexed main
        # table when it grows past the flush threshold (≙ the Lambda store's
        # hot tier shadowing the cold tier, LambdaDataStore.scala:180)
        self.deltas: Dict[str, Optional[FeatureTable]] = {}
        self._stats: Dict[str, object] = {}
        self._counters: Dict[str, int] = {}
        self._interceptors: Dict[str, list] = {}
        # per-type mutation generation (serve-path cache invalidation): every
        # ingest/flush/age-off/update/delete/schema-change bumps it, so a
        # plan cached against generation g is unreachable once the
        # data it described has changed. Monotonic per NAME — it survives
        # remove_schema so a re-created type can't resurrect stale plans.
        self._generations: Dict[str, int] = {}
        # online build-then-swap reindex bookkeeping: per-type status dicts
        # plus the background worker threads (joinable by tests/shutdown)
        self._reindex_status: Dict[str, dict] = {}
        self._reindex_threads: Dict[str, object] = {}
        # incarnation epoch: salts scheduler cache keys (see _next_epoch)
        self.epoch = _next_epoch()
        self._scheduler = None  # lazy QueryScheduler (serve/scheduler.py)
        # audit trail (≙ AuditWriter): params {"audit": True | "path.jsonl"}
        audit_param = self.params.get("audit")
        if audit_param:
            from geomesa_tpu.index.guards import AuditWriter
            self.audit = AuditWriter(
                audit_param if isinstance(audit_param, str) else None,
                max_bytes=self.params.get("audit.max_bytes"))
        else:
            self.audit = None
        # durability (WAL + snapshots + recovery): params
        # {"durability": "<dir>"} or TpuDataStore.open(dir). Attaching to a
        # dir with an existing layout recovers into this store first.
        self.durability = None
        self.recovery_report = None
        # replication role object (replication/): a LogShipper when this
        # store is a fleet primary, a Follower when it is a read replica,
        # None standalone — /healthz and the fence checks read it
        self.replication = None
        dur_dir = self.params.get("durability")
        if dur_dir:
            from geomesa_tpu.durability.manager import attach as _attach
            _attach(self, dur_dir, params=self.params)

    # -- factory SPI --------------------------------------------------------

    @classmethod
    def can_process(cls, params: dict) -> bool:
        return params.get("backend", "tpu") == "tpu"

    @classmethod
    def create(cls, params: dict) -> "TpuDataStore":
        return cls(params)

    @classmethod
    def open(cls, path: str, params: Optional[dict] = None) -> "TpuDataStore":
        """Open (or create) a durable store at ``path``: crash recovery runs
        when a WAL/snapshot layout exists (newest valid snapshot + WAL
        suffix replay, torn tail truncated), and every subsequent mutation
        is write-ahead logged. ``store.recovery_report`` says what recovery
        did; ``store.durability`` exposes WAL/snapshot state."""
        p = dict(params or {})
        p["durability"] = path
        return cls(p)

    def close(self) -> None:
        """Flush + release durability resources (WAL fsync, background
        syncer), stop the query scheduler, and stop a primary-role log
        shipper (a Follower owns its store, not vice versa — it closes
        itself and then this store). Idempotent."""
        repl = self.replication
        if repl is not None and getattr(repl, "role", "") == "primary":
            repl.close()
        with self._lock:
            sched, self._scheduler = self._scheduler, None
        if sched is not None:
            sched.shutdown()
        if self.durability is not None:
            self.durability.close()

    # -- durability plumbing -------------------------------------------------

    def _wal_json(self, kind: str, meta: dict, rows: int = 0) -> None:
        """Log a metadata mutation record (callers hold the store lock;
        log-then-apply). No-op without durability or during replay."""
        if self.durability is not None:
            self.durability.log_json(kind, meta, rows=rows)

    def _wal_table(self, kind: str, meta: dict, table=None, arrays=None,
                   rows: int = 0) -> None:
        if self.durability is not None:
            self.durability.log_table(kind, meta, table=table, arrays=arrays,
                                      rows=rows)

    def _dur_tick(self) -> None:
        """Post-mutation hook, called AFTER the store lock is released:
        writes an incremental snapshot when thresholds are crossed."""
        if self.durability is not None:
            self.durability.maybe_snapshot()

    # -- schema lifecycle ---------------------------------------------------

    def create_schema(self, sft: Union[SimpleFeatureType, str],
                      spec: Optional[str] = None) -> SimpleFeatureType:
        if isinstance(sft, str):
            sft = SimpleFeatureType.from_spec(sft, spec or "")
        sft.feature_expiry  # validate up front, not on the first write
        with self._lock:
            if sft.name in self.schemas:
                raise ValueError(f"Schema {sft.name} already exists")
            self._wal_json("create_schema",
                           {"type": sft.name, "spec": sft.to_spec()})
            self.schemas[sft.name] = sft
            self.tables[sft.name] = None
        return sft

    def get_schema(self, type_name: str) -> SimpleFeatureType:
        return self.schemas[type_name]

    def get_type_names(self) -> List[str]:
        return list(self.schemas)

    def remove_schema(self, type_name: str) -> None:
        with self._lock:
            self._wal_json("remove_schema", {"type": type_name})
            self._remove_schema_locked(type_name)

    def _remove_schema_locked(self, type_name: str) -> None:
        # _interceptors/_counters included: a re-created type of the same
        # name must not inherit the old type's guards or fid sequence.
        # _generations deliberately excluded (bumped instead): cached
        # plans must not survive a drop/re-create of the same name.
        self._bump_generation(type_name)
        for d in (self.schemas, self.tables, self.planners, self._stats,
                  self.deltas, self._counters, self._interceptors):
            d.pop(type_name, None)

    # -- writes -------------------------------------------------------------

    def get_writer(self, type_name: str) -> FeatureWriter:
        if type_name not in self.schemas:
            raise KeyError(type_name)
        return FeatureWriter(self, type_name)

    def load(self, type_name: str, table: FeatureTable,
             stats_cached: Optional[dict] = None) -> None:
        """Bulk load a prebuilt columnar table (the fast ingest path).
        ``stats_cached`` restores checkpointed sketches instead of
        re-observing (io.checkpoint)."""
        self._append(type_name, table, stats_cached)

    def _append(self, type_name: str, batch: FeatureTable,
                stats_cached: Optional[dict] = None) -> None:
        """Append path with LSM tiering: small batches land in the host-side
        delta run (cost ~ O(batch), not O(table)); the main device index
        rebuilds only on the first load or when the delta crosses the flush
        threshold. Queries merge main + delta exactly (see count/query)."""
        with self._lock:
            self._append_locked(type_name, batch, stats_cached)
        self._dur_tick()

    def _append_locked(self, type_name, batch, stats_cached=None) -> None:
        # WAL first (log-then-apply): the batch as handed in — replay runs
        # it through this same path, so write-path age-off re-applies there.
        # The fid counter rides in the meta so a replica/recovered store
        # continues the primary's fid sequence instead of restarting at 0.
        self._wal_table("append", {"type": type_name, "rows": len(batch),
                                   "counter": self._counters.get(type_name,
                                                                 0)},
                        table=batch, rows=len(batch))
        self._append_apply(type_name, batch, stats_cached)

    def _append_apply(self, type_name, batch, stats_cached=None) -> None:
        from geomesa_tpu.metrics import REGISTRY as _metrics
        _metrics.inc("ingest.features", len(batch))
        # every append changes query results (even a delta-tier landing), so
        # the serving caches must miss from here on
        self._bump_generation(type_name)
        # already-expired incoming rows never land (O(batch) mask; the
        # reference's write-path expiry check)
        batch, _ = self._apply_age_off(type_name, batch)
        current = self.tables.get(type_name)
        if current is None:
            self.tables[type_name] = batch
            self.deltas[type_name] = None
            with _trace.span("ingest.index_build", kind="aggregate"):
                self._rebuild_indexes(type_name, stats_cached)
            return
        delta = self.deltas.get(type_name)
        merged_delta = batch if delta is None else FeatureTable.concat([delta, batch])
        from geomesa_tpu import config
        frac = config.LSM_MAX_FRACTION.get()
        threshold = max(50_000, int(frac * len(current)))
        if stats_cached is not None or len(merged_delta) > threshold:
            # flush-through (large batch, or a checkpoint restore that must
            # land its cached sketches against the merged table)
            _metrics.inc("ingest.flushes")
            self.deltas[type_name] = None
            n_old = len(current)
            merged = FeatureTable.concat([current, merged_delta])
            merged, n_exp = self._apply_age_off(type_name, merged)
            if n_exp:
                # checkpointed sketches describe rows age-off just dropped —
                # re-observe rather than restore an overcounting battery
                stats_cached = None
            with _trace.span("ingest.index_build", kind="aggregate"):
                # age-off drops invalidate the resident sorted run's row
                # identity — only a clean append merges incrementally
                if n_exp or not self._merge_rebuild(type_name, merged, n_old,
                                                    stats_cached):
                    self.tables[type_name] = merged
                    self._rebuild_indexes(type_name, stats_cached)
        else:
            _metrics.inc("ingest.delta_appends")
            # stat sketches stay main-table-only while a delta is pending
            # (GeoMesaStats.update REPLACES the battery — re-observing just
            # the batch would swap whole-table estimates for batch-only
            # ones); the estimator drifts by at most the flush threshold
            # (~2%), and the next flush re-observes everything
            self.deltas[type_name] = merged_delta

    def flush(self, type_name: str) -> None:
        """Merge the delta run into the main device index (≙ the Lambda
        tier's persistence flush). No-op when the delta is empty."""
        with self._lock:
            delta = self.deltas.get(type_name)
            if delta is None:
                return
            with _trace.span("ingest.flush", kind="aggregate",
                             type=type_name):
                self._bump_generation(type_name)
                self.deltas[type_name] = None
                current = self.tables[type_name]
                n_old = len(current)
                merged = FeatureTable.concat([current, delta])
                # dtg age-off rides the flush (≙ compaction-time age-off
                # iterators): rows whose TTL lapsed since ingest drop here
                merged, n_exp = self._apply_age_off(type_name, merged)
                # a pure append merges the sorted delta run into the
                # resident sorted run; age-off drops force a full rebuild
                if n_exp or not self._merge_rebuild(type_name, merged,
                                                    n_old):
                    self.tables[type_name] = merged
                    self._rebuild_indexes(type_name)

    def upsert(self, type_name: str, batch: FeatureTable) -> int:
        """Atomic put-by-fid: remove existing rows whose fids collide with
        the batch, then append it — ONE mutation under ONE lock hold, logged
        as ONE WAL record. Idempotent: re-applying the same batch (a crash
        replay, a retried hot-tier persist) converges to the same state
        instead of losing or double-counting rows. ≙ the Lambda tier's
        hot→cold move, which the reference performs as delete+write against
        the persistent store. Returns rows written."""
        if type_name not in self.schemas:
            raise KeyError(type_name)
        with self._lock, _trace.span("ingest.upsert", kind="aggregate",
                                     type=type_name):
            self._wal_table("upsert", {"type": type_name,
                                       "rows": len(batch),
                                       "counter": self._counters.get(
                                           type_name, 0)},
                            table=batch, rows=len(batch))
            self._upsert_locked(type_name, batch)
        self._dur_tick()
        return len(batch)

    def _upsert_locked(self, type_name: str, batch: FeatureTable) -> None:
        from geomesa_tpu.metrics import REGISTRY as _metrics
        _metrics.inc("ingest.upserts")
        batch_fids = np.asarray(batch.fids, dtype=object)
        # collisions within the host-side delta run purge in place (cheap)
        delta = self.deltas.get(type_name)
        if delta is not None:
            ddup = np.isin(np.asarray(delta.fids, dtype=object), batch_fids)
            if ddup.any():
                keep = np.flatnonzero(~ddup)
                self.deltas[type_name] = delta.take(keep) if len(keep) \
                    else None
        current = self.tables.get(type_name)
        main_dup = None
        if current is not None and len(current):
            main_dup = np.isin(np.asarray(current.fids, dtype=object),
                               batch_fids)
            if not main_dup.any():
                main_dup = None
        if main_dup is None:
            # no main-table collisions: ride the ordinary LSM append path —
            # a small hot-tier persist lands in the delta run and must NOT
            # rebuild the cold device index (tests/test_lsm.py)
            self._append_apply(type_name, batch)
            return
        self._bump_generation(type_name)
        current = current.take(np.flatnonzero(~main_dup))
        delta = self.deltas.get(type_name)
        if delta is not None:
            current = FeatureTable.concat([current, delta])
            self.deltas[type_name] = None
        merged = FeatureTable.concat([current, batch]) \
            if len(current) else batch
        merged, _ = self._apply_age_off(type_name, merged)
        self.tables[type_name] = merged
        self._rebuild_indexes(type_name)

    def _apply_age_off(self, type_name: str, table: Optional[FeatureTable],
                       now_ms: Optional[int] = None):
        """(surviving table, n_expired) under the type's
        ``geomesa.feature.expiry`` TTL; no-op without one."""
        sft = self.schemas[type_name]
        exp = sft.feature_expiry
        if exp is None or table is None or len(table) == 0:
            return table, 0
        import time as _time
        attr, ttl_ms = exp
        now = int(_time.time() * 1000) if now_ms is None else int(now_ms)
        vals = np.asarray(table.columns[attr], dtype=np.int64)
        # null dates (NaT → int64 min) never expire — age-off drops only
        # rows whose date actually lapsed, like the reference iterators
        keep = (vals > now - ttl_ms) | (vals == np.iinfo(np.int64).min)
        n_exp = int(len(keep) - keep.sum())
        if n_exp == 0:
            return table, 0
        from geomesa_tpu.metrics import REGISTRY as _metrics
        _metrics.inc("ingest.aged_off", n_exp)
        return table.take(np.flatnonzero(keep)), n_exp

    def age_off(self, type_name: str, now_ms: Optional[int] = None) -> int:
        """Force an age-off compaction of the main table + delta (≙ running
        the reference's DtgAgeOffIterator at major compaction): drops every
        row whose ``geomesa.feature.expiry`` TTL has lapsed and rebuilds the
        device index if anything dropped. Returns the number removed.
        ``now_ms`` overrides the clock (maintenance jobs, tests)."""
        import time as _time
        # resolve the clock BEFORE logging so the WAL record replays with
        # the exact cutoff this compaction used (deterministic recovery)
        now = int(_time.time() * 1000) if now_ms is None else int(now_ms)
        with self._lock, _trace.span("ingest.age_off", kind="aggregate",
                                     type=type_name):
            self._wal_json("age_off", {"type": type_name, "now_ms": now})
            table = self.tables.get(type_name)
            delta = self.deltas.get(type_name)
            # merge the delta WITHOUT flush(): its age-off pass runs on the
            # real clock and would both ignore now_ms and hide its removals
            # from this method's returned count
            if delta is not None:
                table = FeatureTable.concat([table, delta])
            table2, n = self._apply_age_off(type_name, table, now)
            if n or delta is not None:
                self._bump_generation(type_name)
                self.deltas[type_name] = None
                self.tables[type_name] = table2
                self._rebuild_indexes(type_name)
        self._dur_tick()
        return n

    def _snapshot(self, type_name: str):
        """One consistent (planner, delta) pair. The brief lock acquire is
        the whole reader-side protocol: both refs are captured atomically
        w.r.t. flush/append swaps, then the query runs lock-free on the
        captured (immutable) objects."""
        with self._lock:
            return self._main_planner(type_name), self.deltas.get(type_name)

    def _delta_rows(self, delta: Optional[FeatureTable], f,
                    auths) -> "np.ndarray":
        """Matching row indices WITHIN a snapshotted delta run (host f64
        evaluation — the delta is bounded small, so brute force is exact and
        cheap). Takes the delta table itself, not the type name: readers must
        evaluate the SAME delta object their snapshot captured, never a
        re-read that a concurrent flush could have swapped."""
        import numpy as np

        from geomesa_tpu.filter.evaluate import evaluate as _evaluate
        from geomesa_tpu.filter.parser import parse_ecql

        if delta is None:
            return np.empty(0, dtype=np.int64)
        fir = parse_ecql(f) if isinstance(f, str) else f
        if isinstance(fir, ir.FidFilter):
            fids = set(fir.fids)
            rows = np.array([i for i, fid in enumerate(delta.fids)
                             if fid in fids], dtype=np.int64)
        else:
            rows = np.flatnonzero(_evaluate(fir, delta))
        if auths is not None and delta.visibility is not None and len(rows):
            from geomesa_tpu.security.visibility import allowed_codes
            allowed = allowed_codes(delta.visibility.vocab, auths)
            rows = rows[np.isin(delta.visibility.codes[rows], allowed)]
        return rows

    def _build_planner(self, type_name: str, table: FeatureTable,
                       stats_cached: Optional[dict] = None):
        """Construct a fresh (planner, stats) pair over ``table`` WITHOUT
        touching store state — the pure build half of build-then-swap. Safe
        to run off-lock against a captured table (background reindex); the
        caller installs the result under the lock."""
        from geomesa_tpu.stats.store import GeoMesaStats

        sft = self.schemas[type_name]
        names = sft.configured_indices
        indexes: List[object] = []
        for c in INDEX_CLASSES:
            if names is not None and c.name not in names:
                continue
            if c.supports(sft):
                indexes.append(c(sft, table))
                break  # one primary spatial index (others on demand later)
        from geomesa_tpu.index.attribute import AttributeIndex, indexed_attributes
        for attr in indexed_attributes(sft):
            indexes.append(AttributeIndex(sft, table, attr))
        indexes.append(FullScanIndex(sft, table))
        # fresh battery per rebuild (true build-then-swap): re-observing into
        # the SHARED GeoMesaStats would let a lock-free reader's snapshotted
        # planner see a half-populated sketch battery mid-rebuild
        stats = GeoMesaStats(sft)
        timeout = sft.user_data.get("geomesa.query.timeout")
        planner = QueryPlanner(
            sft, table, indexes, stats=stats,
            interceptors=self._interceptors.setdefault(type_name, []),
            audit=self.audit,
            timeout_ms=float(timeout) if timeout else None)
        stats.planner = planner
        if stats_cached is not None:
            stats.cached = stats_cached  # checkpoint restore
        else:
            stats.update(table)  # ≙ statUpdater flush on write
        return planner, stats

    def _install_planner(self, type_name: str, table: FeatureTable,
                         planner, stats) -> None:
        """Swap a fully-built planner in (callers hold the lock)."""
        self._stats[type_name] = stats
        self.planners[type_name] = planner
        from geomesa_tpu.index import prune as _prune
        from geomesa_tpu.metrics import REGISTRY as _metrics
        _metrics.set_gauge(f"store.rows.{type_name}", len(table))
        _metrics.set_gauge(f"store.index_blocks.{type_name}",
                           -(-len(table) // _prune.BLOCK_SIZE))

    def _rebuild_indexes(self, type_name: str,
                         stats_cached: Optional[dict] = None) -> None:
        table = self.tables[type_name]
        planner, stats = self._build_planner(type_name, table, stats_cached)
        self._install_planner(type_name, table, planner, stats)

    def _merge_rebuild(self, type_name: str, merged: FeatureTable,
                       n_old: int,
                       stats_cached: Optional[dict] = None) -> bool:
        """Incremental flush: merge the freshly-sorted delta run into each
        resident index's already-sorted run (index.merge_from) instead of
        re-sorting the whole table. Returns False when ineligible — caller
        falls back to the full rebuild. Callers hold the lock and have NOT
        yet installed ``merged`` into self.tables."""
        from geomesa_tpu import config
        if not config.MERGE_BUILD.get():
            return False
        n_new = len(merged)
        n_delta = n_new - n_old
        if n_old <= 0 or n_delta <= 0:
            return False
        if n_delta > config.MERGE_MAX_FRACTION.get() * max(1, n_old):
            # big deltas amortize better through a full sort — but a flush
            # shape that breaches EVERY time means the incremental path is
            # dead weight, so the fallback is counted and flight-logged for
            # the doctor's merge_fraction_breach cause
            from geomesa_tpu.metrics import REGISTRY as _m
            _m.inc("ingest.merge_fraction_breaches")
            _m.inc(f"ingest.merge_fraction_breaches.{type_name}")
            from geomesa_tpu.obs.flight import RECORDER as _rec
            _rec.record({"kind": "reindex", "type": type_name,
                         "phase": "merge_fraction_breach",
                         "delta_fraction": round(n_delta / max(1, n_old), 3)})
            return False
        old_planner = self.planners.get(type_name)
        current = self.tables.get(type_name)
        if old_planner is None or current is None or len(current) != n_old:
            return False
        sft = self.schemas[type_name]
        from geomesa_tpu.index.attribute import indexed_attributes
        if indexed_attributes(sft):
            # attribute indexes sort by value, not append order — a suffix
            # delta is not a sorted run for them, so no incremental path
            return False
        old_indexes = getattr(old_planner, "indexes", None) or []
        for old in old_indexes:
            if getattr(type(old), "merge_from", None) is None:
                return False
            if getattr(old, "table", None) is not current:
                return False  # stale planner (shouldn't happen under lock)
        from geomesa_tpu.metrics import REGISTRY as _metrics
        from geomesa_tpu.stats.store import GeoMesaStats
        with _trace.span("ingest.merge_build", kind="aggregate",
                         type=type_name):
            indexes = [type(old).merge_from(old, merged, n_old)
                       for old in old_indexes]
            stats = GeoMesaStats(sft)
            timeout = sft.user_data.get("geomesa.query.timeout")
            planner = QueryPlanner(
                sft, merged, indexes, stats=stats,
                interceptors=self._interceptors.setdefault(type_name, []),
                audit=self.audit,
                timeout_ms=float(timeout) if timeout else None)
            stats.planner = planner
            old_stats = self._stats.get(type_name)
            if stats_cached is not None:
                stats.cached = stats_cached  # checkpoint restore
            elif old_stats is not None and \
                    getattr(old_stats, "cached", None) is not None:
                # carry the pre-flush battery: it under-describes only the
                # delta rows (≤ MERGE_MAX_FRACTION) — the same bounded drift
                # readers already accept while a delta run is pending
                stats.cached = old_stats.cached
            else:
                stats.update(merged)
            self.tables[type_name] = merged
            self._install_planner(type_name, merged, planner, stats)
        _metrics.inc("ingest.merge_builds")
        return True

    # -- online build-then-swap reindex --------------------------------------

    def reindex(self, type_name: str, background: bool = True):
        """Rebuild the type's indexes OFF the serving path and atomically
        swap the new generation in (build-then-swap made explicit — the
        maintenance analogue of the reference's offline reindex jobs).
        Readers keep querying the old planner until the install instant;
        the generation bump invalidates every (epoch, type, generation)-
        keyed serving cache for free. ``background=True`` returns
        immediately with a status dict; the worker thread is joinable via
        ``self._reindex_threads[type_name]``."""
        if type_name not in self.schemas:
            raise KeyError(type_name)
        if not background:
            self._reindex_run(type_name)
            return self.reindex_status(type_name)
        import threading
        with self._lock:
            t = self._reindex_threads.get(type_name)
            if t is not None and t.is_alive():
                return self.reindex_status(type_name)  # already running
            self._reindex_status[type_name] = {"state": "running",
                                               "attempts": 0}
            t = threading.Thread(target=self._reindex_run,
                                 args=(type_name,),
                                 name=f"reindex-{type_name}", daemon=True)
            self._reindex_threads[type_name] = t
        t.start()
        return self.reindex_status(type_name)

    def reindex_status(self, type_name: str) -> dict:
        with self._lock:
            st = dict(self._reindex_status.get(type_name,
                                               {"state": "idle"}))
            t = self._reindex_threads.get(type_name)
            st["running"] = bool(t is not None and t.is_alive())
            return st

    def _reindex_run(self, type_name: str, max_retries: int = 3) -> None:
        import time as _time

        from geomesa_tpu import config
        from geomesa_tpu.metrics import REGISTRY as _metrics
        from geomesa_tpu.obs.flight import RECORDER as _flight
        from geomesa_tpu.obs.profiling import PROGRESS as _progress
        throttle = max(0.0, config.REINDEX_THROTTLE_MS.get()) / 1000.0
        status = {"state": "running", "attempts": 0}
        with self._lock:
            self._reindex_status[type_name] = status
        t0 = _time.perf_counter()
        try:
            for attempt in range(1, max_retries + 1):
                status["attempts"] = attempt
                # land any pending delta first so the rebuilt generation
                # covers every row readers can currently see
                self.flush(type_name)
                with self._lock:
                    base_table = self.tables.get(type_name)
                if base_table is None:
                    status["state"] = "failed"
                    status["error"] = "no table"
                    return
                _flight.record({"kind": "reindex", "type": type_name,
                                "phase": "build_started",
                                "rows": len(base_table),
                                "attempt": attempt})
                if throttle:
                    _time.sleep(throttle)  # yield to serving traffic
                # the expensive part runs entirely OFF-lock against the
                # captured immutable table — queries proceed unimpeded
                planner, stats = self._build_planner(type_name, base_table)
                if throttle:
                    _time.sleep(throttle)
                with self._lock:
                    if self.tables.get(type_name) is not base_table:
                        # a concurrent flush/upsert swapped the table while
                        # we built — this generation describes stale rows;
                        # discard and retry against the new table
                        _metrics.inc("reindex.aborts")
                        _metrics.inc(f"reindex.aborts.{type_name}")
                        _flight.record({"kind": "reindex",
                                        "type": type_name,
                                        "phase": "aborted",
                                        "attempt": attempt})
                        continue
                    with _progress.phase("swap_install",
                                         rows=len(base_table),
                                         op="reindex",
                                         type_name=type_name):
                        self._install_planner(type_name, base_table,
                                              planner, stats)
                        self._bump_generation(type_name)
                    gen = self._generations.get(type_name, 0)
                status["state"] = "installed"
                status["generation"] = gen
                status["rows"] = len(base_table)
                status["seconds"] = round(_time.perf_counter() - t0, 3)
                _metrics.inc("reindex.installs")
                _flight.record({"kind": "reindex", "type": type_name,
                                "phase": "installed", "generation": gen,
                                "rows": len(base_table),
                                "attempt": attempt,
                                "seconds": status["seconds"]})
                # ship the rebuilt generation fleet-wide: a fresh snapshot
                # makes follower catch-up land it byte-identically
                if self.durability is not None and \
                        config.REINDEX_SNAPSHOT.get():
                    try:
                        self.durability.snapshot()
                    except Exception:  # noqa: BLE001 - snapshot is advisory
                        pass
                return
            status["state"] = "aborted"
            status["seconds"] = round(_time.perf_counter() - t0, 3)
        except Exception as e:  # noqa: BLE001 - surfaced via status
            status["state"] = "failed"
            status["error"] = f"{type(e).__name__}: {e}"
            status["seconds"] = round(_time.perf_counter() - t0, 3)
            _metrics.inc("reindex.failures")
            _metrics.inc(f"reindex.failures.{type_name}")
            _flight.record({"kind": "reindex", "type": type_name,
                            "phase": "failed", "error": status["error"]})

    def _fid_counter(self, type_name: str) -> int:
        with self._lock:  # read-modify-write: two writers must never share a fid
            c = self._counters.get(type_name, 0)
            self._counters[type_name] = c + 1
            return c

    # -- serve-path cache generation ----------------------------------------

    def _bump_generation(self, type_name: str) -> None:
        """Advance the type's mutation generation (callers hold the lock)."""
        self._generations[type_name] = self._generations.get(type_name, 0) + 1

    def generation(self, type_name: str) -> int:
        """Current mutation generation — the serving caches' invalidation
        token (≙ the reference's metadata/stats cache expiry, made exact)."""
        with self._lock:
            return self._generations.get(type_name, 0)

    def _sched_snapshot(self, type_name: str):
        """(planner, delta, generation, epoch) captured atomically for the
        query scheduler — the scheduler-side twin of ``_snapshot``. The
        epoch salts cache keys so plans cached against a prior store
        incarnation (same name, same restored generation) never alias."""
        with self._lock:
            return (self._main_planner(type_name),
                    self.deltas.get(type_name),
                    self._generations.get(type_name, 0),
                    self.epoch)

    def scheduler(self):
        """The store's micro-batching query scheduler (lazily started; one
        per store). Concurrent counts submitted here coalesce into fused
        batched device dispatches — see serve/scheduler.py. A scheduler
        whose worker threads died (fault injection, a bug) is replaced
        with a fresh one on next access — outstanding futures were already
        failed with a structured error by the crash handler."""
        with self._lock:
            if self._scheduler is not None and not self._scheduler.healthy():
                from geomesa_tpu.metrics import REGISTRY as _metrics
                _metrics.inc("scheduler.restarts")
                self._scheduler.shutdown(timeout=0.1)
                self._scheduler = None
            if self._scheduler is None:
                from geomesa_tpu.serve.scheduler import (QueryScheduler,
                                                         StoreBinding)
                self._scheduler = QueryScheduler(StoreBinding(self))
            return self._scheduler

    def count_many(self, type_name: str, filters,
                   auths: Optional[list] = None,
                   deadline_ms: Optional[float] = None,
                   priority: str = "interactive",
                   tenant: Optional[str] = None) -> List[int]:
        """Counts for many filters through the scheduler: compatible queries
        fuse into single batched device dispatches; repeated/parameterized
        filters hit the plan cache. Order-preserving. ``deadline_ms``
        bounds every count in the set; ``priority`` classes the work for
        admission control ('interactive' | 'batch'); ``tenant`` labels it
        for workload analytics/metering (auths-derived when omitted)."""
        return self.scheduler().count_many(type_name, filters, auths=auths,
                                           deadline_ms=deadline_ms,
                                           priority=priority, tenant=tenant)

    def count_future(self, type_name: str, f: Union[str, ir.Filter] = "INCLUDE",
                     auths: Optional[list] = None,
                     deadline_ms: Optional[float] = None,
                     priority: str = "interactive"):
        """Async count: submit to the scheduler and return the Request
        handle (``.result()`` blocks; ``.future`` is a concurrent.futures
        Future) — the serving-path analogue of PreparedQuery.count_async."""
        return self.scheduler().submit(type_name, f, auths=auths,
                                       deadline_ms=deadline_ms,
                                       priority=priority)

    def count_coalesced(self, type_name: str,
                        f: Union[str, ir.Filter] = "INCLUDE",
                        auths: Optional[list] = None,
                        deadline_ms: Optional[float] = None,
                        priority: str = "interactive",
                        tenant: Optional[str] = None) -> int:
        """Count via the scheduler when serving coalescing is enabled
        (GEOMESA_TPU_SCHEDULER / params {'scheduler': False}); otherwise the
        direct per-request path. The web /count route calls this, so
        concurrent HTTP requests share device dispatches — and propagate
        their deadline/priority/tenant envelope into the scheduler."""
        from geomesa_tpu import config
        if not config.SCHED_ENABLED.get() \
                or self.params.get("scheduler") is False:
            return self.count(type_name, f, auths=auths,
                              deadline_ms=deadline_ms)
        return self.scheduler().count(type_name, f, auths=auths,
                                      deadline_ms=deadline_ms,
                                      priority=priority, tenant=tenant)

    # -- queries ------------------------------------------------------------

    def planner(self, type_name: str) -> QueryPlanner:
        """The type's QueryPlanner over a fully-merged view: any pending
        delta run flushes first, so external consumers (processes, exports,
        aggregation helpers) always see exact state. Datastore-level
        count/query merge the delta inline instead and never force a flush."""
        with self._lock:
            self.flush(type_name)
            return self._main_planner(type_name)

    def _main_planner(self, type_name: str) -> QueryPlanner:
        if type_name not in self.planners:
            if self.tables.get(type_name) is None:
                raise ValueError(f"No data written to {type_name}")
        return self.planners[type_name]

    def cluster_scan(self, type_name: str):
        """ClusterScan over the type's primary index: on an active
        multi-process cluster the (locally-held, key-range-partitioned)
        index columns assemble into process-spanning global arrays —
        counts/density psum to the exact global answer, selects merge in
        rank order. Single-process it is an ordinary DistributedScan
        over the local mesh. The shard layout registers on /cluster."""
        from geomesa_tpu.cluster.exec import ClusterScan
        from geomesa_tpu.cluster.runtime import runtime
        from geomesa_tpu.cluster.table import ClusterShardedTable
        rt = runtime()
        idx = self.planner(type_name).indexes[0]
        host_cols = {k: np.asarray(v)
                     for k, v in idx.device.columns.items()}
        st = ClusterShardedTable.from_local_columns(rt, host_cols)
        rt.register_table(type_name, st.layout.summary())
        return ClusterScan(st)

    def query(self, type_name: str, f: Union[str, ir.Filter] = "INCLUDE",
              hints: Optional[dict] = None, auths: Optional[list] = None,
              deadline_ms: Optional[float] = None):
        """Run a query; ``hints`` switch the result form exactly like the
        reference's QueryHints (conf/QueryHints.scala — DENSITY_*/BIN_*/
        STATS_*/SAMPLING keys):

          hints["density"] = {"bbox": (..), "width": W, "height": H,
                              "weight": attr?}        → DensityGrid
          hints["bin"]     = {"track": attr, "label": attr?, "sort": bool}
                                                       → packed BIN records
          hints["stats"]   = stat spec string          → Stat sketch
          hints["sample"]  = n | {"n": n, "by": attr?} → sampled QueryResult

        Result-shaping hints compose on the plain path (≙ sort/maxFeatures/
        transform/reprojection of QueryPlanner.runQuery:56-94):

          hints["sort"]      = attr | "-attr" | [specs]   (stable, major-first)
          hints["limit"]     = n                          (applied pre-hydration)
          hints["transform"] = ["attr", "out=expr(...)"]  (projected type)
          hints["crs"]       = "EPSG:3857"                (output reprojection)
        """
        from geomesa_tpu.serve.resilience import deadline as _rdl
        with _trace.trace("query.features", type=type_name, filter=str(f)), \
                _rdl.scope(deadline_ms):
            return self._query_impl(type_name, f, hints, auths)

    def _query_impl(self, type_name, f, hints, auths):
        if not hints:
            planner, delta = self._snapshot(type_name)
            res = planner.query(f, auths=auths)
            if delta is None:
                return res
            drows = self._delta_rows(delta, f, auths)
            # stacked row space: delta rows ride above the main table
            # (QueryResult.indices document this via the plan's explain;
            # res.table holds the fully-hydrated rows either way)
            n_main = len(planner.table)
            rows = np.concatenate([res.indices, drows + n_main])
            sub = FeatureTable.concat([res.table, delta.take(drows)]) \
                if len(drows) else res.table
            out = QueryResult(rows, sub, res.plan)
            if res.plan is not None:
                res.plan.explain["stacked_rows_base"] = n_main
            return out
        shaping_keys = {"sort", "limit", "transform", "crs"}
        if shaping_keys.issuperset(hints):
            # shaping merges any pending delta INLINE (sort/limit/transform
            # are host-side anyway) — no flush, the LSM tier stays warm
            from geomesa_tpu.index.shaping import (reproject_table,
                                                   shape_local,
                                                   transform_table)
            planner, delta = self._snapshot(type_name)
            plan = planner.plan(f)
            rows = planner.select_indices(f, plan=plan, auths=auths)
            if delta is None:
                from geomesa_tpu.index.shaping import shape_rows
                rows = shape_rows(planner.table, rows, hints.get("sort"),
                                  hints.get("limit"))
                sub = planner.table.take(rows)
            else:
                drows = self._delta_rows(delta, f, auths)
                sub = FeatureTable.concat(
                    [planner.table.take(rows), delta.take(drows)])
                rows = np.concatenate(
                    [rows, drows + len(planner.table)])
                local = shape_local(sub, hints.get("sort"),
                                    hints.get("limit"))
                rows = rows[local]
                sub = sub.take(local)
            if "transform" in hints:
                sub = transform_table(sub, hints["transform"])
            if "crs" in hints:
                sub = reproject_table(sub, hints["crs"])
            return QueryResult(rows, sub, plan)
        # auths compose with every aggregation hint: the visibility-code
        # mask folds into the device scan (planner._apply_auths) exactly as
        # VisibilityFilter rides the reference's server-side scans
        if "density" in hints:
            # density merges any pending delta INCREMENTALLY (a host grid
            # for the delta rows adds onto the device grid) — a dashboard
            # repaint must never trigger an O(table) flush
            from geomesa_tpu.aggregates.density import density, host_grid
            planner, delta = self._snapshot(type_name)
            d = dict(hints["density"])
            grid = density(planner, f, d["bbox"], d.get("width", 256),
                           d.get("height", 256), d.get("weight"),
                           auths=auths)
            if delta is not None:
                drows = self._delta_rows(delta, f, auths)
                grid.weights = grid.weights + host_grid(
                    delta, drows, d["bbox"], grid.width, grid.height,
                    d.get("weight"))
            return grid
        planner = self.planner(type_name)  # other aggregations see merged state
        if "bin" in hints:
            from geomesa_tpu.aggregates.bin import bin_records
            b = dict(hints["bin"])
            return bin_records(planner, f, b["track"], b.get("label"),
                               b.get("sort", False), auths=auths)
        if "stats" in hints:
            return self.stats(type_name).run_stat(hints["stats"], f,
                                                  auths=auths)
        if "sample" in hints:
            from geomesa_tpu.aggregates.sampling import sample_rows
            s = hints["sample"]
            s = {"n": s} if isinstance(s, int) else dict(s)
            plan = planner.plan(f)
            rows = sample_rows(planner, f, s["n"], s.get("by"), plan=plan,
                               auths=auths)
            return QueryResult(rows, planner.table.take(rows), plan)
        raise ValueError(f"Unknown hints: {sorted(hints)}")

    def count(self, type_name: str, f: Union[str, ir.Filter] = "INCLUDE",
              auths: Optional[list] = None,
              deadline_ms: Optional[float] = None) -> int:
        from geomesa_tpu.metrics import REGISTRY as _metrics
        from geomesa_tpu.serve.resilience import deadline as _rdl
        _metrics.inc("query.counts")
        with _trace.trace("query.count", type=type_name, filter=str(f)), \
                _rdl.scope(deadline_ms):
            return self._count_impl(type_name, f, auths)

    def _count_impl(self, type_name, f, auths) -> int:
        planner, delta = self._snapshot(type_name)
        c = planner.count(f, auths=auths)
        if delta is not None:
            c += len(self._delta_rows(delta, f, auths))
        return c

    JOIN_OPS = ("st_intersects", "st_contains")

    def join(self, type_name: str, with_type: str,
             op: str = "st_intersects",
             f: Union[str, ir.Filter] = "INCLUDE",
             stats: Union[str, Sequence[str]] = "count",
             auths: Optional[list] = None,
             deadline_ms: Optional[float] = None) -> dict:
        """Spatial join of the point type ``type_name`` with the polygons of
        ``with_type``, aggregated by polygon (≙ the Spark SQL join planned by
        geomesa-spark-sql's SpatialJoinStrategy, and the tutorials'
        ShallowJoin):

            SELECT c, count(*), sum(g.<attr>)... FROM <type_name> g
            JOIN <with_type> c ON <op>(c.geom, g.geom) WHERE <f on g>
            GROUP BY c

        ``op``: ``st_intersects`` (a point on a polygon's boundary counts)
        or ``st_contains`` (it does not). ``stats``: ``count`` and
        ``sum(<Integer attribute>)`` terms, a list or comma-separated.
        Exact: against the f64 coordinates of both types, writes to either
        included (a pending polygon delta is flushed first: the polygons'
        segments are read from the device). Returns ``io.export.join_answer``'s
        dict: a row a polygon in the polygon table's order."""
        from geomesa_tpu.filter import geom_batch as _gb
        from geomesa_tpu.filter.parser import parse_ecql
        from geomesa_tpu.io.export import join_answer
        from geomesa_tpu.metrics import REGISTRY as _metrics
        from geomesa_tpu.serve.resilience import deadline as _rdl
        if op not in self.JOIN_OPS:
            raise ValueError(f"join op {op!r}: one of {self.JOIN_OPS}")
        sft, other = self.schemas[type_name], self.schemas[with_type]
        if sft.geometry_attribute is None \
                or sft.geometry_attribute.type_name != "Point":
            raise ValueError(f"join: {type_name!r} is not a point type")
        if other.geometry_attribute is None \
                or other.geometry_attribute.type_name not in (
                    "Polygon", "MultiPolygon"):
            raise ValueError(f"join: {with_type!r} is not a polygon type")
        terms = [t.strip() for t in (stats.split(",") if isinstance(
            stats, str) else stats) if t.strip()]
        attrs = []
        for t in terms:
            m = re.fullmatch(r"sum\((\w+)\)", t)
            if m is None and t != "count":
                raise ValueError(f"join stat {t!r}: count or sum(<attr>)")
            if m is not None:
                a = sft.attribute(m.group(1))
                if a.type_name != "Integer":
                    raise ValueError(
                        f"join: sum({a.name}) needs an Integer attribute")
                if a.name not in attrs:
                    attrs.append(a.name)
        _metrics.inc("query.joins")
        with _trace.trace("query.join", type=type_name, other=with_type,
                          filter=str(f)), _rdl.scope(deadline_ms):
            fir = parse_ecql(f) if isinstance(f, str) else f
            with self._lock:
                # one consistent view of both sides
                if self.tables.get(with_type) is None:
                    return {"op": op, "polygons": 0, "rows": []}
                polygons = self.planner(with_type)
                if self.tables.get(type_name) is None:
                    points = delta = None
                else:
                    points, delta = self._snapshot(type_name)
            boundary = op == "st_intersects"
            n = len(polygons.table)
            counts = np.zeros(n, dtype=np.int64)
            sums = np.zeros((len(attrs), n), dtype=np.int64)
            if points is not None:
                counts, sums = points.join_polygons(
                    fir, polygons, boundary, tuple(attrs), auths=auths)
            if delta is not None:
                rows = self._delta_rows(delta, fir, auths)
                x, y = delta.geometry().point_xy()
                i, polys = _gb.points_in_polygons(
                    x[rows], y[rows], polygons.table.geometry(), boundary)
                tally_join(counts, sums, polys, [
                    np.asarray(delta.columns[a])[rows[i]] for a in attrs])
            _metrics.inc("join.pairs_matched", int(counts.sum()))
            with _trace.span("serialize"):
                return join_answer(op, polygons.table, counts,
                                   dict(zip(attrs, sums)))

    def explain(self, type_name: str, f: Union[str, ir.Filter],
                analyze: bool = False, auths: Optional[list] = None) -> dict:
        planner, delta = self._snapshot(type_name)
        out = planner.explain(f, analyze=analyze, auths=auths)
        if delta is not None:
            out["delta_rows"] = len(delta)  # unflushed LSM run merged inline
            if analyze and "analyze" in out:
                # store-level analyze must match store-level count: the
                # planner executed the main table only, the delta rows
                # merge here exactly like _count_impl does
                d = int(len(self._delta_rows(delta, f, auths)))
                out["analyze"]["rows_matched"] += d
                out["analyze"]["rows_scanned"] += len(delta)
                out["analyze"]["delta_rows_matched"] = d
        if analyze and "analyze" in out:
            # overlay the LIVE scheduler's cache provenance: would this
            # filter be served from the plan cache right now? (peek only —
            # an explain must not skew serving hit rates)
            sched = self._scheduler
            if sched is not None and sched.healthy():
                from geomesa_tpu.filter.parser import parse_ecql as _pe
                f_ir = _pe(f) if isinstance(f, str) else f
                auths_key = None if auths is None \
                    else tuple(sorted(str(a) for a in auths))
                pkey = (self.epoch, type_name, self.generation(type_name),
                        repr(f_ir), auths_key)
                out["analyze"]["provenance"]["plan_cache"] = \
                    "hit" if sched.plans.peek(pkey) else "miss"
                # same key shape as the plan cache: would a scheduled
                # count be answered from the hot-result cache right now?
                out["analyze"]["provenance"]["result_cache"] = \
                    "hit" if sched.results.peek(pkey) else "miss"
        return out

    def stats(self, type_name: str):
        """Per-type stats API (≙ GeoMesaDataStore.stats)."""
        self.planner(type_name)  # materialize
        return self._stats[type_name]

    def add_interceptor(self, type_name: str, interceptor) -> None:
        """Attach a query interceptor/guard (≙ the geomesa.query.interceptors
        SPI registration)."""
        self._interceptors.setdefault(type_name, []).append(interceptor)

    # -- deletes ------------------------------------------------------------

    def update_features(self, type_name: str, f: Union[str, ir.Filter],
                        updates: Dict[str, object]) -> int:
        """Modify attributes of matching features in place (≙ the reference's
        modify writer, GeoMesaFeatureWriter.scala:152-179: read matching
        features, set attributes, rewrite index rows). Columnar form: patch
        the columns at the matching rows, rebuild indexes (bulk-modify
        discipline — key-bearing attributes change index keys anyway).

        ``updates``: attr → scalar, array (len == matches), or callable
        receiving the matching sub-table and returning values.

        Build-then-swap: patched columns land in a NEW FeatureTable that
        replaces the shared one only at the end — a concurrent reader's
        snapshot keeps seeing the consistent pre-update table, never a mix
        of patched and unpatched columns."""
        with self._lock:
            planner = self.planner(type_name)  # flushes any delta first
            rows = planner.select_indices(f)
            if len(rows) == 0:
                return 0
            table = planner.table
            cols: Dict[str, object] = dict(table.columns)
            sub = None
            # WAL record: the RESOLVED mutation (fids + final values, with
            # callables already evaluated) — replay needs no closures and
            # no re-planning of the original filter
            wal_meta = {"type": type_name,
                        "fids": [str(x) for x in table.fids_at(rows)],
                        "scalars": {}, "geoms": {}, "string_lists": {}}
            wal_arrays: Dict[str, object] = {}
            for name, val in updates.items():
                attr = self.schemas[type_name].attribute(name)
                if callable(val):
                    sub = sub if sub is not None else table.take(rows)
                    val = val(sub)
                col = table.columns[name]
                if isinstance(col, GeometryArray):
                    new_geoms = val if isinstance(val, GeometryArray) \
                        else GeometryArray.from_rows(
                            [val] * len(rows) if isinstance(val, str)
                            else list(val))
                    wal_meta["geoms"][name] = [new_geoms.wkt(i)
                                               for i in range(len(rows))]
                    keep = np.ones(len(table), dtype=bool)
                    keep[rows] = False
                    order = np.concatenate([np.flatnonzero(keep), rows])
                    inv = np.empty(len(table), dtype=np.int64)
                    inv[order] = np.arange(len(table))
                    merged = GeometryArray.concat(
                        [col.take(np.flatnonzero(keep)), new_geoms])
                    cols[name] = merged.take(inv)
                elif isinstance(col, StringColumn):
                    # vectorized decode→patch→re-encode (never a per-row
                    # Python loop over the full column)
                    values = np.asarray(col.vocab, dtype=object)[col.codes]
                    values[rows] = val if isinstance(val, str) \
                        else np.asarray([str(v) for v in val], dtype=object)
                    cols[name] = StringColumn.encode(values)
                    if isinstance(val, str):
                        wal_meta["scalars"][name] = val
                    else:
                        wal_meta["string_lists"][name] = [str(v) for v in val]
                else:
                    # copy-on-write: loaded tables may alias caller arrays
                    arr = np.array(col, copy=True)
                    if attr.type_name == "Date":
                        v = np.asarray(val)
                        if v.dtype.kind in "MUS":
                            val = v.astype("datetime64[ms]").astype(np.int64)
                    arr[rows] = val
                    cols[name] = arr
                    if np.ndim(val) == 0:
                        wal_meta["scalars"][name] = val
                    else:
                        wal_arrays[name] = np.asarray(val)
            self._wal_table("update", wal_meta, arrays=wal_arrays,
                            rows=len(rows))
            self._bump_generation(type_name)
            self.tables[type_name] = FeatureTable(
                table.sft, table._fids, cols, table.visibility,
                _n=len(table))
            self._rebuild_indexes(type_name)
            n_updated = int(len(rows))
        self._dur_tick()
        return n_updated

    def update_schema(self, type_name: str, add_attributes: str = "",
                      new_name: Optional[str] = None) -> SimpleFeatureType:
        """Schema evolution (≙ MetadataBackedDataStore.updateSchema:227):
        append new attributes (spec-string syntax; existing rows take the
        type's zero/empty value) and/or rename the type."""
        with self._lock:
            out = self._update_schema_locked(type_name, add_attributes,
                                             new_name)
        self._dur_tick()
        return out

    def _update_schema_locked(self, type_name, add_attributes, new_name):
        self._wal_json("update_schema", {"type": type_name,
                                         "add": add_attributes,
                                         "new_name": new_name})
        sft = self.schemas[type_name]
        spec = sft.to_spec()
        if add_attributes:
            body = spec.split(";")[0]
            user = spec[len(body):]
            spec = body + "," + add_attributes + user
        out = SimpleFeatureType.from_spec(new_name or type_name, spec)
        old_names = {a.name for a in sft.attributes}
        for attr in out.attributes:
            if attr.is_geometry and attr.name not in old_names:
                raise ValueError("Cannot add a geometry attribute")
        table = self.tables.get(type_name)
        if table is not None:
            self.flush(type_name)
            table = self.tables[type_name]
            n = len(table)
            cols: Dict[str, object] = dict(table.columns)
            for attr in out.attributes:
                if attr.name in cols:
                    continue
                if attr.type_name == "String":
                    cols[attr.name] = StringColumn(
                        np.zeros(n, np.int32), [""])
                else:
                    cols[attr.name] = np.zeros(n, dtype=attr.binding)
            new_table = FeatureTable(out, table._fids, cols,
                                     table.visibility, _n=n)
        final = new_name or type_name
        if new_name is not None and new_name != type_name:
            if new_name in self.schemas:
                raise ValueError(f"Schema {new_name} already exists")
            # locked variant: the update_schema record above already covers
            # the rename — a nested remove_schema record would double-log
            self._remove_schema_locked(type_name)
        self._bump_generation(final)
        self.schemas[final] = out
        # the stat battery is built against the OLD attribute set — drop it
        # so the rebuild re-observes with the evolved schema
        self._stats.pop(final, None)
        if table is not None:
            self.tables[final] = new_table
            self.deltas[final] = None
            self._rebuild_indexes(final)
        else:
            self.tables[final] = None
        return out

    def remove_features(self, type_name: str, f: Union[str, ir.Filter]) -> int:
        """Delete matching features; returns the number removed (≙ GeoTools
        removeFeatures / the age-off iterators). Rebuilds indexes over the
        survivors — bulk deletion, matching the columnar build discipline."""
        with self._lock:
            planner = self.planner(type_name)
            rows = planner.select_indices(f)
            if len(rows) == 0:
                return 0
            # log the resolved fid set, not the filter: replay removes
            # exactly these rows regardless of later index/stats drift
            self._wal_json(
                "remove",
                {"type": type_name,
                 "fids": [str(x) for x in planner.table.fids_at(rows)]},
                rows=len(rows))
            keep = np.ones(len(planner.table), dtype=bool)
            keep[rows] = False
            self._bump_generation(type_name)
            self.tables[type_name] = planner.table.take(np.nonzero(keep)[0])
            self._rebuild_indexes(type_name)
            n_removed = int(len(rows))
        self._dur_tick()
        return n_removed


class DataStoreFinder:
    """Registry of datastore factories, keyed by params (SPI-equivalent,
    ≙ META-INF/services DataStoreFactorySpi discovery)."""

    _factories: List[type] = [TpuDataStore]

    @classmethod
    def register(cls, factory: type) -> None:
        if factory not in cls._factories:
            cls._factories.append(factory)

    @classmethod
    def get_data_store(cls, **params):
        for factory in cls._factories:
            if factory.can_process(params):
                return factory.create(params)
        raise ValueError(f"No datastore factory for params {sorted(params)}")
