"""REST / GeoJSON API over a TpuDataStore.

≙ the reference's web surface: the Scalatra data servlets + stats endpoint
(geomesa-web, /root/reference/geomesa-web/geomesa-web-stats/.../
GeoMesaStatsEndpoint.scala) and the pure-JSON API of geomesa-geojson
(geojson-api/.../GeoJsonGtIndex.scala). Stdlib http.server — no framework
dependency; the handler core (`GeoJsonApi.handle`) is transport-agnostic so
it can mount under any WSGI/ASGI shim.

Resilience envelope (serve/resilience/): every request may carry
``?deadline_ms=``/``X-Deadline-Ms`` (default/cap from
GEOMESA_TPU_DEADLINE_*) and ``?priority=``/``X-Priority``
(interactive | batch). Errors come back as a structured JSON envelope
``{"error": ..., "kind": ...}`` with a correct status: deadline-exceeded →
504, admission shed → 429 (+ Retry-After), breaker open → 503
(+ Retry-After), guard veto / bad input → 400, unexpected → 500 — and a
handler thread can no longer die (resetting the client connection) on an
exception anywhere in routing. Degraded counts are flagged:
``{"count": n, "approximate": true, "reason": ...}``.

Routes:
  GET  /types                          → type names
  GET  /types/{t}                      → schema + row count
  GET  /types/{t}/features?cql=&limit=&sort=&crs=   → GeoJSON FeatureCollection
  GET  /types/{t}/features?cql=&select=st_centroid(geom) AS c,val
                                       → projected columns (geometry terms
                                         as WKT, st_* scalars as floats)
  GET  /types/{t}/count?cql=           → {"count": n}  (concurrent requests
                                         coalesce through the micro-batching
                                         scheduler, serve/scheduler.py)
  GET  /types/{t}/join?with={p}&op=st_intersects|st_contains&cql=
       &stats=count,sum(attr),...      → the points of {t} that pass cql,
                                         grouped by the polygons of type {p}
                                         that hold them: {"op", "polygons",
                                         "rows": [{"fid", "name", "count",
                                         "sum": {attr: n}}, ...]}, a row a
                                         polygon in {p}'s order, exact; one
                                         grouped device launch a join
                                         (datastore.join)
  GET  /types/{t}/explain?cql=&analyze=1 → query plan JSON (+ dry-run trace
                                         tree; analyze=1 EXECUTES the plan
                                         and annotates spans with device ms
                                         and cache provenance)
  GET  /types/{t}/stats?stat=<dsl>     → stat sketch JSON
  POST /types/{t}/features             → ingest a GeoJSON FeatureCollection
  POST /types/{t}/reindex              → background build-then-swap reindex
                                         (GET polls its status)
  GET  /metrics                        → metrics snapshot (JSON)
  GET  /metrics?format=prometheus      → Prometheus text exposition (native
                                         _bucket lines carry exemplar trace
                                         ids where a retained trace exists)
  GET  /traces?limit=N                 → recent query traces, newest first
                                         (every span with ``start_ms`` from
                                         its root; a scheduled count's
                                         ``scan`` names its ``batch_id``)
  GET  /traces?retained=1              → the tail-sampled ring (errors, slow
                                         outliers, sampled rest)
  GET  /events?slow_ms=&error=1&kind=&type=&limit=
                                       → flight-recorder wide events (one
                                         per query/count/batch), filtered.
                                         ``device_ms`` is HOST time blocked
                                         until the answer was read back,
                                         never time on the device; a
                                         ``kind=batch`` event holds the
                                         dispatch cycle: ``stages`` (name →
                                         [start epoch ms, ms]),
                                         ``launch_ms``, ``ready_ms``,
                                         ``plan_loop_cpu_ms``, ``first_call``
  GET  /slo                            → SLO burn-rate evaluation (5m/30m/
                                         1h/6h windows, page/ticket state)
  GET  /alerts                         → fleet-doctor detector firings
                                         (evaluated on read)
  GET  /incidents?active=1             → doctor incidents with correlated
                                         timelines + resolution records
  GET  /progress                       → live + recent long-running phases
                                         (index-build encode/upload/sort
                                         with row throughput)
  GET  /scheduler                      → scheduler state (queue depth, batch
                                         histogram, cache hit rates, and
                                         ``slow_cycles``: the last 8 dispatch
                                         cycles that took over 1 s, whole)
  GET  /durability                     → WAL/snapshot status (policy, seq,
                                         unsynced bytes, last-snapshot age)
  GET  /replication                    → fleet role + fencing epoch +
                                         follower acked/lag state
  POST /replication/drain?off=         → admission drain (rolling restart /
                                         pre-failover quiesce)
  POST /replication/promote?port=      → promote this replica to primary
                                         under a fresh fencing epoch
  GET  /healthz                        → liveness + backend, device kind and
                                         count + durability,
                                         recovery/replay, replication and
                                         cluster-shard state
  GET  /cluster                        → partition plane: process count,
                                         per-process rows, Morton key-range
                                         ownership, mesh topology, psum
                                         round counters
  GET  /cluster/balance                → shard balance observatory: per-shard
                                         load shares (hot cells x key-range
                                         ownership), imbalance score,
                                         projected split points
  GET  /fleet/balance                  → the same ledger over fleet-merged
                                         shardwatch + workload states
  GET  /config                         → system-property listing

Mutating routes on a read-only replica (or a fenced ex-primary) return 403
with ``{"kind": "fenced"}``.
"""

from __future__ import annotations

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple
from urllib.parse import parse_qs, urlparse

import numpy as np

from geomesa_tpu import trace as _trace
from geomesa_tpu.metrics import REGISTRY as _metrics
from geomesa_tpu.obs import hostcpu as _hostcpu


class GeoJsonApi:
    """Transport-agnostic request handler core. ``store`` may be a
    TpuDataStore OR a replication Follower — a replica node serves the
    same read API over whatever store the follower currently holds (it
    swaps stores across a snapshot catch-up)."""

    def __init__(self, store):
        self._target = store

    @property
    def store(self):
        return getattr(self._target, "store", self._target)

    def _node_meta(self) -> dict:
        """This node's fleet identity: stable node id + live role (the
        replication role when one is active, the process-stamped role
        otherwise) — the attribution /healthz, /metrics?format=state and
        federated scrapes carry."""
        from geomesa_tpu import trace as _t
        repl = getattr(self.store, "replication", None)
        role = _t.node_role()
        if repl is not None:
            try:
                role = repl.stats().get("role", role)
            except Exception:
                pass
        return {"id": _t.node_id(), "role": role}

    @staticmethod
    def _request_deadline(query: dict, headers) -> Optional[object]:
        """Per-request Deadline from ?deadline_ms= / X-Deadline-Ms, falling
        back to the configured default, capped at the configured max.
        None when unconstrained."""
        from geomesa_tpu import config
        from geomesa_tpu.serve.resilience.deadline import Deadline
        raw = query.get("deadline_ms", [None])[0]
        if raw is None and headers is not None:
            raw = headers.get("X-Deadline-Ms")
        try:
            ms = float(raw) if raw is not None else 0.0
        except (TypeError, ValueError):
            ms = 0.0
        if ms <= 0:
            ms = float(config.DEADLINE_DEFAULT_MS.get())
        if ms <= 0:
            return None
        return Deadline.after_ms(min(ms, float(config.DEADLINE_MAX_MS.get())))

    @staticmethod
    def _request_priority(query: dict, headers) -> str:
        from geomesa_tpu.serve.resilience.admission import normalize_priority
        raw = query.get("priority", [None])[0]
        if raw is None and headers is not None:
            raw = headers.get("X-Priority")
        return normalize_priority(raw)

    @staticmethod
    def _request_tenant(query: dict, headers) -> Optional[str]:
        """Caller-declared tenant from ?tenant= / X-Tenant. None falls back
        to the auth-derived label inside the scheduler (workload metering
        never trusts this for access control — auths stay authoritative)."""
        raw = query.get("tenant", [None])[0]
        if raw is None and headers is not None:
            raw = headers.get("X-Tenant")
        if raw is None:
            return None
        raw = str(raw).strip()
        return raw or None

    # returns (status, payload) — dict for JSON, str for raw text bodies.
    # A 429/503 payload carries retry_after_s; the transport turns it into
    # a Retry-After header.
    def handle(self, method: str, path: str, query: dict,
               body: Optional[bytes] = None,
               headers=None) -> Tuple[int, object]:
        from geomesa_tpu.cluster.cells import NotOwnedError
        from geomesa_tpu.index.guards import QueryGuardError, QueryTimeout
        from geomesa_tpu.replication.fence import FencedError
        from geomesa_tpu.serve.resilience import deadline as _rdl
        from geomesa_tpu.serve.resilience.breaker import CircuitOpenError
        from geomesa_tpu.serve.resilience.admission import ShedError
        try:
            # cross-process trace context: a request carrying X-Trace-Id
            # (the router's proxy hop) opens its root trace as a CHILD of
            # the remote parent — one global id, one stitched fleet tree
            with _trace.remote_parent(_trace.extract_headers(headers)), \
                    _rdl.use(self._request_deadline(query, headers)):
                return self._route(method, path, query, body,
                                   headers=headers)
        except ShedError as e:        # admission control shed this request
            if _trace.enabled():
                _trace.record("shed", "shed", 0.0)
            return 429, {"error": str(e), "kind": "shed",
                         "priority": e.priority,
                         "retry_after_s": e.retry_after_s}
        except CircuitOpenError as e:  # failing fast on a sick device path
            return 503, {"error": str(e), "kind": "breaker_open",
                         "retry_after_s": e.retry_after_s}
        except QueryTimeout as e:     # deadline exceeded / planner timeout
            return 504, {"error": str(e), "kind": "deadline"}
        except FencedError as e:      # read-only replica / fenced ex-primary
            return 403, {"error": str(e), "kind": "fenced"}
        except NotOwnedError as e:    # write routed to the wrong cell
            return 409, {"error": str(e), "kind": "not_owner",
                         "cell": e.cell, "owner": e.owner,
                         "key": e.key}
        except QueryGuardError as e:  # an interceptor vetoed the query
            return 400, {"error": str(e), "kind": "guard"}
        except (KeyError, ValueError, TypeError, IndexError,
                json.JSONDecodeError) as e:
            # planner/parser/data errors stay 400s (client-fixable input)
            return 400, {"error": str(e), "kind": "bad_request"}
        except Exception as e:        # anything else is OUR fault: 500,
            return 500, {"error": str(e), "kind": "internal",
                         "type": type(e).__name__}

    def _route(self, method, path, query, body, headers=None):
        parts = [p for p in path.split("/") if p]
        if parts == ["types"]:
            return 200, {"types": self.store.get_type_names()}
        if parts == ["metrics"]:
            from geomesa_tpu.metrics import REGISTRY
            _hostcpu.sample()    # host.cpu_us.<role>: read only here
            fmt = query.get("format", [None])[0]
            if fmt == "prometheus":
                # str payload → text/plain exposition body
                return 200, REGISTRY.to_prometheus()
            if fmt == "state":
                # bucket-exact registry state for the metrics federator
                # (lossless cross-node histogram merge), tagged with this
                # node's fleet identity; workload rollup/sketch state rides
                # the same payload so one scrape carries both
                from geomesa_tpu.obs.history import HISTORY
                from geomesa_tpu.obs.shardwatch import WATCH
                from geomesa_tpu.obs.workload import WORKLOAD
                state = REGISTRY.export_state()
                state["workload"] = WORKLOAD.export_state()
                state["shardwatch"] = WATCH.export_state()
                state["history"] = HISTORY.export_state()
                return 200, {"node": self._node_meta(), "state": state}
            return 200, REGISTRY.snapshot()
        if parts == ["traces"]:
            from geomesa_tpu.trace import RING
            limit = int(query.get("limit", [50])[0])
            gid = query.get("id", [None])[0]
            if gid is not None:
                # this node's halves of one (global) trace id — what the
                # router-side stitcher / `debug trace --fleet` fetch
                from geomesa_tpu.obs.federation import local_traces_by_id
                return 200, {"id": gid, "traces": local_traces_by_id(gid)}
            if query.get("retained", [None])[0] not in (None, "0", "false"):
                # the tail-sampled ring: errors/cancel/shed/degrade always,
                # slow outliers past the adaptive threshold, plus the
                # probabilistic sample — what /metrics exemplars link to
                from geomesa_tpu.obs.sampling import SAMPLER
                return 200, {"traces": SAMPLER.recent(limit),
                             "sampler": SAMPLER.stats()}
            return 200, {"traces": RING.recent(limit)}
        if parts == ["events"]:
            # flight recorder: wide events filtered by the shared predicate
            from geomesa_tpu.obs.flight import RECORDER
            slow = query.get("slow_ms", [None])[0]
            since = query.get("since_ms", [None])[0]
            return 200, {"events": RECORDER.recent(
                limit=int(query.get("limit", [100])[0]),
                slow_ms=float(slow) if slow is not None else None,
                errors=query.get("error", [None])[0]
                not in (None, "0", "false"),
                kind=query.get("kind", [None])[0],
                type_name=query.get("type", [None])[0],
                since_ms=float(since) if since is not None else None),
                "recorder": RECORDER.stats()}
        if parts == ["slo"]:
            from geomesa_tpu.obs.slo import ENGINE
            return 200, {"slo": ENGINE.evaluate()}
        if parts == ["alerts"]:
            # the doctor's current firings — reading IS detecting (the
            # evaluation runs here, never on the query hot path)
            from geomesa_tpu.obs.doctor import DOCTOR
            return 200, DOCTOR.alerts()
        if parts == ["incidents"]:
            from geomesa_tpu.obs.doctor import DOCTOR
            active = query.get("active", [None])[0] \
                not in (None, "0", "false")
            return 200, DOCTOR.incidents(active_only=active)
        if len(parts) == 3 and parts[0] == "incidents" \
                and parts[2] == "bundle":
            # the forensic bundle frozen when the doctor opened this
            # incident: history slices around the firing, matching flight
            # events, trace gids, replication/cell state, workload hot_set
            from geomesa_tpu.obs.forensics import FORENSICS
            bundle = FORENSICS.get(parts[1])
            if bundle is None:
                return 404, {"error": f"no forensic bundle for "
                                      f"{parts[1]}"}
            return 200, bundle
        if parts == ["history"]:
            # retained metric timelines: ?name=series&since_ms=&tier= for
            # a range; without ?name=, the sampler summary + series index
            from geomesa_tpu.obs.history import HISTORY
            name = query.get("name", [None])[0]
            if name is None:
                return 200, {"history": HISTORY.summary()}
            since = float(query.get("since_ms", [0])[0])
            tier = query.get("tier", [None])[0]
            return 200, {"name": name, "since_ms": since,
                         "samples": HISTORY.range(
                             name, since_ms=since,
                             tier=int(tier) if tier is not None
                             else None)}
        if parts == ["workload"]:
            # streaming workload analytics: windowed rollups, heavy-hitter
            # plan hashes / tenants, hot spatial cells (query LOAD, not data)
            from geomesa_tpu.obs.workload import WORKLOAD
            return 200, {"workload": WORKLOAD.summary()}
        if parts == ["progress"]:
            # long-running operation phases (index builds): live phases
            # with running row throughput + the recent history
            from geomesa_tpu.obs.profiling import PROGRESS
            return 200, {"progress": PROGRESS.snapshot()}
        if parts == ["scheduler"]:
            return 200, self.store.scheduler().stats()
        if parts == ["cache"]:
            # the hot-result cache surface: counters + per-cell warmth, so
            # the doctor's hot_skew suspects can be cross-checked against
            # what is actually cached on this node
            return 200, {"result_cache":
                         self.store.scheduler().results.stats()}
        if parts == ["durability"]:
            d = getattr(self.store, "durability", None)
            if d is None:
                return 200, {"enabled": False}
            return 200, d.status()
        if parts and parts[0] == "replication":
            return self._route_replication(parts[1:], method, query)
        if parts == ["debug", "fault"] and method == "POST":
            # deterministic chaos for subprocess drills: the fleet soak
            # arms mid-run faults (e.g. a repl.apply delay = lag spike)
            # in a child it cannot reach in-process. Hard-gated off by
            # default — the env flag is only set by drill spawners.
            import os as _os
            if _os.environ.get("GEOMESA_TPU_FAULT_API", "").lower() \
                    not in ("1", "true", "on"):
                return 403, {"error": "fault API disabled (spawn with "
                                      "GEOMESA_TPU_FAULT_API=1)",
                             "kind": "forbidden"}
            from geomesa_tpu.durability import faults as _faults
            if query.get("reset", [None])[0]:
                _faults.reset()
                return 200, {"reset": True}
            point = query.get("point", [None])[0]
            if not point:
                return 400, {"error": "missing ?point=",
                             "kind": "bad_request"}
            delay_s = float(query.get("delay_s", [0.0])[0])
            n = int(query.get("n", [1])[0])
            _faults.arm_serve_delay(point, seconds=delay_s, n=n)
            return 200, {"armed": point, "delay_s": delay_s, "n": n}
        if parts == ["fleet", "soak"]:
            # last fleet-soak scoreboard: readable WITHOUT a federator
            # (the orchestrator runs out-of-process; any node can serve
            # the summary it wrote to disk)
            from geomesa_tpu.obs import soakfleet as _soak
            board = _soak.last_run()
            if board is None:
                return 404, {"error": "no soak run recorded "
                                      "(geomesa-tpu soak)"}
            return 200, board
        if parts and parts[0] == "fleet":
            # the single pane of glass — served by whichever node carries
            # a configured federator (the router/primary, typically)
            from geomesa_tpu.obs import federation as _fed
            fed = _fed.federator()
            if fed is None:
                return 404, {"error": "no federator configured on this "
                                      "node (obs.federation.configure)"}
            if parts == ["fleet"]:
                return 200, fed.fleet()
            if parts == ["fleet", "metrics"]:
                return 200, fed.to_prometheus()  # str → text exposition
            if parts == ["fleet", "slo"]:
                return 200, {"slo": fed.slo()}
            if parts == ["fleet", "workload"]:
                # fleet-wide workload intelligence: per-node window states
                # and sketches merged into one hot-set / rollup view
                return 200, fed.fleet_workload()
            if parts == ["fleet", "incidents"]:
                # every node's doctor verdicts with node attribution
                return 200, fed.fleet_incidents()
            if parts == ["fleet", "balance"]:
                # fleet-wide shard balance: merged shardwatch + workload
                # states joined through the same ledger a node runs
                return 200, fed.fleet_balance()
            if parts == ["fleet", "history"]:
                # fleet timelines: equal-tier rings merged at aligned
                # slots with honest per-node gap markers
                return 200, fed.fleet_history()
            return 404, {"error": f"no route {method} {path}"}
        if parts == ["cluster", "balance"]:
            # the shard balance observatory: per-shard load shares joined
            # from hot cells x key-range ownership, imbalance score, and
            # projected split points for the hottest shard
            from geomesa_tpu.obs.shardwatch import WATCH
            return 200, WATCH.balance()
        if parts == ["cluster"]:
            # the partition plane: process count, per-process rows, Morton
            # key-range ownership, mesh topology, psum round counters.
            # (/fleet is the REPLICATION plane: full-copy nodes behind the
            # router. A cluster shard can still have read replicas.)
            from geomesa_tpu.cluster.runtime import runtime as _cluster_rt
            return 200, _cluster_rt(init=False).state()
        if parts == ["cells"]:
            # the shard-cell plane: which cell this node serves (key
            # range, fencing epoch, ingest-gate counters) + the fleet
            # topology when one was configured
            from geomesa_tpu.cluster import cells as _cells
            return 200, _cells.CELLS.state()
        if parts == ["healthz"]:
            import jax
            report = getattr(self.store, "recovery_report", None)
            d = getattr(self.store, "durability", None)
            # overload state reads the LIVE scheduler only — a health probe
            # must never be the thing that spins one up
            sched = getattr(self.store, "_scheduler", None)
            if sched is None:
                overload = {"scheduler": "idle"}
            else:
                overload = {"scheduler": "ok" if sched.healthy()
                            else "unhealthy",
                            "queue_depth": sched._queue.qsize(),
                            "admission": sched.admission.stats(),
                            "breaker": sched.breaker.stats()}
            from geomesa_tpu.obs.slo import ENGINE as _slo_engine
            try:
                slo = _slo_engine.summary()
            except Exception:
                slo = {"status": "unknown"}
            repl = getattr(self.store, "replication", None)
            from geomesa_tpu.cluster.runtime import runtime as _cluster_rt
            c = _cluster_rt(init=False)
            cluster = {"active": c.active()}
            if c.active():
                cluster.update({
                    "process_id": c.process_id,
                    "num_processes": c.num_processes,
                    "psum_rounds": c.psum_rounds,
                    "shard_rows": {
                        t: s.get("proc_rows", [None] * (c.process_id + 1))
                        [c.process_id] for t, s in c.tables.items()}})
            from geomesa_tpu.index import compiled as _fused
            return 200, {"status": "ok",
                         "node": self._node_meta(),
                         "cluster": cluster,
                         "devices": len(jax.local_devices()),
                         "backend": jax.default_backend(),
                         "device_kind": jax.local_devices()[0].device_kind,
                         "types": len(self.store.get_type_names()),
                         "overload": overload,
                         "slo": slo,
                         "fused_query": _fused.stats_snapshot(),
                         "replication": repl.stats() if repl is not None
                         else {"role": "standalone"},
                         "durability": {
                             "enabled": d is not None,
                             "wal_policy": d.wal.policy if d else None,
                             "wal_seq": d.wal.last_seq if d else None,
                             "synced_seq": d.wal.synced_seq if d else None,
                             "unsynced_bytes": d.wal.unsynced_bytes
                             if d else None},
                         "recovery": report.to_dict() if report is not None
                         else {"recovered": False}}
        if parts == ["config"]:
            from geomesa_tpu import config
            return 200, config.describe()
        if len(parts) >= 2 and parts[0] == "types":
            t = parts[1]
            if t not in self.store.get_type_names():
                return 404, {"error": f"no such type {t!r}"}
            rest = parts[2:]
            cql = query.get("cql", ["INCLUDE"])[0]
            if "q" in query:
                # MongoDB-style JSON query (≙ the geojson API's GeoJsonQuery
                # language) — takes precedence over ?cql=
                from geomesa_tpu.web.jsonquery import parse_json_query
                cql = parse_json_query(query["q"][0], self.store.get_schema(t))
            auths = query["auths"][0].split(",") if "auths" in query else None
            if not rest:
                sft = self.store.get_schema(t)
                # one consistent (planner, delta) snapshot — two unlocked
                # reads could straddle a flush and under-count by the delta
                if self.store.tables.get(t) is None:
                    count = 0
                else:
                    planner, delta = self.store._snapshot(t)
                    count = len(planner.table) + (len(delta) if delta is not None else 0)
                return 200, {"name": t, "spec": sft.to_spec(),
                             "attributes": [
                                 {"name": a.name, "type": a.type_name,
                                  "default": a.default}
                                 for a in sft.attributes],
                             "count": count}
            if rest == ["count"]:
                # a freshly provisioned type (schema, zero rows) counts
                # as 0 — a 4xx here would read as node death to the
                # shard router and mark a healthy empty cell dark
                d = self.store.deltas.get(t)
                if self.store.tables.get(t) is None and \
                        (d is None or len(d) == 0):
                    return 200, {"count": 0}
                # coalesced: concurrent counts micro-batch into shared
                # fused device dispatches (serve/scheduler.py); the ambient
                # request deadline propagates through the scheduler and an
                # overload/breaker condition may degrade the answer to the
                # flagged stats estimate
                n = self.store.count_coalesced(
                    t, cql, auths=auths,
                    priority=self._request_priority(query, headers),
                    tenant=self._request_tenant(query, headers))
                out = {"count": int(n)}
                if getattr(n, "approximate", False):
                    out["approximate"] = True
                    out["reason"] = n.reason
                return 200, out
            if rest == ["explain"]:
                analyze = query.get("analyze", [None])[0] \
                    not in (None, "0", "false")
                out = self.store.explain(t, cql, analyze=analyze,
                                         auths=auths)
                return 200, json.loads(json.dumps(out, default=str))
            if rest == ["stats"]:
                stat = query.get("stat", [None])[0]
                if not stat:
                    return 400, {"error": "missing ?stat= DSL expression"}
                res = self.store.stats(t).run_stat(stat, cql, auths=auths)
                return 200, {"stat": stat, "result": res.to_dict()
                             if hasattr(res, "to_dict") else str(res)}
            if rest == ["features"] and method == "GET":
                hints = {}
                if "limit" in query:
                    hints["limit"] = int(query["limit"][0])
                if "sort" in query:
                    hints["sort"] = query["sort"][0]
                if "crs" in query:
                    hints["crs"] = query["crs"][0]
                res = self.store.query(t, cql, hints=hints or None,
                                       auths=auths)
                # what the response will carry: its size is the route's
                # cost (one GeoJSON feature a row, built in Python)
                from geomesa_tpu.metrics import REGISTRY
                REGISTRY.inc("http.features.rows", len(res.table))
                if "select" in query:
                    # geometry-catalog projections: st_* terms evaluate
                    # through the vmapped kernels (GEOM_KERNELS knob),
                    # geometry results serialize as WKT
                    from geomesa_tpu.geom.functions import \
                        projection_columns
                    cols = projection_columns(res.table, None,
                                              query["select"][0])
                    return 200, {"type": t, "count": len(res.table),
                                 "columns": cols}
                from geomesa_tpu.io.export import export
                return 200, json.loads(export(res.table, "geojson"))
            if rest == ["join"] and method == "GET":
                if "with" not in query:
                    return 400, {"error": "missing ?with=<polygon type>"}
                return 200, self.store.join(
                    t, query["with"][0],
                    op=query.get("op", ["st_intersects"])[0], f=cql,
                    stats=query.get("stats", ["count"])[0], auths=auths)
            if rest == ["features"] and method == "POST":
                fc = json.loads(body or b"{}")
                n = self._ingest_geojson(t, fc)
                return 200, {"ingested": n}
            if rest == ["flush"] and method == "POST":
                # force the delta tier into main — lets operators (and the
                # soak orchestrator) provoke the table swap that reindex
                # builds race against
                self.store.flush(t)
                return 200, {"flushed": t}
            if rest == ["reindex"]:
                # POST kicks a background build-then-swap reindex (serving
                # continues against the old generation until the atomic
                # install); GET polls its status
                if method == "POST":
                    return 200, self.store.reindex(t, background=True)
                return 200, self.store.reindex_status(t)
        return 404, {"error": f"no route {method} {path}"}

    def _route_replication(self, rest, method, query):
        """Fleet control surface.

          GET  /replication          role + epoch + follower/lag state
          POST /replication/drain    admission drain (rolling restart /
                                     pre-failover quiesce); ?off=1 undoes
          POST /replication/promote  promote THIS node (a Follower-backed
                                     replica) to primary under a fresh
                                     fencing epoch; ?port= picks the new
                                     shipper port (0 = ephemeral)
          POST /replication/fence    durably fence THIS node under
                                     ?epoch= (ownership handoff: the old
                                     owner refuses every write until
                                     re-promoted; survives restart via
                                     the persisted epoch file)
        """
        repl = getattr(self.store, "replication", None)
        if not rest:
            if repl is None:
                return 200, {"role": "standalone"}
            return 200, repl.stats()
        if rest == ["drain"] and method == "POST":
            off = query.get("off", [None])[0] not in (None, "0", "false")
            self.store.scheduler().admission.drain(not off)
            return 200, {"draining": not off}
        if rest == ["promote"] and method == "POST":
            target = self._target
            if not hasattr(target, "promote"):
                return 400, {"error": "this node is not a promotable "
                                      "replica", "kind": "bad_request"}
            port = int(query.get("port", [0])[0])
            shipper = target.promote(port=port)
            return 200, {"role": "primary", "epoch": shipper.epoch,
                         "address": shipper.address}
        if rest == ["fence"] and method == "POST":
            from geomesa_tpu.cluster import cells as _cells
            from geomesa_tpu.replication import fence as _f
            epoch = int(query.get("epoch", [0])[0])
            store = self.store
            if repl is not None and hasattr(repl, "_fence_self"):
                repl._fence_self(epoch)
            else:
                _f.save_epoch(store.durability.path, epoch)
                store.durability.read_only = True
            if _cells.CELLS.fence is not None:
                _cells.CELLS.fence.epoch = max(
                    _cells.CELLS.fence.epoch, epoch)
            return 200, {"fenced": True, "epoch": epoch}
        return 404, {"error": f"no route {method} /replication/"
                              f"{'/'.join(rest)}"}

    def _ingest_geojson(self, t: str, fc: dict) -> int:
        feats = fc.get("features", [])
        if not feats:
            return 0
        from geomesa_tpu.cluster import cells as _cells
        if _cells.CELLS.active():
            # shard-cell ownership gate: every point's routing key must
            # fall in this node's cell range BEFORE anything is written
            # (atomic refusal — a misrouted batch lands zero rows)
            pts = [f.get("geometry", {}).get("coordinates")
                   for f in feats
                   if (f.get("geometry", {}).get("type") or
                       "Point").upper() == "POINT"]
            if pts:
                _cells.CELLS.ensure_owned([p[0] for p in pts],
                                          [p[1] for p in pts])
        sft = self.store.get_schema(t)
        with self.store.get_writer(t) as w:
            for f in feats:
                props = dict(f.get("properties", {}))
                geom = f.get("geometry") or {}
                coords = geom.get("coordinates")
                gtype = (geom.get("type") or "Point").upper()
                gattr = sft.geometry_attribute.name
                if gtype == "POINT":
                    props[gattr] = f"POINT ({coords[0]} {coords[1]})"
                else:
                    from geomesa_tpu.features.geometry import (NAME_TYPES,
                                                               write_wkt)
                    code = NAME_TYPES[geom.get("type")]
                    props[gattr] = write_wkt(code, coords)
                for a in sft.attributes:
                    if a.type_name == "Date" and a.name in props:
                        props[a.name] = np.datetime64(props[a.name], "ms") \
                            .astype(np.int64)
                w.write(fid=f.get("id"), **props)
        return len(feats)


def _route_family(path: str) -> str:
    """``count`` for /types/{t}/count, ``join`` for /types/{t}/join, else
    the first path word: the name an ``http.request.*`` timer carries, so
    that polls of /metrics and /events never dilute a measured route's."""
    parts = [p for p in path.split("/") if p]
    if len(parts) == 3 and parts[0] == "types" \
            and parts[2] in ("count", "join"):
        return parts[2]
    return re.sub(r"\W", "_", parts[0]) if parts else "root"


class _Conn(threading.local):
    # perf_counter_ns as the listener's accept() returned this thread's
    # connection; taken by the connection's first request
    accepted_ns = None


_conn = _Conn()
_pcn = time.perf_counter_ns
_tcn = time.thread_time_ns


class _Handler(BaseHTTPRequestHandler):
    api: GeoJsonApi = None  # set by serve()

    def _respond(self, status: int, payload) -> None:
        # str payloads are raw text bodies (the Prometheus exposition);
        # everything else serializes as JSON
        if isinstance(payload, str):
            data = payload.encode()
            ctype = "text/plain; version=0.0.4"
        else:
            data = json.dumps(payload).encode()
            ctype = "application/json"
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        if isinstance(payload, dict) and "retry_after_s" in payload:
            # shed (429) / breaker-open (503) backpressure: the standard
            # header clients and proxies honor
            self.send_header("Retry-After",
                             str(max(1, int(-(-payload["retry_after_s"]
                                             // 1)))))
        self.end_headers()
        self.wfile.write(data)

    def _serve(self, method: str) -> None:
        """Route + respond inside a last-resort guard: NOTHING a route
        raises may kill the handler thread and reset the client connection
        — an unexpected error becomes a structured 500 envelope (the
        kind/status mapping itself lives in GeoJsonApi.handle).

        Timed from the parsed request line to the flushed response as
        ``http.request.<route family>`` (``count`` for /types/{t}/count,
        else the first path word), with ``http.respond`` (JSON encoding and
        the socket write) inside it. On the count route it is the root of
        the request's trace, the scheduler's ``query.count`` nests under
        it, and REST's own time is the root's self time; other routes keep
        their own roots (``query.features``, ``explain``) and get the two
        as flat timers.

        With tracing on, before it, the flat timer ``http.accept`` runs
        from the instant the listener's ``accept()`` returned
        (``BacklogHTTPServer``) to here: the handler thread's creation and
        start, its first turn at the interpreter lock and the header read.
        Once the response is flushed the request adds to the counter
        ``http.offcpu_us``: accept to flush, less the thread's CPU time from
        its start (one request a thread: the server speaks HTTP/1.0) and
        less its waits by design (``trace.waiting``); what is left is time
        it wanted to run and did not — the lock, the OS."""
        accepted, _conn.accepted_ns = _conn.accepted_ns, None
        from_accept = accepted is not None and _trace.enabled()
        if from_accept:
            now = _pcn()
            _trace.record("http.accept", "span", (now - accepted) / 1e9, now)
            waited = _trace.waited_ns()
        u = urlparse(self.path)
        family = _route_family(u.path)
        timed = _trace.trace if family == "count" else _trace.span
        # the root below is the upstream hop's child when the request
        # carries trace context (handle() binds it again for its own roots)
        with _trace.remote_parent(_trace.extract_headers(self.headers)), \
                timed("http.request." + family):
            try:
                body = None
                if method == "POST":
                    length = int(self.headers.get("Content-Length", 0))
                    with _trace.waiting():
                        body = self.rfile.read(length) if length else b""
                status, payload = self.api.handle(method, u.path,
                                                  parse_qs(u.query), body,
                                                  headers=self.headers)
            except Exception as e:  # handle() failed outside its own guards
                status, payload = 500, {"error": str(e), "kind": "internal",
                                        "type": type(e).__name__}
            with _trace.span("http.respond"):
                try:
                    self._respond(status, payload)
                except (BrokenPipeError, ConnectionResetError):
                    pass  # client went away; the server thread must survive
        if from_accept:
            off = _pcn() - accepted - (_trace.waited_ns() - waited) - _tcn()
            _metrics.inc("http.offcpu_us", max(0, off) // 1000)

    def do_GET(self):
        self._serve("GET")

    def do_POST(self):
        self._serve("POST")

    def log_message(self, *a):  # quiet by default
        pass


class BacklogHTTPServer(ThreadingHTTPServer):
    # socketserver's default listen backlog is 5: a burst of concurrent
    # clients (the traffic the scheduler exists to batch) overflows it and
    # the excess connects sit in SYN retransmits for 1, 2, 4 … seconds
    request_queue_size = 128

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # request socket → perf_counter_ns as accept() returned it, while
        # tracing is on; taken by the socket's handler thread
        self._accepted = {}

    def get_request(self):
        request, client_address = super().get_request()
        if _trace.enabled():
            self._accepted[request] = _pcn()
        return request, client_address

    def process_request_thread(self, request, client_address):
        """The handler thread: the role ``http`` (obs/hostcpu.py), its
        connection's accept instant for the handler, and its whole CPU
        time to ``host.cpu_us.http`` as it ends."""
        _hostcpu.mark(threading.current_thread(), "http")
        _conn.accepted_ns = self._accepted.pop(request, None)
        try:
            super().process_request_thread(request, client_address)
        finally:
            _hostcpu.charge("http", _tcn())

    def shutdown_request(self, request):
        self._accepted.pop(request, None)   # a request no thread took
        super().shutdown_request(request)


def serve(store, host: str = "127.0.0.1", port: int = 8765,
          background: bool = False):
    """Start the REST server. ``background=True`` returns the server after
    starting a daemon thread (tests / embedded use)."""
    handler = type("BoundHandler", (_Handler,), {"api": GeoJsonApi(store)})
    httpd = BacklogHTTPServer((host, port), handler)
    if background:
        _hostcpu.thread("http.listener", target=httpd.serve_forever,
                        daemon=True).start()
        return httpd
    _hostcpu.mark(threading.current_thread(), "http.listener")
    httpd.serve_forever()
