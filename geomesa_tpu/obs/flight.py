"""Flight recorder: one canonical wide event per request.

The Dapper/Canopy lesson (PAPERS.md): aggregate histograms tell you THAT a
p99 regressed; only a per-request record with every dimension on one row
tells you WHICH queries paid it. Every query/count/batch emits one
structured wide event — trace id, query type, plan hash, plan-cache hit
flag, batch size + batch id, admission class, deadline budget vs
slack, ``device_ms`` vs ``host_ms`` (both host clock: ``device_ms`` is the
host blocked until the answer was read back, never time on the device),
rows scanned/matched, shed/degrade/cancel/
breaker flags, error kind — into a bounded ring plus an optional JSONL
sink with size rotation (the shared durability/rotation.py policy).

Two producers feed it:

  - the micro-batching scheduler emits the rich event per scheduled count
    (it knows cache hits, batch membership, admission class, degradation)
    plus one ``batch`` event per fused device dispatch, which carries the
    dispatch cycle's stages (``stages``: name → [start epoch ms, ms];
    ``launch_ms``/``ready_ms``; see serve/scheduler.py ``_Dispatch``);
  - the trace-close hook derives an event from every other ROOT trace
    (direct counts, feature queries, explains), so the unscheduled paths
    are never dark.

Query with ``RECORDER.recent(slow_ms=..., errors=..., kind=..., ...)`` —
the same ``matches()`` predicate backs ``GET /events`` and the CLI's
``debug events`` / ``debug traces`` filters.
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib
from collections import deque
from typing import Dict, List, Optional

from geomesa_tpu import config
from geomesa_tpu.metrics import REGISTRY as _metrics
from geomesa_tpu.obs import workload as _workload


def plan_hash(type_name: str, f_key: str, auths_key=None) -> str:
    """Stable short hash identifying a (type, normalized filter, auths)
    plan shape across events and processes (crc32 — not salted like
    ``hash()``, so two runs agree)."""
    raw = f"{type_name}|{f_key}|{auths_key}".encode()
    return format(zlib.crc32(raw), "08x")


def tenant_label(tenant=None, auths=None) -> str:
    """Canonical tenant label for workload analytics and metering: the
    explicit tenant (``?tenant=`` / ``X-Tenant`` / submit kwarg) wins;
    otherwise the FIRST sorted auth stands in (one label per principal
    group, bounded cardinality); otherwise ``default``."""
    if tenant:
        return str(tenant)[:64]
    if auths:
        return "auth:" + sorted(str(a) for a in auths)[0][:56]
    return "default"


def matches(rec: dict, slow_ms: Optional[float] = None,
            errors: bool = False, kind: Optional[str] = None,
            type_name: Optional[str] = None,
            since_ms: Optional[float] = None) -> bool:
    """The shared filter predicate over wide events AND trace dicts.

    slow_ms    keep records at least this slow (duration_ms)
    errors     keep only failed/shed/cancelled records
    kind       match the record kind / trace name, or a span kind present
               in its ``stages_ms`` breakdown
    type_name  match the feature type
    since_ms   keep records stamped at/after this wall time — the slice
               filter shared by ``GET /events``, ``debug events`` and the
               forensic-bundle capture path, so flight events line up
               with a history ``range(name, since_ms)`` window
    """
    if slow_ms is not None and float(rec.get("duration_ms") or 0.0) < slow_ms:
        return False
    if since_ms is not None and float(rec.get("ts_ms") or 0.0) < since_ms:
        return False
    if errors and not (rec.get("error") or rec.get("cancelled")
                       or rec.get("shed")):
        return False
    if kind is not None:
        stages = rec.get("stages_ms") or {}
        if kind not in (rec.get("kind"), rec.get("name")) \
                and kind not in stages:
            return False
    if type_name is not None and rec.get("type") != type_name:
        return False
    return True


class FlightRecorder:
    """Bounded ring of wide events + optional rotated JSONL sink."""

    def __init__(self, keep: Optional[int] = None,
                 jsonl_path: Optional[str] = None,
                 max_bytes: Optional[int] = None):
        self._lock = threading.Lock()
        self._ring: deque = deque(
            maxlen=int(keep or config.OBS_RING.get()))
        self._jsonl_path = jsonl_path
        self._max_bytes = max_bytes
        self._fh = None
        self._fh_path = None
        self._fh_bytes = 0
        self._n_recorded = 0
        # cached sink decision for the hot record_trace path (re-read from
        # config every _SINK_REFRESH records and on every read surface, so
        # flipping GEOMESA_TPU_OBS_JSONL at runtime takes effect promptly
        # without an env read per query)
        self._sink_cached = self._sink_path() is not None
        self._sink_age = 0

    _SINK_REFRESH = 512

    # -- sink -----------------------------------------------------------------

    def _sink_path(self) -> Optional[str]:
        if self._jsonl_path is not None:
            return self._jsonl_path or None
        return config.OBS_JSONL.get() or None

    def _write_jsonl_locked(self, line: bytes) -> None:
        path = self._sink_path()
        if path is None:
            if self._fh is not None:
                self._fh.close()
                self._fh = None
            return
        try:
            if self._fh is None or self._fh_path != path:
                if self._fh is not None:
                    self._fh.close()
                d = os.path.dirname(path)
                if d:
                    os.makedirs(d, exist_ok=True)
                self._fh = open(path, "ab")
                self._fh_path = path
                self._fh_bytes = self._fh.tell()
            self._fh.write(line)
            self._fh_bytes += len(line)
            cap = int(self._max_bytes
                      if self._max_bytes is not None
                      else config.OBS_JSONL_MAX_BYTES.get())
            if cap > 0 and self._fh_bytes >= cap:
                from geomesa_tpu.durability.rotation import rotate
                self._fh.close()
                self._fh = None
                def _dropped(p):
                    _metrics.inc("obs.jsonl_dropped")
                    _metrics.inc("journal.gc")
                rotate(path,
                       keep=max(1, int(config.JOURNAL_KEEP.get())),
                       on_drop=_dropped)
        except OSError:
            # a failing sink must never fail the request (dropwizard rule)
            _metrics.inc("obs.jsonl_errors")
            self._fh = None

    # -- recording ------------------------------------------------------------

    def record(self, event: dict) -> None:
        if "ts_ms" not in event:
            event["ts_ms"] = int(time.time() * 1000)
        with self._lock:
            self._ring.append(event)
            self._n_recorded += 1
            if self._sink_path() is not None:
                self._write_jsonl_locked(
                    (json.dumps(event, default=str) + "\n").encode())
        # tee into the workload-analytics plane (one bounded append;
        # aggregation is deferred to its drain)
        _workload.WORKLOAD.offer(event)

    def record_trace(self, t) -> None:
        """Hot-path variant for the trace close hook: the ring holds the
        (already-built) QueryTrace itself and the wide event materializes
        lazily at READ time (``recent()``), with its retention flag
        resolved against the tail sampler then — trace close pays one lock
        + one deque append. With a JSONL sink configured the event must
        serialize now, so it eagerly materializes on that path only."""
        self._sink_age += 1
        if self._sink_age >= self._SINK_REFRESH:
            self._sink_age = 0
            self._sink_cached = self._sink_path() is not None
        if self._sink_cached:
            from geomesa_tpu.obs.sampling import SAMPLER
            SAMPLER.drain()
            self.record(event_from_trace(
                t, retained=SAMPLER.is_retained(t.trace_id)))
            return
        # lockless: deque appends are GIL-atomic (readers tolerate the
        # mutated-during-iteration race — see _ring_snapshot); the count
        # is advisory
        self._ring.append(t)
        self._n_recorded += 1
        # the workload plane gets the raw trace too; its wide event
        # materializes at ITS drain, same deferral as the ring's
        _workload.WORKLOAD.offer(t)

    def _ring_snapshot(self) -> list:
        """Copy the ring despite lockless concurrent appends: deque
        iteration raises RuntimeError when mutated mid-copy — retry."""
        while True:
            try:
                return list(self._ring)
            except RuntimeError:
                continue

    # -- querying -------------------------------------------------------------

    def recent(self, limit: Optional[int] = None,
               slow_ms: Optional[float] = None, errors: bool = False,
               kind: Optional[str] = None,
               type_name: Optional[str] = None,
               since_ms: Optional[float] = None) -> List[dict]:
        """Most-recent-first events passing the shared filter predicate."""
        from geomesa_tpu.obs.sampling import SAMPLER
        SAMPLER.drain()  # settle retention before resolving lazy entries
        self._sink_cached = self._sink_path() is not None
        items = self._ring_snapshot()
        items.reverse()
        out = []
        for e in items:
            if not isinstance(e, dict):  # lazily-recorded trace entry
                e = event_from_trace(
                    e, retained=SAMPLER.is_retained(e.trace_id))
            if matches(e, slow_ms=slow_ms, errors=errors, kind=kind,
                       type_name=type_name, since_ms=since_ms):
                out.append(e)
        if limit is not None:
            out = out[: max(0, int(limit))]
        return out

    def clear(self) -> None:
        self._sink_cached = self._sink_path() is not None
        self._sink_age = 0
        with self._lock:
            self._ring.clear()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def stats(self) -> dict:
        with self._lock:
            return {"depth": len(self._ring), "capacity": self._ring.maxlen,
                    "recorded": self._n_recorded,
                    "jsonl": self._sink_path(),
                    "jsonl_bytes": self._fh_bytes if self._fh else 0}


# process-global recorder (the serving shape: one recorder per process)
RECORDER = FlightRecorder()


# error type -> the wide-event error kind (matches the web envelope kinds)
_ERR_KINDS = {"DeadlineExceeded": "deadline", "ShedError": "shed",
              "CircuitOpenError": "breaker_open",
              "SchedulerCrashed": "crash", "SchedulerShutdown": "shutdown",
              "QueryGuardError": "guard", "QueryTimeout": "deadline"}


def error_kind(e: BaseException) -> str:
    return _ERR_KINDS.get(type(e).__name__, type(e).__name__)


def event_from_request(req, fut) -> dict:
    """The rich wide event for one scheduled request (serve/scheduler.py
    attaches this as a future done-callback — it fires on EVERY resolution
    path: result, degradation, cancellation, shed, crash sweep)."""
    err = None
    rows = None
    if fut.cancelled():
        err = "cancelled"
    else:
        e = fut.exception()
        if e is not None:
            err = error_kind(e)
        else:
            try:
                rows = int(fut.result())
            except Exception:
                pass

    def ms(seconds):
        return None if seconds is None else round(seconds * 1000.0, 3)

    from geomesa_tpu import trace as _trace
    from geomesa_tpu.cluster.runtime import event_dims as _cluster_dims
    return {
        **_cluster_dims(),
        "kind": "count.scheduled",
        "type": req.type_name,
        "trace_id": req.trace_id,
        "trace_gid": req.trace_gid,
        "node_id": _trace.node_id(),
        "role": _trace.node_role(),
        "parent_span": req.parent_span,
        "plan_hash": plan_hash(req.type_name, req.f_key, req.auths_key),
        "duration_ms": round(
            (time.perf_counter_ns() - req.t_submit) / 1e6, 3),
        # submit → its batch closed (none of its neighbours' planning)
        "queue_wait_ms": ms(req.queue_wait_s),
        "plan_cache_hit": req.plan_cache_hit,
        # provenance: "result" = served from the hot-result cache with NO
        # device round trip (device_ms stays zero; workload device-time
        # accounting must not re-bill the original dispatch)
        "cache": "result" if getattr(req, "result_cache_hit", None) else None,
        "batched": req.batched,
        "batch_size": req.batch_size,
        "batch_id": req.batch_id,
        "priority": req.priority,
        "tenant": req.tenant,
        "cell": req.cell,
        "funcs": list(getattr(req, "funcs", ()) or ()) or None,
        "deadline_budget_ms": req.budget_ms,
        "deadline_slack_ms": None if req.deadline is None
        else round(req.deadline.remaining_ms(), 3),
        # launch → resolved, on the host's clock
        "scan_ms": ms(req.scan_s),
        # NOT device time: for a batched request it is scan_ms, the host's
        # wait from launch to its answer (launch, transfer, the program,
        # read-back, the completer's turn). On a chip that idles 96 % of the
        # time that is nearly all host. Device time is in a profiler trace;
        # the dispatch's own split is the kind=batch event (``stages``).
        # Singles carry theirs in the trace / kernel attribution instead
        "device_ms": ms(req.scan_s) if req.batched else None,
        # submit → launch (queue_wait + batch_host); never launched: the
        # queue wait alone
        "host_ms": ms(req.queue_wait_s or 0.0) if req.t_launch is None
        else round((req.t_launch - req.t_submit) / 1e6, 3),
        "rows_scanned": req.rows_scanned,
        "rows_matched": rows,
        "retries": req.retries,
        "cancelled": req.cancelled,
        "degraded": req.degraded,
        "shed": req.shed,
        "breaker_open": req.breaker_open,
        "error": err,
    }


def request_callback(req):
    """Done-callback emitting the request's wide event (guarded: a failing
    recorder must never poison future resolution)."""
    def _cb(fut):
        try:
            if config.OBS_ENABLED.get():
                RECORDER.record(event_from_request(req, fut))
        except Exception:
            pass
    return _cb


def event_from_trace(t, retained: bool = False,
                     stages: Optional[dict] = None) -> dict:
    """Derive a wide event from a closed root QueryTrace (the unscheduled
    paths: direct counts, feature queries, explain). ``stages`` is an
    optional precomputed per-kind self-time breakdown (the close hook
    shares one span walk between sampling and this)."""
    from geomesa_tpu import trace as _trace
    from geomesa_tpu.cluster.runtime import event_dims as _cluster_dims
    if stages is None:
        stages = t.self_times_ms()
    device_ms = stages.get("device_scan", 0.0) + stages.get("device_wait", 0.0)
    attrs = t.root.attrs or {}
    f = attrs.get("filter")
    parent = getattr(t, "parent", None)
    ev = {
        **_cluster_dims(),
        "ts_ms": t.ts_ms,
        "kind": t.name,
        "type": attrs.get("type"),
        "trace_id": t.trace_id,
        "trace_gid": t.global_id,
        "node_id": _trace.node_id(),
        "role": _trace.node_role(),
        "parent_span": parent.span_id if parent is not None else None,
        "parent_node": parent.node if parent is not None else None,
        "retained": bool(retained),
        "duration_ms": round(t.duration_ms, 3),
        "device_ms": round(device_ms, 3),
        "host_ms": round(max(0.0, t.duration_ms - device_ms), 3),
        "stages_ms": {k: round(v, 3) for k, v in stages.items()},
        "cancelled": "cancel" in stages,
        "degraded": "degrade" in stages,
        "shed": "shed" in stages,
        "error": t.error,
    }
    if f is not None:
        ev["plan_hash"] = plan_hash(str(attrs.get("type")), str(f))
    if attrs.get("tenant") is not None:
        ev["tenant"] = attrs.get("tenant")
    return ev
