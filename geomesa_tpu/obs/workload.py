"""Workload intelligence plane: streaming rollups, heavy hitters, hot set.

The flight recorder (obs/flight.py) explains any SINGLE query; this module
answers the fleet-operator questions about the WORKLOAD: which query
shapes dominate, which spatial regions are hot, which tenant is burning
the device budget — the role GeoMesa's stats/audit subsystem plays for
the reference, feeding query-pattern analytics back into planning.

One process-global ``WorkloadAnalytics`` consumes the existing flight
event stream:

  rollups    a fixed ring of time-aligned windows per tier (10s/1m/10m)
             aggregating per (type, plan_hash, admission class, tenant):
             qps, latency p50/p99 on the SHARED metrics.py log-bucket
             geometry (so fleet merges stay lossless), rows scanned/
             matched, device-ms, plan cache-hit rate, shed/
             degrade/error rates.

  sketches   SpaceSaving top-k over plan hashes and tenants plus the
             hot-cell grid over coarse Morton cells (obs/sketches.py) —
             a spatial heatmap of query load.

  hot_set()  the STABLE feed the future result cache consumes: top plan
             hashes + hot cells with explicit confidence bounds
             (estimate is never an undercount; estimate - error is
             never an overcount).

  tenant.*   per-tenant metering counters (queries / device-ms / rows
             scanned) in the process metrics registry, federated like
             every other counter.

Hot-path discipline: producers pay ONE bounded-deque append per event
(obs/flight.py tees each wide event / lazily-recorded trace here);
aggregation happens at read time via ``drain()``, chained into the
registry's pre-drain hook alongside tail sampling — the same deferred
pattern that keeps the obs overhead guard under 5%.

Fleet merge: ``export_state()`` rides the ``/metrics?format=state``
scrape payload; windows merge exactly like histograms (bucket-count
sums over identical wall-clock-aligned window starts), sketches merge
per obs/sketches.py — ``merge_states`` + ``from_state`` back the
Federator's ``GET /fleet/workload``.

Import discipline (obs/__init__ rule): config/metrics + obs.sketches
only — never planner/scheduler/datastore layers (obs.flight imports are
deferred to drain time).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from geomesa_tpu import config
from geomesa_tpu.metrics import (Histogram, REGISTRY as _metrics,
                                 bucket_index)
from geomesa_tpu.obs import sketches as _sk

# window tiers (seconds): the short window answers "now", the long ones
# smooth bursts — all wall-clock aligned so per-node windows line up
SPANS = (10.0, 60.0, 600.0)

# cached GEOMESA_TPU_WORKLOAD verdict for the per-event offer() (same
# refresh pattern as obs.__init__._obs_enabled — no env read per query)
_enabled_cache = [True, 0]
_ENABLED_REFRESH = 64


def enabled() -> bool:
    c = _enabled_cache
    c[1] -= 1
    if c[1] <= 0:
        c[0] = bool(config.WORKLOAD_ENABLED.get())
        c[1] = _ENABLED_REFRESH
    return c[0]


# drain-time fold hooks: other obs planes (shardwatch's per-cell cost
# accumulator) observe every folded event WITHOUT touching the producer
# hot path — hooks run under the analytics lock at drain time and must
# never raise into the fold
_FOLD_HOOKS: List = []


def add_fold_hook(fn) -> None:
    """Register ``fn(event_dict)`` to run for every event folded at
    drain time (idempotent per function)."""
    if fn not in _FOLD_HOOKS:
        _FOLD_HOOKS.append(fn)


def tenant_metric_label(tenant) -> str:
    """A metrics-safe tenant label (the ``tenant.*`` counter namespace
    must stay bounded and exposition-clean)."""
    t = str(tenant or "default")[:64]
    return "".join(c if c.isalnum() or c in "._-" else "_" for c in t) \
        or "default"


class _Group:
    """One (type, plan_hash, priority, tenant) aggregate inside one
    window. Latency buckets use the shared metrics.py geometry so two
    nodes' groups merge by plain bucket-count sums."""

    __slots__ = ("n", "errors", "shed", "degraded", "cancelled",
                 "plan_hits", "plan_known",
                 "rows_scanned", "rows_matched", "device_ms", "buckets")

    def __init__(self):
        self.n = 0
        self.errors = 0
        self.shed = 0
        self.degraded = 0
        self.cancelled = 0
        self.plan_hits = 0
        self.plan_known = 0
        self.rows_scanned = 0
        self.rows_matched = 0
        self.device_ms = 0.0
        self.buckets: Dict[int, int] = {}

    def fold(self, ev: dict) -> None:
        self.n += 1
        if ev.get("error"):
            self.errors += 1
        if ev.get("shed"):
            self.shed += 1
        if ev.get("degraded"):
            self.degraded += 1
        if ev.get("cancelled"):
            self.cancelled += 1
        ph = ev.get("plan_cache_hit")
        if ph is not None:
            self.plan_known += 1
            self.plan_hits += bool(ph)
        self.rows_scanned += int(ev.get("rows_scanned") or 0)
        self.rows_matched += int(ev.get("rows_matched") or 0)
        self.device_ms += float(ev.get("device_ms") or 0.0)
        dur = ev.get("duration_ms")
        if dur is not None:
            bi = bucket_index(float(dur) / 1000.0)
            self.buckets[bi] = self.buckets.get(bi, 0) + 1

    def merge(self, other: "_Group") -> None:
        self.n += other.n
        self.errors += other.errors
        self.shed += other.shed
        self.degraded += other.degraded
        self.cancelled += other.cancelled
        self.plan_hits += other.plan_hits
        self.plan_known += other.plan_known
        self.rows_scanned += other.rows_scanned
        self.rows_matched += other.rows_matched
        self.device_ms += other.device_ms
        for bi, c in other.buckets.items():
            self.buckets[bi] = self.buckets.get(bi, 0) + c

    def to_state(self) -> dict:
        return {"n": self.n, "errors": self.errors, "shed": self.shed,
                "degraded": self.degraded, "cancelled": self.cancelled,
                "plan_hits": self.plan_hits, "plan_known": self.plan_known,
                "rows_scanned": self.rows_scanned,
                "rows_matched": self.rows_matched,
                "device_ms": round(self.device_ms, 3),
                "buckets": {str(bi): c
                            for bi, c in sorted(self.buckets.items())}}

    @classmethod
    def from_state(cls, st: dict) -> "_Group":
        g = cls()
        for f in ("n", "errors", "shed", "degraded", "cancelled",
                  "plan_hits", "plan_known",
                  "rows_scanned", "rows_matched"):
            setattr(g, f, int(st.get(f, 0)))
        g.device_ms = float(st.get("device_ms", 0.0))
        g.buckets = {int(bi): int(c)
                     for bi, c in (st.get("buckets") or {}).items()}
        return g

    def _percentile_ms(self, q: float) -> float:
        h = Histogram()
        h.count = self.n if self.n else sum(self.buckets.values())
        for bi, c in self.buckets.items():
            h.buckets[bi] = c
        return round(h.percentile(q) * 1000.0, 3)

    def summarize(self, span_s: float) -> dict:
        n = self.n
        return {
            "n": n,
            "qps": round(n / span_s, 3),
            "p50_ms": self._percentile_ms(0.50),
            "p99_ms": self._percentile_ms(0.99),
            "error_rate": round(self.errors / n, 4) if n else 0.0,
            "shed_rate": round(self.shed / n, 4) if n else 0.0,
            "degrade_rate": round(self.degraded / n, 4) if n else 0.0,
            "cancel_rate": round(self.cancelled / n, 4) if n else 0.0,
            "plan_cache_hit_rate": round(
                self.plan_hits / self.plan_known, 4)
            if self.plan_known else None,
            "rows_scanned": self.rows_scanned,
            "rows_matched": self.rows_matched,
            "device_ms": round(self.device_ms, 3),
        }


class _Window:
    __slots__ = ("start", "span", "groups")

    def __init__(self, start: float, span: float):
        self.start = start
        self.span = span
        self.groups: Dict[str, _Group] = {}

    @property
    def n(self) -> int:
        return sum(g.n for g in self.groups.values())

    def fold(self, key: str, ev: dict) -> None:
        g = self.groups.get(key)
        if g is None:
            g = self.groups[key] = _Group()
        g.fold(ev)

    def to_state(self) -> dict:
        return {"start": self.start, "span": self.span,
                "groups": {k: g.to_state()
                           for k, g in sorted(self.groups.items())}}

    @classmethod
    def from_state(cls, st: dict) -> "_Window":
        w = cls(float(st.get("start", 0.0)), float(st.get("span", 0.0)))
        for k, gs in (st.get("groups") or {}).items():
            w.groups[k] = _Group.from_state(gs)
        return w


class _WindowRing:
    """Fixed ring of wall-clock-aligned windows for one tier. Not
    internally locked — the analytics lock covers it."""

    def __init__(self, span_s: float, keep: int):
        self.span = float(span_s)
        self.keep = max(1, int(keep))
        self.windows: deque = deque()   # ascending by start
        self.retired_events = 0         # events in rotated-out windows
        self.late_dropped = 0           # older than the retained horizon

    def fold(self, ts_s: float, key: str, ev: dict) -> None:
        """Invariant: the ring holds the NEWEST <= keep wall-aligned
        windows in ascending start order. Conservation: every folded
        event is retained, retired (rotated out), or late-dropped."""
        start = (ts_s // self.span) * self.span
        ws = self.windows
        if ws and start < ws[0].start and len(ws) >= self.keep:
            self.late_dropped += 1  # older than the retained horizon
            return
        # find-or-insert in place (rings are tiny: <= keep entries); the
        # newest window is the hot one, so scan from the right
        for i in range(len(ws) - 1, -1, -1):
            if ws[i].start == start:
                ws[i].fold(key, ev)
                return
            if ws[i].start < start:
                w = _Window(start, self.span)
                ws.insert(i + 1, w)
                break
        else:
            w = _Window(start, self.span)
            ws.insert(0, w)
        w.fold(key, ev)
        while len(ws) > self.keep:
            self.retired_events += ws.popleft().n

    def total_events(self) -> int:
        return sum(w.n for w in self.windows)


def _group_key(ev: dict) -> str:
    return "|".join((str(ev.get("type") or "-"),
                     str(ev.get("plan_hash") or "-"),
                     str(ev.get("priority") or "-"),
                     str(ev.get("tenant") or "default")))


class WorkloadAnalytics:
    """The streaming workload-analytics plane (one per process).

    Producers call ``offer()`` (one bounded deque append); everything
    else — window folding, sketch updates, tenant metering — happens in
    ``drain()``, which the obs pre-drain hook runs before any metrics/
    events/workload read."""

    def __init__(self, clock=time.time, spans=SPANS,
                 keep: Optional[int] = None,
                 sketch_capacity: Optional[int] = None,
                 meter: bool = True):
        self._clock = clock
        self._lock = threading.Lock()
        self._pending: deque = deque()
        self._keep = keep
        self._meter = meter
        k = int(keep if keep is not None
                else config.WORKLOAD_WINDOWS.get())
        cap = int(sketch_capacity if sketch_capacity is not None
                  else config.WORKLOAD_SKETCH_K.get())
        self.rings = {s: _WindowRing(s, k) for s in spans}
        self.plans = _sk.SpaceSaving(cap)
        self.tenants = _sk.SpaceSaving(cap)
        self.cells = _sk.SpaceSaving(cap)
        self.funcs = _sk.SpaceSaving(cap)
        self.consumed = 0
        self.dropped = 0

    # -- producer side (hot path) ---------------------------------------------

    def offer(self, item) -> None:
        """Enqueue one wide event (dict) or closed root trace for
        deferred aggregation. deque appends are GIL-atomic; the bound
        check is advisory (an over-append is harmless)."""
        if not enabled():
            return
        if len(self._pending) >= int(config.WORKLOAD_PENDING.get()):
            self.dropped += 1
            return
        self._pending.append(item)

    # -- consumer side (deferred) ---------------------------------------------

    def drain(self) -> int:
        """Fold the events pending at entry into windows/sketches/meters.
        Reentrancy-safe and cheap when idle (one truthiness check). What
        producers append meanwhile waits for the next reader: a drain that
        ran until the queue was empty never returned while eight clients
        kept it filling, and the page that had asked for it (``/healthz``,
        ``/metrics``) timed out after 120 s (PR 28, `select-c8` rehearsed on
        the CPU, where a select is answered faster than it is folded)."""
        if not self._pending:
            return 0
        out = 0
        with self._lock:
            for _ in range(len(self._pending)):
                try:
                    item = self._pending.popleft()
                except IndexError:
                    break
                ev = item
                if not isinstance(ev, dict):
                    # lazily-enqueued root trace: materialize the wide
                    # event now, at read time (mirrors flight.recent())
                    from geomesa_tpu.obs import flight as _flight
                    try:
                        ev = _flight.event_from_trace(item)
                    except Exception:
                        continue
                if ev.get("kind") == "batch":
                    continue  # per-query events already carry device_ms
                self._fold_event(ev)
                out += 1
        return out

    def _fold_event(self, ev: dict) -> None:
        self.consumed += 1
        if self._meter:  # read-only from_state views skip the hooks too
            for hook in _FOLD_HOOKS:
                try:
                    hook(ev)
                except Exception:
                    pass
        ts_s = float(ev.get("ts_ms") or self._clock() * 1000.0) / 1000.0
        key = _group_key(ev)
        for ring in self.rings.values():
            ring.fold(ts_s, key, ev)
        ph = ev.get("plan_hash")
        if ph:
            self.plans.offer(str(ph))
        tenant = str(ev.get("tenant") or "default")
        self.tenants.offer(tenant)
        cell = ev.get("cell")
        if cell:
            self.cells.offer(str(cell))
        # each distinct st_* name counts ONCE per query (funcs_of dedups
        # repeated occurrences at IR level), so sketch totals are
        # queries-touching-the-function, never call-site counts
        for fn in (ev.get("funcs") or ()):
            self.funcs.offer(str(fn))
        if self._meter:
            label = tenant_metric_label(tenant)
            _metrics.inc(f"tenant.{label}.queries")
            if ev.get("cache") == "result":
                # result-cache hit: the device/scan cost was billed when
                # the original dispatch ran — replaying it here would
                # double-count device time and rows against the tenant
                return
            dms = float(ev.get("device_ms") or 0.0)
            if dms:
                _metrics.inc(f"tenant.{label}.device_ms", dms)
            rows = int(ev.get("rows_scanned") or 0)
            if rows:
                _metrics.inc(f"tenant.{label}.rows_scanned", rows)

    # -- read surfaces --------------------------------------------------------

    def hot_set(self, k: Optional[int] = None) -> dict:
        """The stable feed a result cache consumes: top plan hashes and
        hot cells with explicit confidence bounds. For every entry,
        ``count`` is never an undercount of the true frequency and
        ``count - error`` is never an overcount — a consumer that wants
        certainty keys on ``count - error``."""
        self.drain()
        k = int(k if k is not None else config.WORKLOAD_HOTSET_K.get())

        def entries(sk: _sk.SpaceSaving, with_bbox: bool = False):
            total = sk.n_total
            out = []
            for key, est, err in sk.top(k):
                e = {"key": key, "count": est, "error": err,
                     "at_least": est - err,
                     "fraction": round(est / total, 4) if total else 0.0}
                if with_bbox:
                    e["bbox"] = _sk.cell_bbox(key)
                out.append(e)
            return out

        return {"total": self.plans.n_total,
                "plans": entries(self.plans),
                "cells": entries(self.cells, with_bbox=True),
                "funcs": entries(self.funcs),
                "sketch_capacity": self.plans.capacity}

    def top_tenants(self, k: int = 10) -> List[dict]:
        self.drain()
        total = self.tenants.n_total
        return [{"tenant": t, "count": est, "error": err,
                 "fraction": round(est / total, 4) if total else 0.0}
                for t, est, err in self.tenants.top(k)]

    def rollups(self) -> dict:
        """Per-tier windowed rollups, newest window first, each group
        summarized (qps, p50/p99, rates) from its mergeable state."""
        self.drain()
        out = {}
        for span, ring in sorted(self.rings.items()):
            out[f"{int(span)}s"] = [
                {"start": w.start, "span_s": span, "n": w.n,
                 "groups": {key: g.summarize(span)
                            for key, g in sorted(w.groups.items())}}
                for w in reversed(ring.windows)]
        return out

    def summary(self) -> dict:
        self.drain()
        return {"enabled": enabled(),
                "consumed": self.consumed,
                "dropped": self.dropped,
                "pending": len(self._pending),
                "retired_events": {f"{int(s)}s": r.retired_events
                                   for s, r in sorted(self.rings.items())},
                "hot_set": self.hot_set(),
                "tenants": self.top_tenants(),
                "rollups": self.rollups()}

    def clear(self) -> None:
        with self._lock:
            self._pending.clear()
            k = int(self._keep if self._keep is not None
                    else config.WORKLOAD_WINDOWS.get())
            self.rings = {s: _WindowRing(s, k) for s in self.rings}
            cap = self.plans.capacity
            self.plans = _sk.SpaceSaving(cap)
            self.tenants = _sk.SpaceSaving(cap)
            self.cells = _sk.SpaceSaving(cap)
            self.funcs = _sk.SpaceSaving(cap)
            self.consumed = 0
            self.dropped = 0

    # -- federation -----------------------------------------------------------

    def export_state(self) -> dict:
        """Mergeable wire form for the /metrics?format=state payload —
        windows carry raw bucket counts (merge by summation over equal
        aligned starts), sketches their (count, error) items."""
        self.drain()
        with self._lock:
            return {
                "spans": {str(int(s)): [w.to_state() for w in r.windows]
                          for s, r in sorted(self.rings.items())},
                "plans": self.plans.to_state(),
                "tenants": self.tenants.to_state(),
                "cells": self.cells.to_state(),
                "funcs": self.funcs.to_state(),
                "consumed": self.consumed,
                "dropped": self.dropped,
            }

    @classmethod
    def from_state(cls, state: dict) -> "WorkloadAnalytics":
        """Rebuild a read-only analytics view from (merged) state —
        the Federator's path to fleet hot_set()/rollups()."""
        spans = sorted(float(s) for s in (state.get("spans") or
                                          {str(int(s)): 0 for s in SPANS}))
        w = cls(spans=tuple(spans) or SPANS, keep=max(
            1, max((len(v) for v in (state.get("spans") or {}).values()),
                   default=1)), sketch_capacity=1, meter=False)
        for s_str, windows in (state.get("spans") or {}).items():
            ring = w.rings.get(float(s_str))
            if ring is None:
                continue
            for wst in sorted(windows, key=lambda x: x.get("start", 0.0)):
                ring.windows.append(_Window.from_state(wst))
        w.plans = _sk.SpaceSaving.from_state(state.get("plans") or {})
        w.tenants = _sk.SpaceSaving.from_state(state.get("tenants") or {})
        w.cells = _sk.SpaceSaving.from_state(state.get("cells") or {})
        w.funcs = _sk.SpaceSaving.from_state(state.get("funcs") or {})
        w.consumed = int(state.get("consumed", 0))
        w.dropped = int(state.get("dropped", 0))
        return w


def merge_states(states: List[dict]) -> dict:
    """Merge per-node workload states exactly the way the Federator
    merges histograms: windows with equal (span, start) merge by bucket/
    count summation; sketches merge per obs/sketches.py (commutative)."""
    spans: Dict[str, Dict[float, _Window]] = {}
    plan_sk, ten_sk, cell_sk, func_sk = [], [], [], []
    consumed = dropped = 0
    for st in states:
        if not st:
            continue
        consumed += int(st.get("consumed", 0))
        dropped += int(st.get("dropped", 0))
        plan_sk.append(_sk.SpaceSaving.from_state(st.get("plans") or {}))
        ten_sk.append(_sk.SpaceSaving.from_state(st.get("tenants") or {}))
        cell_sk.append(_sk.SpaceSaving.from_state(st.get("cells") or {}))
        func_sk.append(_sk.SpaceSaving.from_state(st.get("funcs") or {}))
        for s_str, windows in (st.get("spans") or {}).items():
            tier = spans.setdefault(s_str, {})
            for wst in windows:
                w = _Window.from_state(wst)
                have = tier.get(w.start)
                if have is None:
                    tier[w.start] = w
                else:
                    for k, g in w.groups.items():
                        if k in have.groups:
                            have.groups[k].merge(g)
                        else:
                            have.groups[k] = g
    return {
        "spans": {s: [w.to_state()
                      for _, w in sorted(tier.items())]
                  for s, tier in sorted(spans.items())},
        "plans": _sk.SpaceSaving.merge_all(plan_sk).to_state()
        if plan_sk else {},
        "tenants": _sk.SpaceSaving.merge_all(ten_sk).to_state()
        if ten_sk else {},
        "cells": _sk.SpaceSaving.merge_all(cell_sk).to_state()
        if cell_sk else {},
        "funcs": _sk.SpaceSaving.merge_all(func_sk).to_state()
        if func_sk else {},
        "consumed": consumed,
        "dropped": dropped,
    }


# process-global analytics plane (the serving shape: one per process)
WORKLOAD = WorkloadAnalytics()
