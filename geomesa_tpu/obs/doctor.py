"""Fleet doctor: rule-driven anomaly detectors over the telemetry planes.

PRs 5-10 made the fleet visible — stitched traces, federated metrics,
SLO burn rates, recompile profiling, replication telemetry, the workload
hot-set feed — but nothing INTERPRETED any of it. The doctor runs a
fixed rule set over the local registry (and, when a Federator is
configured, the fleet-merged state) on the same injectable clock as
``obs/slo.py``, turning raw counters into attributed incidents:

  slo_burn          multi-window burn-rate page/ticket decisions, reusing
                    the unmodified SloEngine policies (local + fleet; the
                    fleet evaluation suppresses pages computed from a
                    partial merge — see Federator.slo)
  replication_lag   decay-based ``replication.lag_ms`` gauge over its
                    threshold OR a sequence backlog (``lag_seqs``) — the
                    stalled/dead-follower signal
  recompile_churn   ``kernels.recompiles`` advancing faster than the
                    per-minute bar inside the window; the suspect kernel
                    is named from the recompile flight events
  shed_storm        ``admission.shed`` rate over the bar; the dominant
                    shed priority class is the suspect
  breaker_flapping  open/close transition EDGES on one breaker inside
                    the window (state thrash, not steady open)
  wal_fsync_stall   new ``wal.fsync_errors``/retries — durability faults
                    page immediately by default
  hot_skew          one plan/cell/tenant whose GUARANTEED (at_least)
                    share of the workload window exceeds the bar
  shard_imbalance   the shardwatch ledger's GUARANTEED max-over-mean
                    per-shard load ratio over the bar — names the hot
                    shard and carries its projected split keys
  collective_straggler
                    one rank repeatedly the slowest arriver in cluster
                    collective rounds (over-bar spread counts charged
                    by cluster/runtime.py straggler attribution)

Every firing opens (or dedups into) an incident via ``obs/incidents.py``
with a correlated timeline snapshot; detectors that stay clear close
their incident with a resolution record. Evaluation happens ONLY on
read/tick surfaces (``/alerts``, ``/incidents``, the CLI) — the query
hot path never pays for the doctor (the <5% obs-overhead guard holds
with it enabled at defaults).

Import discipline (obs/__init__ rule): config/metrics/trace/obs.* only.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

from geomesa_tpu import config
from geomesa_tpu import trace as _trace
from geomesa_tpu.metrics import REGISTRY as _metrics
from geomesa_tpu.obs.history import SeriesStore
from geomesa_tpu.obs.incidents import IncidentStore

# rule -> (severity default, one-line description — the CLI/docs table)
RULES: Dict[str, Tuple[str, str]] = {
    "slo_burn": ("page", "multi-window SLO burn over page/ticket policy"),
    "replication_lag": ("page", "follower lag_ms/seq backlog over bar"),
    "recompile_churn": ("ticket", "kernels.recompiles rate over bar"),
    "shed_storm": ("page", "admission.shed rate over bar"),
    "breaker_flapping": ("ticket", "breaker open/close edges in window"),
    "wal_fsync_stall": ("page", "new WAL fsync errors/retries"),
    "hot_skew": ("ticket", "single plan/cell/tenant dominates window"),
    "reindex_churn": ("ticket", "build aborts/failed installs or "
                                "merge-fraction breaches over bar"),
    "shard_imbalance": ("ticket", "guaranteed per-shard load "
                                  "max-over-mean ratio over bar"),
    "collective_straggler": ("ticket", "one rank repeatedly slowest in "
                                       "collective rounds"),
    "shard_dark": ("page", "a shard cell with ZERO serving endpoints "
                           "in the router topology"),
    "slo_trend": ("page", "burn-rate slope projects a page within the "
                          "lead horizon before slo_burn fires"),
    "capacity_trend": ("ticket", "per-shard load growth slope projects "
                                 "imbalance within the lead horizon"),
}


class DoctorEngine:
    """The rule evaluator. All collaborators are injectable (registry,
    clock, SLO engine, federator, workload plane, incident store) so
    tests drive it deterministically; the process-global ``DOCTOR``
    late-binds every one of them to the process globals."""

    def __init__(self, registry=None, clock=time.monotonic,
                 slo_engine=None, store: Optional[IncidentStore] = None,
                 journal_path: Optional[str] = None,
                 federator=None, workload=None, shardwatch=None,
                 router=None, forensics=None):
        self._reg = registry if registry is not None else _metrics
        self._clock = clock
        self._slo = slo_engine          # None -> late-bind slo.ENGINE
        self._federator = federator     # None -> late-bind federation
        self._workload = workload       # None -> late-bind WORKLOAD
        self._shardwatch = shardwatch   # None -> late-bind WATCH
        self._router = router           # shard_dark: the routing view
        self.store = store if store is not None else IncidentStore(
            journal_path=journal_path, registry=self._reg,
            node=_trace.node_id())
        self._lock = threading.RLock()
        self._forensics = forensics     # None -> late-bind FORENSICS;
        #                                 False -> capture disabled
        # per-counter retained series for the windowed rate detectors and
        # the predictive trend rules (obs/history.py SeriesStore — each
        # engine owns ONE, so a fresh doctor never fires on preexisting
        # totals and tests stay isolated)
        self.history = SeriesStore()

    # -- late-bound collaborators ---------------------------------------------

    def _slo_engine(self):
        if self._slo is not None:
            return self._slo
        from geomesa_tpu.obs import slo as _slo
        return _slo.ENGINE

    def _fed(self):
        if self._federator is False:    # fleet checks explicitly disabled
            return None
        if self._federator is not None:
            return self._federator
        from geomesa_tpu.obs import federation as _fed
        return _fed.federator()

    def _wl(self):
        if self._workload is not None:
            return self._workload
        from geomesa_tpu.obs import workload as _wl
        return _wl.WORKLOAD

    def _sw(self):
        if self._shardwatch is not None:
            return self._shardwatch
        from geomesa_tpu.obs import shardwatch as _shardwatch
        return _shardwatch.WATCH

    def _fstore(self):
        if self._forensics is False:    # capture explicitly disabled
            return None
        if self._forensics is not None:
            return self._forensics
        from geomesa_tpu.obs import forensics as _forensics
        return _forensics.FORENSICS

    # -- windowed counter deltas ----------------------------------------------

    def _delta(self, key: str, value: float, now: float,
               window_s: float) -> Tuple[float, float]:
        """(per-minute rate, absolute delta) of a counter over the
        trailing window, backed by the engine's retained SeriesStore
        (obs/history.py) — the same store the predictive trend rules
        query, replacing the ad-hoc per-detector deques. The first
        sighting of a counter contributes no delta, so a fresh doctor
        never fires on preexisting totals."""
        self.history.observe(key, value, now, window_s=window_s)
        return self.history.window(key, now, window_s)

    # -- detectors (each returns a list of alert dicts) -----------------------

    def _check_slo(self, now: float) -> List[dict]:
        alerts = []
        engine = self._slo_engine()
        local = engine.evaluate() if engine else {}
        scopes = [("local", local)]
        fed = self._fed()
        if fed is not None:
            try:
                scopes.append(("fleet", fed.slo()))
            except Exception:
                self._reg.inc("doctor.detector_errors")
        for scope, res in scopes:
            for name, obj in sorted((res or {}).items()):
                if not isinstance(obj, dict):
                    continue
                status = obj.get("status")
                if status not in ("page", "ticket"):
                    continue
                detail = {"scope": scope,
                          "burn_rates": obj.get("burn_rates"),
                          "compliance": obj.get("compliance"),
                          "error_budget": obj.get("error_budget")}
                if obj.get("page_suppressed"):
                    detail["page_suppressed"] = True
                alerts.append({
                    "rule": "slo_burn", "severity": status,
                    "cause": f"{scope}-slo:{name}",
                    "detail": detail,
                    "suspect": {"objective": name, "scope": scope},
                    "match": {"slow_ms": config.SLO_LATENCY_MS.get()},
                })
        alerts.extend(self._check_slo_trend(now, local))
        return alerts

    def _check_slo_trend(self, now: float, results: dict) -> List[dict]:
        """slo_trend: the PREDICTIVE page. Every evaluation feeds each
        objective's 5m burn rate into the engine's retained series; a
        positive fitted slope whose projection crosses the page bar
        within DOCTOR_TREND_LEAD_S fires while the current burn is still
        under it — the trend page leads the slo_burn page by design
        (proven by the ramped-handicap drill in obs/trenddrill.py). An
        objective already at page status stays slo_burn's: prediction
        never shadows the fact."""
        trend_on = bool(config.DOCTOR_TREND.get())
        window = float(config.DOCTOR_WINDOW_S.get())
        lead = float(config.DOCTOR_TREND_LEAD_S.get())
        min_pts = max(2, int(config.DOCTOR_TREND_MIN_POINTS.get()))
        from geomesa_tpu.obs.slo import PAGE_BURN
        alerts: List[dict] = []
        for name, obj in sorted((results or {}).items()):
            if not isinstance(obj, dict):
                continue
            burn = (obj.get("burn_rates") or {}).get("5m")
            if burn is None:
                continue            # no traffic in the window: no signal
            key = f"slo.burn5m.{name}"
            # the series samples every tick (not just near the bar) so
            # the fit has a baseline by the time a ramp starts
            self.history.observe(key, float(burn), now, window_s=window)
            if not trend_on:
                continue
            current = float(burn)
            if current >= PAGE_BURN or obj.get("status") == "page":
                continue
            if self.history.points(key, now, window) < min_pts:
                continue
            slope = self.history.slope(key, now, window)
            if slope <= 0.0:
                continue
            projected = current + slope * lead
            if projected < PAGE_BURN:
                continue
            eta_s = (PAGE_BURN - current) / slope
            alerts.append({
                "rule": "slo_trend", "severity": "page",
                "cause": f"trend-slo:{name}",
                "detail": {"burn_5m": round(current, 3),
                           "slope_per_s": round(slope, 5),
                           "projected": round(projected, 3),
                           "page_bar": PAGE_BURN,
                           "lead_s": lead,
                           "eta_s": round(eta_s, 1)},
                "suspect": {"objective": name,
                            "page_projected_in_s": round(eta_s, 1)},
                "match": {"slow_ms": config.SLO_LATENCY_MS.get()},
            })
        return alerts

    def _check_replication(self, now: float, gauges: dict) -> List[dict]:
        try:
            lag_ms = float(gauges.get("replication.lag_ms") or 0.0)
            lag_seqs = int(gauges.get("replication.lag_seqs") or 0)
        except (TypeError, ValueError):
            return []
        bar_ms = float(config.DOCTOR_LAG_MS.get())
        bar_seqs = int(config.DOCTOR_LAG_SEQS.get())
        over_ms = bar_ms > 0 and lag_ms > bar_ms
        over_seqs = bar_seqs > 0 and lag_seqs >= bar_seqs
        if not (over_ms or over_seqs):
            return []
        why = "lag_ms" if over_ms else "lag_seqs"
        return [{
            "rule": "replication_lag", "severity": "page",
            "cause": f"replication:{why}",
            "detail": {"lag_ms": round(lag_ms, 1), "lag_seqs": lag_seqs,
                       "bar_ms": bar_ms, "bar_seqs": bar_seqs},
            "suspect": {"role": _trace.node_role(), "signal": why},
            "match": {"kind": "repl.apply"},
        }]

    def _check_recompiles(self, now: float, counters: dict) -> List[dict]:
        v = counters.get("kernels.recompiles", 0)
        window = float(config.DOCTOR_WINDOW_S.get())
        rate, delta = self._delta("kernels.recompiles", v, now, window)
        bar = float(config.DOCTOR_RECOMPILES_PER_MIN.get())
        if bar <= 0 or delta <= 0 or rate < bar:
            return []
        suspect: dict = {}
        try:
            from geomesa_tpu.obs.flight import RECORDER
            kernels: Dict[str, int] = {}
            for e in RECORDER.recent(limit=64, kind="kernel.recompile"):
                k = str(e.get("kernel") or e.get("name") or "?")
                kernels[k] = kernels.get(k, 0) + 1
            if kernels:
                top = max(kernels.items(), key=lambda kv: kv[1])
                suspect = {"kernel": top[0], "recent_recompiles": top[1]}
        except Exception:
            pass
        return [{
            "rule": "recompile_churn", "severity": "ticket",
            "cause": "kernels:recompiles",
            "detail": {"rate_per_min": round(rate, 2), "delta": delta,
                       "bar_per_min": bar, "total": int(v)},
            "suspect": suspect,
            "match": {"kind": "kernel.recompile"},
        }]

    def _check_shed(self, now: float, counters: dict) -> List[dict]:
        window = float(config.DOCTOR_WINDOW_S.get())
        rate, delta = self._delta("admission.shed",
                                  counters.get("admission.shed", 0),
                                  now, window)
        # per-class deltas ride along so the dominant class is nameable
        classes = {}
        for k, v in counters.items():
            if k.startswith("admission.shed."):
                _r, d = self._delta(k, v, now, window)
                if d > 0:
                    classes[k[len("admission.shed."):]] = d
        bar = float(config.DOCTOR_SHED_PER_MIN.get())
        if bar <= 0 or delta <= 0 or rate < bar:
            return []
        suspect = {}
        if classes:
            top = max(classes.items(), key=lambda kv: kv[1])
            suspect = {"priority": top[0], "shed_in_window": int(top[1])}
        return [{
            "rule": "shed_storm", "severity": "page",
            "cause": "admission:shed",
            "detail": {"rate_per_min": round(rate, 2), "delta": delta,
                       "bar_per_min": bar,
                       "by_class": {k: int(v) for k, v in classes.items()}},
            "suspect": suspect,
            "match": {"errors": True},
        }]

    def _top_type(self, counters: dict, families: Tuple[str, ...],
                  now: float, window: float) -> dict:
        """Dominant per-type delta across the given counter families —
        the suspect names the TYPE whose builds are churning."""
        types: Dict[str, float] = {}
        for fam in families:
            prefix = fam + "."
            for k, v in counters.items():
                if k.startswith(prefix):
                    _r, d = self._delta(k, v, now, window)
                    if d > 0:
                        t = k[len(prefix):]
                        types[t] = types.get(t, 0) + d
        if not types:
            return {}
        top = max(types.items(), key=lambda kv: kv[1])
        return {"type": top[0], "events_in_window": int(top[1])}

    def _check_reindex(self, now: float, counters: dict) -> List[dict]:
        """reindex_churn: the background build machinery is spinning
        without converging — repeated build aborts / failed installs
        (reindex:churn), or the incremental merge path falling back to
        full rebuilds every flush (build:merge_fraction_breach)."""
        window = float(config.DOCTOR_WINDOW_S.get())
        alerts: List[dict] = []
        # per-type deltas sample every tick (not just on firing) so the
        # suspect's baseline exists by the time a bar is crossed
        churn_suspect = self._top_type(
            counters, ("reindex.aborts", "reindex.failures"), now, window)
        breach_suspect = self._top_type(
            counters, ("ingest.merge_fraction_breaches",), now, window)
        churn = counters.get("reindex.aborts", 0) \
            + counters.get("reindex.failures", 0)
        rate, delta = self._delta("reindex.churn", churn, now, window)
        bar = float(config.DOCTOR_REINDEX_PER_MIN.get())
        if bar > 0 and delta > 0 and rate >= bar:
            alerts.append({
                "rule": "reindex_churn", "severity": "ticket",
                "cause": "reindex:churn",
                "detail": {"rate_per_min": round(rate, 2),
                           "delta": int(delta), "bar_per_min": bar,
                           "aborts": int(counters.get("reindex.aborts", 0)),
                           "failures": int(
                               counters.get("reindex.failures", 0))},
                "suspect": churn_suspect,
                "match": {"kind": "reindex"},
            })
        breaches = counters.get("ingest.merge_fraction_breaches", 0)
        rate, delta = self._delta("ingest.merge_fraction_breaches",
                                  breaches, now, window)
        bar = float(config.DOCTOR_MERGE_BREACHES_PER_MIN.get())
        if bar > 0 and delta > 0 and rate >= bar:
            alerts.append({
                "rule": "reindex_churn", "severity": "ticket",
                "cause": "build:merge_fraction_breach",
                "detail": {"rate_per_min": round(rate, 2),
                           "delta": int(delta), "bar_per_min": bar,
                           "max_fraction":
                               float(config.MERGE_MAX_FRACTION.get())},
                "suspect": breach_suspect,
                "match": {"kind": "reindex"},
            })
        return alerts

    def _check_breakers(self, now: float, counters: dict) -> List[dict]:
        window = float(config.DOCTOR_WINDOW_S.get())
        bar = int(config.DOCTOR_BREAKER_FLAPS.get())
        edges: Dict[str, float] = {}
        for k, v in counters.items():
            if not k.startswith("breaker."):
                continue
            if k.endswith(".opened") or k.endswith(".closed"):
                name = k[len("breaker."):k.rfind(".")]
                _r, d = self._delta(k, v, now, window)
                edges[name] = edges.get(name, 0.0) + max(0.0, d)
        alerts = []
        for name, flaps in sorted(edges.items()):
            if bar <= 0 or flaps < bar:
                continue
            alerts.append({
                "rule": "breaker_flapping", "severity": "ticket",
                "cause": f"breaker:{name}",
                "detail": {"edges_in_window": int(flaps), "bar": bar,
                           "window_s": window},
                "suspect": {"breaker": name},
                "match": {"errors": True},
            })
        return alerts

    def _check_wal(self, now: float, counters: dict) -> List[dict]:
        window = float(config.DOCTOR_WINDOW_S.get())
        bar = int(config.DOCTOR_FSYNC_ERRORS.get())
        _r, errs = self._delta("wal.fsync_errors",
                               counters.get("wal.fsync_errors", 0),
                               now, window)
        _r, retries = self._delta("wal.fsync_retries",
                                  counters.get("wal.fsync_retries", 0),
                                  now, window)
        faults = errs + retries
        if bar <= 0 or faults < bar:
            return []
        return [{
            "rule": "wal_fsync_stall", "severity": "page",
            "cause": "wal:fsync",
            "detail": {"errors_in_window": int(errs),
                       "retries_in_window": int(retries), "bar": bar},
            "suspect": {"path": "wal"},
            "match": {"errors": True},
        }]

    def _check_skew(self, now: float) -> List[dict]:
        try:
            wl = self._wl()
            hs = wl.hot_set()
            tenants = wl.top_tenants()
        except Exception:
            return []
        total = int(hs.get("total") or 0)
        if total < int(config.DOCTOR_SKEW_MIN.get()):
            return []
        bar = float(config.DOCTOR_SKEW_FRACTION.get())
        if bar <= 0:
            return []
        alerts = []
        dims = [("plan", hs.get("plans") or []),
                ("cell", hs.get("cells") or []),
                ("tenant", tenants or [])]
        for dim, entries in dims:
            if not entries:
                continue
            e = entries[0]
            key = e.get("key", e.get("tenant"))
            at_least = e.get("at_least")
            if at_least is None:
                at_least = max(0, int(e.get("count", 0))
                               - int(e.get("error", 0)))
            share = float(at_least) / float(total)
            if share < bar:
                continue
            suspect = {dim: key, "share_at_least": round(share, 3)}
            if "bbox" in e:
                suspect["bbox"] = e["bbox"]
            alerts.append({
                "rule": "hot_skew", "severity": "ticket",
                "cause": f"skew:{dim}:{key}",
                "detail": {"dimension": dim, "at_least": int(at_least),
                           "window_total": total, "bar_fraction": bar},
                "suspect": suspect,
                "match": {},
            })
        return alerts

    def _check_shard_imbalance(self, now: float) -> List[dict]:
        """shard_imbalance: the shardwatch ledger's GUARANTEED
        (at_least-based) max-over-mean per-shard load ratio over the bar
        with enough guaranteed load to mean anything — the suspect names
        the hot shard and carries its projected split keys (the exact
        boundaries the split/migrate plane will consume)."""
        try:
            rep = self._sw().balance()
        except Exception:
            return []
        if not rep.get("active"):
            return []
        alerts: List[dict] = []
        for tname, tr in sorted((rep.get("types") or {}).items()):
            sc = tr.get("score") or {}
            if not sc.get("over_bar"):
                continue
            hot = sc.get("hot_shard")
            boundaries = (tr.get("splits") or {}).get("boundaries") or []
            hot_row = (tr.get("shards") or {}).get(hot) or {}
            alerts.append({
                "rule": "shard_imbalance", "severity": "ticket",
                "cause": f"shard:{tname}:{hot}",
                "detail": {
                    "type": tname,
                    "max_over_mean": sc.get("max_over_mean"),
                    "max_over_mean_est": sc.get("max_over_mean_est"),
                    "top_cell_fraction": sc.get("top_cell_fraction"),
                    "imbalance": sc.get("imbalance"),
                    "bar": sc.get("bar"),
                    "guaranteed_total": sc.get("guaranteed_total"),
                    "split_keys": [b["key"] for b in boundaries]},
                "suspect": {"type": tname, "shard": hot,
                            "load_share": hot_row.get("load_share"),
                            "key_range": hot_row.get("key_range")},
                "match": {},
            })
        return alerts

    def _check_capacity_trend(self, now: float) -> List[dict]:
        """capacity_trend: the leading signal the split/merge loop will
        consume. Every evaluation feeds each type's GUARANTEED
        max-over-mean shard-load ratio (the shardwatch ledger's honest
        lower bound) into the retained series; a positive slope whose
        projected bar-crossing lands within DOCTOR_CAPACITY_LEAD_S opens
        a predictive ticket naming the hot shard and the projected
        time-to-imbalance. A type already over the bar stays
        shard_imbalance's."""
        trend_on = bool(config.DOCTOR_TREND.get())
        try:
            rep = self._sw().balance()
        except Exception:
            return []
        if not rep.get("active"):
            return []
        window = float(config.DOCTOR_WINDOW_S.get())
        lead = float(config.DOCTOR_CAPACITY_LEAD_S.get())
        min_pts = max(2, int(config.DOCTOR_TREND_MIN_POINTS.get()))
        alerts: List[dict] = []
        for tname, tr in sorted((rep.get("types") or {}).items()):
            sc = tr.get("score") or {}
            mom = sc.get("max_over_mean")
            bar = sc.get("bar")
            if mom is None or bar is None:
                continue
            key = f"shard.mom.{tname}"
            self.history.observe(key, float(mom), now, window_s=window)
            if not trend_on or sc.get("over_bar"):
                continue
            if self.history.points(key, now, window) < min_pts:
                continue
            slope = self.history.slope(key, now, window)
            if slope <= 0.0:
                continue
            eta_s = (float(bar) - float(mom)) / slope
            if eta_s > lead:
                continue
            hot = sc.get("hot_shard")
            hot_row = (tr.get("shards") or {}).get(hot) or {}
            alerts.append({
                "rule": "capacity_trend", "severity": "ticket",
                "cause": f"trend-shard:{tname}",
                "detail": {"type": tname,
                           "max_over_mean": round(float(mom), 3),
                           "slope_per_s": round(slope, 6),
                           "bar": float(bar),
                           "lead_s": lead,
                           "eta_s": round(eta_s, 1)},
                "suspect": {"type": tname, "shard": hot,
                            "load_share": hot_row.get("load_share"),
                            "imbalance_projected_in_s": round(eta_s, 1)},
                "match": {},
            })
        return alerts

    def attach_router(self, router) -> None:
        """Bind the shard-aware router whose topology the shard_dark
        detector should watch (RouterApi does this on startup)."""
        with self._lock:
            self._router = router

    def _check_shard_dark(self, now: float) -> List[dict]:
        """shard_dark: a shard cell with ZERO serving endpoints in the
        router's topology — every read scatter answers partial and every
        owned write has nowhere to land. One deduped incident per shard,
        naming the dark key range and its last-known cell members (the
        page carries exactly what the operator must respawn)."""
        router = self._router
        if router is None or getattr(router, "topology", None) is None:
            return []
        try:
            health = router.shard_health()
        except Exception:
            return []
        alerts: List[dict] = []
        for sid, row in sorted(health.items()):
            if int(row.get("serving", 0)) > 0:
                continue
            alerts.append({
                "rule": "shard_dark", "severity": "page",
                "cause": f"shard:{sid}",
                "detail": {
                    "key_range": row.get("key_range"),
                    "members": row.get("members"),
                    "healthy": int(row.get("healthy", 0))},
                "suspect": {"shard": sid,
                            "key_range": row.get("key_range"),
                            "members": sorted(
                                (row.get("members") or {}).keys())},
                "match": {},
            })
        return alerts

    def _check_straggler(self, now: float, counters: dict) -> List[dict]:
        """collective_straggler: cluster/runtime.py charges one count
        against the slowest rank of every collective round whose spread
        crosses GEOMESA_TPU_DOCTOR_STRAGGLER_MS; a rank accumulating
        DOCTOR_STRAGGLER_ROUNDS of them inside the window is named."""
        window = float(config.DOCTOR_WINDOW_S.get())
        bar = int(config.DOCTOR_STRAGGLER_ROUNDS.get())
        prefix = "cluster.collective.straggler.rank"
        per_rank: Dict[str, float] = {}
        for k, v in counters.items():
            if k.startswith(prefix):
                _r, d = self._delta(k, v, now, window)
                if d > 0:
                    per_rank[k[len(prefix):]] = d
        if bar <= 0 or not per_rank:
            return []
        alerts: List[dict] = []
        for rank, d in sorted(per_rank.items()):
            if d < bar:
                continue
            try:
                rank_id = int(rank)
            except ValueError:
                rank_id = rank
            alerts.append({
                "rule": "collective_straggler", "severity": "ticket",
                "cause": f"collective:rank{rank}",
                "detail": {
                    "over_bar_rounds_in_window": int(d), "bar": bar,
                    "window_s": window,
                    "spread_bar_ms":
                        float(config.DOCTOR_STRAGGLER_MS.get()),
                    "rounds_total": int(
                        counters.get("cluster.collective.rounds", 0))},
                "suspect": {"rank": rank_id},
                "match": {"kind": "collective"},
            })
        return alerts

    # -- the correlated timeline ----------------------------------------------

    def _timeline(self, alert: dict, counters: dict) -> dict:
        cap = max(0, int(config.DOCTOR_TIMELINE_EVENTS.get()))
        match = dict(alert.get("match") or {})
        events: List[dict] = []
        gids: List[str] = []
        try:
            from geomesa_tpu.obs.flight import RECORDER
            events = RECORDER.recent(limit=cap, **match) if cap else []
        except Exception:
            pass
        try:
            from geomesa_tpu.obs.sampling import SAMPLER
            for t in SAMPLER.recent(cap):
                g = t.get("global_id")
                if g and g not in gids:
                    gids.append(str(g))
        except Exception:
            pass
        demotions = {k: int(v) for k, v in counters.items()
                     if k.startswith("router.demotions")}
        drills = {k: int(v) for k, v in counters.items()
                  if k.startswith("drill.")}
        return {"events": events, "trace_gids": gids,
                "router_demotions": demotions, "drills": drills}

    # -- evaluation -----------------------------------------------------------

    def evaluate(self, tick: bool = True) -> dict:
        """Run every detector, reconcile with the incident store, and
        return ``{alerts, incidents}``. Read/tick surfaces only — never
        called from the query hot path."""
        if not config.DOCTOR_ENABLED.get():
            return {"enabled": False, "alerts": [],
                    "incidents": self.store.active()}
        with self._lock:
            now = self._clock()
            snap = self._reg.snapshot()
            counters = snap.get("counters") or {}
            gauges = snap.get("gauges") or {}
            alerts: List[dict] = []
            for check in (lambda: self._check_slo(now),
                          lambda: self._check_replication(now, gauges),
                          lambda: self._check_recompiles(now, counters),
                          lambda: self._check_shed(now, counters),
                          lambda: self._check_breakers(now, counters),
                          lambda: self._check_wal(now, counters),
                          lambda: self._check_reindex(now, counters),
                          lambda: self._check_skew(now),
                          lambda: self._check_shard_imbalance(now),
                          lambda: self._check_capacity_trend(now),
                          lambda: self._check_shard_dark(now),
                          lambda: self._check_straggler(now, counters)):
                try:
                    alerts.extend(check())
                except Exception:
                    # one broken detector must not take down the surface
                    self._reg.inc("doctor.detector_errors")
            self._reg.inc("doctor.evaluations")
            firing = set()
            for a in alerts:
                self._reg.inc(f"doctor.alerts.{a['rule']}")
                key = (a["rule"], str(a.get("cause", "")))
                firing.add(key)
                timeline = None
                if key not in {(i["rule"], i["cause"])
                               for i in self.store.active()}:
                    timeline = self._timeline(a, counters)
                inc = self.store.open_or_update(a, timeline, now)
                if timeline is not None:
                    # newly opened: freeze the forensic bundle (history
                    # slices, matching events, replication/workload
                    # state) before the system can recover past it
                    fstore = None
                    try:
                        fstore = self._fstore()
                    except Exception:
                        pass
                    if fstore is not None:
                        fstore.capture(inc)
            resolved = []
            if tick:
                resolved = self.store.sweep(
                    firing, now, int(config.DOCTOR_CLEAR_TICKS.get()))
            return {"alerts": alerts,
                    "incidents": self.store.active(),
                    "resolved": [i["id"] for i in resolved]}

    def alerts(self) -> dict:
        """The ``GET /alerts`` payload: current firings + active
        incident ids (evaluates, so reading IS detecting)."""
        res = self.evaluate()
        return {"alerts": res.get("alerts", []),
                "active_incidents": [i["id"] for i in
                                     res.get("incidents", [])],
                "enabled": bool(config.DOCTOR_ENABLED.get())}

    def incidents(self, active_only: bool = False) -> dict:
        """The ``GET /incidents`` payload (evaluates first so the answer
        reflects the present, then includes the resolved tail)."""
        self.evaluate()
        return {"incidents": self.store.all(active_only=active_only),
                "stats": self.store.stats()}

    def reset(self) -> None:
        """Forget rate-detector history and all incidents (tests)."""
        with self._lock:
            self.history.clear()
            self.store.clear()


def verdict(inc: dict) -> str:
    """One human line per incident: what fired, since when, suspected
    cause, linked trace — the CLI ``doctor`` output contract."""
    age_s = None
    if inc.get("opened_ms"):
        age_s = max(0.0, time.time() - inc["opened_ms"] / 1000.0)
    since = f"{age_s:.0f}s ago" if age_s is not None else "unknown"
    suspect = inc.get("suspect") or {}
    cause = ", ".join(f"{k}={v}" for k, v in sorted(suspect.items())) \
        or inc.get("cause", "?")
    tl = inc.get("timeline") or {}
    gids = tl.get("trace_gids") or []
    link = f" trace={gids[0]}" if gids else ""
    status = inc.get("status", "open")
    return (f"[{inc.get('severity', '?').upper()}] {inc.get('rule')}"
            f" ({status}) since {since} x{inc.get('count', 1)}"
            f" — suspected: {cause}{link}")


# -- process-global doctor (the /alerts /incidents surfaces' backing) ---------

DOCTOR = DoctorEngine()
