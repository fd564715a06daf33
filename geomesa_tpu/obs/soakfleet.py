"""Fleet soak scoreboard: chaos-scored SLO verification over a REAL fleet.

Where obs/soak.py drives one in-process store through fault injections,
this orchestrator launches the genuine PR-7/8 topology — a durable
primary, N follower replicas and the read router as *subprocesses* over
localhost WAL-shipping sockets — drives sustained Zipf multi-tenant
traffic through the router, and executes a declarative chaos timeline
mid-run (rolling restart, replica kill, replication-lag spike,
promote-failover, reindex-under-load) while a fleet-level DoctorEngine
watches the run through a Federator.

The run is scored into a scoreboard (JSON + rendered markdown):

  * fleet-federated p50/p99 and SLO burn per phase (steady / each
    fault / recovery), from merged ``query.count`` histogram deltas;
  * doctor incident precision + recall against the known fault
    schedule — every injected fault must open exactly one
    correctly-attributed incident, and no incident may open outside a
    fault window;
  * failover and catch-up times vs their budgets;
  * result-cache hit-rate and per-tenant QoS victim p99 under the
    storm;
  * federation honesty under node death (``partial``/``missing``
    truthful, paging suppressed, ``fed.scrape_errors.<node>`` matching
    the kill window);
  * conservation: no acked write lost (final count == seed + acks) and
    byte-identical durability-dir fingerprints across the surviving
    fleet at exit.

The scoreboard's numeric metrics (``scoreboard_metrics``, the cfg11
names) are part of the scoreboard ``GET /fleet/soak`` serves.
``faulted=False`` replays the same traffic with paced writes and no
chaos: zero incidents allowed.

Knobs (``GEOMESA_TPU_SOAK_*``): SOAK_PHASE_S (per-phase drive window),
SOAK_WAIT_S (incident/catch-up wait ceiling), SOAK_FOLLOWERS,
SOAK_CATCHUP_BUDGET_S, and SOAK_STRETCH — a multiplier on injected
chaos magnitudes (stretch > 1 makes the lag-spike genuinely worse, so
the catch-up and burn-rate axes of the scoreboard move with it).

obs/soakcells.py is this soak's cluster-v2 sibling: the same
launch/drive/score skeleton over a SHARDED fleet of replicated cells
behind the shard-aware router, scored under the cfg16 names (cell failover,
ownership handoff, cross-cell split-brain, dark-shard envelopes).
"""
from __future__ import annotations

import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse
import urllib.request
from typing import Callable, Dict, List, Optional

from geomesa_tpu import config
from geomesa_tpu.metrics import BUCKET_BOUNDS
from geomesa_tpu.metrics import REGISTRY as _metrics

SCOREBOARD_DEFAULT = "SOAK_scoreboard.json"

# the most recent completed run in this process (GET /fleet/soak serves
# it; falls back to the scoreboard file a previous run wrote)
LAST: Optional[dict] = None


def _log(msg: str) -> None:
    """Progress narration (stderr) when GEOMESA_TPU_SOAK_VERBOSE is set —
    a multi-minute multi-process run is undebuggable without it."""
    if os.environ.get("GEOMESA_TPU_SOAK_VERBOSE"):
        print(f"[soakfleet +{time.monotonic() % 100000:.1f}] {msg}",
              file=sys.stderr, flush=True)


def last_run() -> Optional[dict]:
    if LAST is not None:
        return LAST
    path = os.environ.get("GEOMESA_TPU_SOAK_SCOREBOARD", SCOREBOARD_DEFAULT)
    try:
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


# -- plumbing -----------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, path: str, method: str = "GET",
          body: Optional[bytes] = None, timeout: float = 10.0) -> dict:
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=body, method=method)
    if body is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _wait_http(port: int, path: str = "/healthz",
               timeout_s: float = 120.0) -> dict:
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            return _http(port, path, timeout=2.0)
        except Exception as e:  # noqa: BLE001 - startup race, keep polling
            last = e
            time.sleep(0.2)
    raise TimeoutError(f"node on :{port} never served {path}: {last}")


# -- pure scoring helpers (unit-tested without a fleet) -----------------------


def hist_delta_percentile(buckets0: List[int], buckets1: List[int],
                          q: float) -> float:
    """Percentile (in ms) of the observations that landed BETWEEN two
    cumulative bucket snapshots of a merged ``metrics.Histogram`` —
    bucket-resolution, conservative (upper bound), like
    ``Histogram.percentile``."""
    delta = [max(0, int(b1) - int(b0))
             for b0, b1 in zip(buckets0, buckets1)]
    n = sum(delta)
    if n <= 0:
        return 0.0
    rank = max(1, math.ceil(q * n))
    seen = 0
    for i, d in enumerate(delta):
        seen += d
        if seen >= rank:
            return BUCKET_BOUNDS[i] * 1000.0
    return BUCKET_BOUNDS[-1] * 1000.0


def fleet_backlog(seqs: Dict[str, dict], primary: str,
                  followers: List[str]) -> int:
    """Worst follower replication backlog from last-KNOWN positions.
    A dead follower's applied_seq stays frozen while the primary's
    wal_seq advances, so its backlog keeps growing — exactly the signal
    the fleet doctor needs when the node itself can no longer report."""
    head = (seqs.get(primary) or {}).get("wal")
    if head is None:
        return 0
    worst = 0
    for name in followers:
        applied = (seqs.get(name) or {}).get("applied")
        if applied is not None:
            worst = max(worst, int(head) - int(applied))
    return worst


def percentile_ms(vals: List[float], q: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def score_phases(phases: List[dict]) -> dict:
    """Precision/recall of the incident stream against the fault
    schedule.  Recall: fault phases that got exactly one incident with
    the right rule.  Precision: correctly-attributed incidents over all
    incidents opened anywhere in the run (an incident during steady or
    recovery is a false positive by construction)."""
    fault = [p for p in phases if p.get("expected_rule")]
    hits = sum(1 for p in fault if p.get("ok"))
    recall = (hits / len(fault)) if fault else 1.0
    total = sum(len(p.get("new_incidents") or []) for p in phases)
    correct = sum(
        sum(1 for i in (p.get("new_incidents") or [])
            if i.get("rule") == p.get("expected_rule"))
        for p in fault)
    precision = (correct / total) if total else 1.0
    return {"precision": round(precision, 4), "recall": round(recall, 4),
            "fault_phases": len(fault), "detected": hits,
            "incidents_total": total, "correct": correct,
            "false_positives": total - correct}


class _NoWorkload:
    """Silent workload plane: the orchestrator process serves nothing,
    so the skew detector must not read its (possibly dirty, e.g. mid-
    bench) process-global workload state."""

    def hot_set(self, k=None):
        return {"total": 0, "plans": [], "cells": []}

    def top_tenants(self, k=10):
        return []


class _FleetView:
    """Registry facade over a Federator: the fleet DoctorEngine and the
    fleet SloEngine read merged counters, computed replication-backlog
    gauges and merged latency histograms through the same ``snapshot()``
    / ``timer_good_total()`` surface a node-local registry offers.
    ``retarget()`` swaps in the post-failover Federator so the engines
    keep scoring across a primary change."""

    def __init__(self, fed, primary: str, followers: List[str]):
        self.fed = fed
        self.primary = primary
        self.followers = list(followers)
        self.seqs: Dict[str, dict] = {}

    def retarget(self, fed, primary: str, followers: List[str]) -> None:
        self.fed = fed
        self.primary = primary
        self.followers = list(followers)
        keep = {primary, *followers}
        self.seqs = {n: s for n, s in self.seqs.items() if n in keep}

    def observe(self) -> None:
        for name, s in self.fed.refresh().items():
            if not s.ok or not s.healthz:
                continue
            dur = s.healthz.get("durability") or {}
            repl = s.healthz.get("replication") or {}
            d = self.seqs.setdefault(name, {})
            if dur.get("wal_seq") is not None:
                d["wal"] = int(dur["wal_seq"])
            if repl.get("applied_seq") is not None:
                d["applied"] = int(repl["applied_seq"])

    def backlog(self) -> int:
        return fleet_backlog(self.seqs, self.primary, self.followers)

    # -- registry surface (DoctorEngine + SloEngine) --------------------------

    def snapshot(self) -> dict:
        self.observe()
        return {"counters": self.fed.merged_counters(),
                "gauges": {"replication.lag_seqs": float(self.backlog()),
                           "replication.lag_ms": 0.0}}

    def inc(self, name: str, v: int = 1):
        return _metrics.inc(name, v)

    def set_gauge(self, name: str, fn):
        return _metrics.set_gauge(name, fn)

    def timer_good_total(self, name: str, threshold_s: float):
        return self.fed.timer_good_total(name, threshold_s)


# -- traffic ------------------------------------------------------------------

_TENANTS = [f"tenant{k}" for k in range(8)]
# rarest tenant: the QoS "victim" whose p99 under the storm is scored
VICTIM_TENANT = _TENANTS[-1]


def _query_shapes(n: int = 60) -> List[str]:
    shapes = []
    for i in range(n):
        x0 = round(-10.0 + (i % 10) * 1.7, 2)
        y0 = round(-10.0 + (i // 10) * 2.9, 2)
        shapes.append(f"BBOX(geom, {x0}, {y0}, {x0 + 3.0}, {y0 + 3.0})")
    return shapes


class _Traffic(threading.Thread):
    """Sustained Zipf multi-tenant reads through the router, cfg8-shaped:
    ~60 bbox shapes under a 1/r^1.1 popularity law, 8 tenants weighted
    1/r.  Client-side latencies are recorded per (phase, tenant) so the
    scoreboard can report the victim tenant's p99 under the storm."""

    def __init__(self, router_port: int, seed: int = 7,
                 period_s: float = 0.004):
        super().__init__(name="soakfleet-traffic", daemon=True)
        self.router_port = router_port
        self.period_s = period_s
        self.stop_evt = threading.Event()
        self.phase = "warmup"
        self.samples: List[tuple] = []   # (phase, tenant, ms) — append-only
        self.sent = 0
        self.errors = 0
        import random
        self._rng = random.Random(seed)
        self._shapes = _query_shapes()
        self._wshapes = [1.0 / (r + 1) ** 1.1
                         for r in range(len(self._shapes))]
        self._wtenants = [1.0 / (r + 1) for r in range(len(_TENANTS))]

    def set_phase(self, name: str) -> None:
        self.phase = name

    def run(self) -> None:
        while not self.stop_evt.is_set():
            cql = self._rng.choices(self._shapes, self._wshapes)[0]
            tenant = self._rng.choices(_TENANTS, self._wtenants)[0]
            q = urllib.parse.urlencode({"cql": cql, "tenant": tenant})
            t0 = time.perf_counter()
            try:
                _http(self.router_port, f"/types/t/count?{q}", timeout=5.0)
            except Exception:  # noqa: BLE001 - mid-chaos errors are expected
                self.errors += 1
            else:
                self.samples.append(
                    (self.phase, tenant,
                     (time.perf_counter() - t0) * 1000.0))
            self.sent += 1
            self.stop_evt.wait(self.period_s)

    def stop(self) -> None:
        self.stop_evt.set()
        self.join(timeout=10.0)

    def phase_lat(self, phase: str,
                  tenant: Optional[str] = None) -> List[float]:
        return [ms for (p, t, ms) in list(self.samples)
                if p == phase and (tenant is None or t == tenant)]


# -- the orchestrator ---------------------------------------------------------


class FleetSoak:
    """One soak half over a real subprocess fleet.  ``faulted=True``
    executes the chaos timeline and requires one correctly-attributed
    incident per fault; ``faulted=False`` replays the same traffic with
    paced writes and requires zero incidents."""

    def __init__(self, base_dir: str, faulted: bool = True,
                 mini: bool = True, stretch: Optional[float] = None):
        self.base = base_dir
        os.makedirs(base_dir, exist_ok=True)
        self.faulted = faulted
        self.mini = mini
        self.stretch = float(stretch if stretch is not None
                             else config.SOAK_STRETCH.get())
        scale = 1.0 if mini else 3.0
        self.phase_s = float(config.SOAK_PHASE_S.get()) * scale
        self.wait_s = float(config.SOAK_WAIT_S.get())
        self.catchup_budget_s = float(config.SOAK_CATCHUP_BUDGET_S.get())
        self.throttle_ms = 120
        self.primary = "p0"
        n_f = max(2, int(config.SOAK_FOLLOWERS.get()))
        self.followers = [f"r{i + 1}" for i in range(n_f)]
        self.procs: Dict[str, subprocess.Popen] = {}
        self.ports: Dict[str, int] = {}
        self.dirs: Dict[str, str] = {}
        self.ship_ports: Dict[str, int] = {}
        self.router_port = 0
        self.rows = 0            # seed + acked ingests (expected final count)
        self.acked = 0
        self._wb = 100           # write-batch counter (seed used 0..2)
        self.fed = None
        self.fv: Optional[_FleetView] = None
        self.slo_eng = None
        self.doctor = None
        self.traffic: Optional[_Traffic] = None
        self.phases: List[dict] = []
        self._seen: set = set()
        self._phase_burn = 0.0
        self._partial_ok = False
        self._partial_violations = 0
        self._pages_while_partial = 0
        self.threshold_ms = 0.0
        self.failover: Optional[dict] = None
        self.catchup_s: Optional[float] = None
        self.honesty: Optional[dict] = None
        self.cache: Optional[dict] = None
        self.notes: List[str] = []

    # -- process management ---------------------------------------------------

    def _spawn(self, args: List[str],
               extra_env: Optional[dict] = None) -> subprocess.Popen:
        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.update(extra_env or {})
        return subprocess.Popen(
            [sys.executable, "-m", "geomesa_tpu.tools.cli", *args],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)

    def _node_env(self, name: str) -> dict:
        return {"GEOMESA_TPU_NODE_ID": name,
                "GEOMESA_TPU_FAULT_API": "1",
                "GEOMESA_TPU_REINDEX_THROTTLE_MS": str(self.throttle_ms),
                "GEOMESA_TPU_REPL_TRACE_EVERY": "1",
                "GEOMESA_TPU_REPL_ACK_EVERY": "1"}

    def _alive(self, name: str) -> bool:
        p = self.procs.get(name)
        return p is not None and p.poll() is None

    def _signal(self, name: str, sig: int, wait_s: float = 20.0) -> None:
        p = self.procs.get(name)
        if p is None or p.poll() is not None:
            return
        p.send_signal(sig)
        try:
            p.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait(timeout=10.0)

    def _spawn_primary(self) -> None:
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.replication.drills import SPEC, make_batch
        pdir = os.path.join(self.base, "p0")
        self.dirs["p0"] = pdir
        store = TpuDataStore.open(pdir, params={"wal.fsync": "off"})
        try:
            store.create_schema("t", SPEC)
            for i in range(3):
                store.load("t", make_batch(store.schemas["t"], i))
                self.rows += 40
        finally:
            store.close()
        sp, wp = _free_port(), _free_port()
        self.ship_ports["p0"] = sp
        self.ports["p0"] = wp
        self.procs["p0"] = self._spawn(
            ["serve", "-s", pdir, "--durable",
             "--ship-port", str(sp), "--port", str(wp)],
            self._node_env("p0"))
        _wait_http(wp)

    def _spawn_follower(self, name: str, wait: bool = True) -> None:
        rdir = self.dirs.setdefault(name, os.path.join(self.base, name))
        port = self.ports.get(name) or _free_port()
        self.ports[name] = port
        sp = self.ship_ports[self.primary]
        self.procs[name] = self._spawn(
            ["replica", "--dir", rdir, "--follow", f"127.0.0.1:{sp}",
             "--port", str(port), "--id", name],
            self._node_env(name))
        if wait:
            _wait_http(port)

    def _spawn_router(self) -> None:
        self.router_port = _free_port()
        args = ["router", "--port", str(self.router_port)]
        for n in [self.primary, *self.followers]:
            args += ["--endpoint", f"{n}=127.0.0.1:{self.ports[n]}"]
        self.procs["router"] = self._spawn(args, {"GEOMESA_TPU_NODE_ID":
                                                  "router"})
        _wait_http(self.router_port)

    # -- fleet state ----------------------------------------------------------

    def _mk_federator(self):
        from geomesa_tpu.obs.federation import Federator
        nodes = {n: f"127.0.0.1:{self.ports[n]}"
                 for n in [self.primary, *self.followers]}
        return Federator(nodes, ttl_ms=150.0, timeout_s=2.0)

    def _mk_doctor(self) -> None:
        from geomesa_tpu.obs import slo as _slo
        from geomesa_tpu.obs.doctor import DoctorEngine
        self.fed = self._mk_federator()
        self.fv = _FleetView(self.fed, self.primary, self.followers)
        # calibrate the fleet latency SLO off warm routed counts, the
        # same 20x-warm idiom obs/soak.py uses for the node-local soak
        warm = []
        for _ in range(4):
            t0 = time.perf_counter()
            q = urllib.parse.urlencode({"cql": "BBOX(geom, -5, -5, 5, 5)"})
            _http(self.router_port, f"/types/t/count?{q}")
            warm.append((time.perf_counter() - t0) * 1000.0)
        self.threshold_ms = max(60.0, 20.0 * (sum(warm) / len(warm)))
        self.slo_eng = _slo.SloEngine(registry=self.fv)
        self.slo_eng.add(_slo.Objective(
            name="fleet_count", kind="latency", target=0.99,
            timer="query.count", threshold_ms=self.threshold_ms))
        journal = os.path.join(self.base, "fleet_doctor.jsonl")
        self.doctor = DoctorEngine(registry=self.fv,
                                   slo_engine=self.slo_eng,
                                   journal_path=journal,
                                   federator=False,
                                   workload=_NoWorkload())

    def _counters(self) -> dict:
        self.fed.refresh(force=True)
        return self.fed.merged_counters()

    def _hist_snapshot(self):
        self.fed.refresh(force=True)
        h = self.fed._merged_hists("timers").get("query.count")
        if h is None:
            return (0, [0] * len(BUCKET_BOUNDS))
        hist = h[0]
        return (hist.count, list(hist.buckets))

    # -- writes / catch-up ----------------------------------------------------

    def _write_batch(self, n: int = 40) -> int:
        i = self._wb
        self._wb += 1
        feats = []
        for j in range(n):
            x = -9.5 + ((i * 7 + j) % 190) * 0.1
            y = -9.5 + ((i * 11 + j * 3) % 190) * 0.1
            feats.append({
                "type": "Feature", "id": f"s{i}_{j}",
                "geometry": {"type": "Point",
                             "coordinates": [round(x, 3), round(y, 3)]},
                "properties": {"name": "abc"[j % 3], "v": (i + j) % 100,
                               "dtg": "2024-01-01T06:00:00"}})
        body = json.dumps({"type": "FeatureCollection",
                           "features": feats}).encode()
        out = _http(self.ports[self.primary], "/types/t/features",
                    method="POST", body=body, timeout=15.0)
        got = int(out.get("ingested", 0))
        self.acked += got
        self.rows += got
        return got

    def _wait_catchup(self, names: Optional[List[str]] = None,
                      timeout_s: Optional[float] = None) -> Optional[float]:
        """Wait until every named (live) follower reports connected with
        zero lag.  Returns elapsed seconds, or None on timeout."""
        names = [n for n in (names or self.followers) if self._alive(n)]
        t0 = time.monotonic()
        deadline = t0 + (timeout_s if timeout_s is not None else self.wait_s)
        while time.monotonic() < deadline:
            # authoritative head: a follower stalled mid-apply reports a
            # stale primary_seq, so its own lag_seqs can read 0 while it
            # is in fact far behind — always compare against the primary
            try:
                head = int((_http(self.ports[self.primary], "/healthz",
                                  timeout=2.0).get("durability")
                            or {}).get("wal_seq") or 0)
            except Exception:  # noqa: BLE001
                head = None
            ok = head is not None
            for n in names if ok else []:
                try:
                    r = _http(self.ports[n], "/healthz",
                              timeout=2.0).get("replication") or {}
                    applied = r.get("applied_seq")
                    if not r.get("connected") or applied is None \
                            or int(applied) < head:
                        ok = False
                except Exception:  # noqa: BLE001
                    ok = False
            if ok:
                return time.monotonic() - t0
            time.sleep(0.1)
        return None

    def _wait_synced(self, names: List[str], timeout_s: float = 20.0):
        """Wait for each node's WAL to report nothing unsynced, so a
        subsequent shutdown cannot drop an acked tail."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            ok = True
            for n in names:
                try:
                    d = _http(self.ports[n], "/healthz",
                              timeout=2.0).get("durability") or {}
                    if d.get("enabled") and int(d.get("unsynced_bytes")
                                                or 0) > 0:
                        ok = False
                except Exception:  # noqa: BLE001
                    ok = False
            if ok:
                return True
            time.sleep(0.1)
        return False

    # -- doctor drive / phase machinery ---------------------------------------

    def _fresh(self) -> List[dict]:
        return [i for i in self.doctor.store.all()
                if i["id"] not in self._seen]

    def _open_rule(self, rule: str) -> bool:
        return any(i["rule"] == rule for i in self._fresh())

    def _all_resolved(self) -> bool:
        fresh = self._fresh()
        return bool(fresh) and all(i["status"] == "resolved" for i in fresh)

    def _drive(self, seconds: float,
               until: Optional[Callable[[], bool]] = None,
               period_s: float = 0.15) -> bool:
        deadline = time.monotonic() + seconds
        while True:
            self.doctor.evaluate()
            res = self.slo_eng.evaluate(tick=False)
            obj = res.get("fleet_count") or {}
            burns = [b for b in (obj.get("burn_rates") or {}).values()
                     if b is not None]
            if burns:
                self._phase_burn = max(self._phase_burn, max(burns))
            snap = self.fed.snapshot()
            if snap.get("partial"):
                if not self._partial_ok:
                    self._partial_violations += 1
                fslo = self.fed.slo()
                for o in fslo.values():
                    if isinstance(o, dict) and o.get("page"):
                        self._pages_while_partial += 1
            if until is not None and until():
                return True
            if time.monotonic() >= deadline:
                return until is None
            time.sleep(period_s)

    def _run_phase(self, name: str, expected_rule: Optional[str],
                   body: Callable[[], Optional[dict]]) -> dict:
        self._seen = {i["id"] for i in self.doctor.store.all()}
        self._phase_burn = 0.0
        h0 = self._hist_snapshot()
        if self.traffic is not None:
            self.traffic.set_phase(name)
        _log(f"phase {name} start")
        t0 = time.monotonic()
        extra = body() or {}
        dur = time.monotonic() - t0
        h1 = self._hist_snapshot()
        fresh = self._fresh()
        rep = {
            "name": name, "expected_rule": expected_rule,
            "duration_s": round(dur, 2),
            "fleet_p50_ms": round(hist_delta_percentile(h0[1], h1[1],
                                                        0.50), 3),
            "fleet_p99_ms": round(hist_delta_percentile(h0[1], h1[1],
                                                        0.99), 3),
            "requests": max(0, h1[0] - h0[0]),
            "burn": round(self._phase_burn, 3),
            "new_incidents": [{"id": i["id"], "rule": i["rule"],
                               "cause": i["cause"],
                               "severity": i["severity"],
                               "status": i["status"]} for i in fresh],
        }
        rep.update(extra)
        _log(f"phase {name} done in {dur:.1f}s incidents="
             f"{[i['rule'] for i in rep['new_incidents']]}")
        if expected_rule is None:
            rep["ok"] = not fresh
        else:
            rep["exactly_one"] = len(fresh) == 1
            rep["rule_correct"] = bool(fresh) and all(
                i["rule"] == expected_rule for i in fresh)
            rep["resolved"] = bool(fresh) and all(
                i["status"] == "resolved" for i in fresh)
            rep["ok"] = bool(rep["exactly_one"] and rep["rule_correct"]
                             and rep["resolved"])
        self.phases.append(rep)
        return rep

    # -- phase bodies ---------------------------------------------------------

    def _p_steady(self) -> dict:
        c0 = self._counters()
        span = self.phase_s * 1.5
        self._drive(span * 0.4)
        self._write_batch()
        self._wait_catchup(timeout_s=15.0)
        self._drive(span * 0.4)
        self._write_batch()
        self._wait_catchup(timeout_s=15.0)
        self._drive(span * 0.2)
        c1 = self._counters()
        hits = c1.get("result_cache.hits", 0) - c0.get("result_cache.hits", 0)
        miss = (c1.get("result_cache.misses", 0)
                - c0.get("result_cache.misses", 0))
        victim = self.traffic.phase_lat("steady", VICTIM_TENANT)
        self.cache = {
            "hit_rate": round(hits / (hits + miss), 4) if hits + miss else 0.0,
            "hits": hits, "misses": miss,
            "victim_tenant": VICTIM_TENANT,
            "victim_samples": len(victim),
            "victim_p99_ms": round(percentile_ms(victim, 0.99), 3),
        }
        return {"cache": self.cache}

    def _p_rolling_restart(self) -> dict:
        v = self.followers[0]
        self._partial_ok = True              # node is legitimately down
        self._signal(v, signal.SIGINT)       # graceful: a rolling restart
        for _ in range(10):
            self._write_batch(n=20)
            self._drive(0.2)
        found = self._drive(self.wait_s,
                            until=lambda: self._open_rule("replication_lag"))
        self._spawn_follower(v)
        caught = self._wait_catchup([v], timeout_s=self.wait_s)
        self._partial_ok = False
        self._drive(self.wait_s, until=self._all_resolved)
        return {"victim": v, "detected": found,
                "caught_up_s": round(caught, 2) if caught else None}

    def _p_lag_spike(self) -> dict:
        v = self.followers[0]
        delay_s = 0.3 * self.stretch
        n = max(1, int(round(8 * self.stretch)))
        _http(self.ports[v],
              f"/debug/fault?point=repl.apply&delay_s={delay_s}&n={n}",
              method="POST")
        for _ in range(10):
            self._write_batch(n=20)
        found = self._drive(self.wait_s,
                            until=lambda: self._open_rule("replication_lag"))
        t0 = time.monotonic()
        caught = self._wait_catchup(
            [v], timeout_s=max(self.wait_s, delay_s * n + 20.0))
        self.catchup_s = round(time.monotonic() - t0, 2) if caught is None \
            else round(caught, 2)
        self._drive(self.wait_s, until=self._all_resolved)
        return {"victim": v, "detected": found, "delay_s": delay_s,
                "delayed_applies": n, "catchup_s": self.catchup_s,
                "catchup_budget_s": self.catchup_budget_s,
                "within_budget": (caught is not None
                                  and self.catchup_s
                                  <= self.catchup_budget_s)}

    def _p_replica_kill(self) -> dict:
        v = self.followers[-1]
        self._partial_ok = True
        self._signal(v, signal.SIGKILL)      # crash, not a restart
        # federation-honesty block, isolated so the scrape-error count
        # is exact: M forced refreshes against a dead node must cost
        # exactly M fed.scrape_errors.<node> and flag partial+missing
        key = f"fed.scrape_errors.{v}"
        c0 = _metrics.snapshot()["counters"].get(key, 0)
        forced = 4
        for _ in range(forced):
            self.fed.refresh(force=True)
            time.sleep(0.05)
        c1 = _metrics.snapshot()["counters"].get(key, 0)
        snap = self.fed.snapshot()
        honesty = {
            "node": v, "forced_refreshes": forced,
            "scrape_errors_delta": c1 - c0,
            "scrape_errors_exact": (c1 - c0) == forced,
            "partial_during_kill": bool(snap.get("partial")),
            "missing_exact": snap.get("missing") == [v],
        }
        for _ in range(12):
            self._write_batch(n=20)
        found = self._drive(self.wait_s,
                            until=lambda: self._open_rule("replication_lag"))
        self._spawn_follower(v)
        caught = self._wait_catchup([v], timeout_s=self.wait_s)
        # once the node is back, a forced refresh must cost nothing
        c2 = _metrics.snapshot()["counters"].get(key, 0)
        self.fed.refresh(force=True)
        c3 = _metrics.snapshot()["counters"].get(key, 0)
        honesty["clean_after_respawn"] = (c3 - c2) == 0
        honesty["partial_cleared"] = not self.fed.snapshot().get("partial")
        self._partial_ok = False
        self.honesty = honesty
        self._drive(self.wait_s, until=self._all_resolved)
        return {"victim": v, "detected": found, "honesty": honesty,
                "caught_up_s": round(caught, 2) if caught else None}

    def _p_failover(self) -> dict:
        old = self.primary
        self._wait_catchup(timeout_s=self.wait_s)
        expected = self.rows
        self._partial_ok = True
        self._signal(old, signal.SIGKILL)
        new_ship = _free_port()
        res = _http(self.router_port, f"/promote?port={new_ship}",
                    method="POST", timeout=60.0)
        promoted = res["promoted"]
        self.failover = {
            "old_primary": old, "promoted": promoted,
            "duration_ms": float(res["duration_ms"]),
            "budget_ms": float(res["budget_ms"]),
            "within_budget": bool(res["within_budget"]),
        }
        addr = (res.get("result") or {}).get("address") or ""
        self.ship_ports[promoted] = int(addr.rsplit(":", 1)[1]) \
            if ":" in addr else new_ship
        self.primary = promoted
        self.followers = [n for n in self.followers if n != promoted]
        self.notes.append(f"{old} killed; {promoted} promoted "
                          f"(dir {old} excluded from exit fingerprints)")
        # conservation at the moment of failover: every acked write must
        # already be on the promoted node
        cnt = int(_http(self.ports[promoted],
                        "/types/t/count", timeout=30.0)["count"])
        self.failover["count_at_promote"] = cnt
        self.failover["expected"] = expected
        self.failover["no_acked_loss"] = cnt == expected
        # re-point the observability plane at the surviving fleet
        self.fed = self._mk_federator()
        self.fv.retarget(self.fed, self.primary, self.followers)
        self._partial_ok = False
        # the stale follower still points at the dead primary's shipper:
        # writes to the NEW primary grow its backlog until re-pointed
        for _ in range(12):
            self._write_batch(n=20)
        found = self._drive(self.wait_s,
                            until=lambda: self._open_rule("replication_lag"))
        stale = self.followers[0]
        self._partial_ok = True              # restart window: node down
        self._signal(stale, signal.SIGINT)
        self._spawn_follower(stale)          # follows the new ship port
        caught = self._wait_catchup([stale],
                                    timeout_s=self.catchup_budget_s * 2)
        self._partial_ok = False
        self.failover["stale_follower"] = stale
        self.failover["repoint_catchup_s"] = round(caught, 2) if caught \
            else None
        self._drive(self.wait_s, until=self._all_resolved)
        return {"failover": self.failover, "detected": found}

    def _p_reindex_churn(self) -> dict:
        p = self.primary
        port = self.ports[p]
        c0 = self._counters()
        _http(port, "/types/t/reindex", method="POST")
        aborts = 0
        deadline = time.monotonic() + self.wait_s
        while aborts < 2 and time.monotonic() < deadline:
            self._write_batch(n=20)
            _http(port, "/types/t/flush", method="POST", timeout=15.0)
            st = _http(port, "/types/t/reindex")
            if not st.get("running") and st.get("state") != "installed":
                _http(port, "/types/t/reindex", method="POST")
            time.sleep(0.06)
            aborts = (self._counters().get("reindex.aborts", 0)
                      - c0.get("reindex.aborts", 0))
        found = self._drive(self.wait_s,
                            until=lambda: self._open_rule("reindex_churn"))
        # let one build land clean (no concurrent flushes)
        deadline = time.monotonic() + self.wait_s
        while time.monotonic() < deadline:
            st = _http(port, "/types/t/reindex")
            if st.get("state") == "installed" and not st.get("running"):
                break
            if not st.get("running"):
                _http(port, "/types/t/reindex", method="POST")
            time.sleep(0.2)
        self._wait_catchup(timeout_s=self.wait_s)
        self._drive(self.wait_s, until=self._all_resolved)
        return {"aborts": int(aborts), "detected": found,
                "installed": st.get("state") == "installed"}

    def _p_recovery(self) -> dict:
        self._drive(self.phase_s)
        self._write_batch()
        caught = self._wait_catchup(timeout_s=self.wait_s)
        self._drive(self.phase_s * 0.5)
        return {"caught_up_s": round(caught, 2) if caught else None}

    # -- clean-half bodies (same traffic, no chaos) ---------------------------

    def _p_clean_writes(self) -> dict:
        for _ in range(6):
            self._write_batch(n=20)
            self._wait_catchup(timeout_s=15.0)
            self._drive(0.4)
        return {}

    def _p_clean_reindex(self) -> dict:
        port = self.ports[self.primary]
        _http(port, "/types/t/reindex", method="POST")
        deadline = time.monotonic() + self.wait_s
        st = {}
        while time.monotonic() < deadline:
            st = _http(port, "/types/t/reindex")
            if not st.get("running") and st.get("state") in ("installed",
                                                             "aborted"):
                break
            self._drive(0.2)
        self._wait_catchup(timeout_s=self.wait_s)
        return {"state": st.get("state")}

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> None:
        _log(f"spawning fleet under {self.base}")
        self._spawn_primary()
        for n in self.followers:
            self._spawn_follower(n)
        self._wait_catchup(timeout_s=self.wait_s)
        self._spawn_router()
        _log("fleet up; calibrating SLO threshold")
        self._mk_doctor()
        _log(f"threshold_ms={self.threshold_ms:.1f}")
        self.traffic = _Traffic(self.router_port)
        self.traffic.start()
        # let the merge surfaces warm so phase-0 deltas are meaningful
        self._drive(1.0)

    def _shutdown(self) -> None:
        if self.traffic is not None:
            self.traffic.stop()
        live = [n for n in [self.primary, *self.followers]
                if self._alive(n)]
        self._wait_catchup(timeout_s=self.wait_s)
        _log("quiesced; waiting WAL sync")
        self._wait_synced(live)
        # SIGINT → KeyboardInterrupt → graceful close paths (the replica
        # CLI closes its Follower; the primary's batch syncer has
        # already fsynced everything after the quiesce above)
        for n in list(self.procs):
            self._signal(n, signal.SIGINT)

    def _conservation(self) -> dict:
        from geomesa_tpu.replication.drills import fingerprint_dir
        out = {"expected_rows": self.rows, "acked_ingests": self.acked}
        try:
            out["final_count"] = int(_http(self.ports[self.primary],
                                           "/types/t/count",
                                           timeout=30.0)["count"])
        except Exception as e:  # noqa: BLE001
            out["final_count"] = -1
            out["count_error"] = str(e)
        out["loss"] = out["expected_rows"] - out["final_count"]
        self._shutdown()
        prints = {}
        for n in [self.primary, *self.followers]:
            try:
                prints[n] = fingerprint_dir(self.dirs[n])
            except Exception as e:  # noqa: BLE001
                prints[n] = {"error": str(e)}
        vals = list(prints.values())
        out["fingerprints"] = prints
        out["fingerprints_matched"] = (len(vals) > 1
                                       and all(v == vals[0] for v in vals)
                                       and "error" not in vals[0])
        return out

    def run(self) -> dict:
        t_start = time.time()
        knobs = [
            (config.DOCTOR_WINDOW_S, 8.0),
            (config.DOCTOR_LAG_MS, 1e12),        # seqs-only: deterministic
            (config.DOCTOR_LAG_SEQS, 4.0),
            (config.DOCTOR_RECOMPILES_PER_MIN, 1e12),
            (config.DOCTOR_SHED_PER_MIN, 1e12),
            (config.DOCTOR_BREAKER_FLAPS, 1e12),
            (config.DOCTOR_FSYNC_ERRORS, 1e12),
            (config.DOCTOR_SKEW_MIN, 1e12),
            (config.DOCTOR_CLEAR_TICKS, 2),
            (config.DOCTOR_REINDEX_PER_MIN, 3.0),
            # forced flushes during the churn phase legitimately breach
            # the merge fraction; only the abort signal is under test
            (config.DOCTOR_MERGE_BREACHES_PER_MIN, 0.0),
        ]
        saved = [(p, p._override) for p, _ in knobs]
        try:
            for p, v in knobs:
                p.set(v)
            self.start()
            if self.faulted:
                self._run_phase("steady", None, self._p_steady)
                self._run_phase("rolling_restart", "replication_lag",
                                self._p_rolling_restart)
                self._run_phase("lag_spike", "replication_lag",
                                self._p_lag_spike)
                self._run_phase("replica_kill", "replication_lag",
                                self._p_replica_kill)
                self._run_phase("failover", "replication_lag",
                                self._p_failover)
                self._run_phase("reindex_churn", "reindex_churn",
                                self._p_reindex_churn)
                self._run_phase("recovery", None, self._p_recovery)
            else:
                self._run_phase("steady", None, self._p_steady)
                self._run_phase("writes", None, self._p_clean_writes)
                self._run_phase("reindex", None, self._p_clean_reindex)
                self._run_phase("recovery", None, self._p_recovery)
            conservation = self._conservation()
        finally:
            if self.traffic is not None and self.traffic.is_alive():
                self.traffic.stop()
            for n, p in self.procs.items():
                if p.poll() is None:
                    p.kill()
                    try:
                        p.wait(timeout=10.0)
                    except subprocess.TimeoutExpired:
                        pass
            for p, old in saved:
                if old is None:
                    p.unset()
                else:
                    p.set(old)
            art = os.environ.get("GEOMESA_TPU_SOAK_ARTIFACT")
            if art:
                mode = "faulted" if self.faulted else "clean"
                src = os.path.join(self.base, "fleet_doctor.jsonl")
                if os.path.exists(src):
                    shutil.copyfile(src, f"{art}.fleet.{mode}.jsonl")
        doctor_score = score_phases(self.phases)
        fault_burns = [p["burn"] for p in self.phases
                       if p.get("expected_rule")]
        report = {
            "mode": "chaos" if self.faulted else "clean",
            "mini": self.mini,
            "stretch": self.stretch,
            "duration_s": round(time.time() - t_start, 1),
            "threshold_ms": round(self.threshold_ms, 1),
            "phases": self.phases,
            "doctor": doctor_score,
            "slo": {"worst_fault_phase_burn": round(max(fault_burns,
                                                        default=0.0), 3),
                    "overall_worst_burn": round(max(
                        (p["burn"] for p in self.phases), default=0.0), 3),
                    "partial_outside_fault_windows":
                        self._partial_violations,
                    "pages_while_partial": self._pages_while_partial},
            "failover": self.failover,
            "catchup_s": self.catchup_s,
            "honesty": self.honesty,
            "cache": self.cache,
            "conservation": conservation,
            "traffic": {"requests": self.traffic.sent if self.traffic
                        else 0,
                        "errors": self.traffic.errors if self.traffic
                        else 0},
            "notes": self.notes,
        }
        checks = [doctor_score["precision"] == 1.0,
                  doctor_score["recall"] == 1.0,
                  conservation["loss"] == 0,
                  conservation["fingerprints_matched"],
                  self._partial_violations == 0,
                  self._pages_while_partial == 0]
        if self.faulted:
            h = self.honesty or {}
            checks += [bool(h.get("scrape_errors_exact")),
                       bool(h.get("partial_during_kill")),
                       bool(h.get("missing_exact")),
                       bool(h.get("partial_cleared")),
                       bool((self.failover or {}).get("no_acked_loss"))]
            if self.stretch == 1.0:
                checks += [bool((self.failover or {}).get("within_budget"))]
        else:
            checks += [doctor_score["incidents_total"] == 0]
        report["ok"] = all(checks)
        return report


# -- entry points -------------------------------------------------------------


def run_fleet_soak(base_dir: Optional[str] = None, faulted: bool = True,
                   mini: bool = True,
                   stretch: Optional[float] = None) -> dict:
    """Run one soak half, managing a scratch dir when none is given."""
    tmp = None
    if base_dir is None:
        tmp = tempfile.mkdtemp(prefix="geomesa-soakfleet-")
        base_dir = tmp
    try:
        return FleetSoak(base_dir, faulted=faulted, mini=mini,
                         stretch=stretch).run()
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def scoreboard_metrics(board: dict) -> dict:
    """Flatten the scoreboard into its numeric cfg11 metrics (the
    ``metrics`` of the scoreboard ``GET /fleet/soak`` serves)."""
    m: Dict[str, float] = {}
    ch = (board.get("halves") or {}).get("chaos")
    cl = (board.get("halves") or {}).get("clean")
    if ch:
        steady = next((p for p in ch["phases"] if p["name"] == "steady"),
                      None)
        if steady:
            m["cfg11_steady_fleet_p50_ms"] = steady["fleet_p50_ms"]
            m["cfg11_steady_fleet_p99_ms"] = steady["fleet_p99_ms"]
        if ch.get("failover"):
            m["cfg11_failover_ms"] = ch["failover"]["duration_ms"]
        if ch.get("catchup_s") is not None:
            m["cfg11_catchup_s"] = ch["catchup_s"]
        m["cfg11_worst_phase_burn_rate"] = \
            ch["slo"]["worst_fault_phase_burn"]
        m["cfg11_doctor_precision"] = ch["doctor"]["precision"]
        m["cfg11_doctor_recall"] = ch["doctor"]["recall"]
        m["cfg11_acked_write_loss"] = ch["conservation"]["loss"]
        m["cfg11_fingerprints_matched"] = int(
            ch["conservation"]["fingerprints_matched"]
            and (cl is None or cl["conservation"]["fingerprints_matched"]))
        if ch.get("cache"):
            m["cfg11_storm_cache_hit_rate"] = ch["cache"]["hit_rate"]
            m["cfg11_storm_victim_p99_ms"] = ch["cache"]["victim_p99_ms"]
    if cl:
        p99s = [p["fleet_p99_ms"] for p in cl["phases"]
                if p.get("requests")]
        if p99s:
            m["cfg11_clean_fleet_p99_ms"] = max(p99s)
        m["cfg11_clean_incidents"] = cl["doctor"]["incidents_total"]
    return m


def render_scoreboard(board: dict) -> str:
    """Markdown rendering of a scoreboard (written next to the JSON)."""
    lines = ["# Fleet soak scoreboard", ""]
    lines.append(f"- mini: {board.get('mini')}  ok: **{board.get('ok')}**")
    for mode, half in (board.get("halves") or {}).items():
        lines += ["", f"## {mode} half "
                      f"({'PASS' if half.get('ok') else 'FAIL'}, "
                      f"{half.get('duration_s')}s)", ""]
        lines.append("| phase | expected | incidents | p50 ms | p99 ms "
                     "| burn | ok |")
        lines.append("|---|---|---|---|---|---|---|")
        for p in half.get("phases", []):
            rules = ", ".join(i["rule"] for i in p["new_incidents"]) or "-"
            lines.append(
                f"| {p['name']} | {p.get('expected_rule') or '-'} "
                f"| {rules} | {p['fleet_p50_ms']} | {p['fleet_p99_ms']} "
                f"| {p['burn']} | {'yes' if p.get('ok') else 'NO'} |")
        d = half.get("doctor") or {}
        lines.append("")
        lines.append(f"- doctor precision **{d.get('precision')}** / "
                     f"recall **{d.get('recall')}** "
                     f"({d.get('correct')}/{d.get('incidents_total')} "
                     f"incidents correct, "
                     f"{d.get('detected')}/{d.get('fault_phases')} faults "
                     f"detected)")
        fo = half.get("failover")
        if fo:
            lines.append(
                f"- failover: {fo['old_primary']} → {fo['promoted']} in "
                f"{fo['duration_ms']}ms (budget {fo['budget_ms']}ms, "
                f"within: {fo['within_budget']}; acked rows at promote "
                f"{fo['count_at_promote']}/{fo['expected']})")
        if half.get("catchup_s") is not None:
            lines.append(f"- lag-spike catch-up: {half['catchup_s']}s")
        hon = half.get("honesty")
        if hon:
            lines.append(
                f"- federation honesty ({hon['node']} killed): "
                f"scrape_errors {hon['scrape_errors_delta']}/"
                f"{hon['forced_refreshes']} exact="
                f"{hon['scrape_errors_exact']}, partial="
                f"{hon['partial_during_kill']}, missing_exact="
                f"{hon['missing_exact']}, cleared="
                f"{hon['partial_cleared']}")
        cache = half.get("cache")
        if cache:
            lines.append(
                f"- storm cache hit-rate {cache['hit_rate']} "
                f"({cache['hits']}h/{cache['misses']}m); victim "
                f"{cache['victim_tenant']} p99 {cache['victim_p99_ms']}ms "
                f"over {cache['victim_samples']} samples")
        cons = half.get("conservation") or {}
        lines.append(
            f"- conservation: {cons.get('final_count')}/"
            f"{cons.get('expected_rows')} rows (loss {cons.get('loss')}), "
            f"fingerprints_matched={cons.get('fingerprints_matched')}")
    metrics = board.get("metrics") or {}
    if metrics:
        lines += ["", "## cfg11 gate metrics", ""]
        lines.append("| metric | value |")
        lines.append("|---|---|")
        for k in sorted(metrics):
            lines.append(f"| {k} | {metrics[k]} |")
    return "\n".join(lines) + "\n"


def run(mini: bool = True, scoreboard_path: Optional[str] = None,
        base_dir: Optional[str] = None,
        halves: tuple = ("chaos", "clean"),
        stretch: Optional[float] = None) -> dict:
    """Run the full soak (chaos + clean halves), write the scoreboard
    JSON + markdown, and remember it for GET /fleet/soak."""
    global LAST
    scoreboard_path = scoreboard_path or os.environ.get(
        "GEOMESA_TPU_SOAK_SCOREBOARD", SCOREBOARD_DEFAULT)
    board: dict = {"schema": 1, "mini": mini, "halves": {},
                   "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                              time.gmtime())}
    for half in halves:
        board["halves"][half] = run_fleet_soak(
            base_dir=os.path.join(base_dir, half) if base_dir else None,
            faulted=(half == "chaos"), mini=mini, stretch=stretch)
    board["metrics"] = scoreboard_metrics(board)
    board["ok"] = all(h.get("ok") for h in board["halves"].values())
    with open(scoreboard_path, "w", encoding="utf-8") as f:
        json.dump(board, f, indent=2, sort_keys=True)
    md_path = os.path.splitext(scoreboard_path)[0] + ".md"
    with open(md_path, "w", encoding="utf-8") as f:
        f.write(render_scoreboard(board))
    LAST = board
    return board
