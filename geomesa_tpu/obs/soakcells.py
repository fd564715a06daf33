"""Cluster-cell chaos soak: shard-routed writes + scatter-gather reads
under cell failover, mid-ingest ownership handoff, split-brain refusal
and a fully dark shard — the cfg16 gate.

Topology (all real subprocesses, like obs/soakfleet):

    router (shard-aware scatter-gather, tools/cli `router --shard ...`)
      ├── cell s0  keys [0, MID)   = s0p (primary) + s0r (replica)
      └── cell s1  keys [MID, TOP] = s1p (primary) + s1r (replica)

Every write goes through the router's POST /types/t/features and is
split by Morton key ownership (cluster/cells.geo_key); every read is a
scatter-gather count whose envelope must flip ``partial: true`` +
``missing_shards`` the moment a cell goes dark — and never otherwise.

Chaos half (two-sided, like cfg11/cfg12: each fault must be DETECTED
where expected and NOTHING may fire anywhere else):

  steady        routed writes land on their owning cells, counts exact
  cell_failover SIGKILL s0's primary: reads keep answering (follower =
                demoted-not-dropped), the dark cell's write sub-batch is
                refused loudly, /promote?shard=s0 flips the follower to
                primary inside GEOMESA_TPU_REPL_FAILOVER_BUDGET_MS, and
                the resurrected ex-primary is fenced before it rejoins
  handoff       /handoff?shard=s1 mid-ingest: drain + fence the old
                owner BEFORE the successor accepts (cells.hand_off)
  split_brain   both fenced losers (one per cell) take a direct write
                and BOTH must refuse with 403 {"kind": "fenced"} while
                the routed path still lands every row
  shard_dark    kill BOTH s0 members: the doctor opens exactly one
                ``shard_dark`` incident naming the key range + members,
                scatter reads answer partial with the missing range,
                and the incident resolves once the cell is respawned
  recovery      full-fleet catch-up, counts exact again

Clean half replays routed writes + reads with zero faults and requires
ZERO incidents.  Both halves end with conservation: the routed count
equals every acked write and the per-cell WAL-codec fingerprints of
primary and replica stores are byte-identical (zero acked-write loss).

The orchestrator watches the fleet through its OWN in-process
ReplicaRouter (HttpEndpoints + the same ShardCells topology) handed to
DoctorEngine(router=...), with every other detector bar parked at 1e12
— so precision/recall against the fault schedule is deterministic and
only ``shard_dark`` can ever fire.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from typing import Callable, Dict, List, Optional

from geomesa_tpu import config
from geomesa_tpu.obs.soakfleet import (_NoWorkload, _Traffic, _free_port,
                                       _http, _wait_http, percentile_ms,
                                       score_phases)

SCOREBOARD_DEFAULT = "SOAKCELLS_scoreboard.json"

# most recent scoreboard (GET /cluster/soak reads this)
LAST: Optional[dict] = None


def _log(msg: str) -> None:
    if os.environ.get("GEOMESA_TPU_SOAK_VERBOSE"):
        print(f"[soakcells +{time.monotonic() % 100000:.1f}] {msg}",
              file=sys.stderr, flush=True)


def last_run() -> Optional[dict]:
    return LAST


class CellSoak:
    """One soak half over a real two-cell subprocess cluster."""

    def __init__(self, base_dir: str, faulted: bool = True,
                 mini: bool = True):
        self.base = base_dir
        os.makedirs(base_dir, exist_ok=True)
        self.faulted = faulted
        self.mini = mini
        scale = 1.0 if mini else 3.0
        self.phase_s = float(config.SOAK_PHASE_S.get()) * scale
        self.wait_s = float(config.SOAK_WAIT_S.get())
        bits = int(config.CELL_GEO_KEY_BITS.get())
        self.mid = 1 << (2 * bits - 1)    # east/west hemisphere split
        self.top = (1 << (2 * bits)) - 1
        self.ranges = {"s0": (0, self.mid - 1),
                       "s1": (self.mid, self.top)}
        # current ROLE map — flips on failover/handoff; membership is
        # fixed (s0p/s0r always belong to cell s0)
        self.primary = {"s0": "s0p", "s1": "s1p"}
        self.replica = {"s0": "s0r", "s1": "s1r"}
        self.procs: Dict[str, subprocess.Popen] = {}
        self.ports: Dict[str, int] = {}
        self.ship_ports: Dict[str, int] = {}
        self.dirs: Dict[str, str] = {}
        self.router_port = 0
        self.rows = 0
        self.acked = 0
        self._wb = 0
        self.doctor = None
        self.obs_router = None
        self.traffic: Optional[_Traffic] = None
        self.phases: List[dict] = []
        self._seen: set = set()
        self.failover: Optional[dict] = None
        self.handoff_report: Optional[dict] = None
        self.split_brain = {"refusals": 0, "attempts": []}
        self.dark: Optional[dict] = None
        self.partial_envelope: Optional[dict] = None
        self.counts: List[dict] = []
        self.notes: List[str] = []

    # -- process management ---------------------------------------------------

    def _nodes(self) -> List[str]:
        return ["s0p", "s0r", "s1p", "s1r"]

    def _cell_spec(self, shard: str) -> str:
        lo, hi = self.ranges[shard]
        return f"{shard}={lo}:{hi}"

    def _member_spec(self, shard: str) -> str:
        p, r = sorted([self.primary[shard], self.replica[shard]])
        return f"{self._cell_spec(shard)}={p},{r}"

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

    def _spawn_primary(self, shard: str, name: str,
                       ship_port: Optional[int] = None) -> None:
        """Spawn (or resurrect) ``name`` as cell ``shard``'s durable
        primary.  First spawn seeds the schema offline."""
        d = self.dirs.setdefault(name, os.path.join(self.base, name))
        if not os.path.exists(d):
            from geomesa_tpu.datastore import TpuDataStore
            from geomesa_tpu.replication.drills import SPEC
            store = TpuDataStore.open(d, params={"wal.fsync": "off"})
            try:
                store.create_schema("t", SPEC)
            finally:
                store.close()
        sp = ship_port or _free_port()
        wp = self.ports.get(name) or _free_port()
        self.ship_ports[name] = sp
        self.ports[name] = wp
        self.procs[name] = self._spawn(
            ["serve", "-s", d, "--durable",
             "--ship-port", str(sp), "--port", str(wp),
             "--cell", self._cell_spec(shard)],
            self._node_env(name))
        _wait_http(wp)

    def _spawn_replica(self, shard: str, name: str,
                       follow_port: int, wait: bool = True) -> None:
        d = self.dirs.setdefault(name, os.path.join(self.base, name))
        port = self.ports.get(name) or _free_port()
        self.ports[name] = port
        self.procs[name] = self._spawn(
            ["replica", "--dir", d, "--follow",
             f"127.0.0.1:{follow_port}", "--port", str(port),
             "--id", name, "--cell", self._cell_spec(shard)],
            self._node_env(name))
        if wait:
            _wait_http(port)

    def _spawn_router(self) -> None:
        self.router_port = _free_port()
        args = ["router", "--port", str(self.router_port)]
        for n in self._nodes():
            args += ["--endpoint", f"{n}=127.0.0.1:{self.ports[n]}"]
        for shard in ("s0", "s1"):
            args += ["--shard", self._member_spec(shard)]
        self.procs["router"] = self._spawn(
            args, {"GEOMESA_TPU_NODE_ID": "router"})
        _wait_http(self.router_port)

    def _mk_doctor(self) -> None:
        """The orchestrator's own observation plane: an in-process
        shard-aware router over the same endpoints + topology, so the
        doctor's shard_dark detector sees what the fleet router sees."""
        from geomesa_tpu.cluster.cells import ShardCells
        from geomesa_tpu.metrics import MetricsRegistry
        from geomesa_tpu.obs.doctor import DoctorEngine
        from geomesa_tpu.serve.router import HttpEndpoint, ReplicaRouter
        eps = [HttpEndpoint(n, f"http://127.0.0.1:{self.ports[n]}",
                            timeout_s=2.0) for n in self._nodes()]
        topo = ShardCells.from_specs([self._member_spec("s0"),
                                      self._member_spec("s1")])
        self.obs_router = ReplicaRouter(eps, topology=topo)
        self.doctor = DoctorEngine(
            registry=MetricsRegistry(),
            slo_engine=False,
            journal_path=os.path.join(self.base, "cells_doctor.jsonl"),
            federator=False,
            workload=_NoWorkload(),
            router=self.obs_router)

    def start(self) -> None:
        for shard in ("s0", "s1"):
            self._spawn_primary(shard, self.primary[shard])
            self._spawn_replica(shard, self.replica[shard],
                                self.ship_ports[self.primary[shard]])
        self._spawn_router()
        self._mk_doctor()
        # warm the routed read path before traffic starts sampling
        for _ in range(3):
            self._count_routed()
        self.traffic = _Traffic(self.router_port, period_s=0.02)
        self.traffic.start()

    # -- writes / reads / catch-up --------------------------------------------

    def _write_batch(self, n: int = 40) -> dict:
        """One routed write through the fleet router.  The x grid spans
        both hemispheres so every batch splits across both cells; only
        rows the envelope reports WRITTEN count as acked."""
        i = self._wb
        self._wb += 1
        feats = []
        for j in range(n):
            x = -9.5 + ((i * 7 + j * 19) % 190) * 0.1
            y = -9.5 + ((i * 11 + j * 3) % 190) * 0.1
            feats.append({
                "type": "Feature", "id": f"c{i}_{j}",
                "geometry": {"type": "Point",
                             "coordinates": [round(x, 3), round(y, 3)]},
                "properties": {"name": "abc"[j % 3], "v": (i + j) % 100,
                               "dtg": "2024-01-01T06:00:00"}})
        body = json.dumps({"type": "FeatureCollection",
                           "features": feats}).encode()
        try:
            env = _http(self.router_port, "/types/t/features",
                        method="POST", body=body, timeout=30.0)
        except urllib.error.HTTPError as e:  # non-2xx: nothing acked
            return {"written": 0, "partial": True, "error": str(e)}
        got = int(env.get("written", 0))
        self.acked += got
        self.rows += got
        return env

    def _count_routed(self, timeout: float = 30.0) -> dict:
        return _http(self.router_port, "/types/t/count?cql=INCLUDE",
                     timeout=timeout)

    def _note_count(self, phase: str, env: dict) -> bool:
        exact = (int(env.get("count", -1)) == self.rows
                 and not env.get("partial"))
        self.counts.append({"phase": phase, "count": env.get("count"),
                            "expected": self.rows,
                            "partial": bool(env.get("partial")),
                            "exact": exact})
        return exact

    def _head_seq(self, name: str) -> Optional[int]:
        try:
            hz = _http(self.ports[name], "/healthz", timeout=2.0)
        except Exception:  # noqa: BLE001
            return None
        d = hz.get("durability") or {}
        if d.get("wal_seq") is not None:
            return int(d["wal_seq"])
        r = hz.get("replication") or {}
        v = r.get("applied_seq", r.get("last_seq"))
        return int(v) if v is not None else None

    def _wait_catchup(self, shards: Optional[List[str]] = None,
                      timeout_s: Optional[float] = None) -> bool:
        """Wait until each cell's replica has applied its primary's WAL
        head (always compared against the PRIMARY — a stalled follower
        can report zero lag against a stale view of the head)."""
        shards = shards or ["s0", "s1"]
        deadline = time.monotonic() + (timeout_s if timeout_s is not None
                                       else self.wait_s)
        while time.monotonic() < deadline:
            ok = True
            for shard in shards:
                rep = self.replica[shard]
                if not self._alive(rep) or \
                        not self._alive(self.primary[shard]):
                    continue
                head = self._head_seq(self.primary[shard])
                if head is None:
                    ok = False
                    continue
                try:
                    r = _http(self.ports[rep], "/healthz",
                              timeout=2.0).get("replication") or {}
                    applied = r.get("applied_seq")
                    if not r.get("connected") or applied is None \
                            or int(applied) < head:
                        ok = False
                except Exception:  # noqa: BLE001
                    ok = False
            if ok:
                return True
            time.sleep(0.1)
        return False

    def _wait_synced(self, names: Optional[List[str]] = None,
                     timeout_s: float = 20.0) -> bool:
        names = [n for n in (names or self._nodes()) if self._alive(n)]
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

    def _quiesce(self, shards: Optional[List[str]] = None) -> None:
        """Catch up + fsync so a subsequent SIGKILL cannot strand an
        acked row on exactly one node of a cell."""
        self._wait_catchup(shards)
        self._wait_synced()

    # -- doctor drive / phase machinery ---------------------------------------

    def _fresh(self) -> List[dict]:
        return [i for i in self.doctor.store.all()
                if i["id"] not in self._seen]

    def _open_rule(self, rule: str) -> bool:
        return any(i["rule"] == rule for i in self._fresh())

    def _all_resolved(self) -> bool:
        fresh = self._fresh()
        return bool(fresh) and all(i["status"] == "resolved"
                                   for i in fresh)

    def _drive(self, seconds: float,
               until: Optional[Callable[[], bool]] = None,
               period_s: float = 0.15) -> bool:
        deadline = time.monotonic() + seconds
        while True:
            self.doctor.evaluate()
            if until is not None and until():
                return True
            if time.monotonic() >= deadline:
                return until is None
            time.sleep(period_s)

    def _run_phase(self, name: str, expected_rule: Optional[str],
                   body: Callable[[], Optional[dict]]) -> dict:
        self._seen = {i["id"] for i in self.doctor.store.all()}
        if self.traffic is not None:
            self.traffic.set_phase(name)
        _log(f"phase {name} start")
        t0 = time.monotonic()
        extra = body() or {}
        dur = time.monotonic() - t0
        fresh = self._fresh()
        lat = self.traffic.phase_lat(name) if self.traffic else []
        rep = {
            "name": name, "expected_rule": expected_rule,
            "duration_s": round(dur, 2),
            "p50_ms": round(percentile_ms(lat, 0.50), 3),
            "p99_ms": round(percentile_ms(lat, 0.99), 3),
            "requests": len(lat),
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
        span = max(2.0, self.phase_s)
        self._drive(span * 0.4)
        e1 = self._write_batch()
        self._wait_catchup(timeout_s=15.0)
        self._drive(span * 0.3)
        e2 = self._write_batch()
        self._wait_catchup(timeout_s=15.0)
        self._drive(span * 0.3)
        exact = self._note_count("steady", self._count_routed())
        return {"counts_exact": exact,
                "write_partial": bool(e1.get("partial")
                                      or e2.get("partial")),
                "routed": {k: e1.get("routed", {}).get(k, 0)
                           + e2.get("routed", {}).get(k, 0)
                           for k in ("s0", "s1")}}

    def _p_cell_failover(self) -> dict:
        """SIGKILL cell s0's primary, fail over inside the cell within
        the budget, and fence the resurrected ex-primary before it can
        accept a write it no longer owns."""
        shard = "s0"
        old, rep = self.primary[shard], self.replica[shard]
        self._quiesce()
        p = self.procs[old]
        p.kill()
        p.wait(timeout=10.0)
        # reads survive the kill: the follower is demoted-not-dropped
        read_env = self._count_routed()
        # the dark cell's write sub-batch is refused LOUDLY (partial
        # envelope), never silently dropped — the other cell still lands
        kill_env = self._write_batch()
        new_sp = _free_port()
        res = _http(self.router_port,
                    f"/promote?port={new_sp}&shard={shard}",
                    method="POST", timeout=60.0)
        self.failover = {
            "shard": shard, "old_primary": old,
            "promoted": res.get("promoted"),
            "duration_ms": res.get("duration_ms"),
            "budget_ms": res.get("budget_ms"),
            "within_budget": bool(res.get("within_budget")),
            "epoch": (res.get("result") or {}).get("epoch"),
        }
        self.primary[shard], self.replica[shard] = rep, old
        self.ship_ports[rep] = new_sp
        # resurrect the loser as a primary that MISSED the failover
        # (true split-brain) — the runbook fences it before rejoin
        self._spawn_primary(shard, old)
        epoch = self.failover["epoch"] or 0
        fenced = _http(self.ports[old],
                       f"/replication/fence?epoch={int(epoch)}",
                       method="POST", timeout=10.0)
        post_env = self._write_batch()
        self._wait_catchup(timeout_s=15.0)
        exact = self._note_count("cell_failover", self._count_routed())
        return {"failover": self.failover,
                "read_partial_during_kill": bool(read_env.get("partial")),
                "write_partial_during_kill":
                    bool(kill_env.get("partial")),
                "loser_fenced": bool(fenced.get("fenced")),
                "post_failover_write_partial":
                    bool(post_env.get("partial")),
                "counts_exact": exact}

    def _p_handoff(self) -> dict:
        """Graceful ownership handoff on cell s1 in the middle of an
        ingest stream: drain + fence the old owner FIRST, promote the
        successor, and keep landing routed writes."""
        shard = "s1"
        old, rep = self.primary[shard], self.replica[shard]
        w1 = self._write_batch()
        res = _http(self.router_port, f"/handoff?shard={shard}",
                    method="POST", timeout=60.0)
        w2 = self._write_batch()
        self.handoff_report = {
            "shard": shard, "old_owner": res.get("old_owner"),
            "new_owner": res.get("new_owner"),
            "caught_up": bool(res.get("caught_up")),
            "head_seq": res.get("head_seq"),
            "epoch": res.get("epoch"),
            "duration_ms": res.get("duration_ms"),
        }
        self.primary[shard], self.replica[shard] = rep, old
        addr = (res.get("promoted") or {}).get("address") or ""
        try:
            self.ship_ports[rep] = int(addr.rsplit(":", 1)[1])
        except (IndexError, ValueError):
            pass
        self._wait_catchup(shards=["s0"], timeout_s=10.0)
        return {"handoff": self.handoff_report,
                "mid_ingest_write_partial": bool(w1.get("partial")),
                "post_handoff_write_partial": bool(w2.get("partial"))}

    def _direct_write_attempt(self, name: str, x: float) -> dict:
        """Bypass the router and write straight to one node — the
        split-brain probe.  A fenced loser MUST answer 403."""
        body = json.dumps({"type": "FeatureCollection", "features": [{
            "type": "Feature", "id": f"sb_{name}",
            "geometry": {"type": "Point", "coordinates": [x, 1.0]},
            "properties": {"name": "sb", "v": 1,
                           "dtg": "2024-01-01T06:00:00"}}]}).encode()
        try:
            out = _http(self.ports[name], "/types/t/features",
                        method="POST", body=body, timeout=10.0)
            return {"node": name, "refused": False, "status": 200,
                    "response": out}
        except urllib.error.HTTPError as e:
            kind = None
            try:
                kind = json.loads(e.read().decode()).get("kind")
            except Exception:  # noqa: BLE001
                pass
            return {"node": name, "refused": e.code == 403,
                    "status": e.code, "kind": kind}
        except Exception as e:  # noqa: BLE001
            return {"node": name, "refused": False, "status": None,
                    "error": str(e)}

    def _p_split_brain(self) -> dict:
        """Both cells now hold a fenced loser — s0's resurrected
        ex-primary and s1's handed-off old owner.  Each takes a direct
        write aimed at its own key range; BOTH must refuse, and the
        routed path must still land a full batch.  Then the losers
        rejoin as replicas of the new owners and converge."""
        for loser, x in (("s0p", -5.0), ("s1p", 5.0)):
            att = self._direct_write_attempt(loser, x)
            self.split_brain["attempts"].append(att)
            if att["refused"]:
                self.split_brain["refusals"] += 1
        routed = self._write_batch()
        # rejoin: SIGINT each loser, respawn as a replica of the winner
        for shard in ("s0", "s1"):
            loser = self.replica[shard]
            self._signal(loser, signal.SIGINT)
            self._spawn_replica(shard, loser,
                                self.ship_ports[self.primary[shard]])
        self._wait_catchup(timeout_s=self.wait_s)
        exact = self._note_count("split_brain", self._count_routed())
        return {"split_brain": self.split_brain,
                "routed_write_partial": bool(routed.get("partial")),
                "counts_exact": exact}

    def _p_shard_dark(self) -> dict:
        """Kill BOTH members of cell s0: the doctor pages ``shard_dark``
        naming the key range + members, scatter reads flip partial with
        the missing range, writes refuse the dead cell's rows loudly —
        then the cell respawns and the incident resolves."""
        shard = "s0"
        self._quiesce()
        for n in (self.primary[shard], self.replica[shard]):
            p = self.procs[n]
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10.0)
        detected = self._drive(self.wait_s * 2,
                               until=lambda:
                               self._open_rule("shard_dark"))
        inc = next((i for i in self.doctor.store.all()
                    if i["rule"] == "shard_dark"), None)
        env = self._count_routed()
        missing = env.get("missing_shards") or []
        self.partial_envelope = {
            "partial": bool(env.get("partial")),
            "missing_shards": missing,
            "names_range": any(m.get("shard") == shard
                               and m.get("key_range")
                               == list(self.ranges[shard])
                               for m in missing),
        }
        dark_write = self._write_batch()
        # respawn the cell: the promoted survivor resumes as primary
        # from its own WAL, the other member rejoins as its replica
        self._spawn_primary(shard, self.primary[shard],
                            ship_port=_free_port())
        self._spawn_replica(shard, self.replica[shard],
                            self.ship_ports[self.primary[shard]])
        self._wait_catchup(timeout_s=self.wait_s)
        resolved = self._drive(self.wait_s * 2,
                               until=self._all_resolved)
        self.dark = {
            "detected": detected, "resolved": resolved,
            "incident": None if inc is None else {
                "rule": inc["rule"], "cause": inc["cause"],
                "severity": inc["severity"],
                "suspect": inc.get("suspect")},
        }
        return {"dark": self.dark,
                "partial_envelope": self.partial_envelope,
                "dark_write_partial": bool(dark_write.get("partial"))}

    def _p_recovery(self) -> dict:
        env = self._write_batch()
        self._wait_catchup(timeout_s=self.wait_s)
        self._drive(max(2.0, self.phase_s))
        exact = self._note_count("recovery", self._count_routed())
        return {"counts_exact": exact,
                "write_partial": bool(env.get("partial"))}

    def _p_clean_writes(self) -> dict:
        partial = False
        for _ in range(4):
            partial = partial or bool(self._write_batch().get("partial"))
            self._drive(0.3)
        self._wait_catchup(timeout_s=15.0)
        exact = self._note_count("writes", self._count_routed())
        return {"counts_exact": exact, "write_partial": partial}

    # -- conservation ---------------------------------------------------------

    def _shutdown(self) -> None:
        self._quiesce()
        for n in list(self.procs):
            self._signal(n, signal.SIGINT)

    def _conservation(self) -> dict:
        from geomesa_tpu.replication.drills import fingerprint_dir
        out = {"expected_rows": self.rows, "acked_ingests": self.acked}
        try:
            env = self._count_routed()
            out["final_count"] = int(env["count"])
            out["final_partial"] = bool(env.get("partial"))
        except Exception as e:  # noqa: BLE001
            out["final_count"] = -1
            out["final_partial"] = True
            out["count_error"] = str(e)
        out["loss"] = out["expected_rows"] - out["final_count"]
        self._shutdown()
        cells_out = {}
        matched = True
        for shard in ("s0", "s1"):
            prints = {}
            for n in (self.primary[shard], self.replica[shard]):
                try:
                    prints[n] = fingerprint_dir(self.dirs[n])
                except Exception as e:  # noqa: BLE001
                    prints[n] = {"error": str(e)}
            vals = list(prints.values())
            cell_ok = (len(vals) == 2 and vals[0] == vals[1]
                       and "error" not in vals[0])
            cells_out[shard] = {"fingerprints": prints,
                                "matched": cell_ok}
            matched = matched and cell_ok
        out["cells"] = cells_out
        out["fingerprints_matched"] = matched
        return out

    # -- the half -------------------------------------------------------------

    def run(self) -> dict:
        t_start = time.time()
        knobs = [
            (config.DOCTOR_WINDOW_S, 8.0),
            (config.DOCTOR_CLEAR_TICKS, 2),
            # everything but shard_dark parked: precision/recall against
            # the fault schedule must be deterministic
            (config.DOCTOR_LAG_MS, 1e12),
            (config.DOCTOR_LAG_SEQS, 1e12),
            (config.DOCTOR_RECOMPILES_PER_MIN, 1e12),
            (config.DOCTOR_SHED_PER_MIN, 1e12),
            (config.DOCTOR_BREAKER_FLAPS, 1e12),
            (config.DOCTOR_FSYNC_ERRORS, 1e12),
            (config.DOCTOR_SKEW_MIN, 1e12),
            (config.DOCTOR_REINDEX_PER_MIN, 1e12),
            (config.DOCTOR_MERGE_BREACHES_PER_MIN, 1e12),
            (config.DOCTOR_STRAGGLER_MS, 1e12),
            (config.DOCTOR_IMBALANCE_MIN, 1e12),
        ]
        saved = [(p, p._override) for p, _ in knobs]
        conservation: dict = {}
        try:
            for p, v in knobs:
                p.set(v)
            self.start()
            if self.faulted:
                self._run_phase("steady", None, self._p_steady)
                self._run_phase("cell_failover", None,
                                self._p_cell_failover)
                self._run_phase("handoff", None, self._p_handoff)
                self._run_phase("split_brain", None, self._p_split_brain)
                self._run_phase("shard_dark", "shard_dark",
                                self._p_shard_dark)
                self._run_phase("recovery", None, self._p_recovery)
            else:
                self._run_phase("steady", None, self._p_steady)
                self._run_phase("writes", None, self._p_clean_writes)
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
                mode = "chaos" if self.faulted else "clean"
                src = os.path.join(self.base, "cells_doctor.jsonl")
                if os.path.exists(src):
                    shutil.copyfile(src, f"{art}.cells.{mode}.jsonl")
        doctor_score = score_phases(self.phases)
        report = {
            "mode": "chaos" if self.faulted else "clean",
            "mini": self.mini,
            "duration_s": round(time.time() - t_start, 1),
            "rows": self.rows, "acked": self.acked,
            "phases": self.phases,
            "doctor": doctor_score,
            "failover": self.failover,
            "handoff": self.handoff_report,
            "split_brain": self.split_brain,
            "dark": self.dark,
            "partial_envelope": self.partial_envelope,
            "counts": self.counts,
            "conservation": conservation,
            "traffic": {"requests": self.traffic.sent if self.traffic
                        else 0,
                        "errors": self.traffic.errors if self.traffic
                        else 0},
            "notes": self.notes,
        }
        by_name = {p["name"]: p for p in self.phases}
        checks = {
            "phases_ok": all(p.get("ok") for p in self.phases),
            "doctor_precision": doctor_score["precision"] == 1.0,
            "doctor_recall": doctor_score["recall"] == 1.0,
            "counts_exact": bool(self.counts) and all(
                c["exact"] for c in self.counts),
            "zero_loss": conservation.get("loss") == 0,
            "fingerprints_matched":
                bool(conservation.get("fingerprints_matched")),
        }
        if self.faulted:
            fo = self.failover or {}
            fl = by_name.get("cell_failover") or {}
            checks.update({
                "failover_within_budget": bool(fo.get("within_budget")),
                "reads_survived_primary_kill":
                    fl.get("read_partial_during_kill") is False,
                "dark_cell_write_refused_loudly":
                    fl.get("write_partial_during_kill") is True,
                "post_failover_write_full":
                    fl.get("post_failover_write_partial") is False,
                "handoff_caught_up":
                    bool((self.handoff_report or {}).get("caught_up")),
                "split_brain_refused_both":
                    self.split_brain["refusals"] == 2,
                "shard_dark_fired": bool((self.dark or {}).get(
                    "detected")),
                "shard_dark_resolved": bool((self.dark or {}).get(
                    "resolved")),
                "partial_envelope_seen": bool(
                    (self.partial_envelope or {}).get("partial")
                    and (self.partial_envelope or {}).get(
                        "names_range")),
            })
        else:
            checks["zero_incidents"] = \
                doctor_score["incidents_total"] == 0
        report["checks"] = checks
        report["ok"] = all(checks.values())
        return report


# -- entry points -------------------------------------------------------------


def run_cell_soak(base_dir: Optional[str] = None, faulted: bool = True,
                  mini: bool = True) -> dict:
    """Run one soak half, managing a scratch dir when none is given."""
    tmp = None
    if base_dir is None:
        tmp = tempfile.mkdtemp(prefix="geomesa-soakcells-")
        base_dir = tmp
    try:
        return CellSoak(base_dir, faulted=faulted, mini=mini).run()
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def scoreboard_metrics(board: dict) -> dict:
    """Flatten the scoreboard into its numeric cfg16 metrics (the
    ``metrics`` of the scoreboard ``GET /cluster/soak`` serves)."""
    m: Dict[str, float] = {}
    ch = (board.get("halves") or {}).get("chaos")
    cl = (board.get("halves") or {}).get("clean")
    if ch:
        steady = next((p for p in ch["phases"]
                       if p["name"] == "steady"), None)
        if steady:
            m["cfg16_steady_p50_ms"] = steady["p50_ms"]
            m["cfg16_steady_p99_ms"] = steady["p99_ms"]
        if ch.get("failover"):
            m["cfg16_failover_ms"] = ch["failover"]["duration_ms"]
            m["cfg16_failover_within_budget"] = float(
                ch["failover"]["within_budget"])
        if ch.get("handoff"):
            m["cfg16_handoff_ms"] = ch["handoff"]["duration_ms"]
        m["cfg16_doctor_precision"] = ch["doctor"]["precision"]
        m["cfg16_doctor_recall"] = ch["doctor"]["recall"]
        m["cfg16_acked_write_loss"] = float(
            ch["conservation"]["loss"]
            + (cl["conservation"]["loss"] if cl else 0))
        m["cfg16_fingerprints_matched"] = float(
            ch["conservation"]["fingerprints_matched"]
            and (cl is None
                 or cl["conservation"]["fingerprints_matched"]))
        m["cfg16_split_brain_refused"] = float(
            (ch.get("split_brain") or {}).get("refusals", 0))
        m["cfg16_shard_dark_fired"] = float(
            bool((ch.get("dark") or {}).get("detected")))
        m["cfg16_partial_envelope_seen"] = float(
            bool((ch.get("partial_envelope") or {}).get("partial")
                 and (ch.get("partial_envelope") or {}).get(
                     "names_range")))
    if cl:
        m["cfg16_clean_incidents"] = float(
            cl["doctor"]["incidents_total"])
    return m


def render_scoreboard(board: dict) -> str:
    """Markdown rendering of a scoreboard (written next to the JSON)."""
    lines = ["# Cluster cell soak scoreboard", ""]
    lines.append(f"- mini: {board.get('mini')}  ok: **{board.get('ok')}**")
    for mode, half in (board.get("halves") or {}).items():
        lines += ["", f"## {mode} half "
                      f"({'PASS' if half.get('ok') else 'FAIL'}, "
                      f"{half.get('duration_s')}s, "
                      f"{half.get('rows')} rows routed)", ""]
        lines.append("| phase | expected | incidents | p50 ms | p99 ms "
                     "| ok |")
        lines.append("|---|---|---|---|---|---|")
        for p in half.get("phases", []):
            rules = ", ".join(i["rule"]
                              for i in p["new_incidents"]) or "-"
            lines.append(
                f"| {p['name']} | {p.get('expected_rule') or '-'} "
                f"| {rules} | {p['p50_ms']} | {p['p99_ms']} "
                f"| {'yes' if p.get('ok') else 'NO'} |")
        d = half.get("doctor") or {}
        lines.append("")
        lines.append(f"- doctor precision **{d.get('precision')}** / "
                     f"recall **{d.get('recall')}** "
                     f"({d.get('correct')}/{d.get('incidents_total')} "
                     f"incidents correct)")
        fo = half.get("failover")
        if fo:
            lines.append(
                f"- failover: {fo['old_primary']} → {fo['promoted']} in "
                f"{fo['duration_ms']}ms (budget {fo['budget_ms']}ms, "
                f"within: {fo['within_budget']})")
        ho = half.get("handoff")
        if ho:
            lines.append(
                f"- handoff: {ho['old_owner']} → {ho['new_owner']} in "
                f"{ho['duration_ms']}ms (caught_up: {ho['caught_up']}, "
                f"epoch {ho['epoch']})")
        sb = half.get("split_brain")
        if sb and sb.get("attempts"):
            lines.append(f"- split-brain: {sb['refusals']}/"
                         f"{len(sb['attempts'])} fenced losers refused")
        pe = half.get("partial_envelope")
        if pe:
            lines.append(f"- dark-shard envelope: partial="
                         f"{pe['partial']}, names_range="
                         f"{pe['names_range']}")
        cons = half.get("conservation") or {}
        lines.append(
            f"- conservation: {cons.get('final_count')}/"
            f"{cons.get('expected_rows')} rows (loss "
            f"{cons.get('loss')}), fingerprints_matched="
            f"{cons.get('fingerprints_matched')}")
        checks = half.get("checks") or {}
        bad = [k for k, v in checks.items() if not v]
        if bad:
            lines.append(f"- FAILED checks: {', '.join(sorted(bad))}")
    metrics = board.get("metrics") or {}
    if metrics:
        lines += ["", "## cfg16 gate metrics", ""]
        lines.append("| metric | value |")
        lines.append("|---|---|")
        for k in sorted(metrics):
            lines.append(f"| {k} | {metrics[k]} |")
    return "\n".join(lines) + "\n"


def run(mini: bool = True, scoreboard_path: Optional[str] = None,
        base_dir: Optional[str] = None,
        halves: tuple = ("chaos", "clean")) -> dict:
    """Run the full soak (chaos + clean halves), write the scoreboard
    JSON + markdown, and remember it for ``GET /cluster/soak``."""
    global LAST
    scoreboard_path = scoreboard_path or os.environ.get(
        "GEOMESA_TPU_SOAKCELLS_SCOREBOARD", SCOREBOARD_DEFAULT)
    board: dict = {"schema": 1, "mini": mini, "halves": {},
                   "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                              time.gmtime())}
    for half in halves:
        board["halves"][half] = run_cell_soak(
            base_dir=os.path.join(base_dir, half) if base_dir else None,
            faulted=(half == "chaos"), mini=mini)
    board["metrics"] = scoreboard_metrics(board)
    board["ok"] = all(h.get("ok") for h in board["halves"].values())
    with open(scoreboard_path, "w", encoding="utf-8") as f:
        json.dump(board, f, indent=2, sort_keys=True)
    md_path = os.path.splitext(scoreboard_path)[0] + ".md"
    with open(md_path, "w", encoding="utf-8") as f:
        f.write(render_scoreboard(board))
    LAST = board
    return board
