"""ReplicaRouter: health-, overload- and lag-aware query routing across a
replicated serving fleet.

≙ the reference's reliance on the key-value store's client: an HBase/
Accumulo scan transparently retries against whichever tablet server holds
a healthy replica of the range. Here the router is explicit: it holds one
Endpoint per fleet node (in-process store/Follower objects, or remote
nodes addressed by their REST base URL), probes each node's `/healthz`
surface (overload section, breaker state, replication lag, fencing), and
spreads reads:

  healthy   in the rotation — round-robin across primary + fresh replicas
  demoted   out of the rotation but NOT dropped: a stale (lag over the
            bounded-staleness budget), breaker-open, unhealthy-scheduler
            or draining node still serves when nothing healthier is up —
            availability beats freshness at the bottom of the ladder
  down      probe/transport failure: skipped until a later probe revives

Reads that need read-your-writes freshness pin to the primary
(``freshness="strong"``); bounded reads accept any non-demoted node.
Failover = ``promote()``: drain the old primary via admission control,
pick the replica with the highest applied seq, and promote it under a new
fencing epoch.

Cell affinity (GEOMESA_TPU_AFFINITY): each routed count is stamped with
its coarse Morton cell (obs/sketches.cell_key — the same Z2 bit interleave
the curves use) and, when the workload plane marks that cell hot, the
rotation is re-ordered so the SAME healthy endpoint always leads for that
cell — its result/plan caches stay warm for the hot region instead
of the heat smearing round-robin across the fleet. Cold cells keep the
plain rotation; ``freshness="strong"`` pins and demotion are never
overridden (affinity only re-orders the healthy tier)."""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
import zlib
from typing import Dict, List, Optional

from geomesa_tpu import config
from geomesa_tpu.metrics import REGISTRY as _metrics

HEALTHY, DEMOTED, DOWN = "healthy", "demoted", "down"


class EndpointDown(Exception):
    """Transport/probe failure against one endpoint."""


class EndpointOverloaded(Exception):
    """The endpoint shed the request (429) or failed fast (503). Carries
    the replica's structured error envelope so the router hop can replay
    it VERBATIM: ``status``, the raw response ``body`` bytes, and the
    ``retry_after`` header value (None for local endpoints, which carry
    the structured fields instead)."""

    def __init__(self, msg: str, status: int = 503,
                 body: Optional[bytes] = None,
                 retry_after: Optional[str] = None,
                 envelope: Optional[dict] = None):
        super().__init__(msg)
        self.status = int(status)
        self.body = body
        self.retry_after = retry_after
        self.envelope = envelope or {}


class EndpointDeadline(EndpointOverloaded):
    """The endpoint reported deadline-exceeded (504). TERMINAL for the
    routed request: the deadline is request-global, so retrying another
    replica would spend device time on an answer nobody can use."""

    def __init__(self, msg: str, body: Optional[bytes] = None,
                 envelope: Optional[dict] = None):
        super().__init__(msg, status=504, body=body, envelope=envelope)


class NoEndpointAvailable(Exception):
    """Every endpoint in the fleet is down."""


class Endpoint:
    """One fleet node. Subclasses implement the transport."""

    def __init__(self, name: str):
        self.name = name
        self.last_probe: Optional[dict] = None
        self.last_probe_ts = 0.0
        self.failures = 0
        self._last_state: Optional[str] = None  # demotion-transition edge

    # -- transport hooks ------------------------------------------------------

    def _probe(self) -> dict:
        raise NotImplementedError

    def count(self, type_name: str, cql: str = "INCLUDE",
              auths: Optional[list] = None,
              deadline_ms: Optional[float] = None,
              priority: str = "interactive",
              tenant: Optional[str] = None) -> int:
        raise NotImplementedError

    def promote(self, port: int = 0) -> dict:
        raise NotImplementedError

    def drain(self) -> None:
        raise NotImplementedError

    def fence(self, epoch: int) -> dict:
        """Durably fence this node under ``epoch``: it refuses every
        subsequent write until re-promoted (the ownership-handoff and
        split-brain loser discipline)."""
        raise NotImplementedError

    def ingest(self, type_name: str, fc: dict,
               deadline_ms: Optional[float] = None) -> dict:
        """Write one GeoJSON FeatureCollection to this node."""
        raise NotImplementedError

    # -- probing --------------------------------------------------------------

    def probe(self, ttl_s: Optional[float] = None,
              clock=time.monotonic) -> Optional[dict]:
        """Cached health probe; None when the node is unreachable."""
        if ttl_s is None:
            ttl_s = float(config.REPL_PROBE_TTL_MS.get()) / 1000.0
        now = clock()
        if self.last_probe_ts and now - self.last_probe_ts < ttl_s:
            return self.last_probe
        t0 = time.perf_counter()
        try:
            p = self._probe()
            self.failures = 0
        except Exception:
            p = None
            self.failures += 1
        # per-endpoint probe latency: the router's own view of how slow
        # each node's health surface answers (failed probes count too —
        # a timing-out replica IS the signal)
        _metrics.observe(f"router.probe.{self.name}",
                         time.perf_counter() - t0)
        _metrics.inc("router.probes")
        self.last_probe = p
        self.last_probe_ts = now
        return p

    def _demotion_reason(self, p: dict, staleness_ms: float) \
            -> Optional[str]:
        if p.get("fenced"):
            return "fenced"
        if p.get("draining"):
            return "draining"
        if p.get("breaker_open"):
            return "breaker_open"
        if not p.get("scheduler_ok", True):
            return "scheduler_unhealthy"
        if (p.get("lag_ms") or 0.0) > staleness_ms:
            return "stale"
        return None

    def classify(self, staleness_ms: Optional[float] = None) -> str:
        p = self.probe()
        if staleness_ms is None:
            staleness_ms = float(config.REPL_STALENESS_MS.get())
        if p is None:
            state, reason = DOWN, None
        else:
            reason = self._demotion_reason(p, staleness_ms)
            state = DEMOTED if reason is not None else HEALTHY
        if state != self._last_state:
            # transition edges only — a demoted node re-probed every TTL
            # is ONE demotion, not one per request (`debug replication`
            # dumps these; demotions were previously silent)
            if state == DEMOTED:
                _metrics.inc(f"router.demotions.{reason}")
                _metrics.inc("router.demotions")
            elif state == DOWN:
                _metrics.inc("router.endpoint_down")
            self._last_state = state
        return state

    @property
    def role(self) -> str:
        return (self.last_probe or {}).get("role", "unknown")


def _health_from_parts(role: str, repl_stats: Optional[dict],
                       sched) -> dict:
    """Canonical probe dict from a node's replication stats + live
    scheduler (the same fields HttpEndpoint extracts from /healthz)."""
    out = {"ok": True, "role": role, "fenced": False, "lag_ms": 0.0,
           "lag_seqs": 0, "applied_seq": None, "epoch": None,
           "scheduler_ok": True, "breaker_open": False, "queue_depth": 0,
           "draining": False}
    if repl_stats:
        out["role"] = repl_stats.get("role", role)
        out["fenced"] = bool(repl_stats.get("fenced"))
        out["lag_ms"] = float(repl_stats.get("lag_ms") or 0.0)
        out["lag_seqs"] = int(repl_stats.get("lag_seqs") or 0)
        out["applied_seq"] = repl_stats.get("applied_seq",
                                            repl_stats.get("last_seq"))
        out["epoch"] = repl_stats.get("epoch")
        if repl_stats.get("dead"):
            raise EndpointDown("replica apply loop is dead")
    if sched is not None:
        out["scheduler_ok"] = sched.healthy()
        out["breaker_open"] = sched.breaker.state != "closed"
        out["queue_depth"] = sched._queue.qsize()
        out["draining"] = sched.admission.draining
    return out


class LocalEndpoint(Endpoint):
    """In-process node: a TpuDataStore, or a replication role object
    (Follower / a store carrying a LogShipper)."""

    def __init__(self, name: str, target):
        super().__init__(name)
        self.target = target

    @property
    def store(self):
        # a Follower proxies to its live store (which it may swap across a
        # snapshot install); a plain store is itself
        return getattr(self.target, "store", self.target)

    def _probe(self) -> dict:
        store = self.store
        if store.durability is not None and store.durability.closed:
            raise EndpointDown("store is closed")
        repl = getattr(store, "replication", None)
        repl_stats = repl.stats() if repl is not None else None
        role = repl_stats["role"] if repl_stats else "standalone"
        sched = getattr(store, "_scheduler", None)  # live only, never spawn
        return _health_from_parts(role, repl_stats, sched)

    def count(self, type_name, cql="INCLUDE", auths=None, deadline_ms=None,
              priority="interactive", tenant=None) -> int:
        from geomesa_tpu.serve.resilience.admission import ShedError
        from geomesa_tpu.serve.resilience.breaker import CircuitOpenError
        try:
            return self.store.count_coalesced(
                type_name, cql, auths=auths, deadline_ms=deadline_ms,
                priority=priority, tenant=tenant)
        except ShedError as e:
            raise EndpointOverloaded(
                str(e), status=429,
                envelope={"error": str(e), "kind": "shed",
                          "priority": e.priority,
                          "retry_after_s": e.retry_after_s})
        except CircuitOpenError as e:
            raise EndpointOverloaded(
                str(e), status=503,
                envelope={"error": str(e), "kind": "breaker_open",
                          "retry_after_s": e.retry_after_s})
        except ValueError as e:
            # a closed store surfaces as ValueError("WAL is closed") etc.
            if "closed" in str(e):
                raise EndpointDown(str(e))
            raise

    def promote(self, port: int = 0) -> dict:
        shipper = self.target.promote(port=port)
        self.target = self.store  # the Follower role object is done
        return {"role": "primary", "epoch": shipper.epoch,
                "address": shipper.address}

    def drain(self) -> None:
        self.store.scheduler().admission.drain(True)

    def fence(self, epoch: int) -> dict:
        from geomesa_tpu.replication import fence as _f
        store = self.store
        repl = getattr(store, "replication", None)
        if repl is not None and hasattr(repl, "_fence_self"):
            repl._fence_self(int(epoch))
        else:
            _f.save_epoch(store.durability.path, int(epoch))
            store.durability.read_only = True
        self.last_probe_ts = 0.0
        return {"fenced": True, "epoch": int(epoch)}

    def ingest(self, type_name, fc, deadline_ms=None) -> dict:
        from geomesa_tpu.web.server import GeoJsonApi
        api = GeoJsonApi(self.store)
        written = api._ingest_geojson(type_name, fc)
        return {"written": int(written)}


class HttpEndpoint(Endpoint):
    """Remote node addressed by its REST base URL (web/server.py)."""

    def __init__(self, name: str, base_url: str, timeout_s: float = 5.0):
        super().__init__(name)
        self.base = base_url.rstrip("/")
        self.timeout_s = float(timeout_s)

    def _request(self, path: str, method: str = "GET",
                 propagate: bool = False,
                 body: Optional[bytes] = None,
                 timeout_s: Optional[float] = None) -> dict:
        req = urllib.request.Request(self.base + path, method=method,
                                     data=body)
        if body is not None:
            req.add_header("Content-Type", "application/json")
        if propagate:
            # cross-process trace context: the remote node opens its
            # request trace as a child of the current span, so the
            # stitcher can reassemble ONE fleet-wide tree
            from geomesa_tpu import trace as _t
            for k, v in _t.inject_headers().items():
                req.add_header(k, v)
        try:
            with urllib.request.urlopen(
                    req, timeout=(timeout_s if timeout_s is not None
                                  else self.timeout_s)) as r:
                return json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            body = None
            envelope = {}
            try:
                body = e.read()
                envelope = json.loads(body.decode())
            except Exception:
                pass
            retry_after = e.headers.get("Retry-After") if e.headers else None
            if e.code in (429, 503):
                # the replica's structured envelope + Retry-After ride
                # the exception so the router hop replays them verbatim
                raise EndpointOverloaded(f"{self.name}: HTTP {e.code}",
                                         status=e.code, body=body,
                                         retry_after=retry_after,
                                         envelope=envelope)
            if e.code == 504:
                raise EndpointDeadline(f"{self.name}: HTTP 504",
                                       body=body, envelope=envelope)
            raise EndpointDown(f"{self.name}: HTTP {e.code}")
        except (urllib.error.URLError, OSError, ValueError) as e:
            raise EndpointDown(f"{self.name}: {e}")

    def _probe(self) -> dict:
        hz = self._request("/healthz")
        repl = hz.get("replication") or None
        overload = hz.get("overload", {})
        out = _health_from_parts("standalone", repl, None)
        if overload.get("scheduler") not in (None, "idle", "ok"):
            out["scheduler_ok"] = False
        out["queue_depth"] = int(overload.get("queue_depth", 0))
        breaker = overload.get("breaker") or {}
        out["breaker_open"] = breaker.get("state", "closed") != "closed"
        admission = overload.get("admission") or {}
        out["draining"] = bool(admission.get("draining"))
        return out

    def count(self, type_name, cql="INCLUDE", auths=None, deadline_ms=None,
              priority="interactive", tenant=None) -> int:
        from geomesa_tpu import trace as _t
        q = {"cql": cql, "priority": priority}
        if auths:
            q["auths"] = ",".join(auths)
        if deadline_ms:
            q["deadline_ms"] = str(deadline_ms)
        if tenant:
            q["tenant"] = tenant
        # the proxy span is the remote half's parent: its span id rides
        # X-Span-Id, and its wall time minus the remote root's wall time
        # is the hop's network cost in the stitched tree
        with _t.span(f"proxy.{self.name}", kind="remote_call",
                     endpoint=self.name):
            out = self._request(f"/types/{type_name}/count?"
                                + urllib.parse.urlencode(q),
                                propagate=True)
        return int(out["count"])

    def promote(self, port: int = 0) -> dict:
        return self._request(f"/replication/promote?port={int(port)}",
                             method="POST")

    def drain(self) -> None:
        self._request("/replication/drain", method="POST")

    def fence(self, epoch: int) -> dict:
        out = self._request(f"/replication/fence?epoch={int(epoch)}",
                            method="POST")
        self.last_probe_ts = 0.0
        return out

    def ingest(self, type_name, fc, deadline_ms=None) -> dict:
        path = f"/types/{type_name}/features"
        if deadline_ms:
            path += f"?deadline_ms={float(deadline_ms)}"
        out = self._request(
            path, method="POST",
            body=json.dumps(fc).encode(),
            timeout_s=(max(1.0, float(deadline_ms) / 1000.0 + 1.0)
                       if deadline_ms else None))
        return {"written": int(out.get("ingested", 0))}


class ReplicaRouter:
    """Spread queries across primary + replicas; fail over reads around
    sick nodes; orchestrate promote-by-highest-acked-seq failover."""

    def __init__(self, endpoints: List[Endpoint],
                 staleness_ms: Optional[float] = None,
                 topology=None):
        self.endpoints: Dict[str, Endpoint] = {e.name: e for e in endpoints}
        self._staleness_ms = staleness_ms
        # shard topology (cluster/cells.ShardCells): when present, reads
        # scatter-gather across cells and writes route by key ownership
        self.topology = topology
        self._lock = threading.Lock()
        self._rr = 0
        self._n_requests = 0
        self._n_failovers = 0
        self._n_promotions = 0
        self._n_scatters = 0
        self._n_partials = 0
        self._n_shard_retries = 0
        self._n_handoffs = 0
        # cell affinity: LRU-bounded cql -> Morton cell memo (a
        # high-cardinality filter stream evicts instead of growing or
        # clearing wholesale) + a short-TTL snapshot of the workload
        # plane's hot cells (at_least floored)
        self._n_affinity = 0
        from geomesa_tpu.serve.scheduler import LruCache
        self._cell_memo = LruCache(int(config.ROUTER_CELL_MEMO.get()),
                                   "router.cell_memo")
        from geomesa_tpu.metrics import REGISTRY
        REGISTRY.set_gauge("router.cell_memo.size",
                           lambda: len(self._cell_memo))
        self._hot_cells: Dict[str, int] = {}
        self._hot_at = 0.0

    # -- selection ------------------------------------------------------------

    def _staleness(self) -> float:
        return float(self._staleness_ms
                     if self._staleness_ms is not None
                     else config.REPL_STALENESS_MS.get())

    def probe_all(self, force: bool = False) -> Dict[str, Optional[dict]]:
        out = {}
        for name, ep in self.endpoints.items():
            if force:
                ep.last_probe_ts = 0.0
            out[name] = ep.probe()
        return out

    def _primary(self, eps: Optional[Dict[str, Endpoint]] = None) \
            -> Optional[Endpoint]:
        for ep in (eps or self.endpoints).values():
            p = ep.probe()
            if p is not None and p.get("role") == "primary" \
                    and not p.get("fenced"):
                return ep
        return None

    def _query_cell(self, cql: str) -> Optional[str]:
        """The query's coarse Morton cell (LRU-memoized per cql string —
        bounded by GEOMESA_TPU_ROUTER_CELL_MEMO, size exported as the
        router.cell_memo.size gauge; None results are cached too)."""
        from geomesa_tpu.serve.scheduler import _MISS
        cached = self._cell_memo.get(cql)
        if cached is not _MISS:
            return cached
        from geomesa_tpu.filter.parser import parse_ecql
        from geomesa_tpu.serve.scheduler import _query_cell
        try:
            cell = _query_cell(parse_ecql(cql))
        except Exception:
            cell = None
        self._cell_memo.put(cql, cell)
        return cell

    def _cell_is_hot(self, cell: str) -> bool:
        """Whether the workload plane guarantees (at_least) enough hits on
        the cell to justify pinning it (short-TTL snapshot of hot_set())."""
        floor = int(config.AFFINITY_MIN_AT_LEAST.get())
        if floor <= 0:
            return True
        now = time.monotonic()
        if now - self._hot_at > \
                float(config.RESULT_CACHE_HOTSET_TTL_S.get()):
            from geomesa_tpu.obs.workload import WORKLOAD
            try:
                hs = WORKLOAD.hot_set()
                self._hot_cells = {e["key"]: e["at_least"]
                                   for e in hs["cells"]}
            except Exception:
                self._hot_cells = {}
            self._hot_at = now
        return self._hot_cells.get(cell, 0) >= floor

    def candidates(self, freshness: str = "bounded",
                   cell: Optional[str] = None) -> List[Endpoint]:
        """Ordered endpoints to try. strong → the primary only (read-your-
        writes); bounded → healthy nodes in rotation, then demoted nodes
        (stale replicas are demoted, never dropped), down nodes skipped.
        A hot ``cell`` re-orders the healthy tier so the same endpoint
        leads for that cell every time (cache warmth); demotion and
        strong pins are never overridden."""
        if freshness == "strong":
            prim = self._primary()
            if prim is None:
                raise NoEndpointAvailable("no live primary for a strong "
                                          "read")
            return [prim]
        staleness = self._staleness()
        healthy, demoted = [], []
        for ep in self.endpoints.values():
            c = ep.classify(staleness)
            if c == HEALTHY:
                healthy.append(ep)
            elif c == DEMOTED:
                demoted.append(ep)
        if cell is not None and healthy \
                and bool(config.AFFINITY_ENABLED.get()) \
                and self._cell_is_hot(cell):
            # consistent choice over a STABLE ordering (by name), so the
            # pick survives rotation state, probe order and healthy-set
            # membership of the other endpoints
            stable = sorted(healthy, key=lambda e: e.name)
            pin = stable[zlib.crc32(cell.encode()) % len(stable)]
            with self._lock:
                self._n_affinity += 1
            _metrics.inc("router.affinity_pins")
            out = [pin] + [e for e in healthy if e is not pin] + demoted
            return out
        with self._lock:
            self._rr += 1
            rot = self._rr
        healthy = healthy[rot % len(healthy):] + healthy[:rot % len(healthy)] \
            if healthy else []
        out = healthy + demoted
        if not out:
            raise NoEndpointAvailable("every endpoint is down")
        return out

    # -- serving --------------------------------------------------------------

    def count(self, type_name: str, cql: str = "INCLUDE",
              auths: Optional[list] = None,
              deadline_ms: Optional[float] = None,
              priority: str = "interactive",
              freshness: str = "bounded",
              tenant: Optional[str] = None) -> int:
        """Route one count; fails over across candidates on transport
        errors and overload sheds. Raises the last error when every
        candidate refuses. ``tenant`` rides through to the serving
        node's QoS admission, so per-tenant fairness holds fleet-wide."""
        self._n_requests += 1
        _metrics.inc("router.requests")
        if freshness == "strong":
            _metrics.inc("router.strong_pins")
        cell = self._query_cell(cql) \
            if freshness != "strong" and config.AFFINITY_ENABLED.get() \
            else None
        last: Optional[Exception] = None
        for i, ep in enumerate(self.candidates(freshness, cell=cell)):
            try:
                n = ep.count(type_name, cql, auths=auths,
                             deadline_ms=deadline_ms, priority=priority,
                             tenant=tenant)
                _metrics.inc(f"router.served.{ep.name}")
                if i > 0:
                    self._n_failovers += 1
                    _metrics.inc("router.read_failovers")
                return n
            except EndpointDeadline:
                # terminal: the deadline is request-global — another
                # replica cannot beat a clock that already expired
                raise
            except (EndpointDown, EndpointOverloaded) as e:
                # transport death invalidates the cached probe immediately
                if isinstance(e, EndpointDown):
                    ep.last_probe = None
                    ep.failures += 1
                _metrics.inc("router.endpoint_errors")
                last = e
        raise last if last is not None else NoEndpointAvailable(
            "no candidate endpoints")

    # -- shard-aware scatter-gather -------------------------------------------

    def _cell_members(self, shard: str) -> Dict[str, Endpoint]:
        cell = self.topology.cell(shard)
        return {n: self.endpoints[n] for n in cell.members
                if n in self.endpoints}

    def shard_candidates(self, shard: str,
                         writes: bool = False) -> List[Endpoint]:
        """Ordered members of one cell to try: healthy in rotation,
        then demoted (the demoted-not-dropped tier — a stale follower
        still answers when its cell's primary is gone). ``writes``
        leads with the cell primary instead of rotating (only it can
        accept mutations; followers stay as retry probes that surface
        a just-promoted successor)."""
        staleness = self._staleness()
        healthy, demoted = [], []
        for ep in self._cell_members(shard).values():
            c = ep.classify(staleness)
            if c == HEALTHY:
                healthy.append(ep)
            elif c == DEMOTED:
                demoted.append(ep)
        if writes:
            healthy.sort(
                key=lambda e: (e.last_probe or {}).get("role")
                != "primary")
            return healthy + demoted
        with self._lock:
            self._rr += 1
            rot = self._rr
        if healthy:
            healthy = healthy[rot % len(healthy):] \
                + healthy[:rot % len(healthy)]
        return healthy + demoted

    def scatter_shards(self, call, deadline_ms: Optional[float] = None,
                       writes: bool = False):
        """Run ``call(endpoint, budget_ms, shard)`` once per shard cell,
        concurrently, with per-shard deadline budgets carved from the
        request deadline (CELL_SHARD_BUDGET_FRACTION of the REMAINING
        deadline per attempt, floored at CELL_SHARD_MIN_BUDGET_MS) and
        partial-shard retry against the cell's remaining members.

        Returns ``(results, meta)``: ``results`` maps shard -> the
        call's value IN KEY-RANGE ORDER (so concatenating per-shard
        payloads is the rank-order merge — the same discipline as
        cluster/exec.ordered_merge), with None for a shard every member
        refused; ``meta`` carries served_by/retries per shard."""
        topo = self.topology
        if topo is None:
            raise ValueError("scatter_shards needs a shard topology")
        with self._lock:
            self._n_scatters += 1
        _metrics.inc("router.scatters")
        t0 = time.monotonic()
        frac = float(config.CELL_SHARD_BUDGET_FRACTION.get())
        floor_ms = float(config.CELL_SHARD_MIN_BUDGET_MS.get())
        retry = bool(config.CELL_RETRY_FOLLOWERS.get())
        results: Dict[str, object] = {c.shard: None for c in topo.cells}
        meta: Dict[str, dict] = {c.shard: {"served_by": None,
                                           "retries": 0,
                                           "error": None}
                                 for c in topo.cells}

        def budget() -> Optional[float]:
            if deadline_ms is None:
                return None
            remaining = float(deadline_ms) \
                - (time.monotonic() - t0) * 1000.0
            return max(floor_ms, remaining * frac)

        def spent() -> bool:
            return deadline_ms is not None and \
                (time.monotonic() - t0) * 1000.0 >= float(deadline_ms)

        def one_shard(shard: str) -> None:
            cands = self.shard_candidates(shard, writes=writes)
            if not retry:
                cands = cands[:1]
            for i, ep in enumerate(cands):
                if i > 0 and spent():
                    meta[shard]["error"] = "deadline"
                    return
                try:
                    results[shard] = call(ep, budget(), shard)
                    meta[shard]["served_by"] = ep.name
                    meta[shard]["retries"] = i
                    if i > 0:
                        with self._lock:
                            self._n_shard_retries += 1
                        _metrics.inc("router.shard_retries")
                    return
                except EndpointDeadline as e:
                    # terminal for the whole request's clock: another
                    # member cannot beat a deadline that expired
                    meta[shard]["error"] = f"deadline: {e}"
                    return
                except (EndpointDown, EndpointOverloaded) as e:
                    if isinstance(e, EndpointDown):
                        ep.last_probe = None
                        ep.failures += 1
                    _metrics.inc("router.endpoint_errors")
                    meta[shard]["error"] = str(e)
            if not cands:
                meta[shard]["error"] = "no live member"

        from geomesa_tpu.obs import hostcpu
        threads = [hostcpu.thread("program.other", target=one_shard,
                                  args=(c.shard,), daemon=True)
                   for c in topo.cells]
        for th in threads:
            th.start()
        join_s = (float(deadline_ms) / 1000.0 + 5.0) \
            if deadline_ms else 60.0
        for th in threads:
            th.join(timeout=max(0.1, join_s - (time.monotonic() - t0)))
        return results, meta

    def _partial_envelope(self, results: dict, meta: dict) -> dict:
        """The explicit missing-shard contract: when a shard is truly
        dark the answer says WHICH key range is absent instead of
        silently undercounting."""
        topo = self.topology
        missing = [dict(topo.cell(s).summary(),
                        error=meta[s].get("error"))
                   for s, v in results.items() if v is None]
        out = {"partial": bool(missing),
               "shards": {s: {"value": v, **meta[s]}
                          for s, v in results.items()}}
        if missing:
            out["missing_shards"] = missing
            with self._lock:
                self._n_partials += 1
            _metrics.inc("router.partial_results")
        return out

    def count_scatter(self, type_name: str, cql: str = "INCLUDE",
                      auths: Optional[list] = None,
                      deadline_ms: Optional[float] = None,
                      priority: str = "interactive",
                      tenant: Optional[str] = None) -> dict:
        """Scatter one count across every shard cell and sum. The
        response envelope carries per-shard attribution and flips
        ``partial: true`` + ``missing_shards`` when a cell is dark."""
        results, meta = self.scatter_shards(
            lambda ep, bdg, _s: int(ep.count(
                type_name, cql, auths=auths, deadline_ms=bdg,
                priority=priority, tenant=tenant)),
            deadline_ms=deadline_ms)
        env = self._partial_envelope(results, meta)
        env["count"] = int(sum(v for v in results.values()
                               if v is not None))
        return env

    def ingest_scatter(self, type_name: str, fc: dict,
                       deadline_ms: Optional[float] = None) -> dict:
        """Route one FeatureCollection's writes by Morton key ownership:
        split the batch by each point's routing key (cells.geo_key),
        send every sub-batch to its owning cell (primary-first, with
        follower probes surfacing a just-promoted successor), and
        report per-shard landings. A dark cell's sub-batch is refused
        loudly in the envelope — never silently dropped."""
        feats = fc.get("features", [])
        if not feats:
            return {"written": 0, "partial": False, "shards": {}}
        from geomesa_tpu.cluster import cells as _cells
        xs, ys = [], []
        for f in feats:
            g = f.get("geometry") or {}
            if (g.get("type") or "Point").upper() != "POINT":
                raise ValueError("shard-routed ingest supports Point "
                                 "features (cells route by point key)")
            xs.append(float(g["coordinates"][0]))
            ys.append(float(g["coordinates"][1]))
        owners = self.topology.route_points(xs, ys)
        by_shard: Dict[str, list] = {}
        for f, o in zip(feats, owners):
            by_shard.setdefault(self.topology.cells[int(o)].shard,
                                []).append(f)

        def write(ep, bdg, shard):
            feats_s = by_shard.get(shard)
            if not feats_s:
                # this cell owns no rows of the batch: nothing to send,
                # and the shard is not "missing" — it was never addressed
                return 0
            out = ep.ingest(type_name,
                            {"type": "FeatureCollection",
                             "features": feats_s},
                            deadline_ms=bdg)
            return int(out.get("written", 0))

        results, meta = self.scatter_shards(
            write, deadline_ms=deadline_ms, writes=True)
        env = self._partial_envelope(results, meta)
        env["written"] = int(sum(v for v in results.values()
                                 if v is not None))
        env["routed"] = {s: len(v) for s, v in by_shard.items()}
        return env

    def shard_health(self) -> Dict[str, dict]:
        """Per-shard endpoint health for the doctor's ``shard_dark``
        rule: healthy/demoted/down member counts + the key range."""
        if self.topology is None:
            return {}
        staleness = self._staleness()
        out = {}
        for cell in self.topology.cells:
            states = {}
            for name, ep in self._cell_members(cell.shard).items():
                states[name] = ep.classify(staleness)
            out[cell.shard] = {
                "key_range": [int(cell.key_lo), int(cell.key_hi)],
                "members": states,
                "healthy": sum(1 for s in states.values()
                               if s == HEALTHY),
                "serving": sum(1 for s in states.values()
                               if s in (HEALTHY, DEMOTED)),
            }
        return out

    def handoff(self, shard: str, wait_s: Optional[float] = None) -> dict:
        """Graceful ownership handoff inside one cell: drain + fence
        the old owner BEFORE the successor accepts (cells.hand_off)."""
        from geomesa_tpu.cluster import cells as _cells
        eps = self._cell_members(shard)
        for ep in eps.values():
            ep.last_probe_ts = 0.0
        old = self._primary(eps)
        if old is None:
            raise NoEndpointAvailable(f"shard {shard}: no live primary "
                                      "to hand off from")
        cands = sorted(
            ((int((ep.probe() or {}).get("applied_seq") or 0), n, ep)
             for n, ep in eps.items()
             if ep is not old and ep.probe() is not None
             and (ep.last_probe or {}).get("role") == "replica"),
            reverse=True)
        if not cands:
            raise NoEndpointAvailable(f"shard {shard}: no live replica "
                                      "to hand off to")
        _seq, new_name, new = cands[0]
        report = _cells.hand_off(old, new, wait_s=wait_s)
        self.probe_all(force=True)
        with self._lock:
            self._n_handoffs += 1
        _metrics.inc("router.handoffs")
        return dict(report, shard=shard, old_owner=old.name,
                    new_owner=new_name)

    # -- failover -------------------------------------------------------------

    def promote(self, port: int = 0,
                shard: Optional[str] = None) -> dict:
        """Failover: drain the old primary (when reachable), promote the
        replica with the highest applied seq under a fresh fencing epoch,
        and report whether the whole operation landed inside the
        configured failover deadline budget. ``shard`` scopes the whole
        operation to ONE cell's members — in-cell failover never touches
        the other shards' primaries."""
        t0 = time.monotonic()
        eps = self.endpoints if shard is None \
            else self._cell_members(shard)
        for ep in eps.values():
            ep.last_probe_ts = 0.0
            ep.probe()
        old = self._primary(eps)
        if old is not None:
            try:
                old.drain()
            except Exception:
                pass  # a dead primary cannot be drained — that's the point
        replicas = [(ep.last_probe.get("applied_seq") or 0, name, ep)
                    for name, ep in eps.items()
                    if ep.last_probe is not None
                    and ep.last_probe.get("role") == "replica"]
        if not replicas:
            raise NoEndpointAvailable("no live replica to promote")
        replicas.sort(reverse=True)
        seq, name, winner = replicas[0]
        result = winner.promote(port=port)
        for ep in eps.values():
            ep.last_probe_ts = 0.0
            ep.probe()
        dur_ms = (time.monotonic() - t0) * 1000.0
        budget = float(config.REPL_FAILOVER_BUDGET_MS.get())
        self._n_promotions += 1
        _metrics.inc("router.promotions")
        return {"promoted": name, "acked_seq": seq, "result": result,
                "shard": shard,
                "old_primary": old.name if old is not None else None,
                "duration_ms": round(dur_ms, 1),
                "budget_ms": budget,
                "within_budget": dur_ms <= budget}

    # -- surfaces -------------------------------------------------------------

    def stats(self) -> dict:
        staleness = self._staleness()
        out = {
            "staleness_ms": staleness,
            "requests": self._n_requests,
            "read_failovers": self._n_failovers,
            "promotions": self._n_promotions,
            "affinity_pins": self._n_affinity,
            "affinity_enabled": bool(config.AFFINITY_ENABLED.get()),
            "scatters": self._n_scatters,
            "partial_results": self._n_partials,
            "shard_retries": self._n_shard_retries,
            "handoffs": self._n_handoffs,
            "endpoints": {
                name: {"state": ep.classify(staleness),
                       "role": ep.role,
                       "failures": ep.failures,
                       "probe": ep.last_probe}
                for name, ep in self.endpoints.items()},
        }
        if self.topology is not None:
            out["topology"] = self.topology.summary()
        return out

    def node_targets(self) -> Dict[str, Optional[str]]:
        """name -> base URL (None for in-process endpoints) — the node
        map the federator and the trace stitcher fetch from."""
        out: Dict[str, Optional[str]] = {}
        for name, ep in self.endpoints.items():
            out[name] = ep.base if isinstance(ep, HttpEndpoint) else None
        return out


# -- the router's own HTTP surface (the fleet's front door) -------------------


class RouterApi:
    """Transport-agnostic request handler for a router node: proxied
    counts with cross-process trace propagation, the federated fleet
    surfaces, and the trace stitcher.

    Routes:
      GET /types/{t}/count?cql=&freshness=   routed count (one stitched
                                             trace across router + the
                                             serving node); a replica's
                                             429/503/504 envelope and
                                             Retry-After header survive
                                             the hop VERBATIM
      GET /fleet                             per-node health/lag/seq +
                                             fleet SLO burn rates
      GET /fleet/metrics                     federated Prometheus (node-
                                             labeled counters/gauges,
                                             exactly-merged histograms)
      GET /fleet/slo                         fleet-level burn rates only
      GET /fleet/incidents                   every node's doctor verdicts
                                             with node attribution
      GET /alerts, /incidents                this router's own doctor
      GET /traces?id=G                       the STITCHED cross-process
                                             tree for global trace id G
                                             (+ the collected halves)
      GET /router                            router stats (states, probes)
      GET /shards                            per-shard cell health (key
                                             ranges, member states) when
                                             a shard topology is set
      GET /metrics[?format=prometheus]       this router process's own
                                             registry
      GET /healthz                           router liveness + node id
      POST /promote?port=[&shard=]           router-orchestrated failover
                                             (scoped to one cell when a
                                             ?shard= is named)
      POST /handoff?shard=                   graceful ownership handoff:
                                             drain + fence the old cell
                                             owner before the successor
                                             accepts writes
      POST /types/{t}/features               shard-routed ingest: the
                                             batch splits by Morton key
                                             ownership and each sub-batch
                                             lands on its owning cell

    With a shard topology, GET count scatter-gathers across cells with
    per-shard deadline budgets and answers with the partial-result
    envelope (``partial: true`` + ``missing_shards``) when a cell is
    dark, instead of a silent undercount.
    """

    def __init__(self, router: ReplicaRouter, federator=None):
        from geomesa_tpu import obs as _obs
        from geomesa_tpu.obs import federation as _fed
        _obs.install()
        _trace_mod().set_node_role("router")
        self.router = router
        if federator is None:
            nodes = dict(router.node_targets())
            nodes.setdefault(_trace_mod().node_id(), None)  # self
            federator = _fed.Federator(nodes)
        self.federator = federator
        if router.topology is not None:
            # the router's own doctor watches the shard map it routes
            # by: a cell with zero live endpoints opens one shard_dark
            # incident naming the key range + last-known members
            from geomesa_tpu.obs.doctor import DOCTOR
            DOCTOR.attach_router(router)

    # returns (status, payload, headers) — payload bytes are replayed
    # verbatim (the error-envelope contract), dicts serialize as JSON
    def handle(self, method: str, path: str, query: dict,
               headers=None, body: Optional[bytes] = None):
        try:
            return self._route(method, path, query, headers, body)
        except NoEndpointAvailable as e:
            return 503, {"error": str(e), "kind": "no_endpoint"}, {}
        except EndpointOverloaded as e:
            # the terminal candidate's envelope, replayed verbatim:
            # body bytes when the hop captured them (HttpEndpoint),
            # the structured envelope otherwise (LocalEndpoint)
            hdrs = {}
            if e.retry_after is not None:
                hdrs["Retry-After"] = str(e.retry_after)
            elif e.envelope.get("retry_after_s") is not None:
                hdrs["Retry-After"] = str(max(
                    1, int(-(-float(e.envelope["retry_after_s"]) // 1))))
            payload = e.body if e.body is not None else (
                e.envelope or {"error": str(e), "kind": "overloaded"})
            return e.status, payload, hdrs
        except EndpointDown as e:
            return 502, {"error": str(e), "kind": "endpoint_down"}, {}
        except (KeyError, ValueError, TypeError) as e:
            return 400, {"error": str(e), "kind": "bad_request"}, {}
        except Exception as e:
            return 500, {"error": str(e), "kind": "internal",
                         "type": type(e).__name__}, {}

    def _route(self, method, path, query, headers, body=None):
        from geomesa_tpu import trace as _t
        from geomesa_tpu.metrics import REGISTRY as _reg
        from geomesa_tpu.obs import federation as _fed
        parts = [p for p in path.split("/") if p]
        if parts == ["healthz"]:
            return 200, {"status": "ok",
                         "node": {"id": _t.node_id(), "role": "router"},
                         "router": self.router.stats()}, {}
        if parts == ["router"]:
            return 200, self.router.stats(), {}
        if parts == ["metrics"]:
            if query.get("format", [None])[0] == "prometheus":
                return 200, _reg.to_prometheus(), {}
            if query.get("format", [None])[0] == "state":
                return 200, {"node": {"id": _t.node_id(),
                                      "role": "router"},
                             "state": _reg.export_state()}, {}
            return 200, _reg.snapshot(), {}
        if parts == ["fleet"]:
            return 200, self.federator.fleet(), {}
        if parts == ["fleet", "metrics"]:
            return 200, self.federator.to_prometheus(), {}
        if parts == ["fleet", "slo"]:
            return 200, {"slo": self.federator.slo()}, {}
        if parts == ["fleet", "incidents"]:
            return 200, self.federator.fleet_incidents(), {}
        if parts == ["fleet", "soak"]:
            # last fleet-soak scoreboard (this process's run, or the
            # scoreboard file a previous run left behind)
            from geomesa_tpu.obs import soakfleet as _soak
            board = _soak.last_run()
            if board is None:
                return 404, {"error": "no soak run recorded "
                                      "(geomesa-tpu soak)"}, {}
            return 200, board, {}
        if parts == ["incidents"]:
            # the router process's OWN doctor (it has breakers/demotions
            # worth diagnosing too)
            from geomesa_tpu.obs.doctor import DOCTOR
            active = query.get("active", [None])[0] \
                not in (None, "0", "false")
            return 200, DOCTOR.incidents(active_only=active), {}
        if parts == ["alerts"]:
            from geomesa_tpu.obs.doctor import DOCTOR
            return 200, DOCTOR.alerts(), {}
        if parts == ["traces"]:
            gid = query.get("id", [None])[0]
            if not gid:
                return 400, {"error": "the router trace surface needs "
                                      "?id=<global trace id>"}, {}
            nodes = dict(self.federator.nodes)
            halves = _fed.collect_trace(gid, nodes)
            return 200, {"id": gid,
                         "stitched": _fed.stitch(halves),
                         "traces": halves}, {}
        if parts == ["promote"] and method == "POST":
            port = int(query.get("port", [0])[0])
            shard = query.get("shard", [None])[0]
            return 200, self.router.promote(port=port, shard=shard), {}
        if parts == ["shards"]:
            if self.router.topology is None:
                return 404, {"error": "router has no shard topology "
                                      "(start with --shard)"}, {}
            return 200, {"shards": self.router.shard_health()}, {}
        if parts == ["handoff"] and method == "POST":
            shard = query.get("shard", [None])[0]
            if not shard:
                return 400, {"error": "handoff needs ?shard="}, {}
            wait = query.get("wait_s", [None])[0]
            return 200, self.router.handoff(
                shard, wait_s=float(wait) if wait else None), {}
        if len(parts) == 3 and parts[0] == "types" \
                and parts[2] == "features" and method == "POST":
            if self.router.topology is None:
                return 404, {"error": "shard-routed ingest needs a "
                                      "shard topology (--shard)"}, {}
            import json as _json
            fc = _json.loads(body or b"{}")
            raw_dl = query.get("deadline_ms", [None])[0]
            if raw_dl is None and headers is not None:
                raw_dl = headers.get("X-Deadline-Ms")
            with _t.trace("router.ingest", type=parts[1]) as tr:
                env = self.router.ingest_scatter(
                    parts[1], fc,
                    deadline_ms=float(raw_dl) if raw_dl else None)
                env["trace"] = tr.global_id if tr is not None else None
            return (202 if env.get("partial") else 200), env, {}
        if len(parts) == 3 and parts[0] == "types" \
                and parts[2] == "count":
            t = parts[1]
            cql = query.get("cql", ["INCLUDE"])[0]
            auths = query["auths"][0].split(",") \
                if "auths" in query else None
            freshness = query.get("freshness", ["bounded"])[0]
            raw_dl = query.get("deadline_ms", [None])[0]
            if raw_dl is None and headers is not None:
                raw_dl = headers.get("X-Deadline-Ms")
            deadline_ms = float(raw_dl) if raw_dl else None
            priority = query.get("priority", ["interactive"])[0]
            tenant = query.get("tenant", [None])[0]
            if tenant is None and headers is not None:
                tenant = headers.get("X-Tenant")
            # the routed query's ROOT trace: the proxy span inside it
            # (HttpEndpoint.count) parents the remote half
            with _t.trace("router.count", type=t, filter=cql,
                          freshness=freshness) as tr:
                if self.router.topology is not None:
                    env = self.router.count_scatter(
                        t, cql, auths=auths, deadline_ms=deadline_ms,
                        priority=priority, tenant=tenant)
                    env["trace"] = tr.global_id if tr is not None \
                        else None
                    return (202 if env.get("partial") else 200), env, {}
                n = self.router.count(t, cql, auths=auths,
                                      deadline_ms=deadline_ms,
                                      priority=priority,
                                      tenant=tenant,
                                      freshness=freshness)
                gid = tr.global_id if tr is not None else None
            return 200, {"count": int(n), "trace": gid}, {}
        return 404, {"error": f"no route {method} {path}"}, {}


def _trace_mod():
    from geomesa_tpu import trace as _t
    return _t


def serve_router(router: ReplicaRouter, host: str = "127.0.0.1",
                 port: int = 8760, federator=None,
                 background: bool = False):
    """Start the router's HTTP surface. ``background=True`` returns the
    server after starting a daemon thread (tests / embedded use)."""
    import json as _json
    from http.server import BaseHTTPRequestHandler

    from geomesa_tpu.web.server import BacklogHTTPServer

    api = RouterApi(router, federator=federator)

    class _RouterHandler(BaseHTTPRequestHandler):
        def _serve(self, method):
            try:
                u = urllib.parse.urlparse(self.path)
                blen = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(blen) if blen else None
                status, payload, extra = api.handle(
                    method, u.path, urllib.parse.parse_qs(u.query),
                    headers=self.headers, body=body)
            except Exception as e:
                status, payload, extra = 500, {"error": str(e),
                                               "kind": "internal"}, {}
            if isinstance(payload, bytes):
                data, ctype = payload, "application/json"
            elif isinstance(payload, str):
                data, ctype = payload.encode(), "text/plain; version=0.0.4"
            else:
                data = _json.dumps(payload, default=str).encode()
                ctype = "application/json"
            try:
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                for k, v in (extra or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(data)
            except (BrokenPipeError, ConnectionResetError):
                pass

        def do_GET(self):
            self._serve("GET")

        def do_POST(self):
            self._serve("POST")

        def log_message(self, *a):
            pass

    httpd = BacklogHTTPServer((host, port), _RouterHandler)
    httpd.router_api = api
    from geomesa_tpu.obs import hostcpu
    if background:
        hostcpu.thread("http.listener", target=httpd.serve_forever,
                       daemon=True).start()
        return httpd
    hostcpu.mark(threading.current_thread(), "http.listener")
    httpd.serve_forever()
