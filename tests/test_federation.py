"""Fleet-wide observability plane tests (ISSUE 8 acceptance suite).

Cross-process trace propagation (header inject/extract, child-of-remote
roots, propagated sampling), the stitcher (one tree, network hop made
explicit), metrics federation (bucket-exact lossless merge, node-labeled
Prometheus passing the exposition-conformance invariants, fleet SLO burn
rates over merged samples), replication-pipeline telemetry
(ship→apply/ship→ack timers, the exemplar-linked repl.e2e histogram),
router decision visibility, and the verbatim error-envelope hop. The
two-process propagation test spawns a real serving subprocess; the full
3-node demo (primary + 2 replicas + router) is marked slow and runs in
the CI ``fleet-obs`` job.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu import obs as _obs
from geomesa_tpu import trace as _trace

_obs.install()  # the close-hook wiring any store-bearing process gets
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.metrics import (BUCKET_BOUNDS, MetricsRegistry,
                                 REGISTRY)
from geomesa_tpu.obs import federation as fed
from geomesa_tpu.obs.federation import (Federator, NodeScrape,
                                        collect_trace, stitch,
                                        render_stitched)
from geomesa_tpu.obs.sampling import SAMPLER
from geomesa_tpu.replication.drills import SPEC, make_batch
from geomesa_tpu.serve.router import (EndpointOverloaded, HttpEndpoint,
                                      LocalEndpoint, ReplicaRouter,
                                      RouterApi)


class _Headers(dict):
    def get(self, k, d=None):
        return dict.get(self, k, d)


def _mk_store(tmp_path, name="s", rows=200):
    store = TpuDataStore.open(str(tmp_path / name),
                              params={"wal.fsync": "off"})
    store.create_schema("t", SPEC)
    store.load("t", make_batch(store.schemas["t"], 0, n=rows))
    return store


# -- trace propagation --------------------------------------------------------


def test_inject_extract_child_of_remote_parent():
    with _trace.trace("router.count", type="t") as parent:
        with _trace.span("proxy.r1", kind="remote_call"):
            hdrs = _trace.inject_headers()
    assert hdrs["X-Trace-Id"] == parent.global_id
    assert hdrs["X-Trace-Node"] == _trace.node_id()
    span_id = int(hdrs["X-Span-Id"])
    ctx = _trace.extract_headers(_Headers(hdrs))
    with _trace.remote_parent(ctx):
        with _trace.trace("query.count", type="t") as child:
            pass
    d = child.to_dict()
    # ONE cross-process trace: the child adopts the parent's global id
    # and records which span it hangs under
    assert d["global_id"] == parent.global_id
    assert d["parent"] == {"trace": parent.global_id, "span": span_id,
                           "node": _trace.node_id()}
    assert d["node"] == _trace.node_id()
    assert "role" in d


def test_propagation_disabled_and_no_context():
    assert _trace.extract_headers(None) is None
    assert _trace.extract_headers(_Headers()) is None
    assert _trace.inject_headers() == {}  # no active trace
    config.FED_PROPAGATE.set(False)
    try:
        with _trace.trace("router.count"):
            assert _trace.inject_headers() == {}
        assert _trace.extract_headers(
            _Headers({"X-Trace-Id": "x-1"})) is None
    finally:
        config.FED_PROPAGATE.unset()


def test_propagated_sampling_decision_retains_child():
    """An upstream keep-decision retains every downstream half — a
    stitched fleet trace is never partial."""
    ctx = _trace.RemoteParent("other-7", 3, "other", sampled=True)
    with _trace.remote_parent(ctx):
        with _trace.trace("query.count", type="t") as child:
            pass
    assert child.sampled_hint
    SAMPLER.drain()
    assert SAMPLER.is_retained(child.trace_id)
    retained = {t["id"]: t for t in SAMPLER.recent(None)}
    assert retained[child.trace_id]["global_id"] == "other-7"


def test_stitch_assembles_one_tree_with_network_hop():
    with _trace.trace("router.count", type="t") as parent:
        with _trace.span("proxy.r1", kind="remote_call"):
            hdrs = _trace.inject_headers()
            time.sleep(0.002)  # the "wire": parent span outlives child
            ctx = _trace.extract_headers(_Headers(hdrs))
    with _trace.remote_parent(ctx):
        with _trace.trace("query.count", type="t") as child:
            with _trace.span("plan"):
                pass
    st = stitch([parent.to_dict(), child.to_dict()])
    assert st["global_id"] == parent.global_id
    assert len(st["hops"]) == 1
    hop = st["hops"][0]
    assert hop["network_ms"] is not None and hop["network_ms"] > 0
    # the remote half hangs under the proxy span, wrapped in a `remote`
    # span that makes the hop explicit
    proxy = st["spans"]["children"][0]
    assert proxy["name"] == "proxy.r1"
    remote = proxy["children"][-1]
    assert remote["kind"] == "remote"
    assert remote["children"][0]["name"] == "query.count"
    text = render_stitched(st)
    assert "query.count" in text and "network=" in text


def test_local_traces_by_id_searches_both_rings():
    with _trace.trace("query.count", type="t") as t:
        pass
    halves = fed.local_traces_by_id(t.global_id)
    assert len(halves) == 1 and halves[0]["id"] == t.trace_id
    assert fed.local_traces_by_id(str(t.trace_id))  # local-id lookup too


# -- metrics federation: lossless merge + conformance -------------------------


def _scrape(name, role, counters=None, timers=(), gauges=None,
            exemplars=None, values=()):
    """A synthetic node scrape from a REAL per-node registry — the merge
    tests exercise exactly the bytes a remote /metrics?format=state
    returns."""
    reg = MetricsRegistry()
    for k, v in (counters or {}).items():
        reg.inc(k, v)
    for k, secs in timers:
        for s in secs:
            reg.observe(k, s)
    for k, vals in values:
        for v in vals:
            reg.observe_value(k, v)
    for k, (sec, ref) in (exemplars or {}).items():
        reg.observe_exemplar(k, sec, ref)
    for k, v in (gauges or {}).items():
        reg.set_gauge(k, v)
    s = NodeScrape(name)
    s.ok = True
    s.healthz = {"status": "ok", "node": {"id": name, "role": role},
                 "replication": {"role": role, "lag_ms": 1.5,
                                 "applied_seq": 42},
                 "durability": {"wal_seq": 50, "synced_seq": 48},
                 "overload": {"scheduler": "ok", "queue_depth": 0,
                              "admission": {"draining": False},
                              "breaker": {"state": "closed"}},
                 "slo": {"status": "ok"}}
    s.state = reg.export_state()
    return s


def _pinned_federator(scrapes, clock=time.monotonic):
    f = Federator({s.name: f"http://unused-{s.name}" for s in scrapes},
                  ttl_ms=1e12, clock=clock)
    f._scrapes = {s.name: s for s in scrapes}
    f._last_refresh = clock()
    return f


def test_histogram_merge_is_lossless():
    """Merged fleet percentiles == what ONE process observing every
    sample would report (same fixed bucket geometry on every node)."""
    rng = np.random.default_rng(0)
    a = rng.lognormal(-4, 1, 400).tolist()
    b = rng.lognormal(-2, 0.5, 300).tolist()
    f = _pinned_federator([
        _scrape("n1", "primary", timers=[("query.count", a)]),
        _scrape("n2", "replica", timers=[("query.count", b)])])
    merged, _ex = f._merged_hists("timers")["query.count"], None
    h, _ = f._merged_hists("timers")["query.count"]
    oracle = MetricsRegistry()
    for s in a + b:
        oracle.observe("query.count", s)
    want = oracle.export_state()["timers"]["query.count"]
    assert h.count == want["count"] == 700
    assert h.total_s == pytest.approx(want["total"])
    got_buckets = {i: c for i, c in enumerate(h.buckets) if c}
    assert got_buckets == {int(i): c
                           for i, c in want["buckets"].items()}
    # identical percentiles, not approximately — the merge is exact
    for q in (0.5, 0.9, 0.99):
        assert h.percentile(q) == \
            oracle._timers["query.count"].percentile(q)


def test_timer_good_total_merged_matches_per_node_sum():
    fast, slow = [0.010] * 90, [2.0] * 10
    f = _pinned_federator([
        _scrape("n1", "primary", timers=[("query.count", fast)]),
        _scrape("n2", "replica", timers=[("query.count", slow)])])
    good, total = f.timer_good_total("query.count", 0.250)
    assert total == 100
    assert good == 90  # the slow node's tail counts against the fleet


def _parse_exposition(text):
    """Single-pass conformance parser (the test_obs invariants, extended
    to labeled federated samples)."""
    import re
    types, samples = {}, {}
    line_re = re.compile(
        r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
        r"(\{(?P<labels>[^}]*)\})?"
        r" (?P<value>-?[0-9.eE+-]+|[+-]Inf)"
        r"(?P<exemplar> # \{[^}]*\} -?[0-9.eE+-]+)?$")
    for line in text.strip().split("\n"):
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ")
            assert name not in types, f"duplicate # TYPE for {name}"
            types[name] = typ
            continue
        if line.startswith("#"):
            continue
        m = line_re.match(line)
        assert m, f"malformed exposition line: {line!r}"
        labels = {}
        if m.group("labels"):
            for kv in m.group("labels").split(","):
                k, v = kv.split("=", 1)
                assert v.startswith('"') and v.endswith('"'), \
                    f"malformed label value in {line!r}"
                labels[k] = v.strip('"')
        samples.setdefault(m.group("name"), []).append(
            (labels, m.group("value")))
    return types, samples


def test_federated_exposition_conformance():
    """ISSUE 8 satellite: the federated output passes the conformance
    invariants — no duplicate # TYPE across nodes, well-formed `node`
    labels, merged _bucket cumulativity, +Inf == _count."""
    rng = np.random.default_rng(1)
    f = _pinned_federator([
        _scrape("n1", "primary",
                counters={"scheduler.queries": 100, "admission.shed": 3},
                timers=[("query.count",
                         rng.lognormal(-4, 1, 200).tolist())],
                gauges={"process.rss_bytes": 1e6,
                        "process.cpu_seconds_total": 12.5}),
        _scrape("n2", "replica",
                counters={"scheduler.queries": 40},
                timers=[("query.count",
                         rng.lognormal(-3, 1, 100).tolist())],
                gauges={"process.rss_bytes": 2e6,
                        "process.cpu_seconds_total": 3.5})])
    text = f.to_prometheus()
    types, samples = _parse_exposition(text)  # asserts single # TYPE

    # counters: one family, one well-formed node-labeled sample per node
    qs = samples["geomesa_tpu_scheduler_queries_total"]
    assert types["geomesa_tpu_scheduler_queries_total"] == "counter"
    assert {lab["node"]: int(v) for lab, v in qs} == {"n1": 100, "n2": 40}
    # a counter present on ONE node emits one labeled sample
    shed = samples["geomesa_tpu_admission_shed_total"]
    assert [lab["node"] for lab, _v in shed] == ["n1"]
    # monotone *_total gauges keep the counter-type contract
    assert types["geomesa_tpu_process_cpu_seconds_total"] == "counter"
    assert types["geomesa_tpu_process_rss_bytes"] == "gauge"

    # merged histogram family: le increasing, cumulative, +Inf == _count
    fam = "geomesa_tpu_query_count_seconds_hist"
    assert types[fam] == "histogram"
    les, counts = [], []
    for lab, v in samples[fam + "_bucket"]:
        les.append(float("inf") if lab["le"] == "+Inf"
                   else float(lab["le"]))
        counts.append(int(v))
    assert les == sorted(les) and les[-1] == float("inf")
    assert all(a <= b for a, b in zip(counts, counts[1:]))
    assert counts[-1] == int(samples[fam + "_count"][0][1]) == 300
    # summary family count matches too
    assert int(samples["geomesa_tpu_query_count_seconds_count"][0][1]) \
        == 300


def test_federated_value_histograms_merge_and_conform():
    """ISSUE 10 satellite: raw-unit value histograms (observe_value
    families — batch sizes, cover cardinalities) ride export_state() and
    federate exactly like timers: merged losslessly across nodes, emitted
    as conformant summary + _hist families (no _seconds suffix)."""
    a = [4.0] * 30 + [16.0] * 10
    b = [8.0] * 25 + [16.0] * 5
    s1 = _scrape("n1", "primary", values=[("scheduler.batch_size", a)])
    s2 = _scrape("n2", "replica", values=[("scheduler.batch_size", b)])
    # the state payload really carries the values section per node
    assert s1.state["values"]["scheduler.batch_size"]["count"] == 40
    f = _pinned_federator([s1, s2])
    h, _ex = f._merged_hists("values")["scheduler.batch_size"]
    oracle = MetricsRegistry()
    for v in a + b:
        oracle.observe_value("scheduler.batch_size", v)
    want = oracle.export_state()["values"]["scheduler.batch_size"]
    assert h.count == want["count"] == 70
    assert h.total_s == pytest.approx(want["total"])
    assert {i: c for i, c in enumerate(h.buckets) if c} \
        == {int(i): c for i, c in want["buckets"].items()}
    # exposition: raw-unit family (no _seconds), single # TYPE, merged
    # _bucket cumulativity, +Inf == _count == 70
    text = f.to_prometheus()
    types, samples = _parse_exposition(text)
    assert types["geomesa_tpu_scheduler_batch_size"] == "summary"
    assert "geomesa_tpu_scheduler_batch_size_seconds" not in types
    fam = "geomesa_tpu_scheduler_batch_size_hist"
    assert types[fam] == "histogram"
    counts = [int(v) for _lab, v in samples[fam + "_bucket"]]
    assert all(x <= y for x, y in zip(counts, counts[1:]))
    assert counts[-1] == 70
    assert int(samples["geomesa_tpu_scheduler_batch_size_count"][0][1]) \
        == 70


def test_federated_exemplar_refs_rewritten_to_global_ids():
    """An integer exemplar ref from node N federates as N's fetchable
    global trace id; pinned string refs pass through unchanged."""
    s1 = _scrape("n1", "primary",
                 exemplars={"repl.e2e": (0.004, "n2-77")})
    reg = MetricsRegistry()
    reg.observe("query.count", 0.5)
    from geomesa_tpu.metrics import bucket_index
    with reg._lock:
        reg._exemplars["query.count"] = {bucket_index(0.5): (123, 0.5)}
    s2 = NodeScrape("n2")
    s2.ok = True
    s2.healthz = {"node": {"id": "n2", "role": "replica"}}
    s2.state = reg.export_state()
    f = _pinned_federator([s1, s2])
    merged = f._merged_hists("timers")
    _h, ex = merged["query.count"]
    assert list(ex.values())[0][0] == "n2-123"
    _h2, ex2 = merged["repl.e2e"]
    assert list(ex2.values())[0][0] == "n2-77"
    text = f.to_prometheus()
    assert 'trace_id="n2-123"' in text


def test_fleet_slo_burn_rates_over_merged_samples():
    """'count latency' is judged across the fleet: burn rates computed
    from MERGED good/total, on a fake clock."""
    t = [0.0]
    s1 = _scrape("n1", "primary",
                 counters={"scheduler.queries": 100},
                 timers=[("query.count", [0.010] * 100)])
    s2 = _scrape("n2", "replica",
                 counters={"scheduler.queries": 100,
                           "admission.shed": 0},
                 timers=[("query.count", [0.010] * 100)])
    f = _pinned_federator([s1, s2], clock=lambda: t[0])
    first = f.slo()
    assert first["count_latency"]["total"] == 200  # merged
    # advance: node 2 goes bad — its CUMULATIVE state now holds 200 more
    # queries of which 100 were slow and 50 shed
    reg = MetricsRegistry()
    reg.inc("scheduler.queries", 300)
    reg.inc("admission.shed", 50)
    for _ in range(200):
        reg.observe("query.count", 0.010)
    for _ in range(100):
        reg.observe("query.count", 2.0)
    s2.state = reg.export_state()
    t[0] = 400.0  # inside 30m/1h/6h, past the 5m window
    out = f.slo()
    lat = out["count_latency"]
    assert lat["total"] == 100 + 300
    burn_5m = lat["burn_rates"]["5m"]
    assert burn_5m is not None and burn_5m > 100  # 100/200 bad vs 0.1%
    avail = out["count_availability"]
    assert avail["burn_rates"]["5m"] > 100  # 50/200 shed
    assert lat["status"] in ("ok", "ticket", "page")


def test_fleet_surface_reports_per_node_health():
    f = _pinned_federator([
        _scrape("n1", "primary", counters={"x": 1}),
        _scrape("n2", "replica", counters={"x": 1})])
    down = NodeScrape("n3")
    down.error = "connection refused"
    f._scrapes["n3"] = down
    fl = f.fleet()
    assert fl["nodes"]["n1"]["role"] == "primary"
    assert fl["nodes"]["n2"]["lag_ms"] == 1.5
    assert fl["nodes"]["n2"]["wal_seq"] == 50
    assert fl["nodes"]["n2"]["applied_seq"] == 42
    assert fl["nodes"]["n2"]["breaker"] == "closed"
    assert fl["nodes"]["n3"] == {"ok": False,
                                 "error": "connection refused"}
    assert "slo" in fl


# -- router decision visibility (satellite) -----------------------------------


def test_router_probe_timer_and_demotion_counters(tmp_path):
    store = _mk_store(tmp_path, "rtr")
    try:
        ep = LocalEndpoint("n1", store)
        router = ReplicaRouter([ep], staleness_ms=1000.0)
        before = REGISTRY.snapshot()["counters"]
        assert ep.classify() == "healthy"
        # drain -> demoted, counted ONCE per transition (not per probe)
        store.scheduler().admission.drain(True)
        ep.last_probe_ts = 0.0
        assert ep.classify() == "demoted"
        ep.last_probe_ts = 0.0
        assert ep.classify() == "demoted"
        snap = REGISTRY.snapshot()
        c = snap["counters"]
        assert c.get("router.demotions.draining", 0) \
            == before.get("router.demotions.draining", 0) + 1
        assert c.get("router.probes", 0) > before.get("router.probes", 0)
        assert snap["timers"]["router.probe.n1"]["count"] >= 2
        # strong reads pin to the primary and are counted
        store.scheduler().admission.drain(False)
        ep.last_probe_ts = 0.0
        try:
            router.count("t", freshness="strong")
        except Exception:
            pass  # standalone store has no 'primary' role: the pin
            # counter is what this asserts
        assert REGISTRY.snapshot()["counters"].get(
            "router.strong_pins", 0) >= 1
    finally:
        store.close()


# -- verbatim error envelope through the router hop (satellite) ---------------


@pytest.fixture
def web_node(tmp_path):
    from geomesa_tpu.web import serve
    store = _mk_store(tmp_path, "web")
    httpd = serve(store, port=0, background=True)
    port = httpd.server_address[1]
    yield store, f"http://127.0.0.1:{port}", port
    httpd.shutdown()
    store.close()


def test_error_envelope_survives_router_hop_verbatim(web_node):
    store, base, port = web_node
    store.scheduler()  # spin it up
    store.scheduler().admission.drain(True)
    try:
        # the replica's own 429 body, fetched directly
        direct = urllib.request.Request(
            f"{base}/types/t/count?cql=INCLUDE")
        try:
            urllib.request.urlopen(direct, timeout=5)
            pytest.fail("expected 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            want_body = e.read()
            want_retry = e.headers["Retry-After"]
        want = json.loads(want_body.decode())
        assert want["kind"] == "shed" and "error" in want

        # the same request through the router hop: status, body bytes and
        # Retry-After all replay verbatim
        api = RouterApi(ReplicaRouter(
            [HttpEndpoint("r1", base)], staleness_ms=1e9))
        status, payload, hdrs = api.handle(
            "GET", "/types/t/count", {"cql": ["INCLUDE"]})
        assert status == 429
        assert payload == want_body
        assert hdrs["Retry-After"] == want_retry
    finally:
        store.scheduler().admission.drain(False)


def test_deadline_504_passes_through_terminal(web_node):
    store, base, port = web_node
    api = RouterApi(ReplicaRouter(
        [HttpEndpoint("r1", base)], staleness_ms=1e9))
    status, payload, _h = api.handle(
        "GET", "/types/t/count",
        {"cql": ["INCLUDE"], "deadline_ms": ["0.001"]})
    assert status == 504
    assert json.loads(payload.decode())["kind"] == "deadline"


def test_local_endpoint_overload_carries_envelope(tmp_path):
    store = _mk_store(tmp_path, "localenv")
    try:
        store.scheduler().admission.drain(True)
        ep = LocalEndpoint("n1", store)
        with pytest.raises(EndpointOverloaded) as ei:
            ep.count("t")
        assert ei.value.status == 429
        assert ei.value.envelope["kind"] == "shed"
        assert ei.value.envelope["retry_after_s"] > 0
    finally:
        store.scheduler().admission.drain(False)
        store.close()


# -- web surfaces: node meta, state export, /fleet, /traces?id= ---------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, json.loads(r.read())


def test_healthz_node_meta_and_state_route(web_node):
    store, base, port = web_node
    status, hz = _get(f"{base}/healthz")
    assert status == 200
    assert hz["node"]["id"] == _trace.node_id()
    assert hz["node"]["role"] in ("standalone", "primary", "replica",
                                  "router")
    status, st = _get(f"{base}/metrics?format=state")
    assert st["node"]["id"] == _trace.node_id()
    assert "counters" in st["state"] and "timers" in st["state"]
    # bucket-exact: a timer state carries sparse buckets
    some = next(iter(st["state"]["timers"].values()))
    assert set(some) == {"count", "total", "max", "buckets"}


def test_traces_by_id_route_and_fleet_routes(web_node):
    store, base, port = web_node
    q = urllib.parse.quote("BBOX(geom, -5, -5, 5, 5)")
    status, out = _get(f"{base}/types/t/count?cql={q}")
    assert status == 200
    # find the trace the count produced, by global id, over HTTP
    recent = _trace.RING.recent(5)
    gid = next(t["global_id"] for t in recent
               if t["name"] == "query.count")
    status, body = _get(f"{base}/traces?id={urllib.parse.quote(gid)}")
    assert status == 200 and body["traces"]
    assert body["traces"][0]["global_id"] == gid

    # /fleet 404s until a federator is configured, then federates self
    status, _ = _get_status(f"{base}/fleet")
    assert status == 404
    fed.configure({"self": None})
    try:
        status, fl = _get(f"{base}/fleet")
        assert status == 200 and "self" in fl["nodes"]
        with urllib.request.urlopen(f"{base}/fleet/metrics",
                                    timeout=10) as r:
            text = r.read().decode()
        types, samples = _parse_exposition(text)
        assert any(t == "counter" for t in types.values())
        status, slo_body = _get(f"{base}/fleet/slo")
        assert "count_latency" in slo_body["slo"]
    finally:
        fed.FEDERATOR = None


def _get_status(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# -- replication-pipeline telemetry -------------------------------------------


def test_repl_pipeline_telemetry_and_exemplar(tmp_path):
    """ship→apply and ship→ack timers populate; repl.e2e carries an
    exemplar naming the follower's RETAINED apply trace (fetchable by
    global id — the fleet-p99 → exemplar → remote-span walkthrough)."""
    from geomesa_tpu.replication import Follower, LogShipper
    config.REPL_TRACE_EVERY.set(1)
    config.REPL_ACK_EVERY.set(1)
    store = _mk_store(tmp_path, "prim", rows=40)
    shipper = LogShipper(store)
    flw = None
    try:
        flw = Follower(str(tmp_path / "repl"), shipper.address,
                       follower_id="r1")
        store.load("t", make_batch(store.schemas["t"], 1, n=40))
        want_seq = store.durability.wal.last_seq
        assert flw.wait_for_seq(want_seq, timeout=20.0)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            snap = REGISTRY.snapshot()
            if snap["timers"].get("repl.e2e", {}).get("count"):
                break
            time.sleep(0.05)
        snap = REGISTRY.snapshot()
        assert snap["timers"]["repl.ship_to_apply"]["count"] >= 1
        assert snap["timers"]["repl.ship_to_ack"]["count"] >= 1
        assert snap["timers"]["repl.e2e"]["count"] >= 1
        ex = REGISTRY.export_state()["exemplars"].get("repl.e2e")
        assert ex, "repl.e2e must carry an apply-trace exemplar"
        ref = next(iter(ex.values()))[0]
        assert isinstance(ref, str) and "-" in ref
        # the exemplar names a real, retained, fetchable apply trace
        halves = fed.local_traces_by_id(ref)
        assert halves and halves[0]["name"] == "repl.apply"
        # and the pinned exemplar survives into the text exposition
        assert f'trace_id="{ref}"' in REGISTRY.to_prometheus()
    finally:
        if flw is not None:
            flw.close()
        shipper.close()
        store.close()
        config.REPL_TRACE_EVERY.unset()
        config.REPL_ACK_EVERY.unset()


# -- flight-event fleet dimensions --------------------------------------------


def test_flight_events_carry_node_role_parent(tmp_path):
    from geomesa_tpu.obs.flight import RECORDER
    store = _mk_store(tmp_path, "fl")
    try:
        ctx = _trace.RemoteParent("routerX-9", 5, "routerX", sampled=False)
        with _trace.remote_parent(ctx):
            store.count_coalesced("t", "BBOX(geom, -5, -5, 5, 5)")
        evs = [e for e in RECORDER.recent(20)
               if e.get("kind") == "count.scheduled"
               and e.get("trace_gid") == "routerX-9"]
        assert evs, "the scheduled count's wide event must carry the gid"
        e = evs[0]
        assert e["node_id"] == _trace.node_id()
        assert e["role"] in ("standalone", "primary", "replica", "router")
        assert e["parent_span"] == 5
    finally:
        store.close()


# -- two-process propagation (the acceptance test) ----------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_http(port, path="/healthz", timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=2) as r:
                return json.loads(r.read().decode())
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(f"port {port} never became healthy")


def _spawn_cli(*args, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(env_extra or {})
    return subprocess.Popen(
        [sys.executable, "-m", "geomesa_tpu.tools.cli", *args],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)


def _write_artifact(stitched):
    path = os.environ.get("GEOMESA_TPU_STITCH_ARTIFACT")
    if path:
        with open(path, "w") as fh:
            json.dump(stitched, fh, indent=2, default=str)


def test_two_process_propagation_one_stitched_trace(tmp_path):
    """A routed query against a REAL serving subprocess yields ONE
    stitched trace: the remote process's root (the REST span, with its
    query.count inside) is a child of this process's proxy span, with the
    network hop explicit."""
    pdir = str(tmp_path / "node")
    store = TpuDataStore.open(pdir, params={"wal.fsync": "off"})
    store.create_schema("t", SPEC)
    store.load("t", make_batch(store.schemas["t"], 0, n=500))
    want = store.count("t", "BBOX(geom, -5, -5, 5, 5)")
    store.close()

    web_port = _free_port()
    proc = _spawn_cli("serve", "-s", pdir, "--durable",
                      "--port", str(web_port),
                      env_extra={"GEOMESA_TPU_NODE_ID": "srv1"})
    try:
        _wait_http(web_port)
        base = f"http://127.0.0.1:{web_port}"
        api = RouterApi(ReplicaRouter([HttpEndpoint("srv1", base)],
                                      staleness_ms=1e9))
        q = urllib.parse.quote("BBOX(geom, -5, -5, 5, 5)")
        status, payload, _h = api.handle(
            "GET", "/types/t/count", {"cql": ["BBOX(geom, -5, -5, 5, 5)"]})
        assert status == 200
        assert payload["count"] == want
        gid = payload["trace"]
        assert gid and gid.startswith(_trace.node_id())

        # collect both halves: this process's router trace + the remote
        # serving process's child, over its /traces?id= surface
        halves = collect_trace(gid, {"local": None, "srv1": base})
        nodes = {t["node"] for t in halves}
        assert _trace.node_id() in nodes and "srv1" in nodes, halves
        st = stitch(halves)
        assert st is not None and len(st["hops"]) >= 1
        hop = next(h for h in st["hops"] if h["to"] == "srv1")
        assert hop["network_ms"] is not None and hop["network_ms"] >= 0
        remote_roots = [t for t in halves if t["node"] == "srv1"]
        assert remote_roots[0]["parent"]["trace"] == gid
        # the REST span is the remote root; the scheduler's count under it
        assert remote_roots[0]["name"] == "http.request.count"
        assert "query.count" in [
            c["name"] for c in remote_roots[0]["root"]["children"]]
        # the remote half contains real serving spans (scan/plan/etc.)
        assert remote_roots[0]["stages_ms"], remote_roots[0]
        _write_artifact({"stitched": st, "halves": halves})

        # the router's own /traces?id= surface stitches it server-side
        status, body, _h = api.handle("GET", "/traces",
                                      {"id": [gid]})
        assert status == 200 and body["stitched"] is not None
        assert body["stitched"]["global_id"] == gid
    finally:
        proc.terminate()
        proc.wait(timeout=10)


@pytest.mark.slow
def test_three_node_fleet_demo_stitched_federated(tmp_path):
    """The ISSUE 8 acceptance demo: primary + 2 replicas + router. One
    routed query -> ONE stitched trace across processes; GET
    /fleet/metrics passes the conformance parse with per-node labels;
    fleet SLO evaluates over merged samples; repl.e2e populates with
    exemplars."""
    pdir = str(tmp_path / "primary")
    store = TpuDataStore.open(pdir, params={"wal.fsync": "off"})
    store.create_schema("t", SPEC)
    for i in range(3):
        store.load("t", make_batch(store.schemas["t"], i, n=5_000))
    want = store.count("t", "BBOX(geom, -5, -5, 5, 5)")
    store.close()

    ship_port, web_p = _free_port(), _free_port()
    web_r1, web_r2 = _free_port(), _free_port()
    procs = [_spawn_cli("serve", "-s", pdir, "--durable",
                        "--ship-port", str(ship_port),
                        "--port", str(web_p),
                        env_extra={"GEOMESA_TPU_NODE_ID": "p0",
                                   "GEOMESA_TPU_REPL_TRACE_EVERY": "1",
                                   "GEOMESA_TPU_REPL_ACK_EVERY": "1"})]
    try:
        _wait_http(web_p)
        for rdir, port, rid in ((str(tmp_path / "r1"), web_r1, "r1"),
                                (str(tmp_path / "r2"), web_r2, "r2")):
            procs.append(_spawn_cli(
                "replica", "--dir", rdir,
                "--follow", f"127.0.0.1:{ship_port}",
                "--port", str(port), "--id", rid,
                env_extra={"GEOMESA_TPU_NODE_ID": rid}))
        for port in (web_r1, web_r2):
            _wait_http(port)
        # wait for catch-up
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            hz = _wait_http(web_r1)
            if (hz.get("replication") or {}).get("lag_seqs") == 0:
                break
            time.sleep(0.3)

        nodes = {"p0": f"http://127.0.0.1:{web_p}",
                 "r1": f"http://127.0.0.1:{web_r1}",
                 "r2": f"http://127.0.0.1:{web_r2}"}
        eps = [HttpEndpoint(n, u) for n, u in nodes.items()]
        router = ReplicaRouter(eps)
        fedr = Federator({**nodes, _trace.node_id(): None})
        api = RouterApi(router, federator=fedr)

        # one routed query -> one stitched cross-process trace
        status, payload, _h = api.handle(
            "GET", "/types/t/count",
            {"cql": ["BBOX(geom, -5, -5, 5, 5)"]})
        assert status == 200 and payload["count"] == want
        gid = payload["trace"]
        status, body, _h = api.handle("GET", "/traces", {"id": [gid]})
        st = body["stitched"]
        assert st is not None and len(st["hops"]) == 1
        assert st["hops"][0]["to"] in ("p0", "r1", "r2")
        assert st["hops"][0]["network_ms"] is not None
        _write_artifact({"stitched": st, "halves": body["traces"]})

        # a write lands on the primary and ships: repl.e2e populates
        fc = {"type": "FeatureCollection", "features": [
            {"type": "Feature", "id": f"w{i}",
             "geometry": {"type": "Point", "coordinates": [1.0, 2.0]},
             "properties": {"name": "w", "v": 1,
                            "dtg": "2024-01-01T06:00:00"}}
            for i in range(8)]}
        req = urllib.request.Request(
            f"http://127.0.0.1:{web_p}/types/t/features",
            data=json.dumps(fc).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert json.loads(r.read())["ingested"] == 8
        deadline = time.monotonic() + 60
        e2e = None
        while time.monotonic() < deadline:
            fedr.refresh(force=True)
            e2e = fedr._repl_e2e_summary()
            if e2e and e2e.get("count"):
                break
            time.sleep(0.5)
        assert e2e and e2e["count"] >= 1
        assert e2e.get("exemplars"), "repl.e2e must carry exemplars"

        # spread a few more routed reads so several nodes serve
        for _ in range(6):
            api.handle("GET", "/types/t/count",
                       {"cql": ["BBOX(geom, -5, -5, 5, 5)"]})
        fedr.refresh(force=True)  # step past the scrape TTL

        # federated prometheus over the REAL 4-node fleet conforms
        status, text, _h = api.handle("GET", "/fleet/metrics", {})
        types, samples = _parse_exposition(text)
        served = {lab["node"] for lab, _v in
                  samples["geomesa_tpu_scheduler_queries_total"]}
        assert len(served) >= 2, served  # round-robin spread, per node
        shipped = {lab["node"] for lab, _v in
                   samples["geomesa_tpu_replication_shipped_frames_total"]}
        assert "p0" in shipped
        applied = {lab["node"] for lab, _v in
                   samples["geomesa_tpu_replication_applied_records_total"]}
        assert {"r1", "r2"} <= applied
        # fleet SLO over merged samples
        status, fl, _h = api.handle("GET", "/fleet", {})
        roles = {n["role"] for n in fl["nodes"].values()
                 if n.get("ok")}
        assert "primary" in roles and "replica" in roles
        assert "count_latency" in fl["slo"]
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


# -- CLI ----------------------------------------------------------------------


def test_cli_debug_trace_renders_stitched(capsys):
    from geomesa_tpu.tools.cli import main
    with _trace.trace("router.count", type="t") as parent:
        with _trace.span("proxy.r1", kind="remote_call"):
            hdrs = _trace.inject_headers()
    ctx = _trace.extract_headers(_Headers(hdrs))
    with _trace.remote_parent(ctx):
        with _trace.trace("query.count", type="t"):
            pass
    main(["debug", "trace", "--id", parent.global_id])
    out = capsys.readouterr().out
    assert "router.count" in out and "query.count" in out
    assert "remote:" in out or "network=" in out


def test_cli_fleet_status(web_node, capsys):
    from geomesa_tpu.tools.cli import main
    store, base, port = web_node
    main(["fleet", "status", "--addr", f"127.0.0.1:{port}"])
    out = capsys.readouterr().out
    assert "NODE" in out and "slo count_latency" in out
    main(["fleet", "status", "--addr", f"127.0.0.1:{port}", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert f"127.0.0.1:{port}" in out["nodes"]
