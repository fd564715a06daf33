"""Request-centric observability (geomesa_tpu/obs/): flight-recorder wide
events, tail-based trace sampling + /metrics exemplars, per-kernel device
cost attribution, explain(analyze=True), and the SLO burn-rate engine.

Everything here is deterministic: the SLO engine runs on a fake clock,
sampling decisions use pinned rates (0/1) or directly-constructed traces
with hand-set durations, and nothing sleeps.
"""

import json
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu import obs
from geomesa_tpu import trace as trace_mod
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.metrics import REGISTRY, MetricsRegistry
from geomesa_tpu.obs import attrib
from geomesa_tpu.obs.flight import (RECORDER, FlightRecorder,
                                    event_from_trace, matches, plan_hash)
from geomesa_tpu.obs.sampling import SAMPLER, TailSampler
from geomesa_tpu.obs.slo import (PAGE_BURN, ENGINE, Objective, SloEngine)
from geomesa_tpu.trace import QueryTrace


@pytest.fixture(autouse=True)
def _obs_defaults():
    """Install the obs hooks and reset the per-test mutable surfaces."""
    obs.install()
    RECORDER.clear()
    SAMPLER.clear()
    yield
    for p in (config.OBS_SAMPLE, config.OBS_SLOW_MS, config.OBS_JSONL):
        p.unset()
    RECORDER.clear()
    SAMPLER.clear()


@pytest.fixture(scope="module")
def store():
    rng = np.random.default_rng(11)
    n = 5000
    ds = TpuDataStore()
    ds.create_schema("obs_t", "v:Int,*geom:Point")
    ds.load("obs_t", FeatureTable.build(ds.get_schema("obs_t"), {
        "v": rng.integers(0, 100, n).astype(np.int32),
        "geom": (rng.uniform(-20, 20, n), rng.uniform(-20, 20, n))}))
    yield ds
    ds.close()


def _mktrace(name="query.count", duration_ms=1.0, error=None, kinds=(),
             **attrs):
    """Hand-built closed root trace (duration under OUR control, no sleeps)."""
    t = QueryTrace(name, attrs or None)
    t.root.duration_ms = float(duration_ms)
    for k in kinds:
        t.root.add_child(trace_mod._leaf(k, k, 0.0))
    t.error = error
    return t


# -- flight recorder ----------------------------------------------------------


def test_wide_event_per_direct_count(store):
    config.OBS_SAMPLE.set(0.0)
    store.count("obs_t", "BBOX(geom, -5, -5, 5, 5)")
    evs = RECORDER.recent(kind="query.count", type_name="obs_t")
    assert evs, "a direct count must emit one wide event"
    ev = evs[0]
    assert ev["trace_id"] > 0 and ev["duration_ms"] > 0
    assert ev["device_ms"] >= 0 and ev["host_ms"] >= 0
    assert ev["error"] is None and not ev["cancelled"] and not ev["shed"]
    assert "plan" in ev["stages_ms"]
    # stable plan hash: derivable from (type, filter) alone
    assert ev["plan_hash"] == plan_hash("obs_t", "BBOX(geom, -5, -5, 5, 5)")


def test_wide_event_per_scheduled_count(store):
    q = "BBOX(geom, -6, -6, 6, 6)"
    # the repeat pass must REACH the dispatch boundary (its wide event pins
    # rows_scanned / batch_id), not resolve from the hot-result cache
    store.scheduler().results.clear()
    config.RESULT_CACHE_ENABLED.set(False)
    try:
        n1 = store.count_coalesced("obs_t", q)
        RECORDER.clear()
        n2 = store.count_coalesced("obs_t", q)  # second pass: plan cache hit
    finally:
        config.RESULT_CACHE_ENABLED.unset()
    assert n1 == n2
    evs = RECORDER.recent(kind="count.scheduled")
    assert evs, "a scheduled count must emit one wide event"
    ev = evs[0]
    assert ev["type"] == "obs_t"
    assert ev["plan_cache_hit"] is True          # repeat filter
    assert ev["priority"] == "interactive"
    assert ev["batch_id"] is not None and ev["batch_size"] >= 1
    assert ev["rows_scanned"] and ev["rows_matched"] == n2
    assert ev["retries"] == 0 and ev["error"] is None
    # the fused dispatch itself also logs one batch event
    assert RECORDER.recent(kind="batch")


def test_wide_event_deadline_cancelled(store):
    # a dead-on-arrival deadline is cancelled at submit — before admission,
    # queueing, or dispatch — and the wide event records it
    sched = store.scheduler()
    req = sched.submit("obs_t", "INCLUDE", deadline_ms=0.000001)
    with pytest.raises(Exception):
        req.result(timeout=5)
    evs = [e for e in RECORDER.recent(kind="count.scheduled")
           if e["cancelled"]]
    assert evs and evs[0]["error"] == "deadline"
    assert evs[0]["deadline_budget_ms"] is not None


def test_flight_filters_share_one_predicate():
    slow = {"kind": "query.count", "duration_ms": 900.0, "error": None}
    err = {"kind": "query.count", "duration_ms": 1.0, "error": "ValueError"}
    shed = {"kind": "count.scheduled", "duration_ms": 1.0, "shed": True}
    ok = {"kind": "query.count", "duration_ms": 1.0, "type": "a",
          "stages_ms": {"refine": 0.4}}
    assert matches(slow, slow_ms=500) and not matches(ok, slow_ms=500)
    assert matches(err, errors=True) and matches(shed, errors=True)
    assert not matches(ok, errors=True)
    assert matches(ok, kind="refine")            # span kind in stages
    assert matches(ok, kind="query.count")       # record kind
    assert not matches(ok, kind="batch")
    assert matches(ok, type_name="a") and not matches(ok, type_name="b")


def test_flight_jsonl_sink_rotates(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    rec = FlightRecorder(keep=64, jsonl_path=path, max_bytes=2000)
    for i in range(50):
        rec.record({"kind": "query.count", "i": i, "duration_ms": 1.0})
    rec.close()
    assert (tmp_path / "flight.jsonl.1").exists(), "sink must have rotated"
    # every line of the live file is intact JSON
    with open(path) as fh:
        rows = [json.loads(line) for line in fh]
    assert rows and all("kind" in r for r in rows)


# -- tail-based trace sampling ------------------------------------------------


def test_sampler_keeps_errors_and_outcomes_always():
    config.OBS_SAMPLE.set(0.0)
    s = TailSampler(keep=16)
    assert s.offer(_mktrace(error="ValueError"))
    assert s.offer(_mktrace(kinds=("cancel",)))
    assert s.offer(_mktrace(kinds=("shed",)))
    assert s.offer(_mktrace(kinds=("degrade",)))
    assert not s.offer(_mktrace())  # ordinary fast trace, rate 0
    assert s.stats()["kept"] == 4


def test_sampler_fixed_slow_threshold():
    config.OBS_SAMPLE.set(0.0)
    config.OBS_SLOW_MS.set(50.0)
    s = TailSampler(keep=16)
    assert s.offer(_mktrace(duration_ms=60.0))
    assert not s.offer(_mktrace(duration_ms=10.0))


def test_sampler_adaptive_p99_threshold():
    config.OBS_SAMPLE.set(0.0)
    config.OBS_SLOW_MS.set(0.0)  # adaptive
    s = TailSampler(keep=16)
    # below 100 observations nothing is "slow"
    assert not s.offer(_mktrace(duration_ms=500.0))
    for _ in range(200):
        s.offer(_mktrace(duration_ms=1.0))
    # the rolling p99 sits near 1ms now: a 100x outlier retains
    assert s.offer(_mktrace(duration_ms=100.0))
    assert not s.offer(_mktrace(duration_ms=1.0))
    assert s.stats()["slow_threshold_ms"] > 0


def test_sampler_probabilistic_rest():
    import random
    config.OBS_SAMPLE.set(1.0)
    s = TailSampler(keep=16, rng=random.Random(7))
    assert s.offer(_mktrace())      # rate 1.0: everything retains
    config.OBS_SAMPLE.set(0.0)
    assert not s.offer(_mktrace())  # rate 0: ordinary traces drop


def test_retained_ring_and_is_retained_eviction():
    config.OBS_SAMPLE.set(0.0)
    s = TailSampler(keep=4)
    ids = []
    for _ in range(8):
        t = _mktrace(error="X")
        s.offer(t)
        ids.append(t.trace_id)
    assert all(s.is_retained(i) for i in ids[-4:])
    assert not any(s.is_retained(i) for i in ids[:4])  # evicted
    assert len(s.recent()) == 4


def test_exemplars_link_metrics_buckets_to_retained_traces(store):
    config.OBS_SAMPLE.set(1.0)  # retain everything → exemplars exist
    store.count("obs_t", "BBOX(geom, -3, -3, 3, 3)")
    text = REGISTRY.to_prometheus()
    ex_lines = [l for l in text.splitlines() if "trace_id=" in l]
    assert ex_lines, "retained traces must surface as bucket exemplars"
    # every LOCAL exemplar names a trace the sampled ring actually
    # retains; cross-node refs (pinned by observe_exemplar, e.g. the
    # repl.e2e apply-trace link) are global `<node>-<id>` strings the
    # local ring cannot vouch for
    import re
    checked = 0
    for line in ex_lines:
        ref = re.search(r'trace_id="([^"]+)"', line).group(1)
        if ref.isdigit():
            assert SAMPLER.is_retained(int(ref))
            checked += 1
    assert checked, "the count trace must land a local exemplar"


# -- per-kernel device cost attribution ---------------------------------------


def test_attrib_series_land_in_registry():
    from geomesa_tpu import trace
    attrib.record_transfer("count_multi.point_boxes", 4, 1024)
    attrib.record_compile("count_multi.point_boxes", 4, 0.5)
    with attrib.kernel("count_multi.point_boxes", 4):
        trace.device_fetch(lambda out: out, lambda: 1)
    snap = attrib.snapshot()
    c = snap["counters"]
    assert c["kernel.count_multi.point_boxes.b4.transfer_bytes"] >= 1024
    assert c["kernel.count_multi.point_boxes.b4.compiles"] >= 1
    assert snap["timers"]["kernel.count_multi.point_boxes.b4.dispatch"][
        "count"] >= 1
    # time blocked on a read-back is host time: charged to no kernel
    assert not [k for k in (*c, *snap["timers"])
                if k.endswith((".device_wait", ".dispatches"))]


def test_attrib_compile_probe_counts_once():
    calls = []

    def fake_kernel(x):
        calls.append(x)
        return x

    before = REGISTRY.snapshot()["counters"].get(
        "kernel.test_mode.test.b1.compiles", 0)
    probed = attrib.compile_probe(fake_kernel, "test_mode.test", 1)
    assert probed(1) == 1 and probed(2) == 2 and probed(3) == 3
    after = REGISTRY.snapshot()["counters"].get(
        "kernel.test_mode.test.b1.compiles", 0)
    assert after == before + 1  # only the first call is a compile
    assert calls == [1, 2, 3]


def test_scheduled_count_attributes_device_cost(store):
    RECORDER.clear()
    store.count_coalesced("obs_t", "BBOX(geom, -7, -7, 7, 7)")
    snap = attrib.snapshot()
    dispatched = [k for k in snap["counters"]
                  if k.startswith("kernel.count_multi") and
                  k.endswith(".transfer_bytes")]
    assert dispatched, "a fused dispatch must charge its kernel series"
    waited = [k for k in snap["timers"]
              if k.startswith("kernel.count_multi") and
              k.endswith(".device_wait")]
    assert not waited, "a read-back's wait is host time, no kernel's"


# -- explain(analyze=True) ----------------------------------------------------


def test_explain_analyze_executes_and_annotates(store):
    q = "BBOX(geom, -5, -5, 5, 5)"
    ref = store.count("obs_t", q)
    out = store.explain("obs_t", q, analyze=True)
    a = out["analyze"]
    assert a["executed"] and a["rows_matched"] == ref
    assert a["rows_scanned"] >= a["rows_matched"]
    assert a["duration_ms"] > 0
    assert abs(a["device_ms"] + a["host_ms"] - a["duration_ms"]) < 0.01
    assert "plan" in a["stages_ms"]
    # the span tree carries per-node device attribution
    root = out["trace"]["root"]
    assert "device_ms" in root
    kinds = {}

    def walk(n):
        kinds[n["kind"]] = n
        for c in n.get("children", ()):
            walk(c)

    walk(root)
    assert "device_ms" in kinds.get("plan", {"device_ms": 0})
    assert kinds["plan"]["cached"] is False


def test_explain_analyze_cache_provenance(store):
    q = "BBOX(geom, -8.5, -8.5, 8.5, 8.5)"
    out = store.explain("obs_t", q, analyze=True)
    prov = out["analyze"]["provenance"]
    assert prov["plan"] == "fresh"
    if "plan_cache" in prov:           # live scheduler present
        assert prov["plan_cache"] == "miss"
    store.count_coalesced("obs_t", q)  # seed the serving plan cache
    out = store.explain("obs_t", q, analyze=True)
    assert out["analyze"]["provenance"].get("plan_cache") == "hit"


def test_explain_dry_run_unchanged_without_analyze(store):
    out = store.explain("obs_t", "BBOX(geom, -5, -5, 5, 5)")
    assert "analyze" not in out and "trace" in out


# -- SLO burn-rate engine -----------------------------------------------------


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def test_slo_latency_burn_rates_deterministic():
    reg = MetricsRegistry()
    clock = FakeClock()
    eng = SloEngine(registry=reg, clock=clock)
    eng.add(Objective(name="lat", kind="latency", target=0.999,
                      timer="q", threshold_ms=100.0))
    for _ in range(1000):
        reg.observe("q", 0.01)         # all good
    eng.tick()
    clock.advance(21601)               # age the baseline past every window
    for _ in range(900):
        reg.observe("q", 0.01)
    for _ in range(100):
        reg.observe("q", 1.0)          # 10% bad from here on
    out = eng.evaluate()
    lat = out["lat"]
    # windowed error rate 100/1000 = 10%; budget 0.1% → burn 100x
    for w in ("5m", "30m", "1h", "6h"):
        assert lat["burn_rates"][w] == pytest.approx(100.0, rel=0.01)
    assert lat["page"] and lat["ticket"] and lat["status"] == "page"
    assert lat["burn_rates"]["5m"] >= PAGE_BURN


def test_slo_multiwindow_suppresses_stale_burn():
    """A burst that stopped an hour ago pages NOTHING: the fast window is
    clean even though the slow window still remembers the burn."""
    reg = MetricsRegistry()
    clock = FakeClock()
    eng = SloEngine(registry=reg, clock=clock)
    eng.add(Objective(name="lat", kind="latency", target=0.999,
                      timer="q", threshold_ms=100.0))
    eng.tick()                          # t0 baseline (empty)
    clock.advance(60)
    for _ in range(500):
        reg.observe("q", 1.0)           # a terrible burst...
    eng.tick()
    clock.advance(3700)                 # ...that ended over an hour ago
    for _ in range(1000):
        reg.observe("q", 0.01)          # clean traffic since
    out = eng.evaluate()
    lat = out["lat"]
    assert lat["burn_rates"]["5m"] == 0.0
    assert lat["burn_rates"]["6h"] > PAGE_BURN  # slow window still hot
    assert lat["status"] == "ok", "multi-window gating must not page"


def test_slo_availability_objective():
    reg = MetricsRegistry()
    clock = FakeClock()
    eng = SloEngine(registry=reg, clock=clock)
    eng.add(Objective(name="avail", kind="availability", target=0.99,
                      total_counter="req.total",
                      bad_counters=("req.shed", "req.cancelled")))
    reg.inc("req.total", 1000)
    eng.tick()
    clock.advance(21601)
    reg.inc("req.total", 1000)
    reg.inc("req.shed", 30)
    reg.inc("req.cancelled", 20)
    out = eng.evaluate()
    av = out["avail"]
    # 50/1000 = 5% error rate over a 1% budget → burn 5x: ticket territory
    for w in ("5m", "30m", "1h", "6h"):
        assert av["burn_rates"][w] == pytest.approx(5.0, rel=0.01)
    assert not av["page"] and av["status"] == "ok"  # 5 < ticket bar 6


def test_slo_no_traffic_windows_are_null():
    reg = MetricsRegistry()
    eng = SloEngine(registry=reg, clock=FakeClock())
    eng.add(Objective(name="lat", kind="latency", target=0.999,
                      timer="q", threshold_ms=100.0))
    out = eng.evaluate()
    assert all(v is None for v in out["lat"]["burn_rates"].values())
    assert out["lat"]["status"] == "ok"


def test_default_objectives_installed():
    names = {o.name for o in ENGINE.objectives()}
    assert {"count_latency", "count_availability"} <= names


# -- gauges -------------------------------------------------------------------


def test_pressure_gauges_registered(tmp_path):
    g = REGISTRY.snapshot()["gauges"]
    assert g["process.rss_bytes"] > 1024 * 1024
    assert g["trace.ring_depth"] >= 0
    assert "wal.open_segments" in g
    # a live durable store surfaces its WAL segment files
    ds = TpuDataStore.open(str(tmp_path / "dur"))
    try:
        assert REGISTRY.snapshot()["gauges"]["wal.open_segments"] >= 1
    finally:
        ds.close()


# -- web surfaces -------------------------------------------------------------


@pytest.fixture(scope="module")
def server(store):
    from geomesa_tpu.web import serve
    httpd = serve(store, port=0, background=True)
    port = httpd.server_address[1]
    yield f"http://127.0.0.1:{port}", store
    httpd.shutdown()


def _get(url):
    with urllib.request.urlopen(url) as r:
        return r.status, json.loads(r.read())


def test_events_route_filters(server):
    base, ds = server
    q = urllib.parse.quote("BBOX(geom, -4, -4, 4, 4)")
    _get(f"{base}/types/obs_t/count?cql={q}")
    status, body = _get(f"{base}/events?limit=50")
    assert status == 200 and body["events"]
    assert body["recorder"]["depth"] >= 1
    status, body = _get(f"{base}/events?slow_ms=1e12")
    assert body["events"] == []        # nothing is that slow
    status, body = _get(f"{base}/events?type=obs_t&limit=5")
    assert all(e["type"] == "obs_t" for e in body["events"])


def test_traces_retained_route(server):
    base, ds = server
    config.OBS_SAMPLE.set(1.0)
    try:
        q = urllib.parse.quote("BBOX(geom, -2, -2, 2, 2)")
        _get(f"{base}/types/obs_t/count?cql={q}")
        status, body = _get(f"{base}/traces?retained=1&limit=10")
        assert status == 200 and body["traces"]
        assert body["sampler"]["kept"] >= 1
    finally:
        config.OBS_SAMPLE.unset()


def test_slo_route_and_healthz_section(server):
    base, ds = server
    status, body = _get(f"{base}/slo")
    assert status == 200
    assert "count_latency" in body["slo"]
    assert set(body["slo"]["count_latency"]["burn_rates"]) \
        == {"5m", "30m", "1h", "6h"}
    status, hz = _get(f"{base}/healthz")
    assert hz["slo"]["status"] in ("ok", "ticket", "page", "unknown")


def test_explain_analyze_route(server):
    base, ds = server
    q = urllib.parse.quote("BBOX(geom, -5, -5, 5, 5)")
    status, body = _get(f"{base}/types/obs_t/explain?cql={q}&analyze=1")
    assert status == 200 and body["analyze"]["executed"]
    status, body = _get(f"{base}/types/obs_t/explain?cql={q}")
    assert "analyze" not in body


# -- prometheus exposition conformance (satellite) ----------------------------


def _parse_exposition(text):
    """Single-pass parser: returns (types: name->type, samples:
    name->[(labels dict, value)]). Raises on malformed lines."""
    import re
    types = {}
    samples = {}
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
                labels[k] = v.strip('"')
        samples.setdefault(m.group("name"), []).append(
            (labels, m.group("value")))
    return types, samples


def test_prometheus_exposition_conformance(server):
    base, ds = server
    q = urllib.parse.quote("BBOX(geom, -5, -5, 5, 5)")

    def rest_counts():
        return REGISTRY.snapshot()["timers"].get(
            "http.request.count", {}).get("count", 0)

    n0 = rest_counts()
    for _ in range(3):
        _get(f"{base}/types/obs_t/count?cql={q}")
    # a count's REST root closes once its response is flushed, after the
    # client has it: let the last one land before the two reads
    deadline = time.time() + 5
    while rest_counts() < n0 + 3 and time.time() < deadline:
        time.sleep(0.005)
    with urllib.request.urlopen(f"{base}/metrics?format=prometheus") as r:
        text = r.read().decode()
    status, snap = _get(f"{base}/metrics")
    # a scrape is a timed request too: it moves its own three timers
    for own in ("http.request.metrics", "http.respond", "http.accept"):
        snap["timers"].pop(own, None)

    types, samples = _parse_exposition(text)  # asserts no duplicate TYPEs

    # histogram families: le strictly increasing, cumulative counts
    # non-decreasing, +Inf == _count, _sum consistent with the JSON snapshot
    hist_families = [n for n, t in types.items() if t == "histogram"]
    assert hist_families, "native histogram families must be emitted"
    for fam in hist_families:
        buckets = samples.get(fam + "_bucket", [])
        assert buckets, f"{fam} has no buckets"
        les, counts = [], []
        for labels, val in buckets:
            les.append(float("inf") if labels["le"] == "+Inf"
                       else float(labels["le"]))
            counts.append(int(val))
        assert les == sorted(les) and les[-1] == float("inf")
        assert all(a <= b for a, b in zip(counts, counts[1:])), \
            f"{fam} buckets not cumulative"
        total = int(samples[fam + "_count"][0][1])
        assert counts[-1] == total, f"{fam} +Inf bucket != _count"

    # _count/_sum of every timer family match the JSON snapshot
    def sane(name):
        return "geomesa_tpu_" + "".join(
            c if c.isalnum() or c == "_" else "_" for c in name)

    for name, h in snap["timers"].items():
        fam = sane(name) + "_seconds"
        assert int(samples[fam + "_count"][0][1]) == h["count"]
        # the JSON snapshot rounds total_s to 6 decimals; compare at that
        # granularity
        assert float(samples[fam + "_sum"][0][1]) \
            == pytest.approx(h["total_s"], abs=1e-6)
        hist_count = int(samples[fam + "_hist_count"][0][1])
        assert hist_count == h["count"]


def test_prometheus_new_process_and_kernel_families(server):
    """ISSUE 6 satellite: process.cpu_seconds_total (a monotone gauge
    probe exported as a counter — no doubled _total suffix) and
    kernels.recompiles (plain counter) appear in the exposition with
    correct types, and both survive the single-pass conformance parse."""
    base, ds = server
    from geomesa_tpu.index.spatial import _boxes_fp62
    kern = ds.planner("obs_t").indexes[0].kernels
    kern.counts_multi("point_boxes", _boxes_fp62(
        [(-5, -5, 5, 5), (-4, -4, 4, 4)]), None, None)
    kern.counts_multi("point_boxes", _boxes_fp62(
        [(-5, -5, 5, 5), (-4, -4, 4, 4), (-3, -3, 3, 3)]), None, None)
    with urllib.request.urlopen(f"{base}/metrics?format=prometheus") as r:
        text = r.read().decode()
    types, samples = _parse_exposition(text)
    assert types["geomesa_tpu_process_cpu_seconds_total"] == "counter"
    assert float(samples["geomesa_tpu_process_cpu_seconds_total"][0][1]) > 0
    assert "geomesa_tpu_process_cpu_seconds_total_total" not in types
    assert types["geomesa_tpu_kernels_recompiles_total"] == "counter"
    assert int(samples["geomesa_tpu_kernels_recompiles_total"][0][1]) >= 1
    # ordinary gauges stay gauges
    assert types["geomesa_tpu_process_rss_bytes"] == "gauge"


# -- CLI ----------------------------------------------------------------------


def test_cli_debug_events_slo_kernels(capsys, store):
    from geomesa_tpu.tools.cli import main
    store.count("obs_t", "BBOX(geom, -5, -5, 5, 5)")
    main(["debug", "events", "--limit", "5"])
    out = json.loads(capsys.readouterr().out)
    assert "events" in out and "recorder" in out
    main(["debug", "slo"])
    out = json.loads(capsys.readouterr().out)
    assert "count_latency" in out["slo"]
    main(["debug", "kernels"])
    out = json.loads(capsys.readouterr().out)
    assert "counters" in out["kernels"]
    assert "recompiles" in out and "device_memory" in out


def test_cli_debug_traces_filters(capsys, store):
    from geomesa_tpu.tools.cli import main
    store.count("obs_t", "BBOX(geom, -5, -5, 5, 5)")
    main(["debug", "traces", "--limit", "5"])
    unfiltered = json.loads(capsys.readouterr().out)
    assert unfiltered
    main(["debug", "traces", "--slow", "1e12"])
    assert json.loads(capsys.readouterr().out) == []
    main(["debug", "traces", "--errors"])
    errs = json.loads(capsys.readouterr().out)
    assert all(t.get("error") for t in errs)
    main(["debug", "traces", "--kind", "query.count", "--limit", "3"])
    named = json.loads(capsys.readouterr().out)
    assert all(t["name"] == "query.count" or "query.count" in
               t.get("stages_ms", {}) for t in named)
