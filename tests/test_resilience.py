"""Query-lifecycle resilience (serve/resilience/): deadline propagation and
pre-dispatch cancellation, admission control + load shedding, circuit
breaker + retry, graceful degradation, crash-safe scheduler workers, and the
web error envelope. Every overload/failure behavior is driven
deterministically through the serve-side fault injections in
durability/faults.py — no test here depends on racing real load."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.durability import faults
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.metrics import REGISTRY
from geomesa_tpu.serve.resilience import deadline as rdl
from geomesa_tpu.serve.resilience.admission import (AdmissionController,
                                                    ShedError)
from geomesa_tpu.serve.resilience.breaker import (CircuitBreaker,
                                                  CircuitOpenError,
                                                  retry_call)
from geomesa_tpu.serve.resilience.deadline import Deadline, DeadlineExceeded
from geomesa_tpu.serve.resilience.degrade import ApproximateCount
from geomesa_tpu.serve.scheduler import (QueryScheduler, SchedulerCrashed,
                                         SchedulerShutdown, StoreBinding)

DURING = "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
BOX = "BBOX(geom, -10, 5, 10, 25) AND " + DURING


def _mk_store(n=30_000, seed=7):
    rng = np.random.default_rng(seed)
    ds = TpuDataStore()
    ds.create_schema(
        "t", "v:Int,dtg:Date,*geom:Point;geomesa.z3.interval=week")
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    ds.load("t", FeatureTable.build(ds.get_schema("t"), {
        "v": rng.integers(0, 100, n).astype(np.int32),
        "dtg": base + rng.integers(0, 30 * 86400000, n),
        "geom": (rng.uniform(-60, 60, n), rng.uniform(-40, 40, n))}))
    return ds


@pytest.fixture(scope="module")
def store():
    ds = _mk_store()
    yield ds
    ds.close()


@pytest.fixture()
def sched(store):
    """A fresh scheduler per test (resilience tests mutate breaker state,
    kill workers, etc. — they must not leak into each other)."""
    s = QueryScheduler(StoreBinding(store), flush_size=8, window_us=300)
    yield s
    faults.reset()
    s.shutdown(timeout=2)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# -- deadline primitives ------------------------------------------------------


def test_deadline_expiry_and_check():
    dl = Deadline.after_ms(10_000)
    assert not dl.expired and dl.remaining_ms() > 9_000
    dl.check("plan")  # no raise
    past = Deadline.after_ms(-1)
    assert past.expired
    with pytest.raises(DeadlineExceeded) as ei:
        past.check("scan")
    assert ei.value.stage == "scan" and ei.value.overrun_ms >= 0


def test_ambient_deadline_nests_to_sooner():
    outer = Deadline.after_ms(50)
    inner = Deadline.after_ms(100_000)
    with rdl.use(outer):
        assert rdl.current() is outer
        with rdl.use(inner):  # cannot loosen the enclosing budget
            assert rdl.current() is outer
        tight = Deadline.after_ms(1)
        with rdl.use(tight):
            assert rdl.current() is tight
    assert rdl.current() is None


def test_resolve_prefers_explicit_but_clamps_to_ambient():
    amb = Deadline.after_ms(10)
    with rdl.use(amb):
        assert rdl.resolve(None, 100_000) is amb
        assert rdl.resolve(None, None) is amb
    assert rdl.resolve(None, None) is None
    assert rdl.resolve(None, 100).remaining_ms() <= 100


def test_planner_honors_ambient_deadline(store):
    planner = store.planner("t")
    with rdl.use(Deadline.after_ms(-1)):
        with pytest.raises(DeadlineExceeded):
            planner.count(BOX)
    # and without one the same query answers
    assert planner.count(BOX) >= 0


def test_datastore_count_deadline_ms(store):
    with pytest.raises(DeadlineExceeded):
        store.count("t", BOX, deadline_ms=1e-6)
    assert store.count("t", BOX, deadline_ms=60_000) == store.count("t", BOX)


# -- scheduler deadline propagation + pre-dispatch cancellation ---------------


def test_expired_deadline_cancelled_before_dispatch(store, sched):
    from geomesa_tpu.trace import RING
    c0 = REGISTRY.snapshot()["counters"]
    fused0 = c0.get("scheduler.fused", 0)
    RING.clear()
    with pytest.raises(DeadlineExceeded):
        sched.count("t", BOX, deadline_ms=1e-6)
    req = sched.submit("t", BOX, deadline_ms=1e-6)
    with pytest.raises(DeadlineExceeded):
        req.result(timeout=5)
    assert req.cancelled and not req.batched and req.scan_s is None
    c1 = REGISTRY.snapshot()["counters"]
    assert c1.get("scheduler.deadline_cancelled", 0) >= \
        c0.get("scheduler.deadline_cancelled", 0) + 2
    # trace-verified: the cancelled query shows a cancel leaf and NO scan
    # (no device work was spent on it)
    tr = next(t for t in RING.recent(10) if t["name"] == "query.count")
    assert "cancel" in tr["stages_ms"]
    assert "scan" not in tr["stages_ms"]
    assert c1.get("scheduler.fused", 0) == fused0


def test_deadline_expiring_in_queue_cancels_at_dispatch(store, sched):
    # stall the collector so the queued request's deadline lapses before
    # its batch reaches dispatch
    config.DEADLINE_DEGRADE_MS.set(0)  # force cancel, not degrade
    try:
        faults.arm_serve_delay("sched.collect", seconds=0.15, n=1)
        req = sched.submit("t", BOX, deadline_ms=30)
        with pytest.raises(DeadlineExceeded):
            req.result(timeout=5)
        assert req.cancelled and req.plan is None  # never even planned
    finally:
        config.DEADLINE_DEGRADE_MS.unset()


def test_nearly_spent_deadline_degrades_to_estimate(store, sched):
    # plenty of degrade floor: a queued request with a short (but live)
    # deadline resolves as a flagged approximation, not an error
    config.DEADLINE_DEGRADE_MS.set(10_000)
    try:
        n = sched.count("t", BOX, deadline_ms=500)
        assert isinstance(n, ApproximateCount)
        assert n.approximate and n.reason == "deadline"
        exact = store.count("t", BOX)
        assert n >= 0  # an int, usable as one
        # the estimator is histogram-mass based: same order of magnitude
        assert abs(int(n) - exact) <= max(1000, exact)
    finally:
        config.DEADLINE_DEGRADE_MS.unset()


# -- admission control / load shedding ----------------------------------------


def test_admission_controller_bounds_and_sheds():
    ctl = AdmissionController(interactive_limit=2, batch_limit=1)
    assert ctl.admit("interactive") == "interactive"
    assert ctl.admit("interactive") == "interactive"
    with pytest.raises(ShedError) as ei:
        ctl.admit("interactive")
    assert ei.value.retry_after_s > 0
    # batch class has its own bound
    assert ctl.admit("analytics") == "batch"
    with pytest.raises(ShedError):
        ctl.admit("batch")
    ctl.release("interactive")
    assert ctl.admit("interactive") == "interactive"
    st = ctl.stats()
    assert st["shed"]["interactive"] == 1 and st["shed"]["batch"] == 1
    assert st["admitted"]["interactive"] == 3


def test_overload_burst_sheds_excess_and_answers_admitted(store):
    """The 4x saturation burst: a tightly bounded scheduler under slow
    device rounds sheds the excess with backpressure and answers every
    admitted request — admitted + shed == submitted, nothing silently
    dropped or left hanging."""
    limit = 8
    config.ADMIT_INTERACTIVE.set(limit)
    s = QueryScheduler(StoreBinding(store), flush_size=4, window_us=200)
    try:
        s.count("t", BOX)  # warm the kernel path outside the burst
        faults.arm_serve_delay("sched.device_wait", seconds=0.05, n=1000)
        submitted = 4 * limit
        results, sheds, errors = [], [], []
        lock = threading.Lock()
        start = threading.Barrier(submitted)

        def client(i):
            start.wait()
            try:
                n = s.count("t", f"BBOX(geom, {-10 - i % 5}, 5, 10, 25) "
                                 f"AND {DURING}", timeout=30)
                with lock:
                    results.append(n)
            except ShedError as e:
                with lock:
                    sheds.append(e)
            except Exception as e:  # pragma: no cover - failure detail
                with lock:
                    errors.append(e)

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(submitted)]
        [t.start() for t in ts]
        [t.join(timeout=60) for t in ts]
        assert not errors, errors
        assert len(results) + len(sheds) == submitted  # (c) none dropped
        assert len(sheds) > 0, "4x overload must shed"
        assert len(results) >= limit  # everything admitted was answered
        assert all(e.retry_after_s > 0 for e in sheds)  # (b) backpressure
        st = s.admission.stats()
        assert st["shed"]["interactive"] == len(sheds)
    finally:
        faults.reset()
        config.ADMIT_INTERACTIVE.unset()
        s.shutdown(timeout=5)


def test_interactive_dequeues_before_batch(store):
    """Priority classes: with a stalled collector and a mixed backlog, all
    interactive requests dispatch in an earlier-or-same batch than every
    batch-class request (the priority queue serves rank 0 first)."""
    s = QueryScheduler(StoreBinding(store), flush_size=4, window_us=200)
    try:
        faults.arm_serve_delay("sched.collect", seconds=0.1, n=1)
        order = []
        lock = threading.Lock()
        reqs = []
        # first submit lands in the stalled collector's hands; the rest
        # queue behind it and sort by (rank, seq)
        first = s.submit("t", BOX)
        for i in range(3):
            r = s.submit("t", f"v < {50 + i}", priority="batch")
            r.future.add_done_callback(
                lambda f, k=f"b{i}": (lock.acquire(), order.append(k),
                                      lock.release()))
            reqs.append(r)
        for i in range(3):
            r = s.submit("t", f"BBOX(geom, {-9 - i}, 5, 10, 25) AND "
                              f"{DURING}")
            r.future.add_done_callback(
                lambda f, k=f"i{i}": (lock.acquire(), order.append(k),
                                      lock.release()))
            reqs.append(r)
        first.result(timeout=10)
        [r.result(timeout=10) for r in reqs]
        i_last = max(i for i, k in enumerate(order) if k.startswith("i"))
        b_first = min(i for i, k in enumerate(order) if k.startswith("b"))
        assert i_last < b_first, order
    finally:
        s.shutdown(timeout=5)


# -- circuit breaker + retry --------------------------------------------------


def test_breaker_transitions_deterministic():
    clk = [0.0]
    b = CircuitBreaker("test", threshold=3, cooldown_ms=1000, probes=2,
                       clock=lambda: clk[0])
    assert b.state == "closed" and b.allow()
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"
    b.record_failure()                      # threshold: opens
    assert b.state == "open" and not b.allow()
    assert b.retry_after_s() == pytest.approx(1.0)
    clk[0] = 0.5
    assert not b.allow()                    # still cooling down
    clk[0] = 1.1
    assert b.allow()                        # half-open: first probe
    assert b.state == "half_open"
    assert b.allow()                        # second probe slot
    assert not b.allow()                    # probes bounded
    b.record_success()
    b.record_success()                      # both probes pass: closes
    assert b.state == "closed" and b.allow()
    # a failing probe re-opens instead
    for _ in range(3):
        b.record_failure()
    clk[0] = 2.5
    assert b.allow() and b.state == "half_open"
    b.record_failure()
    assert b.state == "open"
    assert b.retry_after_s() == pytest.approx(1.0)


def test_retry_call_backoff_and_jitter_deterministic():
    import random
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    c0 = REGISTRY.snapshot()["counters"].get("retry.attempts", 0)
    out = retry_call(flaky, attempts=4, base_ms=0.01, cap_ms=0.02,
                     rng=random.Random(42))
    assert out == "ok" and len(calls) == 3
    assert REGISTRY.snapshot()["counters"]["retry.attempts"] == c0 + 2
    # exhausted attempts re-raise the last error
    calls.clear()
    with pytest.raises(RuntimeError):
        retry_call(lambda: (_ for _ in ()).throw(RuntimeError("always")),
                   attempts=2, base_ms=0.01, cap_ms=0.02,
                   rng=random.Random(1))


def test_retry_does_not_sleep_past_deadline():
    t0 = time.perf_counter()
    with rdl.use(Deadline.after_ms(30)):
        with pytest.raises(RuntimeError):
            retry_call(lambda: (_ for _ in ()).throw(RuntimeError("x")),
                       attempts=10, base_ms=500, cap_ms=5000)
    assert time.perf_counter() - t0 < 2.0


def test_injected_dispatch_errors_retry_then_succeed(store, sched):
    # two transient failures at the dispatch boundary, three attempts:
    # the query still answers exactly, and the retries were counted
    ref = store.count("t", BOX)
    faults.arm_serve_error("sched.dispatch", n=2)
    c0 = REGISTRY.snapshot()["counters"].get("retry.attempts", 0)
    assert sched.count("t", BOX, timeout=30) == ref
    assert REGISTRY.snapshot()["counters"]["retry.attempts"] >= c0 + 2


def test_breaker_opens_on_dispatch_failures_then_degrades(store):
    config.RETRY_ATTEMPTS.set(1)       # every failure reaches the breaker
    config.BREAKER_THRESHOLD.set(2)
    config.BREAKER_COOLDOWN_MS.set(60_000)
    # result_cache=0: the repeated BOX count must REACH the faulty
    # dispatch boundary, not resolve from the hot-result cache
    s = QueryScheduler(StoreBinding(store), flush_size=4, window_us=200,
                       result_cache=0)
    try:
        s.count("t", BOX)  # warm + prove healthy
        faults.arm_serve_error("sched.dispatch", n=2)
        for _ in range(2):
            with pytest.raises(RuntimeError):
                s.count("t", BOX, timeout=10)
        assert s.breaker.state == "open"
        faults.reset()
        # breaker open -> eligible counts degrade at submit: flagged
        # approximate, no device work, resolved immediately
        n = s.count("t", BOX, timeout=10)
        assert isinstance(n, ApproximateCount)
        assert n.reason == "breaker_open"
        snap = REGISTRY.snapshot()["counters"]
        assert snap.get("degrade.approximate.breaker_open", 0) >= 1
        assert snap.get("breaker.device_dispatch.opened", 0) >= 1
    finally:
        for p in (config.RETRY_ATTEMPTS, config.BREAKER_THRESHOLD,
                  config.BREAKER_COOLDOWN_MS):
            p.unset()
        s.shutdown(timeout=5)


def test_breaker_half_open_recovers_through_probes(store):
    config.RETRY_ATTEMPTS.set(1)
    config.BREAKER_THRESHOLD.set(1)
    config.BREAKER_COOLDOWN_MS.set(50)
    config.BREAKER_PROBES.set(1)
    config.BREAKER_DEGRADE.set(False)  # fail fast instead of degrading
    s = QueryScheduler(StoreBinding(store), flush_size=4, window_us=200,
                       result_cache=0)
    try:
        ref = s.count("t", BOX)
        faults.arm_serve_error("sched.dispatch", n=1)
        with pytest.raises(RuntimeError):
            s.count("t", BOX, timeout=10)
        assert s.breaker.state == "open"
        faults.reset()
        time.sleep(0.08)  # cooldown elapses -> half-open probe allowed
        assert s.count("t", BOX, timeout=10) == ref
        assert s.breaker.state == "closed"
    finally:
        for p in (config.RETRY_ATTEMPTS, config.BREAKER_THRESHOLD,
                  config.BREAKER_COOLDOWN_MS, config.BREAKER_PROBES,
                  config.BREAKER_DEGRADE):
            p.unset()
        s.shutdown(timeout=5)


# -- crash-safe workers -------------------------------------------------------


def test_killed_collector_fails_outstanding_futures_promptly(store):
    """Satellite regression: a died worker must fail every outstanding
    future with a structured error within 1s — result(timeout=...) raises
    instead of hanging forever."""
    s = QueryScheduler(StoreBinding(store), flush_size=64, window_us=50_000)
    try:
        faults.arm_serve_crash("sched.collect", at=1)
        # the gate sleeps before it crashes: on a loaded host the collector
        # would otherwise die between two of the submits below, and the
        # later ones be refused instead of left outstanding
        faults.arm_serve_delay("sched.collect", seconds=0.1, n=1)
        reqs = [s.submit("t", f"BBOX(geom, {-10 - i}, 5, 10, 25) AND "
                              f"{DURING}") for i in range(4)]
        t0 = time.perf_counter()
        for r in reqs:
            with pytest.raises(SchedulerCrashed) as ei:
                r.result(timeout=1.0)
            assert ei.value.worker == "collector"
        assert time.perf_counter() - t0 < 1.0, \
            "outstanding futures must fail within 1s of worker death"
        assert not s.healthy()
        assert REGISTRY.snapshot()["counters"].get(
            "scheduler.worker_deaths", 0) >= 1
    finally:
        faults.reset()
        s.shutdown(timeout=2)


def test_killed_completer_fails_outstanding_futures(store):
    s = QueryScheduler(StoreBinding(store), flush_size=4, window_us=200)
    try:
        faults.arm_serve_crash("sched.complete", at=1)
        req = s.submit("t", BOX)
        with pytest.raises((SchedulerCrashed, SchedulerShutdown)):
            req.result(timeout=2.0)
        assert not s.healthy()
    finally:
        faults.reset()
        s.shutdown(timeout=2)


def test_store_replaces_unhealthy_scheduler(store):
    s = store.scheduler()
    ref = s.count("t", BOX)
    # the probe submit must travel through the (crashing) collector, not
    # resolve from the hot-result cache
    s.results.clear()
    config.RESULT_CACHE_ENABLED.set(False)
    faults.arm_serve_crash("sched.collect", at=1)
    req = s.submit("t", BOX)
    try:
        with pytest.raises(SchedulerCrashed):
            req.result(timeout=2.0)
    finally:
        config.RESULT_CACHE_ENABLED.unset()
    faults.reset()
    s2 = store.scheduler()          # a fresh, healthy scheduler
    assert s2 is not s and s2.healthy()
    assert s2.count("t", BOX) == ref
    assert REGISTRY.snapshot()["counters"].get("scheduler.restarts", 0) >= 1


def test_shutdown_drains_queued_futures(store):
    """Satellite regression: shutdown with requests still queued resolves
    them (gracefully if the workers drain, structurally otherwise) — a
    caller blocked on result() never hangs past shutdown."""
    s = QueryScheduler(StoreBinding(store), flush_size=64, window_us=50_000)
    faults.arm_serve_delay("sched.collect", seconds=0.3, n=1)
    reqs = [s.submit("t", f"v < {i}") for i in range(6)]
    s.shutdown(timeout=0.05)  # tighter than the stall: forces the sweep
    t0 = time.perf_counter()
    for r in reqs:
        try:
            r.result(timeout=1.0)
        except (SchedulerShutdown, SchedulerCrashed):
            pass  # structured failure is the contract; hanging is the bug
    assert time.perf_counter() - t0 < 2.0
    assert all(r.future.done() for r in reqs)
    faults.reset()
    s.shutdown(timeout=2)  # idempotent


def test_shutdown_then_submit_raises(store):
    s = QueryScheduler(StoreBinding(store), flush_size=4, window_us=200)
    s.shutdown()
    with pytest.raises(RuntimeError):
        s.submit("t", "INCLUDE")


# -- the web error envelope + overload surfaces -------------------------------


@pytest.fixture()
def httpd(store):
    from geomesa_tpu.web import serve
    server = serve(store, port=0, background=True)
    yield server
    server.shutdown()


def _get(httpd, path):
    port = httpd.server_address[1]
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def test_web_deadline_exceeded_maps_to_504(httpd):
    config.DEADLINE_DEGRADE_MS.set(0)  # force the error, not degradation
    try:
        status, _, body = _get(
            httpd, "/types/t/count?cql=INCLUDE&deadline_ms=0.000001")
        assert status == 504
        assert body["kind"] == "deadline" and "error" in body
    finally:
        config.DEADLINE_DEGRADE_MS.unset()


def test_web_degraded_count_is_flagged(httpd, store):
    config.DEADLINE_DEGRADE_MS.set(10_000)
    try:
        q = "BBOX(geom,%20-10,%205,%2010,%2025)"
        status, _, body = _get(
            httpd, f"/types/t/count?cql={q}&deadline_ms=200")
        assert status == 200
        assert body["approximate"] is True and body["reason"] == "deadline"
    finally:
        config.DEADLINE_DEGRADE_MS.unset()


def test_web_shed_maps_to_429_with_retry_after(httpd, store):
    config.ADMIT_INTERACTIVE.set(1)
    try:
        sched = store.scheduler()
        if not sched.healthy():  # an earlier kill-test may have crashed it
            sched = store.scheduler()
        faults.arm_serve_delay("sched.collect", seconds=0.4, n=1)
        q = "BBOX(geom,%20-10,%205,%2010,%2025)"
        codes, headers = [], []
        lock = threading.Lock()

        def client():
            st, hd, _ = _get(httpd, f"/types/t/count?cql={q}")
            with lock:
                codes.append(st)
                headers.append(hd)

        ts = [threading.Thread(target=client) for _ in range(6)]
        [t.start() for t in ts]
        [t.join(timeout=30) for t in ts]
        assert codes.count(200) >= 1
        shed_i = [i for i, c in enumerate(codes) if c == 429]
        assert shed_i, f"expected sheds among {codes}"
        for i in shed_i:
            assert int(headers[i]["Retry-After"]) >= 1
    finally:
        faults.reset()
        config.ADMIT_INTERACTIVE.unset()


def test_web_bad_request_envelope(httpd):
    status, _, body = _get(httpd, "/types/t/count?cql=NOT%20(VALID")
    assert status == 400
    assert body["kind"] == "bad_request" and "error" in body


def test_web_guard_envelope(httpd, store):
    # the planner shares the store's interceptor list by reference, and
    # "v < 47" (no attribute index) was never planned before, so the guard
    # fires on the cache-miss plan
    from geomesa_tpu.index.guards import FullTableScanGuard
    store.add_interceptor("t", FullTableScanGuard())
    try:
        status, _, body = _get(httpd, "/types/t/count?cql=v%20%3C%2047")
        assert (status, body["kind"]) == (400, "guard")
    finally:
        store._interceptors["t"].clear()


def test_web_healthz_overload_state(httpd, store):
    store.scheduler().count("t", "INCLUDE")
    status, _, body = _get(httpd, "/healthz")
    assert status == 200
    ov = body["overload"]
    assert ov["scheduler"] in ("ok", "idle")
    if ov["scheduler"] == "ok":
        assert "admission" in ov and ov["breaker"]["state"] in (
            "closed", "open", "half_open")


# -- CLI + metrics surfaces ---------------------------------------------------


def test_cli_debug_admission(capsys, tmp_path, store):
    from geomesa_tpu.tools.cli import main
    store.scheduler().count("t", BOX)
    main(["debug", "admission"])
    out = json.loads(capsys.readouterr().out)
    assert "metrics" in out


def test_snapshot_prefixed():
    REGISTRY.inc("admission.admitted")
    snap = REGISTRY.snapshot_prefixed("admission.")
    assert snap["counters"].get("admission.admitted", 0) >= 1
    assert all(k.startswith("admission.") for k in snap["counters"])


def test_scheduler_stats_include_resilience(store, sched):
    sched.count("t", BOX)
    st = sched.stats()
    assert st["healthy"] is True
    assert st["admission"]["limits"]["interactive"] > 0
    assert st["breaker"]["state"] == "closed"


# -- WAL fsync retry ----------------------------------------------------------


def test_wal_fsync_retry_absorbs_transient_errors(tmp_path):
    from geomesa_tpu.durability.wal import WriteAheadLog, scan_segment, segments
    config.RETRY_WAL_FSYNC.set(3)
    try:
        d = str(tmp_path / "wal")
        w = WriteAheadLog(d, fsync="always")
        faults.arm_fsync_errors(2)  # two transient failures, three attempts
        w.append_json("remove", {"type": "t", "fids": ["a"]})
        w.close()
        recs, _, err = scan_segment(segments(d)[0])
        assert err is None and len(recs) == 1
        assert REGISTRY.snapshot()["counters"].get("wal.fsync_retries",
                                                   0) >= 2
    finally:
        config.RETRY_WAL_FSYNC.unset()
        faults.reset()


# -- stream tier --------------------------------------------------------------


def test_lambda_count_deadline(store):
    from geomesa_tpu.stream.live import LambdaDataStore
    lam = LambdaDataStore(store, "t")
    base = np.datetime64("2020-01-06T00:00:00", "ms").astype(np.int64)
    lam.put("hot.1", v=1, dtg=int(base), geom=(0.0, 10.0))
    assert lam.count(BOX) == store.count("t", BOX) + 1
    with pytest.raises(DeadlineExceeded):
        lam.count(BOX, deadline_ms=1e-6)
