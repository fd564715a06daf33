"""Wall-clock budget pins for the aggregate/process hot paths.

Gated behind GEOMESA_TPU_PERF_TESTS=1 (absolute-time pins flake on loaded CI
hosts — the advisor's r3 finding); speeds on the chip come from
``benchmark/run.py`` and the ledger. Run explicitly with:

    GEOMESA_TPU_PERF_TESTS=1 python -m pytest tests/test_perf_budget.py
"""

import os
import time

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("GEOMESA_TPU_PERF_TESTS") != "1",
    reason="perf pins run only with GEOMESA_TPU_PERF_TESTS=1")


@pytest.fixture(scope="module")
def world():
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.features.table import FeatureTable
    rng = np.random.default_rng(99)
    n = 2_000_000
    x = np.clip(rng.normal(0, 40, n), -180, 180)
    y = np.clip(rng.normal(0, 20, n), -90, 90)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    dtg = base + rng.integers(0, 30 * 86400000, n)
    ds = TpuDataStore()
    ds.create_schema("perf", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
    ds.load("perf", FeatureTable.build(ds.get_schema("perf"),
                                       {"dtg": dtg, "geom": (x, y)}))
    return ds.planner("perf")


def _p50(fn, reps=5):
    fn()  # warm (compiles excluded — the pins are steady-state budgets)
    lat = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        lat.append(time.perf_counter() - t0)
    return float(np.median(lat)) * 1000


def test_density_budget(world):
    from geomesa_tpu.aggregates.density import prepare_density
    run = prepare_density(world, "BBOX(geom, -10, 5, 10, 25)",
                          (-10, 5, 10, 25), 512, 512)
    assert _p50(run) < 500, "density p50 budget (500ms at 2M steady-state)"


def test_knn_budget(world):
    from geomesa_tpu.process.knn import knn
    knn(world, 2.0, 10.0, 10)  # warm
    lat = []
    for i in range(5):
        t0 = time.perf_counter()
        knn(world, 2.0 + i * 0.1, 10.0, 10)
        lat.append(time.perf_counter() - t0)
    assert float(np.median(lat)) * 1000 < 2000, "knn p50 budget (2s bar)"


def test_pruned_count_budget(world):
    pq = world.prepare("BBOX(geom, -10, 5, 10, 25) AND "
                       "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
    assert _p50(pq.count) < 500, "pruned count p50 budget"


def test_scheduler_coalescing_5x(world):
    """Serving acceptance bar: 64 concurrent clients on the cfg1-like
    synthetic workload sustain >= 5x the qps through the micro-batching
    scheduler vs the unbatched per-request path in the same process, and
    plan-cache hits skip the plan stage entirely (trace-tree verified)."""
    import threading

    from geomesa_tpu.serve.scheduler import PlannerBinding, QueryScheduler
    from geomesa_tpu.trace import RING

    # cfg1-like range-pruned regime: distinct overlapping bbox+time queries
    # whose covers are a small candidate fraction (the serving sweet spot;
    # the cell gdelt-z3-10m.count-c64 is the full-scale version on the chip)
    queries = [
        f"BBOX(geom, {-4 + 0.05 * i}, {6 + 0.025 * i}, {-1 + 0.05 * i}, "
        f"{9 + 0.025 * i}) AND "
        "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
        for i in range(64)]
    # window sized for the client population: 64 synchronous clients all
    # resubmit within a few ms of a batch resolving, so an 8ms cap lets
    # batches refill instead of fragmenting (the adaptive window stays at
    # the cap under this load)
    sched = QueryScheduler(PlannerBinding({"perf": world}), flush_size=64,
                           window_us=8000)
    n_threads = 64

    def run_clients(fn, reps):
        lats: list = []
        lock = threading.Lock()
        barrier = threading.Barrier(n_threads + 1)

        def client(i):
            q = queries[i % len(queries)]
            mine = []
            barrier.wait()
            for _ in range(reps):
                t0 = time.perf_counter()
                fn(q)
                mine.append(time.perf_counter() - t0)
            with lock:
                lats.extend(mine)

        ths = [threading.Thread(target=client, args=(i,))
               for i in range(n_threads)]
        for t in ths:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in ths:
            t.join()
        return lats, time.perf_counter() - t0

    try:
        ref = {q: world.count(q) for q in queries[:4]}  # warm + correctness
        got = sched.count_many("perf", queries)         # warm scheduler path
        assert got[:4] == [ref[q] for q in queries[:4]]
        lat_s, wall_s = run_clients(lambda q: sched.count("perf", q), 10)
        sched_qps = len(lat_s) / wall_s
        lat_u, wall_u = run_clients(lambda q: world.count(q), 3)
        unbatched_qps = len(lat_u) / wall_u
        assert sched_qps >= 5 * unbatched_qps, (
            f"scheduler {sched_qps:.0f} qps < 5x unbatched "
            f"{unbatched_qps:.0f} qps")
        # plan-cache hits skip the plan stage entirely (trace tree)
        RING.clear()
        sched.count("perf", queries[0])
        tr = RING.recent(1)[0]
        assert "plan" not in tr["stages_ms"] and "queue_wait" in tr["stages_ms"]
    finally:
        sched.shutdown()


def test_overload_admitted_p99_bounded(world):
    """Overload acceptance bar (ISSUE 4): under a deterministic 4x
    saturation burst with injected 20ms device rounds, the p99 latency of
    ADMITTED interactive requests stays bounded — load shedding converts
    what would be unbounded queueing delay into prompt 429s, so the work
    the server accepts still meets its deadline."""
    import threading

    from geomesa_tpu import config
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.durability import faults
    from geomesa_tpu.serve.resilience.admission import ShedError
    from geomesa_tpu.serve.scheduler import PlannerBinding, QueryScheduler

    limit = 8
    config.ADMIT_INTERACTIVE.set(limit)
    sched = QueryScheduler(PlannerBinding({"perf": world}), flush_size=4,
                           window_us=300)
    try:
        q = ("BBOX(geom, -10, 5, 10, 25) AND "
             "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
        sched.count("perf", q)  # warm outside the burst
        faults.arm_serve_delay("sched.device_wait", seconds=0.02, n=10_000)
        submitted = 4 * limit
        lat_ok, sheds = [], []
        lock = threading.Lock()
        start = threading.Barrier(submitted)

        def client(i):
            start.wait()
            t0 = time.perf_counter()
            try:
                sched.count(
                    "perf", f"BBOX(geom, {-10 - 0.1 * (i % 5)}, 5, 10, 25) "
                            "AND dtg DURING 2020-01-05T00:00:00Z/"
                            "2020-01-12T00:00:00Z", timeout=30)
            except ShedError as e:
                with lock:
                    sheds.append(e)
                return
            with lock:
                lat_ok.append(time.perf_counter() - t0)

        ths = [threading.Thread(target=client, args=(i,))
               for i in range(submitted)]
        [t.start() for t in ths]
        [t.join(timeout=60) for t in ths]
        assert len(lat_ok) + len(sheds) == submitted
        assert sheds, "a 4x burst against a bounded queue must shed"
        p99 = float(np.percentile(np.asarray(lat_ok) * 1000, 99))
        # admitted depth <= limit, batches of 4, 20ms per device round:
        # worst admitted wait ~ (limit/4 + 1) rounds ~ 60ms; 500ms is the
        # generous loaded-CI bar the shedding exists to guarantee
        assert p99 < 500, f"admitted p99 {p99:.0f}ms unbounded under burst"
    finally:
        faults.reset()
        config.ADMIT_INTERACTIVE.unset()
        sched.shutdown(timeout=5)


@pytest.mark.parametrize("path", ["planner", "dispatch_cycle"])
def test_tracing_overhead_under_5pct(path):
    """The observability layer must never silently regress the hot path:
    span/trace overhead on a 10k-feature count query stays <5% vs
    ``trace.disabled()``. Estimator: INTERLEAVED minima — each rep times one
    disabled and one traced call back to back, so host-frequency drift hits
    both arms equally, and the min-of-each isolates the intrinsic machinery
    cost from scheduler noise. ``dispatch_cycle``: sixteen counts through
    the scheduler as one size-flushed batch — the request leaves, the cycle
    record's clock reads, its ``sched.stage.*`` observations and the
    ``sched.*`` annotations against none of them."""
    from geomesa_tpu import trace
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.features.table import FeatureTable
    from geomesa_tpu.serve.scheduler import QueryScheduler, StoreBinding

    rng = np.random.default_rng(5)
    n = 10_000
    ds = TpuDataStore()
    ds.create_schema("ov", "v:Int,*geom:Point")
    ds.load("ov", FeatureTable.build(ds.get_schema("ov"), {
        "v": rng.integers(0, 100, n).astype(np.int32),
        "geom": (rng.uniform(-20, 20, n), rng.uniform(-20, 20, n))}))
    planner = ds.planner("ov")
    q = "BBOX(geom, -5, -5, 5, 5)"
    sched = None
    if path == "planner":
        def run():
            planner.count(q)
    else:
        # no result cache: every rep has to go through a whole cycle
        sched = QueryScheduler(StoreBinding(ds), flush_size=16,
                               result_cache=0)
        qs = [f"BBOX(geom, {-5 - i * 0.5}, -5, 5, 5)" for i in range(16)]

        def run():
            sched.count_many("ov", qs)

    def timed():
        t0 = time.perf_counter()
        run()
        return time.perf_counter() - t0

    def measure():
        base = traced = float("inf")
        for _ in range(400):
            with trace.disabled():
                base = min(base, timed())
            traced = min(traced, timed())
        return traced / base - 1.0, base, traced

    run()  # warm: compiles + transfer shapes excluded
    # noise only ever INFLATES the estimate, so the best of a few rounds is
    # the intrinsic machinery cost; one clean round proves the bar
    overhead, base, traced = min(measure() for _ in range(3))
    if sched is not None:
        sched.shutdown()
    assert overhead < 0.05, (
        f"tracing overhead {overhead:.1%} (traced {traced * 1e6:.0f}us vs "
        f"disabled {base * 1e6:.0f}us)")


def test_obs_flight_recorder_overhead_under_5pct():
    """ISSUE 5 acceptance bar, extended by ISSUE 10: with the flight
    recorder + tail sampling + WORKLOAD ANALYTICS enabled AT DEFAULTS
    (obs hooks installed, wide event per query, sampling decision per
    trace close, workload tee per event, kernel attribution labels), a
    count query's cost stays <5% over observability disabled. Same
    interleaved-minima estimator as the tracing guard — each rep times
    one disabled and one fully-observed call back to back."""
    from geomesa_tpu import config, obs, trace
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.features.table import FeatureTable
    from geomesa_tpu.obs.flight import RECORDER
    from geomesa_tpu.obs.sampling import SAMPLER
    from geomesa_tpu.obs.workload import WORKLOAD

    obs.install()
    rng = np.random.default_rng(6)
    n = 10_000
    ds = TpuDataStore()
    ds.create_schema("ov2", "v:Int,*geom:Point")
    ds.load("ov2", FeatureTable.build(ds.get_schema("ov2"), {
        "v": rng.integers(0, 100, n).astype(np.int32),
        "geom": (rng.uniform(-20, 20, n), rng.uniform(-20, 20, n))}))
    planner = ds.planner("ov2")
    q = "BBOX(geom, -5, -5, 5, 5)"

    def timed():
        t0 = time.perf_counter()
        planner.count(q)
        return time.perf_counter() - t0

    def measure():
        base = observed = float("inf")
        for _ in range(400):
            with trace.disabled():  # also mutes close hooks (no root trace)
                base = min(base, timed())
            observed = min(observed, timed())
        return observed / base - 1.0, base, observed

    planner.count(q)  # warm
    # defaults on: OBS enabled, sampling/flight/workload at shipped rates
    for p in (config.OBS_ENABLED, config.OBS_SAMPLE, config.OBS_SLOW_MS,
              config.WORKLOAD_ENABLED):
        p.unset()
    RECORDER.clear()
    SAMPLER.clear()
    WORKLOAD.clear()
    overhead, base, observed = min(measure() for _ in range(3))
    assert len(RECORDER), "flight events must actually have been recorded"
    # the workload plane really rode the measured run (its producer cost
    # is inside the <5% bar, not switched off)
    WORKLOAD.drain()
    assert WORKLOAD.consumed, "workload analytics must have consumed events"
    assert overhead < 0.05, (
        f"obs overhead {overhead:.1%} (observed {observed * 1e6:.0f}us vs "
        f"disabled {base * 1e6:.0f}us)")
