"""Device-level kernel profiling (obs/profiling.py): recompile detection
via signature hashing, XLA cost-analysis gauges, build-phase progress +
GET /progress, the deterministic kernel handicap, and the device/process
pressure gauges. Everything deterministic — recompiles are forced by
shape, never by timing.
"""

import json
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.metrics import REGISTRY, register_device_gauges
from geomesa_tpu.obs import profiling
from geomesa_tpu.obs.flight import RECORDER


@pytest.fixture(scope="module")
def store():
    rng = np.random.default_rng(21)
    n = 20_000
    ds = TpuDataStore()
    ds.create_schema("prof_t", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    ds.load("prof_t", FeatureTable.build(ds.get_schema("prof_t"), {
        "dtg": base + rng.integers(0, 7 * 86400000, n),
        "geom": (rng.uniform(-20, 20, n), rng.uniform(-20, 20, n))}))
    yield ds
    ds.close()


def _recompiles() -> int:
    return REGISTRY.snapshot()["counters"].get("kernels.recompiles", 0)


def _boxes(*rects):
    from geomesa_tpu.index.spatial import _boxes_fp62
    return _boxes_fp62(list(rects))


# -- recompile detection ------------------------------------------------------


def test_new_fused_batch_shape_is_exactly_one_recompile(store):
    """ISSUE 6 acceptance: forcing a new fused-batch shape increments
    kernels.recompiles by EXACTLY one — and the flight recorder carries
    the triggering shape."""
    kern = store.planner("prof_t").indexes[0].kernels
    b2 = _boxes((-5, -5, 5, 5), (-4, -4, 4, 4))
    b3 = _boxes((-5, -5, 5, 5), (-4, -4, 4, 4), (-3, -3, 3, 3))
    kern.counts_multi("point_boxes", b2, None, None)   # tier 2 (cold)
    c0 = _recompiles()
    kern.counts_multi("point_boxes", b2, None, None)   # same shape: cached
    assert _recompiles() == c0
    RECORDER.clear()
    kern.counts_multi("point_boxes", b3, None, None)   # tier 4: NEW shape
    assert _recompiles() == c0 + 1
    evs = RECORDER.recent(kind="kernel.recompile")
    assert len(evs) == 1
    ev = evs[0]
    assert ev["kernel"] == "count_multi.point_boxes"
    assert ev["reason"] == "new_shape"
    assert ev["shape"]["n_boxes"] == 4  # the padded tier that compiled
    kern.counts_multi("point_boxes", b3, None, None)   # cached again
    assert _recompiles() == c0 + 1


def test_first_compile_per_kernel_is_not_a_recompile():
    from geomesa_tpu.obs.profiling import note_signature
    seen: dict = {}
    c0 = _recompiles()
    note_signature(seen, "count.point_boxes", ("count", 1))
    assert _recompiles() == c0  # cold compile, not churn
    note_signature(seen, "count.point_boxes", ("count", 2))
    assert _recompiles() == c0 + 1
    # an evicted signature re-jitting counts too (it IS a recompilation)
    note_signature(seen, "count.point_boxes", ("count", 1))
    assert _recompiles() == c0 + 2


def test_two_instances_are_not_churn(store):
    """Two indexes each compiling their own kernels must not read as
    recompiles (the seen-set is per ScanKernels instance)."""
    from geomesa_tpu.index.scan import ScanKernels
    cols = store.planner("prof_t").indexes[0].kernels.cols
    c0 = _recompiles()
    b1 = _boxes((-5, -5, 5, 5))
    for _ in range(2):
        ScanKernels(cols).count("point_boxes", b1, None, None)
    assert _recompiles() == c0


# -- cost analysis + compile telemetry ---------------------------------------


def test_cost_analysis_gauges_land_in_kernel_series(store):
    from geomesa_tpu.obs import attrib
    store.count("prof_t", "BBOX(geom, -5, -5, 5, 5)")
    gauges = attrib.snapshot()["gauges"]
    flops = {k: v for k, v in gauges.items()
             if k.startswith("kernel.") and k.endswith(".flops")}
    assert flops, f"no flops gauges in {sorted(gauges)}"
    assert all(v > 0 for v in flops.values())
    hbm = {k: v for k, v in gauges.items() if k.endswith(".hbm_bytes")}
    assert hbm and all(v > 0 for v in hbm.values())


def test_compile_telemetry_recorded(store):
    from geomesa_tpu.obs import attrib
    snap = attrib.snapshot()
    compiles = {k: v for k, v in snap["counters"].items()
                if k.endswith(".compiles")}
    assert compiles and all(v >= 1 for v in compiles.values())


# -- kernel handicap (the regression gate's fault hook) ----------------------


def test_kernel_handicap_stretches_matching_kernels(store):
    import time
    profiling.arm_kernel_handicap("count.point_boxes", 50.0)
    try:
        kern = None
        from geomesa_tpu.index.scan import ScanKernels
        kern = ScanKernels(store.planner("prof_t").indexes[0].kernels.cols)
        b = _boxes((-5, -5, 5, 5))
        kern.count("point_boxes", b, None, None)  # compile rep (unstretched)
        t0 = time.perf_counter()
        kern.count("point_boxes", b, None, None)
        stretched = time.perf_counter() - t0
        profiling.reset_kernel_handicap()
        kern2 = ScanKernels(store.planner("prof_t").indexes[0].kernels.cols)
        kern2.count("point_boxes", b, None, None)
        plain = float("inf")
        for _ in range(3):   # the least of three: one descheduling is noise
            t0 = time.perf_counter()
            kern2.count("point_boxes", b, None, None)
            plain = min(plain, time.perf_counter() - t0)
        # 50x handicap dominates scheduler noise even on a loaded host
        assert stretched > 5 * plain, (stretched, plain)
    finally:
        profiling.reset_kernel_handicap()


# -- build phase progress -----------------------------------------------------


def test_progress_phases_report_throughput():
    profiling.PROGRESS.clear()
    RECORDER.clear()
    with profiling.PROGRESS.phase("encode", rows=1000, type_name="pt"):
        snap = profiling.PROGRESS.snapshot()
        assert snap["active"] and snap["active"][0]["phase"] == "encode"
        assert snap["active"][0]["done"] is False
    snap = profiling.PROGRESS.snapshot()
    assert not snap["active"]
    done = snap["recent"][0]
    assert done["phase"] == "encode" and done["done"] and done["rows"] == 1000
    assert done["rows_per_s"] > 0
    # finished phases emit a progress flight event + a build.* timer
    evs = RECORDER.recent(kind="progress")
    assert evs and evs[0]["phase"] == "encode"
    assert REGISTRY.snapshot()["timers"]["build.encode"]["count"] >= 1


def test_index_build_emits_phases(monkeypatch):
    """The numpy build path (native disabled) reports host_sort +
    upload_gather phases with row counts."""
    from geomesa_tpu import native
    # the native lib caches its load result, so the env knob is too late
    # here — force the numpy path directly
    monkeypatch.setattr(native, "available", lambda: False)
    profiling.PROGRESS.clear()
    rng = np.random.default_rng(5)
    n = 5000
    ds = TpuDataStore()
    ds.create_schema("prog_t", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    ds.load("prog_t", FeatureTable.build(ds.get_schema("prog_t"), {
        "dtg": base + rng.integers(0, 7 * 86400000, n),
        "geom": (rng.uniform(-20, 20, n), rng.uniform(-20, 20, n))}))
    ds.count("prog_t", "BBOX(geom, -5, -5, 5, 5)")  # forces the index build
    phases = {e["phase"] for e in profiling.PROGRESS.recent(type_name="prog_t")}
    assert {"host_sort", "upload_gather"} <= phases
    by_phase = {e["phase"]: e
                for e in profiling.PROGRESS.recent(type_name="prog_t")}
    assert by_phase["host_sort"]["rows"] == n
    # and explain carries the build section for this type
    out = ds.explain("prog_t", "BBOX(geom, -5, -5, 5, 5)")
    assert "build" in out and out["build"]["recent_phases"]


def test_progress_web_route(store):
    from geomesa_tpu.web.server import serve
    profiling.PROGRESS.clear()
    with profiling.PROGRESS.phase("upload", rows=10, type_name="w"):
        pass
    httpd = serve(store, port=0, background=True)
    try:
        port = httpd.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/progress") as r:
            out = json.loads(r.read())
        assert out["progress"]["recent"][0]["phase"] == "upload"
    finally:
        httpd.shutdown()


# -- pressure gauges ----------------------------------------------------------


def test_cpu_and_memory_gauges():
    register_device_gauges()
    gauges = REGISTRY.snapshot()["gauges"]
    assert gauges.get("process.cpu_seconds_total", 0) > 0
    assert gauges.get("process.rss_bytes", 0) > 0
    # device memory gauges are backend-dependent (CPU reports nothing);
    # the probe must simply never raise through the surface
    from geomesa_tpu.index.device import memory_snapshot
    assert isinstance(memory_snapshot(), dict)


def test_cpu_seconds_exports_as_counter():
    register_device_gauges()
    text = REGISTRY.to_prometheus()
    assert "# TYPE geomesa_tpu_process_cpu_seconds_total counter" in text
    assert "geomesa_tpu_process_cpu_seconds_total_total" not in text


def test_profiling_disabled_skips_everything(monkeypatch, store):
    monkeypatch.setenv("GEOMESA_TPU_PROFILING", "0")
    assert not profiling.enabled()
    from geomesa_tpu.index.scan import ScanKernels
    kern = ScanKernels(store.planner("prof_t").indexes[0].kernels.cols)
    c0 = _recompiles()
    kern.counts_multi("point_boxes", _boxes((-5, -5, 5, 5)), None, None)
    kern.counts_multi("point_boxes",
                      _boxes((-5, -5, 5, 5), (-4, -4, 4, 4),
                             (-3, -3, 3, 3)), None, None)
    assert _recompiles() == c0  # detector off, queries still work
