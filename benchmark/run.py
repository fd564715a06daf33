#!/usr/bin/env python3
"""One run of one cell: load, serve, warm, measure, compare, print one line.

    python benchmark/run.py --workload <config>.<mix> --seed <n>
                            --seconds <s> --trace <0|1>

One process, which holds the chip alone and spawns nothing. It refuses to run
unless ``jax.default_backend() == "tpu"``; ``--rehearse-rows N`` lets it run on
whatever backend there is at N rows, and such a run prints its numbers under
``rehearsal`` and none under a metric's name.

Everything that belongs to one configuration, one traffic mix, one loop kind,
one operation or one per-layer metric is a file found by its name (see
README.md); this file holds no list of them. From the program it takes
``DataStoreFinder``, ``web.serve`` and what the server says of itself over
HTTP.
"""

import argparse
import collections
import gc
import http.client
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SNAPSHOTS = ("/healthz", "/metrics", "/scheduler")
TRACE_SLICE_S = 3.0   # a trace of the whole window is too large to reduce
TRACER_LEAD_S = 0.25  # longer than any program of a cell runs on the device
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


GC_PAUSES = []   # (ended at, generation, seconds) of every collection


def _gc_watch(phase: str, info: dict) -> None:
    """The server shares this process: a long collection stalls every
    request in flight, and the window's line says whether one did."""
    if phase == "start":
        _gc_watch.began = time.perf_counter()
    else:
        now = time.perf_counter()
        GC_PAUSES.append((now, info["generation"], now - _gc_watch.began))


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, by file: names may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            cfg = next(c for c in bench["configs"]
                       if c["name"] == cell["config"])
            return cell, load_json(ROOT, cfg["file"]), load_json(
                HERE, "traffic", cell["traffic"] + ".json")
    raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")


# -- what the server says of itself -----------------------------------------


def http_json(port: int, path: str) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"GET {path}: {resp.status}")
        return json.loads(resp.read())
    finally:
        conn.close()


def snapshot(port: int) -> dict:
    """Every page of SNAPSHOTS, keyed by its path: taken as the window opens
    and as it closes, and handed whole to the per-layer readers."""
    return {path: http_json(port, path) for path in SNAPSHOTS}


# -- the run ------------------------------------------------------------------


def device_checks(ds, type_name: str, rehearsal: bool) -> int:
    """Every device column sits on a TPU device, or the run ends."""
    plane_bytes = 0
    for idx in ds.planners[type_name].indexes:
        for name, arr in idx.device.columns.items():
            plane_bytes += int(arr.nbytes)
            if not rehearsal and any(d.platform != "tpu"
                                     for d in arr.devices()):
                raise SystemExit(f"run.py: device column {idx.name}.{name} "
                                 f"is not on a TPU device")
    return plane_bytes


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile of all requests (not of chunks)."""
    return sorted_vals[max(0, math.ceil(q * len(sorted_vals)) - 1)]


def start_jax(cell: dict, rehearsal: bool):
    """Compile cache, the listener that counts new programs, the look for the
    chip (exit code 2 without one). Returns (jax, compiles, device, peaks)."""
    sys.path.insert(0, ROOT)
    from geomesa_tpu import config as gconfig
    cache_dir = gconfig.enable_compile_cache()   # before any backend starts
    import jax
    # the batched count's tiers compile in 0.8-1.6 s each: under JAX's 1 s
    # threshold they would be compiled again in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = []   # (when, seconds) of every program compiled or loaded
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((time.perf_counter(), secs))
        if event == COMPILE_EVENT else None)
    if not rehearsal and (jax.default_backend() != "tpu"
                          or len(jax.devices()) < cell["chips"]):
        print(f"run.py: refusing to run: backend {jax.default_backend()!r}, "
              f"{len(jax.devices())} device(s); the cell asks for "
              f"{cell['chips']} TPU chip(s)", file=sys.stderr)
        raise SystemExit(2)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    peaks = load_json(HERE, "peaks.json").get(dev.device_kind)
    if peaks is None and not rehearsal:
        raise SystemExit(f"run.py: no peaks for device kind "
                         f"{dev.device_kind!r} in peaks.json")
    say(f"device: {json.dumps(device)}  compile cache: {cache_dir}")
    return jax, compiles, device, peaks


def set_up(config: dict, data, corpus: dict, rehearsal: bool):
    """DataStoreFinder → load → web.serve, as `geomesa-tpu serve` would."""
    from geomesa_tpu import web
    from geomesa_tpu.datastore import DataStoreFinder
    ds = DataStoreFinder.get_data_store(type="tpu")
    t = time.perf_counter()
    data.load(ds, corpus, config["type_name"], config["schema"])
    say(f"phase load: {time.perf_counter() - t:.3f} s")
    got = sorted(i.name for i in ds.planners[config["type_name"]].indexes)
    if got != sorted(config["index"]):
        raise SystemExit(f"run.py: indexes {got}, configuration says "
                         f"{config['index']}")
    plane_bytes = device_checks(ds, config["type_name"], rehearsal)
    httpd = web.serve(ds, host="127.0.0.1", port=0, background=True)
    return ds, httpd, plane_bytes


def trace_slice(jax, port: int, seconds: float):
    """Profile TRACE_SLICE_S of the window, a second in; returns the trace
    directory, the slice as the host marks it and the batched dispatches the
    recorder held as it closed. The marks are Unix nanoseconds, the clock the
    profiler session dates its collection by: they lie inside the collection,
    and the trace places them among the device's operations."""
    tdir = tempfile.mkdtemp(prefix="geomesa_bench_trace_")
    time.sleep(min(1.0, max(0.0, seconds - TRACE_SLICE_S)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    on = time.time_ns()
    # the device's tracer records a program from its launch on: the one in
    # flight as the tracer comes on is not in the trace (a join's 60-80 ms
    # read as idle time; PERF.md, PR 34), so the slice opens when that one
    # has ended
    time.sleep(TRACER_LEAD_S)
    lo = time.time_ns()
    time.sleep(min(TRACE_SLICE_S, seconds))
    hi = time.time_ns()
    # the recorder's ring holds some seconds of events and stop_trace may
    # take ten times as long: fetch the slice's dispatches while it runs
    fetched = []
    fetch = threading.Thread(target=lambda: fetched.extend(http_json(
        port, "/events?kind=batch&limit=100000")["events"]))
    fetch.start()
    jax.profiler.stop_trace()
    fetch.join()
    say(f"phase stop_trace: {(time.time_ns() - hi) / 1e9:.3f} s; the tracer "
        f"was on from Unix ns {on}")
    return tdir, (lo, hi), fetched


def reduce_trace(tdir: str, marks: tuple, keep: str):
    """The slice reduced on the trace's own clock (trace_reduce), or None and
    one line where the profiler wrote no trace, or one that does not place
    the marks: a share of a window that is only the host's would put busy
    time and window on two clocks. ``keep`` names a directory the
    ``.xplane.pb`` is copied to."""
    sys.path.insert(0, HERE)
    import trace_reduce
    xplanes = [os.path.join(d, f) for d, _, fs in os.walk(tdir)
               for f in fs if f.endswith(".xplane.pb")]
    trace = None
    try:
        if not xplanes:
            raise ValueError("the profiler wrote no .xplane.pb")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(xplanes[0], keep)
        trace = trace_reduce.reduce_file(xplanes[0], marks=marks)
    except ValueError as e:
        print(f"run.py: {e}", file=sys.stderr)
    shutil.rmtree(tdir, ignore_errors=True)
    if trace:
        start, stop = trace["span_unix_s"]
        say(f"trace: {json.dumps(trace['planes'])} window "
            f"{trace['window_s']:.6f} s, Unix ns {marks[0]}..{marks[1]}; the "
            f"profiler's collection opened {1e3 * (marks[0] / 1e9 - start):.3f}"
            f" ms before it and closed {1e3 * (stop - marks[1] / 1e9):.3f} ms "
            f"after it")
    return trace


def judge(data, op, traffic: dict, corpus: dict, ok: list, seed: int,
          control: str) -> tuple:
    """Answers of the timed window against the plain reference: a sample drawn
    from the seed, with the slowest request in it. ``control`` names one of
    the data module's controls: it is put in the program's place, so that its
    answers to the same requests are what the comparison judges."""
    import numpy as np
    rng = np.random.default_rng([seed, 0xC0])
    k = min(len(ok), traffic["check_sample"])
    picks = set(rng.choice(len(ok), size=k, replace=False).tolist())
    picks.add(max(range(len(ok)), key=lambda i: ok[i][1] - ok[i][0]))
    picks = sorted(picks)
    t = time.perf_counter()
    ref = data.Reference(corpus)
    want = [op.expected(ref, traffic["params"], ok[i][4]) for i in picks]
    got = [ok[i][3] for i in picks]
    if control:
        own = sum(g != w for g, w in zip(got, want))
        say(f"the program's own answers: {own} wrong of {len(picks)}; "
            f"judged below: control {control}")
        low = data.controls(corpus)[control]
        got = [op.expected(low, traffic["params"], ok[i][4]) for i in picks]
    wrong = [(ok[i][4], g, w) for i, g, w in zip(picks, got, want) if g != w]
    say(f"phase reference: {time.perf_counter() - t:.3f} s for "
        f"{len(picks)} answers; first mismatches {wrong[:3]}")
    return len(wrong), len(picks)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default="",
                   help="put this control of the data module in the "
                        "program's place when the answers are compared; "
                        "`correct` then has to read false")
    p.add_argument("--keep-trace", default="",
                   help="copy the traced run's .xplane.pb into this "
                        "directory, to reduce it again by hand")
    p.add_argument("--rehearse-rows", type=int, default=0,
                   help="run at this many rows on any backend; prints no "
                        "metric under its name")
    args = p.parse_args(argv)
    rehearsal = args.rehearse_rows > 0

    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = find_cell(bench, args.workload)
    jax, compiles, device, peaks = start_jax(cell, rehearsal)
    rows = args.rehearse_rows or config["rows"]
    say(f"cell: {cell['name']}  rows: {rows}  seed: {args.seed}  seconds: "
        f"{args.seconds}  trace: {args.trace}  rehearsal: {rehearsal}  "
        f"control: {args.control or None}")
    say(f"traffic: {json.dumps(traffic)}")

    data = load_module("data", config["data"])
    op = load_module("ops", traffic["operation"])
    loop = load_module("loops", traffic["loop"])
    t = time.perf_counter()
    corpus = data.make_corpus(rows, args.seed)
    say(f"phase corpus: {time.perf_counter() - t:.3f} s")
    ds, httpd, plane_bytes = set_up(config, data, corpus, rehearsal)
    port = httpd.server_address[1]
    running = loop.start(
        traffic, port, op, lambda i: op.requests(
            traffic["params"], config, corpus, args.seed, i,
            traffic["clients"]), compiles, say)

    # the window: a slice of the load that warm-up left running
    gc.callbacks.append(_gc_watch)
    before = snapshot(port)
    t0 = time.perf_counter()
    setup_s = t0 - T_PROCESS
    t1 = t0 + args.seconds
    if args.trace:
        tdir, marks, batch_events = trace_slice(jax, port, args.seconds)
        unix_less_perf = time.time() - time.perf_counter()
    time.sleep(max(0.0, t1 - time.perf_counter()))
    after = snapshot(port)
    records, still_out = running.stop()
    t_closed = time.perf_counter()
    closed = snapshot(port)
    mem = max((d.memory_stats() or {} for d in jax.local_devices()),
              key=lambda m: m.get("peak_bytes_in_use", 0))
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    if not rehearsal and mem.get("bytes_in_use", 0) < plane_bytes:
        raise SystemExit(f"run.py: bytes_in_use {mem.get('bytes_in_use')} < "
                         f"plane bytes {plane_bytes}: the table is not on "
                         f"the device")
    httpd.shutdown()
    httpd.server_close()
    ds.close()
    del ds

    # Latency is of the requests sent in [t0, t1), however late the answer;
    # the rate is of the answers that came in [t0, t1), whenever they were
    # sent. Both kinds are attempted, compared and, where bad, failed.
    good = lambda r: r[2] == 200 and r[3] is not None
    sent_in = [r for r in records if t0 <= r[0] < t1]
    done_in = [r for r in records if t0 <= r[1] < t1]
    window = sent_in + [r for r in done_in if r[0] < t0]
    ok = [r for r in window if good(r)]
    failed = len(window) - len(ok) + still_out
    lat = sorted(1000.0 * (r[1] - r[0]) for r in sent_in if good(r))
    answered_in = sum(1 for r in done_in if good(r))
    statuses = collections.Counter(r[2] for r in window)
    in_compile = [round(secs, 3) for at, secs in compiles
                  if t0 <= at <= t_closed]
    counters0 = before["/metrics"]["counters"]
    counters1 = closed["/metrics"]["counters"]
    degraded = counters1.get("scheduler.degraded", 0) \
        - counters0.get("scheduler.degraded", 0)
    breaker = closed["/healthz"]["overload"].get("breaker", {}).get("state")
    fq = {k: v - before["/healthz"]["fused_query"].get(k, 0)
          for k, v in closed["/healthz"]["fused_query"].items()}
    sched0, sched1 = before["/scheduler"], after["/scheduler"]
    from geomesa_tpu import native
    say(f"window: attempted {len(window)} (sent in it {len(sent_in)}, "
        f"answered in it {len(done_in)}) good {len(ok)} failed {failed} "
        f"still-out {still_out} http statuses {json.dumps(statuses)}")
    say("latency ms p50/p75/p90/p95/p99/max: " + "/".join(
        f"{percentile(lat, q):.1f}" for q in (.5, .75, .9, .95, .99, 1.0))
        if lat else "no latency")
    pauses = [(at - t0, gen, secs) for at, gen, secs in GC_PAUSES
              if t0 <= at <= t1]
    slowest = max(sent_in, key=lambda r: r[1] - r[0], default=None)
    say(f"collections inside the window: {len(pauses)}, of generation 2: "
        f"{sum(1 for p in pauses if p[1] == 2)}, longest "
        f"{max((p[2] for p in pauses), default=0.0):.3f} s ending at "
        f"+{max(pauses, key=lambda p: p[2], default=(0.0,))[0]:.1f} s; "
        f"slowest request sent at +{slowest[0] - t0:.1f} s, requests over "
        f"1 s: {sum(1 for v in lat if v > 1000.0)}" if slowest else "")
    first_calls = {k[len("kernel."):-len(".compiles")]: v - counters0.get(k, 0)
                   for k, v in counters1.items()
                   if k.endswith(".compiles") and v != counters0.get(k, 0)}
    say(f"compilations inside the window: {len(in_compile)}  seconds each: "
        f"{in_compile}  first calls by kernel.tier: {json.dumps(first_calls)}")
    say(f"scheduler: flush_reasons {json.dumps(sched1['flush_reasons'])} "
        f"fused +{sched1['fused'] - sched0['fused']} singles "
        f"+{sched1['singles'] - sched0['singles']} batches "
        f"+{sched1['batches'] - sched0['batches']} result_cache hits "
        f"+{sched1['result_cache']['hits'] - sched0['result_cache']['hits']}")
    say(f"fused program deltas: {json.dumps(fq)}  scheduler.degraded: "
        f"+{degraded}  breaker: {breaker}  native encoder loaded: "
        f"{native.available()}")
    say(f"device memory: {json.dumps(mem)}  plane bytes: {plane_bytes}")
    if degraded or breaker != "closed":
        failed += 1   # never hidden: a degraded answer or an open breaker
    if not lat:
        print("run.py: no request was answered", file=sys.stderr)
        return 1

    metrics, trace = {}, None
    if args.trace:
        trace = reduce_trace(tdir, marks, args.keep_trace)
        if trace is None:
            return 1
        # one slice for every reader, the one the trace placed: in Unix time
        # for ts_ms, on the window's clock for the requests
        unix_lo, unix_hi = marks[0] / 1e9, marks[1] / 1e9
        slice_lo, slice_hi = unix_lo - unix_less_perf, unix_hi - unix_less_perf
        batch_events = [e for e in batch_events
                        if 1e3 * unix_lo <= e["ts_ms"] <= 1e3 * unix_hi]
        if trace["busy_s"] > 0:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        else:
            print("run.py: the trace shows no operation on the device inside "
                  "the slice", file=sys.stderr)
            if not rehearsal:
                return 1
            trace = None
        # all that was read, for whatever reader a later PR adds: times are
        # seconds from the window's opening
        ctx = {"cell": cell, "config": config, "traffic": traffic,
               "peaks": peaks, "device": device, "seconds": args.seconds,
               "before": before, "after": after, "closed": closed,
               "memory": mem, "memory_peak_bytes": memory_peak,
               "trace": trace, "slice": (slice_lo - t0, slice_hi - t0),
               "batch_events": batch_events,
               "requests": [(r[0] - t0, r[1] - t0, good(r)) for r in window]}
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, "qps": answered_in / args.seconds,
                  "p50_ms": percentile(lat, 0.50),
                  "p95_ms": percentile(lat, 0.95)}
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = memory_peak

    wrong, checked = judge(data, op, traffic, corpus, ok, args.seed,
                           args.control)
    compared = {"wrong_answers": {"value": wrong, "limit": 0},
                "failed_or_flagged": {"value": failed, "limit": 0}}
    line = {"correct": wrong == 0 and failed == 0, "attempted": len(window),
            "failed": failed, "metrics": {} if rehearsal else metrics,
            "device": device}
    if rehearsal:
        line["rehearsal"] = metrics
    if args.control:
        line["control"] = args.control
    if trace and not 0 < device["busy_s"] <= device["window_s"]:
        print(f"run.py: device.busy_s {device['busy_s']!r} is not above 0 "
              f"and at most device.window_s {device['window_s']!r}",
              file=sys.stderr)
        return 1
    if trace:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["checked"] = checked
    line["compared"] = compared
    print(f"compared: {json.dumps(compared)} of {checked} answers checked",
          file=sys.stderr, flush=True)
    say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
