"""System-property registry: typed, documented runtime knobs.

≙ the reference's three-tier config system (SURVEY.md §5): this is tier 1,
``GeoMesaSystemProperties`` (/root/reference/geomesa-utils/src/main/scala/org/
locationtech/geomesa/utils/conf/GeoMesaSystemProperties.scala:19) — a central
registry of typed properties with environment-variable override and a
programmatic ``set``/``unset`` for tests. Tier 2 (per-datastore params) lives
on TpuDataStore(params); tier 3 (per-type config) rides in SFT user-data
strings (``geomesa.indices``, ``geomesa.z3.interval`` …).

Every property reads its env var on EACH access (late-bound, so tests and
operators can flip knobs at runtime), falling back to a programmatic override
then the default. ``describe()`` lists everything for the CLI/docs.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional


@dataclass
class SystemProperty:
    """One typed knob: ``prop.get()`` → env override → set() value → default."""

    name: str                       # env var name
    default: object
    parse: Callable[[str], object]
    doc: str
    _override: object = field(default=None, repr=False)

    def get(self):
        raw = os.environ.get(self.name)
        if raw is not None:
            try:
                return self.parse(raw)
            except (TypeError, ValueError):
                pass  # malformed env values fall back (reference behavior)
        if self._override is not None:
            return self._override
        return self.default

    def set(self, value) -> None:
        self._override = value

    def unset(self) -> None:
        self._override = None


_REGISTRY: Dict[str, SystemProperty] = {}


def _register(name: str, default, parse, doc: str) -> SystemProperty:
    prop = SystemProperty(name, default, parse, doc)
    _REGISTRY[name] = prop
    return prop


def _parse_bool(s: str) -> bool:
    return s.strip().lower() not in ("0", "false", "no", "off", "")


# -- the knobs ---------------------------------------------------------------

SCAN_RANGES_TARGET = _register(
    "GEOMESA_TPU_SCAN_RANGES_TARGET", 2000, int,
    "Target key ranges per query cover (geomesa.scan.ranges.target, "
    "QueryProperties.scala:22).")

PRUNE_BLOCK = _register(
    "GEOMESA_TPU_PRUNE_BLOCK", 4096, int,
    "Rows per gather block for range-pruned scans.")

PRUNE_MAX_FRACTION = _register(
    "GEOMESA_TPU_PRUNE_MAX_FRAC", 0.25, float,
    "Above this candidate fraction a full-table fused scan beats block "
    "gathering (full-table-scan avoidance threshold).")

PRUNE_ENABLED = _register(
    "GEOMESA_TPU_PRUNE", True, _parse_bool,
    "Master switch for range-pruned scan execution.")

DEVICE_SORT_MIN = _register(
    "GEOMESA_TPU_DEVICE_SORT_MIN", 2_000_000, int,
    "Row count above which index sorts run on the accelerator.")

BUILD_STREAM_CHUNK = _register(
    "GEOMESA_TPU_BUILD_STREAM_CHUNK", 16_777_216, int,
    "Rows per chunk for the streamed native build: the C++ encoder works "
    "on chunk i+1 while chunk i uploads in a background thread (encode and "
    "host->device transfer overlap instead of summing).")

LSM_MAX_FRACTION = _register(
    "GEOMESA_TPU_LSM_MAX_FRAC", 0.02, float,
    "Delta-run flush threshold as a fraction of the main table.")

NO_NATIVE = _register(
    "GEOMESA_TPU_NO_NATIVE", False, _parse_bool,
    "Disable the native C++ encode path (numpy fallback). NB boolean "
    "semantics: '0'/'false'/'no'/'off' mean NOT disabled (earlier releases "
    "treated any non-empty value as disabling).")

JOIN_DEVICE_MIN_PAIRS = _register(
    "GEOMESA_TPU_JOIN_DEVICE_MIN_PAIRS", 32_768, int,
    "Candidate-pair count above which the extent join's exact refine runs "
    "on the device band kernel (below it, host f64 soups win — each device "
    "dispatch pays a host<->device round trip).")

DENSITY_PACK = _register(
    "GEOMESA_TPU_DENSITY_PACK", "auto", str,
    "Density grid readback encoding: auto (cheapest faithful of sparse/u8/"
    "fp16 by wire size), sparse, u8 (unweighted only), fp16, or none (raw "
    "f32 grid). Unknown values fall back to auto. ≙ the reference's sparse "
    "kryo density grids (DensityScan.scala:95).")

SCHED_ENABLED = _register(
    "GEOMESA_TPU_SCHEDULER", True, _parse_bool,
    "Master switch for the micro-batching query scheduler on the serving "
    "path (web /count coalescing). Off: every request plans and dispatches "
    "individually.")

SCHED_FLUSH_SIZE = _register(
    "GEOMESA_TPU_SCHED_FLUSH_SIZE", 64, int,
    "Max queries fused into one batched device dispatch (flush-at-B). "
    "Matches the batched scan kernel's sweet spot (BENCH cfg1 batch64).")

SCHED_WINDOW_US = _register(
    "GEOMESA_TPU_SCHED_WINDOW_US", 1500, int,
    "Max micro-batch collection window in microseconds (flush-at-T). The "
    "scheduler adapts the live window between SCHED_MIN_WINDOW_US and this "
    "cap from observed batch sizes; lone queries never wait the full cap.")

SCHED_MIN_WINDOW_US = _register(
    "GEOMESA_TPU_SCHED_MIN_WINDOW_US", 100, int,
    "Floor of the adaptive collection window (latency bound at low traffic).")

SCHED_PLAN_CACHE = _register(
    "GEOMESA_TPU_SCHED_PLAN_CACHE", 512, int,
    "Plan-cache capacity (normalized filter + generation + auths -> plan). "
    "0 disables plan caching.")

WAL_FSYNC = _register(
    "GEOMESA_TPU_WAL_FSYNC", "batch", str,
    "Write-ahead-log fsync policy: off (OS page cache only — survives "
    "process death, not power loss), batch (group commit: one fsync per "
    "commit window, bounded data-at-risk; default), always (every append "
    "durable before it returns; concurrent appenders share one fsync).")

WAL_SEGMENT_BYTES = _register(
    "GEOMESA_TPU_WAL_SEGMENT_BYTES", 64 * 1024 * 1024, int,
    "WAL segment size before rotation; old segments garbage-collect once a "
    "snapshot covers them.")

WAL_INTERVAL_MS = _register(
    "GEOMESA_TPU_WAL_INTERVAL_MS", 20.0, float,
    "Group-commit window for WAL fsync policy 'batch': the background "
    "syncer fsyncs at most once per window (the max unsynced-data age).")

SNAPSHOT_ROWS = _register(
    "GEOMESA_TPU_SNAPSHOT_ROWS", 500_000, int,
    "Rows logged since the last snapshot that trigger a new incremental "
    "snapshot (which rotates the WAL and GCs covered segments).")

SNAPSHOT_WAL_BYTES = _register(
    "GEOMESA_TPU_SNAPSHOT_WAL_BYTES", 256 * 1024 * 1024, int,
    "WAL payload bytes since the last snapshot that trigger a new one "
    "(bounds replay time after a crash).")

SNAPSHOT_KEEP = _register(
    "GEOMESA_TPU_SNAPSHOT_KEEP", 2, int,
    "Installed snapshots retained; older ones are pruned after each "
    "successful install (keep >= 2 tolerates one corrupt newest snapshot).")

KERNEL_CACHE = _register(
    "GEOMESA_TPU_KERNEL_CACHE", 128, int,
    "Max compiled scan kernels retained per index (LRU). Long-lived servers "
    "with many residual structures stay bounded; evicted signatures "
    "recompile on next use.")

# -- query-lifecycle resilience (serve/resilience/) ---------------------------

DEADLINE_DEFAULT_MS = _register(
    "GEOMESA_TPU_DEADLINE_DEFAULT_MS", 0.0, float,
    "Default per-request deadline the web layer attaches when the client "
    "sends none (X-Deadline-Ms header / ?deadline_ms=). 0 disables the "
    "implicit deadline; production serving should set ~30000.")

DEADLINE_MAX_MS = _register(
    "GEOMESA_TPU_DEADLINE_MAX_MS", 300_000.0, float,
    "Hard cap on client-requested deadlines (a client cannot hold serving "
    "resources longer than this).")

DEADLINE_DEGRADE_MS = _register(
    "GEOMESA_TPU_DEADLINE_DEGRADE_MS", 25.0, float,
    "Graceful degradation floor: when a deadlined count reaches dispatch "
    "with less than this many ms remaining, an eligible query returns the "
    "stats-estimator approximation (flagged) instead of risking a device "
    "round trip it cannot afford. 0 disables degradation (expired queries "
    "then fail with deadline-exceeded only).")

ADMIT_ENABLED = _register(
    "GEOMESA_TPU_ADMIT", True, _parse_bool,
    "Master switch for serving-path admission control (bounded in-flight "
    "work per priority class; excess sheds with 429 + Retry-After).")

ADMIT_INTERACTIVE = _register(
    "GEOMESA_TPU_ADMIT_INTERACTIVE", 512, int,
    "Max in-flight (queued + executing) interactive-class queries before "
    "new ones shed. Sized so a full queue drains within a typical "
    "interactive deadline at the measured batch throughput.")

ADMIT_BATCH = _register(
    "GEOMESA_TPU_ADMIT_BATCH", 128, int,
    "Max in-flight analytics/batch-class queries (the lower bound keeps "
    "background scans from starving interactive traffic; the scheduler "
    "queue additionally serves interactive requests first).")

ADMIT_RETRY_AFTER_S = _register(
    "GEOMESA_TPU_ADMIT_RETRY_AFTER_S", 1.0, float,
    "Retry-After seconds returned with shed (429) responses.")

BREAKER_THRESHOLD = _register(
    "GEOMESA_TPU_BREAKER_THRESHOLD", 5, int,
    "Consecutive device-dispatch failures that open the circuit breaker "
    "(while open, eligible counts degrade to the stats estimator and "
    "other queries fail fast with 503 instead of queueing onto a sick "
    "device path).")

BREAKER_COOLDOWN_MS = _register(
    "GEOMESA_TPU_BREAKER_COOLDOWN_MS", 1000.0, float,
    "How long an open breaker waits before letting half-open probe "
    "traffic through.")

BREAKER_PROBES = _register(
    "GEOMESA_TPU_BREAKER_PROBES", 2, int,
    "Consecutive half-open probe successes required to close the breaker "
    "(any probe failure re-opens and restarts the cooldown).")

BREAKER_DEGRADE = _register(
    "GEOMESA_TPU_BREAKER_DEGRADE", True, _parse_bool,
    "When the breaker is open, serve eligible counts from the stats "
    "estimator (flagged approximate) instead of failing fast.")

RETRY_ATTEMPTS = _register(
    "GEOMESA_TPU_RETRY_ATTEMPTS", 3, int,
    "Max attempts for the device-dispatch retry wrapper (capped "
    "exponential backoff with full jitter between attempts).")

RETRY_BASE_MS = _register(
    "GEOMESA_TPU_RETRY_BASE_MS", 5.0, float,
    "Backoff base: attempt i sleeps uniform(0, min(cap, base * 2^i)) ms.")

RETRY_CAP_MS = _register(
    "GEOMESA_TPU_RETRY_CAP_MS", 100.0, float,
    "Backoff ceiling per retry sleep.")

RETRY_WAL_FSYNC = _register(
    "GEOMESA_TPU_RETRY_WAL_FSYNC", 1, int,
    "Attempts for a failing WAL group-commit fsync before the error "
    "propagates (transient EIO/disk-pressure absorption). 1 = no retry, "
    "the strict policy the durability tests pin.")

# -- replicated serving fleet (replication/ + serve/router.py) ----------------

REPL_HEARTBEAT_MS = _register(
    "GEOMESA_TPU_REPL_HEARTBEAT_MS", 100.0, float,
    "Primary -> follower heartbeat interval: the shipper sends its last "
    "WAL seq at least this often even when no new frames exist, so a "
    "follower can measure replication lag during write silence.")

REPL_STALENESS_MS = _register(
    "GEOMESA_TPU_REPL_STALENESS_MS", 1000.0, float,
    "Bounded-staleness budget: a replica whose replication lag exceeds "
    "this many ms is DEMOTED by the router (served only when nothing "
    "healthier is up) and spends the replication-staleness SLO's error "
    "budget.")

REPL_ACK_EVERY = _register(
    "GEOMESA_TPU_REPL_ACK_EVERY", 32, int,
    "Follower acks at least every N applied frames (plus on every "
    "heartbeat and on idle); the primary resumes a reconnecting follower "
    "from its last acked seq.")

REPL_RECONNECT_MS = _register(
    "GEOMESA_TPU_REPL_RECONNECT_MS", 200.0, float,
    "Follower reconnect backoff after a dropped/rejected replication "
    "connection (a CRC-rejected shipped frame resyncs after this pause).")

REPL_SLO_TARGET = _register(
    "GEOMESA_TPU_REPL_SLO_TARGET", 0.999, float,
    "Target fraction of staleness checks inside the bounded-staleness "
    "budget for the replication SLO a follower registers (burn-rate "
    "alerting via obs/slo.py rides the standard windows).")

REPL_PROBE_TTL_MS = _register(
    "GEOMESA_TPU_REPL_PROBE_TTL_MS", 250.0, float,
    "Router health-probe cache TTL: endpoint health (overload state, "
    "breaker, replication lag) refreshes at most this often on the "
    "request path.")

REPL_FAILOVER_BUDGET_MS = _register(
    "GEOMESA_TPU_REPL_FAILOVER_BUDGET_MS", 5000.0, float,
    "Deadline budget for a router-driven failover (drain + promote-by-"
    "highest-acked-seq); the fleet drills assert promotion completes "
    "inside it.")

# -- request-centric observability (obs/) -------------------------------------

OBS_ENABLED = _register(
    "GEOMESA_TPU_OBS", True, _parse_bool,
    "Master switch for the request-centric observability layer (flight "
    "recorder wide events, tail-based trace sampling, per-kernel device "
    "attribution). Off: trace close pays nothing beyond the base ring.")

OBS_RING = _register(
    "GEOMESA_TPU_OBS_RING", 2048, int,
    "Flight-recorder ring capacity (wide events retained in memory for "
    "GET /events and `debug events`).")

OBS_TRACE_RING = _register(
    "GEOMESA_TPU_OBS_TRACE_RING", 256, int,
    "Tail-sampled trace ring capacity: retained traces (errors, deadline/"
    "shed/degrade outcomes, slow outliers, probabilistic sample) that "
    "/metrics exemplars link to.")

OBS_SAMPLE = _register(
    "GEOMESA_TPU_OBS_SAMPLE", 0.02, float,
    "Probabilistic retention rate for ordinary traces (errors and slow "
    "outliers are ALWAYS retained — tail-based sampling keeps the "
    "interesting tail at full fidelity and this fraction of the rest).")

OBS_SLOW_MS = _register(
    "GEOMESA_TPU_OBS_SLOW_MS", 0.0, float,
    "Slow-trace retention threshold in ms. 0 = adaptive: retain anything "
    "over the rolling p99 of recent root-trace durations.")

OBS_JSONL = _register(
    "GEOMESA_TPU_OBS_JSONL", "", str,
    "Path for the flight recorder's JSONL sink (one wide event per line, "
    "size-rotated). Empty = in-memory ring only.")

OBS_JSONL_MAX_BYTES = _register(
    "GEOMESA_TPU_OBS_JSONL_MAX_BYTES", 64 * 1024 * 1024, int,
    "Rotation threshold for the flight-recorder JSONL sink (keep-one-"
    "previous, shared durability/rotation.py policy).")

# -- workload intelligence plane (obs/workload.py + obs/sketches.py) ---------

WORKLOAD_ENABLED = _register(
    "GEOMESA_TPU_WORKLOAD", True, _parse_bool,
    "Master switch for the workload-analytics plane (windowed rollups, "
    "heavy-hitter sketches, hot-set feed, per-tenant metering). The hot "
    "path pays one bounded deque append per event; aggregation is "
    "deferred to read time.")

WORKLOAD_WINDOWS = _register(
    "GEOMESA_TPU_WORKLOAD_WINDOWS", 6, int,
    "Windows retained per rollup tier (10s/1m/10m rings): the newest N "
    "wall-clock-aligned windows; older windows rotate out with their "
    "event counts folded into retired_events.")

WORKLOAD_SKETCH_K = _register(
    "GEOMESA_TPU_WORKLOAD_SKETCH_K", 64, int,
    "SpaceSaving sketch capacity (counters tracked) for the plan-hash, "
    "tenant and hot-cell heavy-hitter summaries. Any key with frequency "
    "above total/capacity is guaranteed tracked.")

WORKLOAD_HOTSET_K = _register(
    "GEOMESA_TPU_WORKLOAD_HOTSET_K", 10, int,
    "Entries returned by hot_set() per dimension (top plan hashes, top "
    "cells) — the feed a result cache would key its admission on.")

WORKLOAD_CELL_BITS = _register(
    "GEOMESA_TPU_WORKLOAD_CELL_BITS", 6, int,
    "Resolution of the hot-cell grid: queries map to a coarse Morton "
    "cell on a 2^bits x 2^bits lon/lat grid (6 -> 64x64 world cells, "
    "~5.6 x 2.8 degrees at the equator).")

WORKLOAD_PENDING = _register(
    "GEOMESA_TPU_WORKLOAD_PENDING", 65536, int,
    "Bound on the workload plane's pending-event queue; events past the "
    "bound are counted dropped rather than blocking the hot path.")

SLO_LATENCY_MS = _register(
    "GEOMESA_TPU_SLO_LATENCY_MS", 250.0, float,
    "Latency objective threshold for the default serving SLO: a count "
    "is 'good' when it lands under this many ms.")

SLO_TARGET = _register(
    "GEOMESA_TPU_SLO_TARGET", 0.999, float,
    "Target good-fraction for the default latency SLO (error budget = "
    "1 - target, the quantity burn rates are measured against).")

SLO_AVAIL_TARGET = _register(
    "GEOMESA_TPU_SLO_AVAIL_TARGET", 0.999, float,
    "Target success-fraction for the default availability SLO (sheds, "
    "deadline cancellations and worker deaths spend its budget).")

# -- device profiling (obs/profiling) -----------------------------------------

PROFILING_ENABLED = _register(
    "GEOMESA_TPU_PROFILING", True, _parse_bool,
    "Master switch for device-level kernel profiling: per-kernel XLA "
    "cost_analysis (flops/bytes gauges), compile telemetry, recompile "
    "detection (kernels.recompiles + flight events), and index-build "
    "phase progress. All costs land at compile/build time — the "
    "steady-state dispatch path pays one wrapper call.")

# -- fleet-wide observability (obs/federation.py + trace propagation) ---------

NODE_ID = _register(
    "GEOMESA_TPU_NODE_ID", "", str,
    "Stable node identity for fleet observability (the `node` label on "
    "federated metrics, the node dimension on traces/flight events, the "
    "/healthz attribution). Empty = derived "
    "hostname-pid-suffix, unique per process incarnation.")

FED_PROPAGATE = _register(
    "GEOMESA_TPU_FED_PROPAGATE", True, _parse_bool,
    "Master switch for cross-process trace propagation: the router "
    "injects X-Trace-Id/X-Span-Id/X-Trace-Node/X-Trace-Sampled on "
    "proxied queries and the web layer opens the request trace as a "
    "child of the remote parent. Off: every process traces in "
    "isolation (the pre-fleet behavior).")

FED_TTL_MS = _register(
    "GEOMESA_TPU_FED_TTL_MS", 1000.0, float,
    "Metrics-federation scrape cache TTL: the federator re-scrapes each "
    "node's /healthz + /metrics?format=state at most this often; reads "
    "inside the window serve the cached merge.")

FED_TIMEOUT_S = _register(
    "GEOMESA_TPU_FED_TIMEOUT_S", 2.0, float,
    "Per-node scrape timeout for the metrics federator; a node that "
    "cannot answer inside it is reported down in /fleet rather than "
    "stalling the whole merged surface.")

REPL_TRACE_EVERY = _register(
    "GEOMESA_TPU_REPL_TRACE_EVERY", 64, int,
    "Replication-pipeline exemplar cadence: every Nth applied frame on "
    "a follower runs under a retained root trace whose id rides the ack "
    "back to the primary and lands as the exemplar on the fleet "
    "repl.e2e histogram (fleet p99 -> exemplar -> remote apply trace). "
    "0 disables the traced applies (timers still populate).")

# -- fleet doctor: anomaly detectors + incidents (obs/doctor, obs/incidents) --

DOCTOR_ENABLED = _register(
    "GEOMESA_TPU_DOCTOR", True, _parse_bool,
    "Master switch for the fleet doctor: rule-driven anomaly detectors "
    "(SLO burn, replication lag, recompile churn, shed storm, breaker "
    "flapping, WAL fsync stall, hot-set skew) evaluated on read/tick — "
    "the query hot path never pays for it.")

DOCTOR_WINDOW_S = _register(
    "GEOMESA_TPU_DOCTOR_WINDOW_S", 60.0, float,
    "Observation window for the doctor's rate detectors (recompile "
    "churn, shed storm, breaker flapping): counter deltas older than "
    "this are forgotten, so a burst must sustain inside the window to "
    "keep an incident active.")

DOCTOR_LAG_MS = _register(
    "GEOMESA_TPU_DOCTOR_LAG_MS", 1000.0, float,
    "Replication-lag detector threshold on the decay-based "
    "replication.lag_ms gauge; a follower above it opens a "
    "replication_lag incident.")

DOCTOR_LAG_SEQS = _register(
    "GEOMESA_TPU_DOCTOR_LAG_SEQS", 64, int,
    "Replication-lag detector threshold on sequence backlog "
    "(replication.lag_seqs): a follower this many WAL frames behind "
    "fires even when the time-based gauge has decayed.")

DOCTOR_RECOMPILES_PER_MIN = _register(
    "GEOMESA_TPU_DOCTOR_RECOMPILES_PER_MIN", 6.0, float,
    "Recompile-churn detector threshold: kernels.recompiles advancing "
    "faster than this (rate normalized to per-minute over the doctor "
    "window) opens an incident naming the most-recompiled kernel.")

DOCTOR_SHED_PER_MIN = _register(
    "GEOMESA_TPU_DOCTOR_SHED_PER_MIN", 30.0, float,
    "Shed-storm detector threshold: admission.shed advancing faster "
    "than this per minute over the doctor window opens an incident "
    "naming the dominant shed priority class.")

DOCTOR_BREAKER_FLAPS = _register(
    "GEOMESA_TPU_DOCTOR_BREAKER_FLAPS", 3, int,
    "Breaker-flapping detector threshold: this many open/close "
    "transition edges on one breaker inside the doctor window opens a "
    "breaker_flapping incident.")

DOCTOR_FSYNC_ERRORS = _register(
    "GEOMESA_TPU_DOCTOR_FSYNC_ERRORS", 1, int,
    "WAL fsync-stall detector threshold: this many new wal.fsync_errors "
    "(or fsync retries) inside the doctor window opens an incident — "
    "durability faults page immediately by default.")

DOCTOR_SKEW_FRACTION = _register(
    "GEOMESA_TPU_DOCTOR_SKEW_FRACTION", 0.6, float,
    "Hot-set skew detector threshold: a single plan/cell/tenant whose "
    "guaranteed (at_least) share of the workload window exceeds this "
    "fraction opens a hot_skew incident naming it.")

DOCTOR_SKEW_MIN = _register(
    "GEOMESA_TPU_DOCTOR_SKEW_MIN", 200, int,
    "Minimum events in the workload window before the skew detector "
    "may fire (tiny samples always look skewed).")

DOCTOR_CLEAR_TICKS = _register(
    "GEOMESA_TPU_DOCTOR_CLEAR_TICKS", 2, int,
    "Consecutive clear evaluations required before an active incident "
    "closes with a resolution record (debounces detectors oscillating "
    "around their threshold).")

DOCTOR_JOURNAL = _register(
    "GEOMESA_TPU_DOCTOR_JOURNAL", "", str,
    "Path of the incident journal: every incident open/close appends a "
    "JSONL record with its correlated timeline. Empty disables the "
    "journal (incidents stay queryable in memory).")

DOCTOR_JOURNAL_MAX_BYTES = _register(
    "GEOMESA_TPU_DOCTOR_JOURNAL_MAX_BYTES", 16 * 1024 * 1024, int,
    "Size cap for the incident journal before rotation (keeps one "
    "rotated predecessor, .1, via the durability rotation helper).")

DOCTOR_TIMELINE_EVENTS = _register(
    "GEOMESA_TPU_DOCTOR_TIMELINE_EVENTS", 8, int,
    "Correlated flight events snapshotted into each incident timeline "
    "(matched with the flight recorder's shared predicate, newest "
    "first).")

DOCTOR_REINDEX_PER_MIN = _register(
    "GEOMESA_TPU_DOCTOR_REINDEX_PER_MIN", 3.0, float,
    "reindex_churn bar: background-build aborts + failed installs per "
    "minute over the doctor window before an incident opens (a build "
    "that keeps losing its race with ingest never converges). "
    "0 disables the detector.")

DOCTOR_MERGE_BREACHES_PER_MIN = _register(
    "GEOMESA_TPU_DOCTOR_MERGE_BREACHES_PER_MIN", 6.0, float,
    "merge_fraction_breach bar: incremental merge-builds falling back "
    "to the full rebuild (delta over GEOMESA_TPU_MERGE_MAX_FRACTION) "
    "per minute before the reindex_churn rule flags the ingest shape. "
    "0 disables the cause.")

# -- self-optimizing serving: result cache / affinity / QoS (ISSUE 12) --------

RESULT_CACHE_ENABLED = _register(
    "GEOMESA_TPU_RESULT_CACHE", True, _parse_bool,
    "Master switch for the scheduled-count result cache: hot queries "
    "(admitted by the workload plane's hot_set at_least counts) resolve "
    "from memory without touching the device. Entries are keyed by the "
    "same (epoch, type, generation, filter, auths) tuple that salts the "
    "plan cache, so every mutation path invalidates them exactly.")

RESULT_CACHE_SIZE = _register(
    "GEOMESA_TPU_RESULT_CACHE_SIZE", 2048, int,
    "Entry bound for the result cache (LRU past it). Each entry is one "
    "int plus its key, so memory stays O(entries).")

RESULT_CACHE_MIN_AT_LEAST = _register(
    "GEOMESA_TPU_RESULT_CACHE_MIN_AT_LEAST", 3, int,
    "Admission threshold: a result is cached only when its plan hash or "
    "query cell appears in hot_set() with a guaranteed (at_least) count "
    ">= this, so cold one-off queries never pollute the cache. 0 admits "
    "everything (useful in tests).")

RESULT_CACHE_HOTSET_TTL_S = _register(
    "GEOMESA_TPU_RESULT_CACHE_HOTSET_TTL_S", 1.0, float,
    "How long the cache's view of hot_set() admission keys may be "
    "reused before re-reading the workload plane (bounds the per-miss "
    "admission cost to a dict lookup).")

QOS_ENABLED = _register(
    "GEOMESA_TPU_QOS", True, _parse_bool,
    "Master switch for weighted-fair tenant QoS inside admission "
    "control: each tenant's in-flight share of a priority class is "
    "bounded, so a noisy tenant saturates its own share and sheds 429 "
    "while other tenants' latency holds.")

QOS_TENANT_SHARE = _register(
    "GEOMESA_TPU_QOS_TENANT_SHARE", 0.5, float,
    "Maximum fraction of a priority class's in-flight limit one tenant "
    "may hold while other tenants are active (a lone tenant may use "
    "the full class limit — work-conserving, not a hard quota).")

QOS_TENANT_MIN = _register(
    "GEOMESA_TPU_QOS_TENANT_MIN", 2, int,
    "Floor on the per-tenant in-flight share: fairness never starves a "
    "tenant below this many slots regardless of the share fraction.")

QOS_ACTIVE_S = _register(
    "GEOMESA_TPU_QOS_ACTIVE_S", 2.0, float,
    "How long a tenant counts as active after its last admitted request. "
    "The per-tenant share cap engages only while >= 2 tenants are active "
    "in a class (work-conserving: a lone tenant is never throttled), so "
    "this window is how fast a quiet tenant's claim on fairness decays.")

AFFINITY_ENABLED = _register(
    "GEOMESA_TPU_AFFINITY", True, _parse_bool,
    "Master switch for cell-affinity routing: the router stamps each "
    "query's Morton cell and consistently prefers the same healthy "
    "replica for a hot cell, keeping that replica's result/plan/cover "
    "caches warm. Cold cells and freshness=strong fall back to the "
    "health/lag-aware rotation unchanged.")

AFFINITY_MIN_AT_LEAST = _register(
    "GEOMESA_TPU_AFFINITY_MIN_AT_LEAST", 3, int,
    "A query cell counts as hot for affinity routing once the workload "
    "plane guarantees (at_least) this many hits on it in the current "
    "window. 0 pins every cell (useful in tests).")

# -- incremental / mesh-parallel index builds + online reindex (ISSUE 13) -----

MERGE_BUILD = _register(
    "GEOMESA_TPU_MERGE_BUILD", True, _parse_bool,
    "Master switch for delta-incremental merge builds: an LSM delta-tier "
    "flush merges the already-sorted resident run with the freshly-sorted "
    "delta run (merge-by-key; block metadata rebuilt from the merge, not "
    "a re-sort) instead of re-sorting the full table. Destructive paths "
    "(remove/update/upsert-collision/age-off drops/schema change) always "
    "fall back to a full rebuild.")

MERGE_MAX_FRACTION = _register(
    "GEOMESA_TPU_MERGE_MAX_FRACTION", 0.25, float,
    "Largest delta-to-resident row fraction the merge build accepts; a "
    "flush above it (bulk load through the delta tier) takes the full "
    "rebuild, whose O(n log n) sort amortizes better at that scale.")

SHARD_SORT = _register(
    "GEOMESA_TPU_SHARD_SORT", True, _parse_bool,
    "Master switch for the mesh-sharded index-key sort: shards the build "
    "sort across jax.devices() (per-shard lax.sort + sample splitter "
    "exchange + per-partition merge sort), falling back to the "
    "single-device sort on a 1-device mesh. Bitwise-identical permutation "
    "either way.")

SHARD_SORT_MIN = _register(
    "GEOMESA_TPU_SHARD_SORT_MIN", 500_000, int,
    "Row threshold for the mesh-sharded sort: below it the splitter "
    "exchange + cross-device copies cost more than the single-device "
    "sort saves.")

SHARD_SORT_DEVICES = _register(
    "GEOMESA_TPU_SHARD_SORT_DEVICES", 0, int,
    "Device count for the mesh-sharded sort (0 = every local device). "
    "1 disables sharding regardless of GEOMESA_TPU_SHARD_SORT.")

SHARD_SORT_SAMPLES = _register(
    "GEOMESA_TPU_SHARD_SORT_SAMPLES", 64, int,
    "Sorted-key samples drawn per shard for the splitter exchange; more "
    "samples = better partition balance at a few KB extra download.")

REINDEX_THROTTLE_MS = _register(
    "GEOMESA_TPU_REINDEX_THROTTLE_MS", 0.0, float,
    "Sleep between background-reindex build stages, yielding the device "
    "and the GIL to serving queries. 0 builds flat out.")

REINDEX_SNAPSHOT = _register(
    "GEOMESA_TPU_REINDEX_SNAPSHOT", True, _parse_bool,
    "Write a durability snapshot right after a reindex generation "
    "installs (when the store is durable), so followers converge to the "
    "rebuilt generation through the ordinary snapshot catch-up path "
    "instead of waiting for the next threshold crossing.")

# -- fleet soak scoreboard (ISSUE 14) -----------------------------------------

SOAK_PHASE_S = _register(
    "GEOMESA_TPU_SOAK_PHASE_S", 6.0, float,
    "Wall-clock drive window for the fleet soak's steady and recovery "
    "phases (fault phases run event-driven: inject, wait for the "
    "incident, wait for resolution). The full nightly soak multiplies "
    "this; --mini keeps it.")

SOAK_WAIT_S = _register(
    "GEOMESA_TPU_SOAK_WAIT_S", 60.0, float,
    "Per-condition timeout inside the fleet soak (node healthy, "
    "incident open, incident resolved, catch-up complete). A blown "
    "wait fails that phase's checks instead of hanging the run.")

SOAK_FOLLOWERS = _register(
    "GEOMESA_TPU_SOAK_FOLLOWERS", 2, int,
    "Follower count in the soak fleet (primary + N replicas + router, "
    "each a real subprocess over localhost shipping sockets). The "
    "chaos timeline needs at least 2: one to kill, one to promote.")

SOAK_CATCHUP_BUDGET_S = _register(
    "GEOMESA_TPU_SOAK_CATCHUP_BUDGET_S", 30.0, float,
    "Budget for a restarted/re-pointed replica to fully catch up "
    "(applied seq == primary WAL seq). Scored per fault phase as "
    "catchup_s; a breach fails the phase, not the process.")

SOAK_STRETCH = _register(
    "GEOMESA_TPU_SOAK_STRETCH", 1.0, float,
    "Multiplier on the injected chaos magnitudes (lag-spike delay per "
    "frame and frame count). A stretch > 1 makes the lag-spike "
    "genuinely worse, so the scoreboard's catch-up and burn-rate axes "
    "move with it.")


# -- multi-process cluster runtime (ISSUE 15) ---------------------------------

CLUSTER = _register(
    "GEOMESA_TPU_CLUSTER", False, _parse_bool,
    "Master switch for the multi-process cluster runtime: when true (or "
    "when GEOMESA_TPU_CLUSTER_COORDINATOR is set) the process joins a "
    "jax.distributed cluster and the feature table is PARTITIONED by "
    "Morton key range across processes instead of replicated — counts/"
    "density psum to the exact global answer on every process, selects "
    "stream per-process matches through a host-side ordered merge.")

CLUSTER_COORDINATOR = _register(
    "GEOMESA_TPU_CLUSTER_COORDINATOR", "", str,
    "Coordinator address host:port for jax.distributed.initialize. "
    "Every process in the cluster passes the SAME address; the process "
    "with id 0 binds it. Setting this implies GEOMESA_TPU_CLUSTER=1.")

CLUSTER_NUM_PROCESSES = _register(
    "GEOMESA_TPU_CLUSTER_NUM_PROCESSES", 1, int,
    "Total process count in the cluster (jax.distributed num_processes). "
    "Must match across every process.")

CLUSTER_PROCESS_ID = _register(
    "GEOMESA_TPU_CLUSTER_PROCESS_ID", 0, int,
    "This process's rank in [0, num_processes) — also its Morton "
    "key-range shard ownership slot (rank order == key order).")

CLUSTER_LOCAL_DEVICES = _register(
    "GEOMESA_TPU_CLUSTER_LOCAL_DEVICES", 0, int,
    "Local device count hint passed to jax.distributed.initialize on "
    "backends that need it (CPU dryruns). 0 lets jax/XLA decide "
    "(XLA_FLAGS --xla_force_host_platform_device_count still applies).")

CLUSTER_TOPOLOGY = _register(
    "GEOMESA_TPU_CLUSTER_TOPOLOGY", "auto", str,
    "Mesh topology policy: 'auto' builds a hybrid ICI x DCN mesh "
    "(create_hybrid_device_mesh) when >1 slice is detected and a flat "
    "process-contiguous 'rows' mesh otherwise; 'flat' forces the flat "
    "mesh (CPU dryruns); 'hybrid' requires multi-slice and raises "
    "without it (fail loudly instead of silently degrading).")

CLUSTER_INIT_TIMEOUT_S = _register(
    "GEOMESA_TPU_CLUSTER_INIT_TIMEOUT_S", 120.0, float,
    "Bound on jax.distributed.initialize rendezvous (a missing peer "
    "fails the bring-up instead of hanging the fleet).")

CLUSTER_WEB_REGISTER = _register(
    "GEOMESA_TPU_CLUSTER_WEB_REGISTER", True, _parse_bool,
    "When a cluster process starts its web surface, exchange the bound "
    "address across processes and install a Federator over ALL of them "
    "on every rank — cluster nodes appear in /fleet with no manual "
    "--addr lists.")


# -- shard balance observatory (ISSUE 16) -------------------------------------

SHARDWATCH_ENABLED = _register(
    "GEOMESA_TPU_SHARDWATCH", True, _parse_bool,
    "Master switch for the per-shard load ledger (obs/shardwatch.py): "
    "joins the workload plane's hot Morton cells against cluster "
    "key-range ownership into per-shard load shares, an imbalance "
    "score, and projected split points. Off: balance surfaces report "
    "inactive and the workload fold hook is skipped.")

SHARDWATCH_TOP_CELLS = _register(
    "GEOMESA_TPU_SHARDWATCH_TOP_CELLS", 32, int,
    "How many hot cells the ledger joins per balance report (the k "
    "passed to workload hot_set). Must stay at or below "
    "GEOMESA_TPU_WORKLOAD_SKETCH_K for the at_least guarantees to "
    "cover every joined cell.")

SHARDWATCH_SPLIT_PARTS = _register(
    "GEOMESA_TPU_SHARDWATCH_SPLIT_PARTS", 2, int,
    "How many pieces a projected split divides the hottest shard into "
    "(parts - 1 boundaries). The boundaries are the candidate split "
    "points ROADMAP item 2's split/migrate plane will consume.")

SHARDWATCH_CELL_STATS = _register(
    "GEOMESA_TPU_SHARDWATCH_CELL_STATS", 256, int,
    "Capacity of the per-cell rows-scanned/device-ms accumulator table "
    "fed by the workload drain hook. Cells past the capacity count "
    "toward the ledger's drop counter instead of growing the table.")

DOCTOR_IMBALANCE_RATIO = _register(
    "GEOMESA_TPU_DOCTOR_IMBALANCE_RATIO", 1.5, float,
    "shard_imbalance bar: the doctor opens an incident when the "
    "GUARANTEED (at_least-based) max-over-mean per-shard load ratio "
    "reaches this value — undercount-proof, so sketch error can never "
    "fake an imbalance.")

DOCTOR_IMBALANCE_MIN = _register(
    "GEOMESA_TPU_DOCTOR_IMBALANCE_MIN", 200, int,
    "Total guaranteed hot-cell load floor below which shard_imbalance "
    "never fires (a handful of queries is not a skew signal).")

DOCTOR_STRAGGLER_MS = _register(
    "GEOMESA_TPU_DOCTOR_STRAGGLER_MS", 50.0, float,
    "Per-round straggler bar: a collective round whose slowest-rank "
    "spread exceeds this many milliseconds charges one straggler count "
    "against that rank (cluster.collective.straggler.rank<p>).")

DOCTOR_STRAGGLER_ROUNDS = _register(
    "GEOMESA_TPU_DOCTOR_STRAGGLER_ROUNDS", 5, int,
    "collective_straggler bar: incidents open when one rank accumulates "
    "this many over-bar straggler rounds inside the doctor window.")


# -- single-dispatch query compilation (ISSUE 17) -----------------------------

FUSED_QUERY = _register(
    "GEOMESA_TPU_FUSED_QUERY", True, _parse_bool,
    "Master switch for single-dispatch query compilation "
    "(index/compiled.py): qualifying plan shapes lower the filter IR "
    "into ONE jitted program (cover + scan + residual + aggregate, one "
    "host->device round trip) and repeat shapes bind through the recipe "
    "fast path without replanning. Off: every query runs the staged "
    "planner/scan path.")

FUSED_SHAPE_CACHE = _register(
    "GEOMESA_TPU_FUSED_SHAPE_CACHE", 256, int,
    "LRU capacity of the per-planner (filter shape, auths) -> recipe "
    "cache that lets repeat shapes skip planning entirely. Compiled "
    "program bodies are bounded separately by GEOMESA_TPU_KERNEL_CACHE.")

ROUTER_CELL_MEMO = _register(
    "GEOMESA_TPU_ROUTER_CELL_MEMO", 4096, int,
    "LRU capacity of the router's cql -> Morton-cell affinity memo. "
    "Bounds memory under high-cardinality filter streams; size is "
    "exported as the router.cell_memo.size gauge. <= 0 disables "
    "memoization.")


# -- geometry function catalog (ISSUE 18) ------------------------------------

GEOM_KERNELS = _register(
    "GEOMESA_TPU_GEOM_KERNELS", True, _parse_bool,
    "Evaluate st_* residual predicates through the vmapped device "
    "kernels (geom/catalog.py: certainty-banded classify + f64 host "
    "refine of the uncertain sliver — results stay exact). Off: every "
    "Func residual evaluates on the pure-numpy host oracle.")

GEOM_FUSE = _register(
    "GEOMESA_TPU_GEOM_FUSE", True, _parse_bool,
    "Allow eligible Func residuals (st_contains/st_intersects polygon "
    "literals, st_distance < r point literals, on the index geometry of "
    "a point sft) to lower INTO the single-dispatch fused program. Off: "
    "Func queries stage (still kernel-evaluated when GEOM_KERNELS is "
    "on).")

GEOM_CHUNK = _register(
    "GEOMESA_TPU_GEOM_CHUNK", 4_000_000, int,
    "Element budget for the catalog kernels' pairwise tables "
    "(feature-segment x literal-segment); predicate/distance batches "
    "are chunked so B*S*L stays under it.")


# -- shard cells: replicated write cells + shard-aware serving (ISSUE 19) -----

CELL_ENFORCE = _register(
    "GEOMESA_TPU_CELL_ENFORCE", True, _parse_bool,
    "When this node is registered as a member of a shard cell "
    "(cluster/cells.py), refuse ingests whose routing keys fall outside "
    "the cell's Morton key range (HTTP 409 naming the owning shard). "
    "Off: the gate logs a metric but accepts — migration escape hatch.")

CELL_SHARD_BUDGET_FRACTION = _register(
    "GEOMESA_TPU_CELL_SHARD_BUDGET_FRACTION", 0.45, float,
    "Fraction of the REMAINING request deadline carved out as one "
    "shard attempt's deadline budget in the router's scatter-gather "
    "(passed downstream as deadline_ms). < 0.5 leaves room for one "
    "follower retry against the same shard inside the request deadline.")

CELL_SHARD_MIN_BUDGET_MS = _register(
    "GEOMESA_TPU_CELL_SHARD_MIN_BUDGET_MS", 50.0, float,
    "Floor on a per-shard deadline budget: a nearly-spent request "
    "deadline still gives each shard attempt at least this much, so "
    "budget carving degrades to bounded attempts instead of zero-ms "
    "budgets that can never succeed.")

CELL_RETRY_FOLLOWERS = _register(
    "GEOMESA_TPU_CELL_RETRY_FOLLOWERS", True, _parse_bool,
    "On a shard primary failure mid-scatter, retry that shard against "
    "its remaining cell members (the demoted-not-dropped tier) before "
    "declaring the shard missing in the partial-result envelope.")

CELL_KNN_MAX_ROUNDS = _register(
    "GEOMESA_TPU_CELL_KNN_MAX_ROUNDS", 8, int,
    "Hard cap on cluster-knn radius-exchange collective rounds. The "
    "bounded-radius algorithm is exact in 2 (kth-distance psum + "
    "candidate gather); the cap is the runaway guard the dryrun check "
    "pins against.")

CELL_HANDOFF_DRAIN_S = _register(
    "GEOMESA_TPU_CELL_HANDOFF_DRAIN_S", 10.0, float,
    "Ownership handoff budget for draining the old cell owner and "
    "waiting for the successor to reach the old owner's WAL head "
    "before the epoch bump fences the old owner.")

CELL_GEO_KEY_BITS = _register(
    "GEOMESA_TPU_CELL_GEO_KEY_BITS", 8, int,
    "Per-axis bits of the coarse Z2 routing key used to assign "
    "features to shard cells on the serving write path (the dryrun's "
    "table partition uses the exact z3-derived keys instead).")


# -- telemetry history plane + forensic bundles (ISSUE 20) --------------------

HISTORY_ENABLED = _register(
    "GEOMESA_TPU_HISTORY", True, _parse_bool,
    "Master switch for the telemetry-history sampler: selected registry "
    "series (counter rates, gauges, timer p50/p99 bucket deltas) are "
    "snapshotted into wall-clock-aligned ring tiers on the registry "
    "pre-drain hook, so producers pay nothing and readers pay at most "
    "one snapshot per finest-tier interval.")

HISTORY_TIERS = _register(
    "GEOMESA_TPU_HISTORY_TIERS", "2:300,30:240", str,
    "History ring tiers as comma-separated interval_s:slots pairs. The "
    "default keeps 2s resolution for 10 minutes and 30s resolution for "
    "2 hours; memory stays knob-bounded at slots x tracked series.")

HISTORY_SERIES = _register(
    "GEOMESA_TPU_HISTORY_SERIES", "", str,
    "Extra registry series for the history sampler beyond the built-in "
    "set (comma-separated counter/gauge/timer names; prefix match with "
    "a trailing '.'). The built-ins cover scheduler traffic, sheds, "
    "recompiles, replication lag and the query.count timer.")

HISTORY_MAX_SERIES = _register(
    "GEOMESA_TPU_HISTORY_MAX_SERIES", 64, int,
    "Hard cap on distinct series the history sampler tracks per tier "
    "(memory bound; series beyond the cap are dropped and counted "
    "under history.series_dropped).")

HISTORY_SLICE_S = _register(
    "GEOMESA_TPU_HISTORY_SLICE_S", 120.0, float,
    "Width of the history slice (seconds before the firing) captured "
    "into a forensic bundle when the doctor opens an incident — the "
    "timeline window an operator replays around the page.")

FORENSICS_ENABLED = _register(
    "GEOMESA_TPU_FORENSICS", True, _parse_bool,
    "Capture a forensic bundle (history slices, matching flight events, "
    "retained trace gids, replication/cell state, workload hot_set) "
    "when the doctor opens an incident. Bundles stay fetchable in "
    "memory at GET /incidents/{id}/bundle; a directory makes them "
    "durable.")

FORENSICS_DIR = _register(
    "GEOMESA_TPU_FORENSICS_DIR", "", str,
    "Directory for durable forensic bundles (atomic tmp+rename install, "
    "newest GEOMESA_TPU_FORENSICS_KEEP kept). Empty keeps bundles "
    "in-memory only.")

FORENSICS_KEEP = _register(
    "GEOMESA_TPU_FORENSICS_KEEP", 16, int,
    "Size rotation for the forensic bundle directory: all but this many "
    "newest bundle files are deleted after each capture (forensics.gc "
    "counts the drops).")

DOCTOR_TREND = _register(
    "GEOMESA_TPU_DOCTOR_TREND", True, _parse_bool,
    "Enable the predictive doctor rules: slo_trend (burn-rate slope "
    "projects a page before slo_burn fires) and capacity_trend "
    "(per-shard load growth slope projects time-to-imbalance).")

DOCTOR_TREND_LEAD_S = _register(
    "GEOMESA_TPU_DOCTOR_TREND_LEAD_S", 120.0, float,
    "slo_trend projection horizon: an objective whose 5m burn rate, "
    "extrapolated along its fitted slope this many seconds ahead, "
    "crosses the page bar opens a predictive incident while the "
    "current burn is still under it.")

DOCTOR_TREND_MIN_POINTS = _register(
    "GEOMESA_TPU_DOCTOR_TREND_MIN_POINTS", 5, int,
    "Minimum history samples inside the doctor window before either "
    "trend rule may fire (two points always fit a line; a trend is "
    "only evidence once it persists).")

DOCTOR_CAPACITY_LEAD_S = _register(
    "GEOMESA_TPU_DOCTOR_CAPACITY_LEAD_S", 600.0, float,
    "capacity_trend horizon: a shard whose guaranteed max-over-mean "
    "load ratio is growing fast enough to cross the imbalance bar "
    "within this many seconds opens a predictive incident carrying "
    "the projected time-to-imbalance.")

JOURNAL_KEEP = _register(
    "GEOMESA_TPU_JOURNAL_KEEP", 1, int,
    "Rotated generations kept for the incident and flight-recorder "
    "JSONL journals (path.1 .. path.N). The default keeps one rotated "
    "predecessor, matching the historical rotate-once discipline; long "
    "soaks raise it and rely on the keep-N GC (journal.gc counts "
    "dropped generations) to bound disk.")


def enable_compile_cache() -> str:
    """Give JAX's persistent compilation cache a stable home and return the
    directory in force. Entry points (``chip_smoke.py``, ``benchmark/run.py``,
    the CLI) call this before any backend initialises.

    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads the variable itself, so
    nothing is set here and an operator can place the cache from outside.
    Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path,
    because the path is part of what makes a later process find the entries.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe() -> Dict[str, dict]:
    """name → {value, default, doc} for every registered property
    (the CLI `config` listing / docs surface)."""
    return {
        name: {"value": p.get(), "default": p.default, "doc": p.doc}
        for name, p in sorted(_REGISTRY.items())
    }


def get(name: str) -> SystemProperty:
    return _REGISTRY[name]
