"""Adaptive micro-batching query scheduler + plan caching (serving path).

The whole GeoMesa design amortizes per-query cost by pushing work close to
the data; the TPU build's batched scan kernel proves the same point for
dispatch cost — BENCH cfg1 measures ~0.19ms/query at batch 64 against a
~4.9ms pipelined / ~107ms blocking single-query floor that is dispatch/RTT
bound, not device bound. This module closes that gap for concurrent traffic:

  submit → [plan cache] → micro-batch window → group by kernel key →
  ONE fused device dispatch per group → double-buffered completion

Concurrent count requests are grouped by compatible kernel signature (same
index kernels, primary kind, time windows, device residual) and fused into a
single ``counts_multi[_blocks]`` dispatch over ONE candidate-block cover,
decomposed once for the union of the group's boxes. An adaptive window
flushes at B queries or T µs, whichever first;
the collector thread plans/dispatches batch N+1 while the completer thread
waits on batch N's in-flight device round trip, so host planning overlaps
the RTT instead of summing with it.

Caching in front of the batcher:

  plan cache   (epoch, type, generation, normalized filter, auths) →
               folded plan (epoch = the store incarnation's salt, so a
               restored store never aliases a prior incarnation's plans).
               A hit skips parse + strategy selection + auths fold entirely
               (the trace tree shows no ``plan`` span). Keyed by auths so a
               privileged query's visibility-folded plan can never serve an
               unprivileged caller (tests/test_security.py).
  templates    in the same LRU, under the same key with the filter's
               *shape* (``bind.shape_key``) in the filter's place: a
               groupable count's first plan with the values taken out
               (``bind.PlanTemplate``). A filter that misses the exact key
               and meets its shape's template is not planned again: its
               box, interval and constants are bound into the template
               (no index is asked for a plan, nothing is priced), and the
               plan that comes out equals the planner's on that index. A
               shape whose first plan the collector would not group holds
               None there and is planned in full every time, as is a
               filter whose values do not bind (``sched.plan.bound`` /
               ``.full`` / ``.bind_failed``).

It invalidates through the datastore's per-type generation counter: every
mutation (ingest append, LSM flush, age-off, update, delete, schema change)
bumps the generation, so a stale cached plan is unreachable by construction.
A plan that runs alone (a group of one, or off the fused path) keeps its
candidate-block cover on the plan object the cache holds.

Thread model: callers submit from any thread and block on a per-request
future; one collector thread owns batching/planning/dispatch, one completer
thread owns device readbacks + host fallbacks. Requests capture a consistent
(planner, delta, generation) snapshot at submit time, so a mid-flush mutation
never pairs a pre-flush plan with post-flush state.

Resilience (serve/resilience/): every request may carry a Deadline —
checked when its batch reaches dispatch, so a request that timed out in the
queue is cancelled BEFORE it costs a device round trip; admission control
bounds in-flight work per priority class (interactive requests dequeue
first) and sheds the excess; device dispatch runs behind a circuit breaker
+ capped-jittered retry; a request with (almost) no budget left — or any
eligible count while the breaker is open — degrades to the stats estimator
and resolves with a flagged ApproximateCount. Worker loops are crash-safe:
an unexpected worker death (or shutdown with work still queued) fails every
outstanding future with a structured SchedulerCrashed/SchedulerShutdown
error instead of leaving callers blocked forever.

The dispatch cycle, timed from inside (``_Cycle`` / ``_Dispatch``): one turn
of the collector is idle → window → plan loop (plan and group key a
request, then one cover a group) → per group union, prepare, launch; the
completer adds pickup, delta, ready_wait,
resolve. Every stage is a (start, end) pair on ``trace.py``'s clock. They go
out on the ``kind=batch`` flight event (``stages``), the ``sched.stage.*``
timers, ``slow_cycles`` of ``stats()`` and as ``sched.*`` annotations in a
profiler trace; a request's own trace gets the partition submit → queue_wait
→ batch_host → scan → wake from the same instants.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future, InvalidStateError
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

from geomesa_tpu import config
from geomesa_tpu import trace as _trace
from geomesa_tpu.durability import faults as _faults
from geomesa_tpu.filter import ir
from geomesa_tpu.filter.parser import parse_ecql
from geomesa_tpu.index import bind as _bind
from geomesa_tpu.index.scan import PRIMARY_FNS, Unsupported
from geomesa_tpu.metrics import REGISTRY as _metrics
from geomesa_tpu.obs import attrib as _attrib
from geomesa_tpu.obs import flight as _flight
from geomesa_tpu.obs import hostcpu as _hostcpu
from geomesa_tpu.obs import workload as _workload
from geomesa_tpu.serve.cache import MISS as _RC_MISS
from geomesa_tpu.serve.cache import ResultCache
from geomesa_tpu.serve.resilience import deadline as _rdl
from geomesa_tpu.serve.resilience import degrade as _degrade
from geomesa_tpu.serve.resilience.admission import (AdmissionController,
                                                    ShedError,
                                                    normalize_priority)
from geomesa_tpu.serve.resilience.breaker import CircuitBreaker, retry_call
from geomesa_tpu.serve.resilience.deadline import Deadline, DeadlineExceeded

_pc = time.perf_counter
_pcn = time.perf_counter_ns   # the clock of trace.py's spans
_MISS = object()
_STOP = object()
_SLOW_CYCLE_S = 1.0           # first dequeue → resolved; kept in slow_cycles
_SLOW_CYCLES_KEPT = 8


def _query_cell(f: "ir.Filter") -> Optional[str]:
    """The coarse Morton hot-cell key for a filter's FIRST bbox
    constraint (And recurses; anything else is spatially unkeyed) —
    the workload plane's spatial heatmap dimension."""
    if isinstance(f, ir.BBox):
        from geomesa_tpu.obs.sketches import cell_key
        return cell_key(f.xmin, f.ymin, f.xmax, f.ymax,
                        int(config.WORKLOAD_CELL_BITS.get()))
    if isinstance(f, ir.And):
        for c in f.children:
            cell = _query_cell(c)
            if cell is not None:
                return cell
    return None

# priority-queue ranks: interactive dequeues before batch; _STOP ranks last
# so a graceful shutdown serves already-queued work first
_RANKS = {"interactive": 0, "batch": 1}
_STOP_RANK = 9


class SchedulerCrashed(RuntimeError):
    """A scheduler worker thread died unexpectedly; the outstanding request
    was failed (structured, promptly) rather than left to hang. ``worker``
    names the thread; ``cause`` is the error that killed it."""

    def __init__(self, worker: str, cause: BaseException):
        super().__init__(
            f"scheduler {worker} thread died ({cause!r}); "
            f"outstanding requests failed")
        self.worker = worker
        self.cause = cause


class SchedulerShutdown(RuntimeError):
    """The scheduler was shut down with this request still unresolved."""


# -- caches -------------------------------------------------------------------


class LruCache:
    """Small thread-safe LRU with hit/miss counters fed to the metrics
    registry under ``<prefix>.hits`` / ``<prefix>.misses``. ``capacity <= 0``
    disables the cache (every get misses, puts drop)."""

    def __init__(self, capacity: int, metric_prefix: str):
        self._d: "OrderedDict" = OrderedDict()
        self._cap = int(capacity)
        self._prefix = metric_prefix
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key, tally: bool = True):
        """Cached value or the module ``_MISS`` sentinel. ``tally=False``
        leaves the hit/miss counters alone (a look-up that is no request's
        own: the scheduler's shape templates)."""
        with self._lock:
            if self._cap > 0 and key in self._d:
                self._d.move_to_end(key)
                hit = True
                out = self._d[key]
            else:
                hit = False
                out = _MISS
            if tally:
                self.hits += hit
                self.misses += not hit
        if tally:
            _metrics.inc(f"{self._prefix}.hits" if hit
                         else f"{self._prefix}.misses")
        return out

    def peek(self, key) -> bool:
        """Membership probe WITHOUT touching hit/miss counters or LRU order
        (the explain/analyze provenance overlay must not skew cache stats)."""
        with self._lock:
            return key in self._d

    def put(self, key, value) -> None:
        if self._cap <= 0:
            return
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self._cap:
                self._d.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._d.clear()

    def __len__(self) -> int:
        return len(self._d)

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {"size": len(self._d), "capacity": self._cap,
                    "hits": self.hits, "misses": self.misses,
                    "hit_rate": round(self.hits / total, 4) if total else 0.0}


# -- bindings -----------------------------------------------------------------


class StoreBinding:
    """Bind a scheduler to a TpuDataStore: snapshots are (planner, delta,
    generation) captured atomically w.r.t. mutations; delta rows evaluate
    host-side exactly like the store's own count path."""

    def __init__(self, store):
        self.store = store

    def snapshot(self, type_name: str):
        return self.store._sched_snapshot(type_name)

    def delta_rows(self, delta, f, auths):
        return self.store._delta_rows(delta, f, auths)


class PlannerBinding:
    """Bind a scheduler to bare QueryPlanners (bench / tests — no store, no
    delta tier, one immutable generation). Each binding gets its own epoch
    so two bindings over recycled planner dicts cannot share cache keys."""

    def __init__(self, planners: Dict[str, object]):
        from geomesa_tpu.datastore import _next_epoch
        self._planners = dict(planners)
        self._epoch = _next_epoch()

    def snapshot(self, type_name: str):
        return self._planners[type_name], None, 0, self._epoch

    def delta_rows(self, delta, f, auths):
        return ()


# -- requests -----------------------------------------------------------------


class Request:
    """One in-flight scheduled query. ``result()`` blocks for the count;
    the timing fields feed the caller's trace after resolution.
    ``deadline``/``priority`` are the resilience envelope; ``cancelled`` /
    ``degraded`` say how the request resolved off the exact path."""

    __slots__ = ("type_name", "f_ir", "f_key", "auths", "auths_key",
                 "planner", "delta", "generation", "epoch", "future",
                 "t_submit", "t_closed", "t_plan", "t_launch",
                 "plan", "plan_bound", "queue_wait_s", "scan_s", "staged",
                 "batched", "batch_size", "deadline", "priority",
                 "cancelled", "degraded",
                 # flight-recorder dimensions (obs/flight.py wide events)
                 "trace_id", "trace_gid", "parent_span", "budget_ms",
                 "plan_cache_hit", "batch_id",
                 "rows_scanned", "shed", "breaker_open", "retries",
                 # workload-analytics dimensions (obs/workload.py)
                 "tenant", "cell", "funcs",
                 # hot-result cache (serve/cache.py): True = served from
                 # memory with no device round trip
                 "result_cache_hit")

    def __init__(self, type_name, f_ir, f_key, auths, auths_key,
                 planner, delta, generation, epoch,
                 deadline: Optional[Deadline] = None,
                 priority: str = "interactive",
                 tenant: Optional[str] = None):
        self.type_name = type_name
        self.f_ir = f_ir
        self.f_key = f_key
        self.auths = auths
        self.auths_key = auths_key
        self.planner = planner
        self.delta = delta
        self.generation = generation
        self.epoch = epoch
        self.future: Future = Future()
        # instants on trace.py's clock (perf_counter_ns): submitted, its
        # batch closed, its own (plan start, plan end) on a plan-cache
        # miss (``plan_bound``: bound into its shape's template, not
        # planned), its dispatch launched (a single: handed to the
        # completer). The stages below are their differences, in seconds:
        # queue_wait = submit → closed, scan = launch → resolved
        self.t_submit = _pcn()
        self.t_closed: Optional[int] = None
        self.t_plan: Optional[Tuple[int, int]] = None
        self.t_launch: Optional[int] = None
        self.plan = None
        self.plan_bound = False
        self.queue_wait_s: Optional[float] = None
        self.scan_s: Optional[float] = None
        # spans the completer timed inside a single's ``scan``
        self.staged = None
        self.batched = False
        self.batch_size = 1
        self.deadline = deadline
        self.priority = priority
        self.cancelled = False
        self.degraded = False
        self.trace_id: Optional[int] = None
        self.trace_gid: Optional[str] = None
        self.parent_span: Optional[int] = None
        self.budget_ms: Optional[float] = None
        self.plan_cache_hit: Optional[bool] = None
        self.batch_id: Optional[int] = None
        self.rows_scanned: Optional[int] = None
        self.shed = False
        self.breaker_open = False
        self.retries = 0
        self.tenant = tenant
        self.cell: Optional[str] = None
        # distinct st_* function names in the filter (workload ``funcs``
        # dimension; () for function-free queries)
        from geomesa_tpu.filter import ir as _ir
        self.funcs = _ir.funcs_of(f_ir) if f_ir is not None else ()
        self.result_cache_hit: Optional[bool] = None

    def resolved_ns(self) -> Optional[int]:
        """The instant (``perf_counter_ns``) the request was resolved on the
        completer, where it went through a launch: launch + ``scan``."""
        if self.t_launch is None or self.scan_s is None:
            return None
        return self.t_launch + int(self.scan_s * 1e9)

    def result(self, timeout: Optional[float] = None) -> int:
        """Blocks for the count: a wait by design (``trace.waiting``) that
        ends where the request was resolved; the caller's wake and turn at
        the interpreter lock after that stay off-CPU time."""
        with _trace.waiting() as w:
            try:
                return self.future.result(timeout=timeout)
            finally:
                w.until(self.resolved_ns())


def _groupable(plan) -> bool:
    """True for a plan the collector fuses with others of its group key: a
    device-exact scan whose primary is one box (``_plan_loop``). The plans
    a shape's template is kept for (``_plan_request``)."""
    return (plan.device_exact and plan.primary_kind in PRIMARY_FNS
            and plan.boxes_loose is not None
            and plan.boxes_loose.shape == (1, 8))


def _group_key(plan) -> tuple:
    """What two groupable plans have in common when one dispatch can serve
    both: the index's kernels, the primary, the time windows and the device
    residual with its parameters, byte for byte; only the box differs."""
    rd = plan.residual_device
    wkey = None if plan.windows is None \
        else (plan.windows.shape[0], plan.windows.tobytes())
    rkey = (rd[0], tuple(
        (np.asarray(p).dtype.str, np.asarray(p).shape,
         np.asarray(p).tobytes()) for p in rd[1])) \
        if rd else None
    return id(plan.index.kernels), plan.primary_kind, wkey, rkey


# -- the dispatch cycle, timed ------------------------------------------------


def _observe_stages(stages: Dict[str, Tuple[int, int]]) -> None:
    """(start, end) pairs into the ``sched.stage.<name>`` timers: a window's
    delta of ``total_s`` is that stage's seconds."""
    _metrics.observe_batch([("sched.stage." + name, (end - start) / 1e9)
                            for name, (start, end) in stages.items()])


class _Cycle:
    """One turn of the collector thread, as instants on ``perf_counter_ns``:
    ``idle`` (blocked in ``queue.get()`` with nothing queued) → ``window``
    (first request → batch closed) → the planning loop → one ``_Dispatch``
    per fused group. ``plan`` is the sum over the loop's plan-cache misses
    (``plan_bound`` of them bound into their shape's template, ``plan_full``
    planned by ``_plan``, ``bind_failed`` of those after a template was
    there and the values did not bind);
    ``cover`` the sum over its groups' covers (range decomposition and
    blocks, one for all the boxes of a group: ``cover_misses`` of them);
    ``group`` is the rest of the loop (group keys, deadline checks), so the
    three make the loop's wall exactly, and ``loop_cpu_ns`` is the thread's
    CPU time across it: wall minus CPU is time the thread wanted to run and
    did not (the GIL, the OS)."""

    __slots__ = ("t_idle", "t_first", "t_closed", "t_loop", "t_loop_end",
                 "plan_ns", "cover_ns", "plan_misses", "cover_misses",
                 "plan_bound", "plan_full", "bind_failed",
                 "loop_cpu_ns", "size")

    def __init__(self, t_idle: int, t_first: int):
        self.t_idle = t_idle
        self.t_first = t_first
        self.t_closed = self.t_loop = self.t_loop_end = t_first
        self.plan_ns = self.cover_ns = self.loop_cpu_ns = 0
        self.plan_misses = self.cover_misses = 0
        self.plan_bound = self.plan_full = self.bind_failed = 0
        self.size = 0

    def stages(self) -> Dict[str, Tuple[int, int]]:
        """(start, end) of the cycle's own stages. ``plan`` and ``group``
        interleave request by request inside the planning loop and the
        groups' covers follow: the three are laid end to end from the loop's
        start as plan, cover, group, so only the loop's start and end are
        instants that happened; the lengths are exact."""
        plan_end = self.t_loop + self.plan_ns
        cover_end = plan_end + self.cover_ns
        return {"idle": (self.t_idle, self.t_first),
                "window": (self.t_first, self.t_closed),
                "plan": (self.t_loop, plan_end),
                "cover": (plan_end, cover_end),
                "group": (cover_end, self.t_loop_end)}


class _Dispatch:
    """One fused group's way through a cycle: ``union`` (the group's boxes
    stacked, its cover's tier) → ``prepare`` (padding, host→device puts) →
    ``launch``
    (``disp()`` behind breaker and retries) on the collector; ``pickup`` (on
    the ``_done`` queue) → ``delta`` → ``ready_wait`` (``np.asarray(out)``)
    → ``resolve`` (result cache, ``set_result`` and its done-callbacks, up
    to the last request's release) on the completer. Each stage ends where
    the next begins."""

    __slots__ = ("cycle", "batch_id", "size", "kernel", "tier", "union_tier",
                 "rows_scanned", "cover_boxes", "cover_ranges",
                 "first_call_s", "queue_depth", "threads",
                 "t_union", "t_prepare", "t_launch", "t_put",
                 "t_pickup", "t_wait", "t_ready", "t_resolved")

    def __init__(self, cycle: _Cycle, batch_id: int, size: int):
        self.cycle = cycle
        self.batch_id = batch_id
        self.size = size
        self.kernel = None
        self.tier = self.union_tier = self.rows_scanned = 0
        # boxes decomposed together for this group's cover and the key
        # ranges it came back with (0, 0: no cover was computed)
        self.cover_boxes = self.cover_ranges = 0
        # seconds of the program's first call when this launch made it
        self.first_call_s: Optional[float] = None
        self.queue_depth = self.threads = 0

    def stages(self) -> Dict[str, Tuple[int, int]]:
        return {"union": (self.t_union, self.t_prepare),
                "prepare": (self.t_prepare, self.t_launch),
                "launch": (self.t_launch, self.t_put),
                "pickup": (self.t_put, self.t_pickup),
                "delta": (self.t_pickup, self.t_wait),
                "ready_wait": (self.t_wait, self.t_ready),
                "resolve": (self.t_ready, self.t_resolved)}

    def to_dict(self) -> dict:
        """The dispatch and its cycle as the ``kind=batch`` flight event and
        ``slow_cycles`` carry them: stages as ``[start (epoch ms), length
        (ms)]``."""
        c, ms = self.cycle, _trace.epoch_ms
        return {
            "batch_id": self.batch_id, "kernel": self.kernel,
            "batch_size": self.size, "cycle_size": c.size,
            "tier": self.tier, "union_tier": self.union_tier,
            "rows_scanned": self.rows_scanned,
            "stages": {k: [round(ms(a), 3), round((b - a) / 1e6, 3)]
                       for k, (a, b) in {**c.stages(),
                                         **self.stages()}.items()},
            "launch_ms": round(ms(self.t_launch), 3),
            "ready_ms": round(ms(self.t_ready), 3),
            "plan_loop_cpu_ms": round(c.loop_cpu_ns / 1e6, 3),
            "plan_misses": c.plan_misses, "cover_misses": c.cover_misses,
            "cover_boxes": self.cover_boxes,
            "cover_ranges": self.cover_ranges,
            "first_call": self.first_call_s,
            "queue_depth": self.queue_depth, "threads": self.threads}


# -- the scheduler ------------------------------------------------------------


class QueryScheduler:
    """Micro-batching count scheduler over one store/planner binding.

    Knobs (config.py system properties; constructor args override):
      flush_size     max queries fused per dispatch (flush-at-B)
      window_us      max collection window (flush-at-T µs, adaptive cap)
      min_window_us  adaptive window floor

    The window adapts from observed batch sizes: sustained single-query
    traffic shrinks it toward the floor (don't tax lone queries with the
    full window), mid-size batches that flush on the window grow it toward
    the cap (coalesce more per round trip), and size-capped flushes leave it
    alone (arrivals already outpace the window).
    """

    def __init__(self, binding, flush_size: Optional[int] = None,
                 window_us: Optional[float] = None,
                 min_window_us: Optional[float] = None,
                 plan_cache: Optional[int] = None,
                 result_cache: Optional[int] = None):
        self.binding = binding
        self._flush_size = int(flush_size or config.SCHED_FLUSH_SIZE.get())
        self._max_window_us = float(window_us or config.SCHED_WINDOW_US.get())
        self._min_window_us = float(
            min_window_us or config.SCHED_MIN_WINDOW_US.get())
        self._window_us = self._max_window_us
        self._ema_batch = 1.0
        cap_p = config.SCHED_PLAN_CACHE.get() if plan_cache is None else plan_cache
        self.plans = LruCache(cap_p, "scheduler.plan_cache")
        # hot-result cache: same (epoch, type, generation, filter, auths)
        # keying as the plan cache, admission gated by the workload plane
        self.results = ResultCache(capacity=result_cache)
        # priority queue: (rank, seq, request) — interactive before batch,
        # FIFO within a class, _STOP after all queued work
        self._queue: "queue.PriorityQueue" = queue.PriorityQueue()
        self._seq = itertools.count()
        self._batch_ids = itertools.count(1)
        self._done: "queue.Queue" = queue.Queue()
        # flight recorder / tail sampling / kernel attribution hooks — a
        # bare scheduler (bench, tests) is observable like a store-owned one
        from geomesa_tpu import obs as _obs
        _obs.install()
        # resilience: admission bounds + device-dispatch breaker + the
        # registry of every unresolved request (failed en masse if a worker
        # dies or shutdown leaves work behind)
        self.admission = AdmissionController()
        self.breaker = CircuitBreaker("device_dispatch")
        self._outstanding: set = set()
        self._out_lock = threading.Lock()
        self._crash_error: Optional[SchedulerCrashed] = None
        # collector-thread-only tallies (read-only elsewhere)
        self._batch_hist: Dict[int, int] = {}
        self._flush_reasons: Dict[str, int] = {"size": 0, "window": 0}
        self._n_queries = 0
        self._n_batches = 0
        self._n_fused = 0
        self._n_single = 0
        self._n_group_covers = 0
        self._n_cover_boxes = 0
        self._n_plan = {"bound": 0, "full": 0, "bind_failed": 0}
        # completer-thread-only: the last cycles that took over
        # _SLOW_CYCLE_S, whole (replaced, never mutated: readers take the
        # reference)
        self._slow_cycles: List[dict] = []
        self._running = True
        _metrics.set_gauge("scheduler.queue_depth", self._queue.qsize)
        # compile the fused single-dispatch program tiers for every bound
        # planner's indexes now, so a cold single query through the
        # scheduler doesn't pay the first-query XLA compile — and a program
        # the compiler refuses fails HERE, at start-up, instead of vanishing
        # and resurfacing as a failed first query
        from geomesa_tpu.index import compiled as _fused
        for p in getattr(binding, "_planners", {}).values():
            for idx in getattr(p, "indexes", ()):
                _fused.warm_programs(idx)
        self._collector = _hostcpu.thread(
            "sched.collector", target=self._worker_main,
            args=("collector", self._collect_loop),
            name="geomesa-sched-collect", daemon=True)
        self._completer = _hostcpu.thread(
            "sched.completer", target=self._worker_main,
            args=("completer", self._complete_loop),
            name="geomesa-sched-complete", daemon=True)
        self._collector.start()
        self._completer.start()

    # -- public API ---------------------------------------------------------

    def submit(self, type_name: str, f: Union[str, ir.Filter] = "INCLUDE",
               auths: Optional[list] = None,
               deadline: Optional[Deadline] = None,
               deadline_ms: Optional[float] = None,
               priority: str = "interactive",
               tenant: Optional[str] = None) -> Request:
        """Enqueue one count; returns a Request whose ``result()`` blocks.
        Parse errors and admission sheds (ShedError) raise here, before
        anything queues. The effective deadline is the sooner of the
        explicit one and any ambient request deadline. ``tenant`` labels
        the request for workload analytics/metering (falls back to the
        first sorted auth, then 'default')."""
        if not self._running:
            raise RuntimeError("scheduler is shut down")
        f_ir = parse_ecql(f) if isinstance(f, str) else f
        auths_key = None if auths is None \
            else tuple(sorted(str(a) for a in auths))
        planner, delta, gen, epoch = self.binding.snapshot(type_name)
        dl = _rdl.resolve(deadline, deadline_ms)
        req = Request(type_name, f_ir, repr(f_ir), auths, auths_key,
                      planner, delta, gen, epoch, deadline=dl,
                      priority=normalize_priority(priority),
                      tenant=_flight.tenant_label(tenant, auths))
        if _workload.enabled():
            req.cell = _query_cell(f_ir)
        # flight-recorder envelope: the wide event fires on EVERY resolution
        # path, so the callback attaches before any of them can run
        caller_trace = _trace.current_trace()
        if caller_trace is not None:
            req.trace_id = caller_trace.trace_id
            req.trace_gid = caller_trace.global_id
            if caller_trace.parent is not None:
                req.parent_span = caller_trace.parent.span_id
        req.breaker_open = self.breaker.state != "closed"
        if config.OBS_ENABLED.get():
            req.future.add_done_callback(_flight.request_callback(req))
        _metrics.inc("scheduler.queries")
        if dl is not None:
            req.budget_ms = round(max(0.0, dl.remaining_ms()), 3)
            _metrics.observe_value("deadline.remaining_ms",
                                   max(0.0, dl.remaining_ms()))
            if dl.expired:
                # dead on arrival: fail before admission/queue/dispatch
                # spend anything on it (Tail-at-Scale rule: never do work
                # whose result cannot be delivered in time)
                self._cancel(req, "submit")
                return req
        # hot-result cache: a warm hot query resolves HERE — no admission
        # slot, no queue, no plan, no device round trip. The flight
        # callback above fires on the resolution with cache="result"
        # provenance and zero device-ms, so attribution stays honest.
        if self.results.enabled():
            rkey = (epoch, type_name, gen, req.f_key, req.auths_key)
            cached = self.results.get(rkey)
            if cached is not _RC_MISS:
                req.result_cache_hit = True
                self._resolve(req, cached)
                return req
            req.result_cache_hit = False
        # retry_after_s > 0 means the breaker is open AND still cooling
        # down (probe-free check: allow() would consume a half-open slot)
        if self.breaker.retry_after_s() > 0 and config.BREAKER_DEGRADE.get():
            approx = _degrade.estimate(planner, f_ir, "breaker_open")
            if approx is not None:
                req.degraded = True
                _metrics.inc("scheduler.degraded")
                req.future.set_result(approx)
                return req
        try:
            # tenant rides along for QoS fair-share accounting
            cls = self.admission.admit(req.priority, tenant=req.tenant)
        except ShedError as e:
            # resolve the (unreturned) future so the flight event records
            # the shed before the raise reaches the caller
            req.shed = True
            self._fail(req, e)
            raise
        self._track(req, cls)
        self._queue.put((_RANKS[cls], next(self._seq), req))
        return req

    def count(self, type_name: str, f: Union[str, ir.Filter] = "INCLUDE",
              auths: Optional[list] = None,
              timeout: Optional[float] = None,
              deadline_ms: Optional[float] = None,
              priority: str = "interactive",
              tenant: Optional[str] = None) -> int:
        """Blocking scheduled count. The caller's trace receives queue_wait
        / plan / scan leaves — a plan-cache hit shows NO plan span."""
        with _trace.trace("query.count", type=type_name, filter=str(f),
                          scheduled=True):
            # under a caller's root (the REST span): the root has to say
            # `scheduled` too, or its close derives a second flight event
            _trace.mark_root(scheduled=True)
            t0 = _pcn()
            req = self.submit(type_name, f, auths, deadline_ms=deadline_ms,
                              priority=priority, tenant=tenant)
            if _trace.enabled():
                t1 = _pcn()
                _trace.record("submit", "submit", (t1 - t0) / 1e9, t1)
            return self._finish(req, timeout)

    def count_many(self, type_name: str, filters, auths: Optional[list] = None,
                   timeout: Optional[float] = None,
                   deadline_ms: Optional[float] = None,
                   priority: str = "interactive",
                   tenant: Optional[str] = None) -> List[int]:
        """Counts for many filters, submitted together so they coalesce into
        fused dispatches. Order-preserving."""
        with _trace.trace("query.count_many", type=type_name,
                          n=len(filters), scheduled=True):
            reqs = [self.submit(type_name, f, auths, deadline_ms=deadline_ms,
                                priority=priority, tenant=tenant)
                    for f in filters]
            return [self._finish(r, timeout) for r in reqs]

    def _finish(self, req: Request, timeout: Optional[float]) -> int:
        try:
            return req.result(timeout)
        finally:
            if _trace.enabled():
                self._record_stages(req)
                if req.cancelled:
                    # the trace-visible proof a timed-out query was dropped
                    # WITHOUT a device round trip: a cancel leaf and no scan
                    _trace.record("cancel", "cancel", 0.0)
                if req.degraded:
                    _trace.record("degrade", "degrade", 0.0)
                if req.result_cache_hit:
                    # trace-visible proof the hot answer came from memory:
                    # a cache leaf and NO queue_wait/plan/scan spans
                    _trace.record("result_cache", "cache_hit", 0.0)

    @staticmethod
    def _record_stages(req: Request) -> None:
        """The request's stages into the caller's trace, from the instants
        the collector and completer left on it. queue_wait, batch_host,
        scan and wake follow one another, so with ``submit`` they partition
        the latency. The request's own planning is part of batch_host's
        interval and nests under it: ``plan`` (``bound=True`` where the
        values were bound into the shape's template), fed to its timer here
        and nowhere else (the collector calls the planner's untimed
        ``_plan``).
        The cover is its group's, not the request's: the collector feeds
        ``range_decompose`` once per group cover (``_cover_group``)."""
        rec = _trace.record
        closed, launch = req.t_closed, req.t_launch
        if closed is not None:
            rec("queue_wait", "queue_wait", req.queue_wait_s, closed)
        if launch is not None:
            host = rec("batch_host", "batch_host",
                       (launch - closed) / 1e9, launch)
            if req.t_plan is not None:
                t0, t1 = req.t_plan
                rec("plan", "plan", (t1 - t0) / 1e9, t1,
                    {"bound": True} if req.plan_bound else None, host)
            if req.scan_s is not None:
                resolved = req.resolved_ns()
                scan = rec("scan", "scan", req.scan_s, resolved,
                           None if req.batch_id is None
                           else {"batch_id": req.batch_id})
                for node in req.staged or ():
                    scan.add_child(node)
                # resolved → this thread runs again: with many callers
                # woken at once, their turn at the interpreter lock
                now = _pcn()
                rec("wake", "wake", (now - resolved) / 1e9, now)

    # -- resilience plumbing -------------------------------------------------

    def _track(self, req: Request, cls: str) -> None:
        """Register an admitted request as outstanding; the future's done
        callback (fires on every resolution path) releases its admission
        slot and drops it from the registry."""
        with self._out_lock:
            self._outstanding.add(req)

        def _done(_f, req=req, cls=cls):
            self.admission.release(cls, tenant=req.tenant)
            with self._out_lock:
                self._outstanding.discard(req)

        req.future.add_done_callback(_done)

    def _maybe_cache(self, req: Request, value: int) -> None:
        """Offer a freshly-computed exact count to the result cache (the
        cache applies its own hot-set admission gate). Degraded/cancelled
        answers are never cacheable."""
        if not self.results.enabled() or req.degraded or req.cancelled:
            return
        key = (req.epoch, req.type_name, req.generation, req.f_key,
               req.auths_key)
        self.results.put(
            key, int(value),
            _flight.plan_hash(req.type_name, req.f_key, req.auths_key),
            req.cell)

    @staticmethod
    def _resolve(req: Request, value) -> None:
        try:
            req.future.set_result(value)
        except InvalidStateError:
            pass  # already failed by a crash/shutdown sweep — that wins

    @staticmethod
    def _fail(req: Request, exc: BaseException) -> None:
        try:
            req.future.set_exception(exc)
        except InvalidStateError:
            pass

    def _cancel(self, req: Request, stage: str) -> None:
        req.cancelled = True
        _metrics.inc("scheduler.deadline_cancelled")
        overrun = -req.deadline.remaining_ms() if req.deadline else 0.0
        _metrics.observe_value("deadline.overrun_ms", max(0.0, overrun))
        self._fail(req, DeadlineExceeded(stage, max(0.0, overrun)))

    def _fail_outstanding(self, exc: BaseException) -> None:
        """Resolve EVERY unresolved future with ``exc`` — queued, batched,
        or in flight. Callers blocked in result() unblock promptly."""
        with self._out_lock:
            pending = list(self._outstanding)
        for r in pending:
            if not r.future.done():
                self._fail(r, exc)

    def _worker_main(self, which: str, loop) -> None:
        """Thread wrapper: an escaping error (InjectedCrash is a
        BaseException no inner guard may swallow) marks the scheduler
        crashed and fails all outstanding futures instead of silently
        stranding them."""
        try:
            loop()
        except BaseException as e:  # worker death — by injection or bug
            err = SchedulerCrashed(which, e)
            self._crash_error = err
            self._running = False
            _metrics.inc("scheduler.worker_deaths")
            self._fail_outstanding(err)
            # unblock the surviving worker so it can exit
            if which == "collector":
                self._done.put(_STOP)
            else:
                self._queue.put((_STOP_RANK, next(self._seq), _STOP))

    def healthy(self) -> bool:
        """True while both workers are alive and accepting work (the store
        replaces an unhealthy scheduler on next access). Surfaced through
        /healthz overload state, where the replica/shard router reads it:
        a node whose scheduler died classifies DEMOTED — still a retry
        candidate for its cell, never the first choice."""
        return (self._running and self._collector.is_alive()
                and self._completer.is_alive())

    def stats(self) -> dict:
        """Live scheduler state for the debug surfaces (CLI / web)."""
        return {
            "queue_depth": self._queue.qsize(),
            "flush_size": self._flush_size,
            "window_us": round(self._window_us, 1),
            "window_us_max": self._max_window_us,
            "ema_batch": round(self._ema_batch, 2),
            "queries": self._n_queries,
            "batches": self._n_batches,
            "fused": self._n_fused,
            "singles": self._n_single,
            "flush_reasons": dict(self._flush_reasons),
            "batch_size_hist": {str(k): v for k, v in
                                sorted(self._batch_hist.items())},
            "slow_cycles": self._slow_cycles,
            "group_covers": self._n_group_covers,
            "cover_boxes_mean": round(
                self._n_cover_boxes / self._n_group_covers, 2)
            if self._n_group_covers else 0.0,
            "plan_cache": self.plans.stats(),
            "plan": dict(self._n_plan),
            "result_cache": self.results.stats(),
            "healthy": self.healthy(),
            "admission": self.admission.stats(),
            "breaker": self.breaker.stats(),
        }

    def shutdown(self, timeout: float = 5.0) -> None:
        """Stop both threads. Graceful first: already-queued requests are
        served before the stop sentinel (it ranks last in the priority
        queue). Then ANY still-unresolved future — a died worker, a wedged
        device round, work the join timeout abandoned — is failed with a
        structured SchedulerShutdown, so no caller blocked in ``result()``
        ever hangs past shutdown. Idempotent."""
        if self._running:
            self._running = False
            self._queue.put((_STOP_RANK, next(self._seq), _STOP))
        self._collector.join(timeout=timeout)
        if self._completer.is_alive() and not self._collector.is_alive():
            # collector died/stalled without forwarding the sentinel
            self._done.put(_STOP)
        self._completer.join(timeout=timeout)
        self._fail_outstanding(
            self._crash_error
            or SchedulerShutdown("scheduler shut down with this request "
                                 "unresolved"))

    # -- collector thread ---------------------------------------------------

    def _collect_loop(self) -> None:
        while True:
            t_idle = _pcn()
            with _trace.annotation("sched.idle"):
                _, _, req = self._queue.get()
            cyc = _Cycle(t_idle, _pcn())
            _faults.serve_gate("sched.collect")
            if req is _STOP:
                self._done.put(_STOP)
                return
            batch = [req]
            t0 = _pc()
            reason = "window"
            stop = False
            with _trace.annotation("sched.window"):
                while len(batch) < self._flush_size:
                    remaining = self._window_us / 1e6 - (_pc() - t0)
                    if remaining <= 0:
                        # window expired: drain whatever is ALREADY queued
                        # (no extra wait) — a backlog that arrived during
                        # this window must not fragment into the next one
                        try:
                            while len(batch) < self._flush_size:
                                _, _, nxt = self._queue.get_nowait()
                                if nxt is _STOP:
                                    stop = True
                                    break
                                batch.append(nxt)
                        except queue.Empty:
                            pass
                        break
                    try:
                        _, _, nxt = self._queue.get(timeout=remaining)
                    except queue.Empty:
                        break
                    if nxt is _STOP:
                        stop = True
                        break
                    batch.append(nxt)
                else:
                    reason = "size"
            cyc.t_closed = _pcn()
            cyc.size = len(batch)
            self._account(len(batch), reason)
            try:
                self._dispatch(batch, cyc)
            except Exception as e:  # never kill the loop: fail the batch
                for r in batch:
                    self._fail(r, e)
            if stop:
                self._done.put(_STOP)
                return

    def _account(self, n: int, reason: str) -> None:
        self._n_queries += n
        self._n_batches += 1
        self._flush_reasons[reason] += 1
        self._batch_hist[n] = self._batch_hist.get(n, 0) + 1
        # adaptive window: see class docstring
        self._ema_batch = 0.8 * self._ema_batch + 0.2 * n
        if self._ema_batch <= 1.5:
            self._window_us = max(self._min_window_us, self._window_us * 0.5)
        elif reason == "window" and self._ema_batch < self._flush_size / 2:
            self._window_us = min(self._max_window_us, self._window_us * 1.5)

    def _plan_request(self, req: Request, cyc: _Cycle) -> None:
        """Fill ``req.plan`` via the plan cache (auths-folded). Plans only:
        the candidate-block cover is its group's (``_cover_group``). A cache
        hit leaves ``req.t_plan`` None — the trace shows no plan stage at
        all. A miss looks for the filter shape's template under the same
        key (store incarnation, type, generation, auths: a reload, a merge
        that swaps the planner or other auths never meet a stale one) and
        binds the request's values into it; without one, or where they do
        not bind, the planner's untimed ``_plan`` is called, and the first
        such plan of a shape becomes its template if the collector would
        group it (None if not: such shapes are planned every time). Bind or
        plan, a miss is timed here, once: the seconds reach the ``plan``
        timer through the request's own trace (``_record_stages``)."""
        pkey = (req.epoch, req.type_name, req.generation, req.f_key,
                req.auths_key)
        plan = self.plans.get(pkey)
        if plan is not _MISS:
            req.plan = plan
            req.plan_cache_hit = True
            return
        req.plan_cache_hit = False
        t0 = _pcn()
        planner = req.planner
        plan = tkey = None
        tmpl = _MISS
        # an interceptor may rewrite the filter or veto a plan by its values
        if not planner.interceptors:
            try:
                tkey = (req.epoch, req.type_name, req.generation,
                        ("shape", _bind.shape_key(req.f_ir)), req.auths_key)
            except Unsupported:
                pass    # no shape is kept for it (a FID filter)
            else:
                tmpl = self.plans.get(tkey, tally=False)
                if tmpl is not _MISS and tmpl is not None:
                    plan = tmpl.bind(req.f_ir)
                    if plan is None:
                        cyc.bind_failed += 1
                    else:
                        cyc.plan_bound += 1
                        req.plan_bound = True
        if plan is None:
            cyc.plan_full += 1
            base = planner._plan(req.f_ir)
            plan = planner._apply_auths(base, req.auths)
            # emptiness is a property of the values, not of the shape
            if tkey is not None and tmpl is _MISS and not plan.empty:
                self.plans.put(tkey, _bind.PlanTemplate.of(base, plan)
                               if _groupable(plan) else None)
        t1 = _pcn()
        req.t_plan = (t0, t1)
        cyc.plan_ns += t1 - t0
        cyc.plan_misses += 1
        req.plan = plan
        self.plans.put(pkey, plan)

    def _cover_group(self, grp: List[Request], cyc: _Cycle) -> tuple:
        """ONE candidate-block cover for a fused group: the range
        decomposition of the union of its members' boxes under the time
        windows its key guarantees equal (``cover_blocks``), so a dispatch
        of 32 boxes costs one decomposition, not 32. Returns (blocks, the
        cover's stats): sorted unique int32 block ids, or None to scan the
        table, decided on the UNION's rows against ``PRUNE_MAX_FRACTION``,
        which is what the gather kernel would read; no stats where nothing
        was decomposed.
        A superset by construction; the kernel re-applies every member's
        exact mask. A group of one keeps its cover on the plan object the
        plan cache holds, where ``planner._count`` keeps a single's."""
        if not config.PRUNE_ENABLED.get():
            return None, {}
        lead = grp[0].plan
        alone = len(grp) == 1
        if alone and lead.blocks is not False:
            return lead.blocks, {}   # a repeated lone query: already covered
        t0 = _pcn()
        boxes = list(dict.fromkeys(
            b for r in grp for b in r.plan.explain["boxes"]))
        index = lead.index
        blocks, stats = index.cover_blocks(boxes, index.cover_intervals(lead))
        if alone:
            lead.blocks = blocks
            lead.explain.update(stats)
        t1 = _pcn()
        cyc.cover_ns += t1 - t0
        cyc.cover_misses += 1
        self._n_group_covers += 1
        self._n_cover_boxes += len(boxes)
        if _trace.enabled():
            _metrics.observe("range_decompose", (t1 - t0) / 1e9)
        return blocks, stats

    def _dispatch(self, batch: List[Request], cyc: _Cycle) -> None:
        """Group a collected batch by fused-kernel compatibility and launch
        one async device dispatch per group; everything else falls back to
        per-query execution on the completer thread."""
        groups: Dict[tuple, List[Request]] = {}
        cpu0 = time.thread_time_ns()
        cyc.t_loop = _pcn()
        with _trace.annotation("sched.plan_loop", n=len(batch)):
            self._plan_loop(batch, cyc, groups)
            covers = []
            for grp in groups.values():
                try:
                    covers.append(self._cover_group(grp, cyc))
                except Exception as e:   # a cover's fault fails its group
                    covers.append(None)
                    for r in grp:
                        self._fail(r, e)
        cyc.t_loop_end = _pcn()
        cyc.loop_cpu_ns = time.thread_time_ns() - cpu0
        for name, n in (("bound", cyc.plan_bound), ("full", cyc.plan_full),
                        ("bind_failed", cyc.bind_failed)):
            if n:
                self._n_plan[name] += n
                _metrics.inc("sched.plan." + name, n)
        if _trace.enabled():
            # one observation a cycle, before any of its groups is launched
            # (a dispatch's own stages: the completer, in `_publish`)
            _observe_stages(cyc.stages())
            _metrics.inc("sched.plan_loop_cpu_us", cyc.loop_cpu_ns // 1000)
        for grp, cover in zip(groups.values(), covers):
            if cover is None:
                continue
            if cover[0] is not None and len(cover[0]) == 0:
                # provably-empty candidate set, nothing to dispatch
                for r in grp:
                    self._to_completer_single(r)
                continue
            try:
                self._dispatch_group(grp, cover, cyc)
            except Exception as e:
                for r in grp:
                    self._fail(r, e)

    def _to_completer_single(self, r: Request) -> None:
        r.t_launch = _pcn()
        self._done.put(("single", r))

    def _plan_loop(self, batch: List[Request], cyc: _Cycle,
                   groups: Dict[tuple, List[Request]]) -> None:
        """The per-request part of a dispatch: deadline checks, the plan
        through its cache, the fused-kernel group key. Python and numpy
        only, nothing that should sleep."""
        degrade_floor = config.DEADLINE_DEGRADE_MS.get()
        closed = cyc.t_closed
        for r in batch:
            r.t_closed = closed
            r.queue_wait_s = (closed - r.t_submit) / 1e9
            if r.deadline is not None:
                rem = r.deadline.remaining_ms()
                if rem < 0:
                    # timed out while queued: cancelled HERE, before any
                    # plan/device work is spent on it
                    self._cancel(r, "dispatch")
                    continue
                if degrade_floor and rem < degrade_floor:
                    # not enough budget for a device round trip — serve
                    # the flagged estimator answer instead (when eligible)
                    approx = _degrade.estimate(r.planner, r.f_ir, "deadline")
                    if approx is not None:
                        r.degraded = True
                        _metrics.inc("scheduler.degraded")
                        self._resolve(r, approx)
                        continue
            try:
                self._plan_request(r, cyc)
            except Exception as e:  # parse/guard/plan errors fail one query
                self._fail(r, e)
                continue
            plan = r.plan
            if _groupable(plan):
                groups.setdefault(_group_key(plan), []).append(r)
            else:
                self._n_single += 1
                self._to_completer_single(r)

    def _dispatch_group(self, grp: List[Request], cover: tuple,
                        cyc: _Cycle) -> None:
        """ONE async fused dispatch for a compatible group: per-query boxes
        stack into a (B, 8) array; a covered group scans the blocks of its
        one cover (``_cover_group``; the kernel re-applies the full exact
        mask, so the cover stays a harmless superset), any other the
        table."""
        from geomesa_tpu.index import prune as _prune
        from geomesa_tpu.index.scan import blocks_tier

        self._n_fused += len(grp)
        lead = grp[0].plan
        kern = lead.index.kernels
        batch_id = next(self._batch_ids)
        d = _Dispatch(cyc, batch_id, len(grp))
        union, stats = cover
        d.cover_boxes = stats.get("cover_boxes", 0)
        d.cover_ranges = stats.get("cover_ranges", 0)
        pruned = union is not None
        # attribution tier = the padded batch size the dispatch shipped
        d.tier = max(1, 1 << max(0, (len(grp) - 1)).bit_length())
        d.t_union = _pcn()
        with _trace.annotation("sched.union", batch_id=batch_id):
            boxes = np.concatenate([r.plan.boxes_loose for r in grp], axis=0)
            xfer = boxes.nbytes
            if pruned:
                d.rows_scanned = int(len(union)) * _prune.BLOCK_SIZE
                d.union_tier = blocks_tier(len(union))
                xfer += union.nbytes
        d.t_prepare = _pcn()
        with _trace.annotation("sched.prepare", batch_id=batch_id):
            if pruned:
                disp = kern.prepare_counts_multi_blocks(
                    lead.primary_kind, boxes, lead.windows,
                    lead.residual_device, union, _prune.BLOCK_SIZE)
                d.kernel = f"count_multi_blocks.{lead.primary_kind}"
            else:
                _cols = kern.cols
                d.rows_scanned = int(next(iter(_cols.values())).shape[0]) \
                    if _cols else 0
                disp = kern.prepare_counts_multi(
                    lead.primary_kind, boxes, lead.windows,
                    lead.residual_device)
                d.kernel = f"count_multi.{lead.primary_kind}"
        _attrib.record_transfer(d.kernel, d.tier, xfer)
        for r in grp:
            r.batch_id = batch_id
            r.rows_scanned = d.rows_scanned
        attempts = [0]

        def _launch():
            attempts[0] += 1
            _faults.serve_gate("sched.dispatch")
            return disp()  # async: enqueue only; the completer blocks for it

        d.t_launch = t_launch = _pcn()
        # the device boundary runs behind the breaker + capped-jitter
        # retries: transient dispatch failures retry (and count), a sick
        # device path opens the breaker and subsequent traffic fails fast
        # or degrades instead of piling on
        with _trace.annotation("sched.launch", batch_id=batch_id):
            out = retry_call(_launch, breaker=self.breaker)
        # the probe around a freshly built program timed its first call
        d.first_call_s = _attrib.take_first_call()
        for r in grp:
            r.retries = attempts[0] - 1
            r.t_launch = t_launch
        d.t_put = _pcn()
        self._done.put(("batch", out, grp, d))

    # -- completer thread ---------------------------------------------------

    def _complete_loop(self) -> None:
        while True:
            item = self._done.get()
            if item is _STOP:
                return
            _faults.serve_gate("sched.complete")
            try:
                if item[0] == "batch":
                    self._complete_batch(item[1], item[2], item[3])
                else:
                    self._complete_single(item[1])
            except Exception as e:
                reqs = item[2] if item[0] == "batch" else [item[1]]
                for r in reqs:
                    self._fail(r, e)

    def _complete_batch(self, out, grp: List[Request], d: _Dispatch) -> None:
        d.t_pickup = _pcn()
        # as good as at launch (pickup follows it by ~0.3 ms), and read here
        # because both take a lock the collector should not queue for
        d.queue_depth = self._queue.qsize()
        d.threads = threading.active_count()
        batch_id = d.batch_id
        # host-side LSM-delta counts first: they overlap the in-flight
        # device round trip instead of adding to it
        with _trace.annotation("sched.delta", batch_id=batch_id):
            extras = [len(self.binding.delta_rows(r.delta, r.f_ir, r.auths))
                      if r.delta is not None else 0 for r in grp]
            _faults.serve_gate("sched.device_wait")
        d.t_wait = _pcn()
        try:
            with _trace.annotation("sched.ready_wait", batch_id=batch_id):
                # blocks until the device batch is ready
                counts = np.asarray(out)
        except Exception:
            # a readback failure is a device-path failure too (the dispatch
            # already consumed its retries; the breaker learns either way)
            self.breaker.record_failure()
            raise
        d.t_ready = t_ready = _pcn()
        # host time blocked on the read-back: an upper bound on what the
        # device still had to do when the completer got here, never device
        # time (a device trace gives that)
        wait_s = (t_ready - d.t_wait) / 1e9
        t_launch = d.t_launch
        last = len(grp) - 1
        with _trace.annotation("sched.resolve", batch_id=batch_id):
            for i, r in enumerate(grp):
                r.batched = True
                r.batch_size = len(grp)
                r.scan_s = (_pcn() - t_launch) / 1e9
                n = int(counts[i]) + extras[i]
                self._maybe_cache(r, n)
                if i == last:
                    # the dispatch's record goes out before its last
                    # request is released: whoever holds every answer of a
                    # dispatch finds its event and its timers. `resolve`
                    # therefore ends one set_result short
                    d.t_resolved = _pcn()
                    self._publish(d, grp, wait_s)
                self._resolve(r, n)

    def _publish(self, d: _Dispatch, grp: List[Request],
                 wait_s: float) -> None:
        """A finished dispatch into the ``sched.stage.*`` timers, the
        ``kind=batch`` flight event and, if its cycle took over
        ``_SLOW_CYCLE_S`` from first dequeue to here, ``slow_cycles``."""
        if _trace.enabled():
            _observe_stages(d.stages())
        slow = d.t_resolved - d.cycle.t_first > _SLOW_CYCLE_S * 1e9
        obs = config.OBS_ENABLED.get()
        if not (slow or obs):
            return
        cycle = d.to_dict()
        if slow:
            self._slow_cycles = (self._slow_cycles + [cycle])[
                -_SLOW_CYCLES_KEPT:]
        if obs:
            # the per-dispatch wide event. A fused batch may mix admission
            # classes/tenants: the event carries the distinct labels so the
            # JSONL sink's batch rows are attributable like per-query rows.
            # `duration_ms` is launch → read back; `device_ms` is the host
            # blocked on the read-back, never device time
            _flight.RECORDER.record({
                "kind": "batch", **cycle,
                "type": grp[0].type_name,
                "priority": ",".join(sorted({r.priority for r in grp})),
                "tenant": ",".join(sorted({str(r.tenant or "default")
                                           for r in grp})),
                "duration_ms": round((d.t_ready - d.t_launch) / 1e6, 3),
                "device_ms": round(wait_s * 1000, 3)})

    def _complete_single(self, r: Request) -> None:
        """Fallback execution for plans the fused kernel can't serve (host
        residuals, unions, fid lookups, multi-box primaries, attribute
        slices, empty plans). Runs planner._count with the cached plan — the
        plan/auths work is still amortized even off the fused path. The
        request's deadline rides along as the ambient deadline, so the
        planner's range-decompose/refine checkpoints fire for it too. What
        the planner times here (``range_decompose``, ``refine.device``,
        ``refine``, the device leaves) is kept on the request and hangs
        under its ``scan`` leaf in the caller's trace."""
        if r.deadline is not None and r.deadline.expired:
            self._cancel(r, "single")
            return
        try:
            _faults.serve_gate("sched.single")
            with _rdl.use(r.deadline), _trace.detached() as staged:
                if r.plan.empty:
                    n = 0
                else:  # _count handles empty covers, unions, fids, residuals
                    n = r.planner._count(r.plan, r.f_ir, r.auths)
                if r.delta is not None:
                    n += len(self.binding.delta_rows(r.delta, r.f_ir,
                                                     r.auths))
            if staged is not None:
                r.staged = staged.children
        except DeadlineExceeded as e:
            r.cancelled = True
            _metrics.inc("scheduler.deadline_cancelled")
            self._fail(r, e)
            return
        except Exception as e:
            self._fail(r, e)
            return
        r.scan_s = (_pcn() - r.t_launch) / 1e9
        self._maybe_cache(r, int(n))
        self._resolve(r, int(n))
