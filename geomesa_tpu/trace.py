"""Query tracing: nested spans, per-query traces, a bounded ring of recents.

≙ the reference's Explainer threaded through QueryPlanner (every scan
accounts for its plan, ranges, and timings) plus the QueryEvent audit trail
(index/audit/QueryEvent.scala) — upgraded to a span tree so time attributes
to *stages*, not just plan-vs-scan. The load-bearing distinction is
``device_scan`` (dispatch: host work to enqueue the XLA computation) vs
``device_wait`` (time inside ``block_until_ready``): the dispatch floor and
the device compute are different bottlenecks — this layer makes that split
visible per-query.

Span kinds (the fixed vocabulary hot paths use):

  plan             filter parse + strategy selection
  range_decompose  key-range → candidate-block cover computation
  submit           scheduled count, on the caller's thread: filter parse,
                   Request, result-cache probe, admission (serve/scheduler.py)
  queue_wait       scheduled count: submit → its micro-batch closed
  batch_host       scheduled count: batch closed → device launch (the
                   collector planning the whole batch; the request's own
                   ``plan`` / ``range_decompose`` nest under it)
  scan             umbrella execution stage (staging + kernel + readback);
                   its SELF time is constant staging / host glue. On a
                   scheduled count: launch → resolved, ``batch_id`` in attrs
  wake             scheduled count: resolved → the caller's thread runs again
  device_scan      kernel dispatch (host-side enqueue, async)
  device_wait      block_until_ready on the dispatched result
  refine           host f64 re-evaluation of device candidates
  aggregate        host-side merge/summarize (density decode, join merge…)
  serialize        row hydration / output encoding
  wal_append       write-ahead-log frame write (durability/wal.py)
  wal_fsync        group-commit fsync (the durability tax, measured)
  recovery         snapshot load + WAL replay at DataStore.open()

Usage::

    with trace("query", type="gdelt", filter=str(f)) as t:
        with span("plan"):
            ...
    RING.recent()          # most-recent-first trace dicts (the audit ring)
    with disabled():       # hot-loop opt-out: spans become no-ops
        ...

Every span (and root trace) also feeds ``metrics.REGISTRY`` as a histogram
timer under its name, so the Prometheus surface gets per-stage percentiles
for free — spans REPLACE the ad-hoc ``REGISTRY.time(...)`` calls on the hot
paths. ``trace()`` nests: opened under an active trace it degrades to a
plain span, so datastore-level and planner-level roots compose.

Clock: every span holds its start on ``time.perf_counter_ns``; ``ANCHOR``
pairs one reading of that clock with ``time.time_ns`` at import, so
``epoch_ms(ns)`` puts any span — and the scheduler's dispatch-cycle stages,
which run on threads with no active trace — on the wall clock the flight
recorder stamps ``ts_ms`` with. ``to_dict`` gives ``start_ms`` relative to
the root.

Thread model: the current trace is thread-local (one query per thread, the
ThreadingHTTPServer model); the ring buffer is process-global and locked.

Fleet context (obs/federation.py rides on these primitives): every root
trace carries a process-stable ``node_id``/``role`` dimension and a
globally-unique ``global_id`` (``<node>-<local id>``). A proxied request
propagates its context over HTTP (X-Trace-Id / X-Span-Id / X-Trace-Node /
X-Trace-Sampled — ``inject_headers``/``extract_headers``); the receiving
process opens its root trace as a CHILD of the remote parent
(``remote_parent``), sharing the parent's global id so a stitcher can
reassemble ONE cross-process tree from the per-node halves.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
import zlib
from collections import deque
from typing import Dict, Iterator, List, Optional

from geomesa_tpu.metrics import REGISTRY as _REGISTRY

SPAN_KINDS = ("plan", "range_decompose", "submit", "queue_wait", "batch_host",
              "scan", "wake", "device_scan",
              "device_wait", "refine", "aggregate", "serialize",
              "wal_append", "wal_fsync", "recovery",
              # query-lifecycle resilience (serve/resilience/): a request
              # cancelled at its deadline BEFORE device dispatch, a count
              # degraded to the stats estimator, a request shed by admission
              # control — the overload test asserts on these leaves
              "cancel", "degrade", "shed",
              # long-running build phase (encode/upload/sort — obs/profiling
              # PROGRESS): a traced ingest that triggers a rebuild
              # attributes the build stages instead of one opaque span
              "build_phase",
              # cross-process collective op (cluster/: psum dispatch,
              # host allgather, barrier, row exchange) — stitched traces
              # show where a distributed query's wall time went
              "collective")

_pcn = time.perf_counter_ns  # cached: spans sit on µs-scale hot paths

# (perf_counter_ns, time_ns) read together once: the one bridge between the
# span clock and the wall clock
ANCHOR = (time.perf_counter_ns(), time.time_ns())


def epoch_ms(ns: int) -> float:
    """Wall-clock milliseconds of a ``perf_counter_ns`` reading."""
    return (ANCHOR[1] + (ns - ANCHOR[0])) / 1e6


class _Local(threading.local):
    # class-level defaults make `_local.trace` a plain read on threads that
    # never traced (no getattr-with-default on the hot path)
    trace = None
    stack = None
    remote = None  # pending RemoteParent consumed by the next root trace


_local = _Local()
_ids = itertools.count(1)
_span_ids = itertools.count(1)


# -- node identity (the fleet dimension on every trace/event/metric) ----------


class _Node:
    id: Optional[str] = None
    role = "standalone"


def node_id() -> str:
    """Process-stable node identity: GEOMESA_TPU_NODE_ID, else
    ``<short-hostname>-<pid>`` (unique per incarnation on one host — the
    shape localhost fleets and tests produce)."""
    nid = _Node.id
    if nid is None:
        from geomesa_tpu import config
        nid = str(config.NODE_ID.get() or "").strip()
        if not nid:
            try:
                import socket as _socket
                host = _socket.gethostname().split(".")[0]
            except OSError:
                host = "node"
            nid = f"{host}-{os.getpid()}"
        _Node.id = nid
    return nid


def node_role() -> str:
    return _Node.role


def set_node_role(role: str) -> None:
    """Stamp this process's fleet role (primary / replica / router /
    standalone) — replication and router constructors call it so every
    trace/flight event carries the role it was produced under."""
    _Node.role = str(role)


def _reset_node_for_tests() -> None:
    _Node.id = None
    _Node.role = "standalone"


# -- cross-process propagation ------------------------------------------------


class RemoteParent:
    """The extracted upstream context: the remote parent this process's
    next root trace is a child of."""

    __slots__ = ("trace_id", "span_id", "node", "sampled")

    def __init__(self, trace_id: str, span_id: Optional[int],
                 node: Optional[str], sampled: bool):
        self.trace_id = str(trace_id)
        self.span_id = int(span_id) if span_id else None
        self.node = node
        self.sampled = bool(sampled)

    def to_dict(self) -> dict:
        out = {"trace": self.trace_id}
        if self.span_id is not None:
            out["span"] = self.span_id
        if self.node is not None:
            out["node"] = self.node
        return out


def extract_headers(headers) -> Optional[RemoteParent]:
    """RemoteParent from incoming HTTP headers (None when the request
    carries no trace context or propagation is off)."""
    if headers is None:
        return None
    tid = headers.get("X-Trace-Id")
    if not tid:
        return None
    from geomesa_tpu import config
    if not config.FED_PROPAGATE.get():
        return None
    try:
        span_id = int(headers.get("X-Span-Id") or 0)
    except (TypeError, ValueError):
        span_id = 0
    return RemoteParent(tid, span_id or None, headers.get("X-Trace-Node"),
                        str(headers.get("X-Trace-Sampled") or "0") == "1")


def inject_headers() -> Dict[str, str]:
    """Propagation headers for an outbound hop made under the current
    trace: the trace's global id, the CURRENT span's id (assigned on
    demand — the remote half parents under it), this node, and the
    sampling decision (sticky once made: deterministic on the global id,
    so every hop of one request agrees without coordination)."""
    tr = _local.trace
    if tr is None:
        return {}
    from geomesa_tpu import config
    if not config.FED_PROPAGATE.get():
        return {}
    sp = _local.stack[-1]
    if sp.span_id is None:
        sp.span_id = next(_span_ids)
    gid = tr.global_id
    if not tr.sampled_hint:
        rate = float(config.OBS_SAMPLE.get())
        if rate > 0 and (zlib.crc32(gid.encode()) % 10_000) < rate * 10_000:
            tr.sampled_hint = True
    return {"X-Trace-Id": gid,
            "X-Span-Id": str(sp.span_id),
            "X-Trace-Node": node_id(),
            "X-Trace-Sampled": "1" if tr.sampled_hint else "0"}


class remote_parent:
    """Context manager binding an extracted RemoteParent to this thread:
    the next ROOT trace opened inside becomes its child (adopts the
    remote global id, records the parent span, honors the propagated
    sampling decision). None is a no-op, so callers pass
    ``extract_headers(...)`` unconditionally."""

    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[RemoteParent]):
        self._ctx = ctx

    def __enter__(self):
        self._prev = _local.remote
        if self._ctx is not None:
            _local.remote = self._ctx
        return self._ctx

    def __exit__(self, *exc):
        _local.remote = self._prev
        return False


class _State:
    enabled = True


_state = _State()


def set_enabled(on: bool) -> None:
    """Globally enable/disable tracing (spans become no-ops when off)."""
    _state.enabled = bool(on)


class disabled:
    """Context manager: suspend tracing AND span→registry feeding inside.
    The perf-budget guard compares against this mode."""

    def __enter__(self):
        self._prev = _state.enabled
        _state.enabled = False
        return self

    def __exit__(self, *exc):
        _state.enabled = self._prev
        return False


class Span:
    """One timed stage. ``self_ms`` is duration minus child durations —
    the time this stage spent NOT delegated to a sub-stage. ``children`` is
    None until the first child attaches (most spans are leaves; the lazy
    list keeps leaf allocation to one object on the hot path)."""

    __slots__ = ("name", "kind", "attrs", "start_ns", "duration_ms",
                 "children", "span_id")

    def __init__(self, name: str, kind: Optional[str], attrs: Optional[dict]):
        self.name = name
        self.kind = kind if kind is not None else (
            name if name in SPAN_KINDS else "span")
        self.attrs = attrs
        # perf_counter_ns at entry; None on a leaf recorded without its end
        self.start_ns: Optional[int] = None
        self.duration_ms = 0.0
        self.children: Optional[List[Span]] = None
        # assigned on demand (inject_headers) when this span parents a
        # remote child — the stitcher's attachment point
        self.span_id: Optional[int] = None

    def add_child(self, node: "Span") -> None:
        c = self.children
        if c is None:
            self.children = [node]
        else:
            c.append(node)

    @property
    def self_ms(self) -> float:
        if not self.children:
            return self.duration_ms
        return self.duration_ms - sum(c.duration_ms for c in self.children)

    def walk(self) -> Iterator["Span"]:
        yield self
        if self.children:
            for c in self.children:
                yield from c.walk()

    def to_dict(self, origin_ns: Optional[int] = None) -> dict:
        """``start_ms`` is relative to ``origin_ns`` — the root's start, which
        the root passes down; called on its own a span is its own origin."""
        if origin_ns is None:
            origin_ns = self.start_ns
        d = {"name": self.name, "kind": self.kind,
             "duration_ms": round(self.duration_ms, 3),
             "self_ms": round(self.self_ms, 3)}
        if self.start_ns is not None and origin_ns is not None:
            d["start_ms"] = round((self.start_ns - origin_ns) / 1e6, 3)
        if self.span_id is not None:
            d["span_id"] = self.span_id
        if self.attrs:
            d["attrs"] = {k: str(v) for k, v in self.attrs.items()}
        if self.children:
            d["children"] = [c.to_dict(origin_ns) for c in self.children]
        return d


class QueryTrace:
    """One query's span tree (≙ one QueryEvent, with stage attribution).
    ``error`` is the exception type name when the traced block raised —
    the tail sampler's keep-always signal."""

    __slots__ = ("trace_id", "name", "ts_ms", "root", "error",
                 "parent", "sampled_hint", "_global_id")

    def __init__(self, name: str, attrs: Optional[dict]):
        self.trace_id = next(_ids)
        self.name = name
        self.ts_ms = int(time.time() * 1000)
        self.root = Span(name, "trace", attrs)
        self.error: Optional[str] = None
        # fleet context: the remote parent this trace is a child of, the
        # propagated keep-me sampling decision, and the cross-process id
        # (adopted from the parent, else derived lazily from node+local id)
        self.parent: Optional[RemoteParent] = None
        self.sampled_hint = False
        self._global_id: Optional[str] = None

    @property
    def global_id(self) -> str:
        gid = self._global_id
        if gid is None:
            gid = self._global_id = f"{node_id()}-{self.trace_id}"
        return gid

    @property
    def duration_ms(self) -> float:
        return self.root.duration_ms

    def spans(self) -> Iterator[Span]:
        """Depth-first over every span EXCLUDING the root."""
        for c in self.root.children or ():
            yield from c.walk()

    def kinds(self) -> set:
        return {s.kind for s in self.spans()}

    def self_times_ms(self) -> Dict[str, float]:
        """Total self-time per span kind — the per-stage breakdown."""
        out: Dict[str, float] = {}
        for s in self.spans():
            out[s.kind] = out.get(s.kind, 0.0) + s.self_ms
        return out

    def coverage(self) -> float:
        """Fraction of the root wall time attributed to (non-root) span
        self-times — 1.0 means every microsecond is accounted for."""
        if self.root.duration_ms <= 0:
            return 1.0
        return sum(s.self_ms for s in self.spans()) / self.root.duration_ms

    def to_dict(self) -> dict:
        out = {"id": self.trace_id, "name": self.name, "ts_ms": self.ts_ms,
               "global_id": self.global_id,
               "node": node_id(), "role": _Node.role,
               "duration_ms": round(self.duration_ms, 3),
               "stages_ms": {k: round(v, 3)
                             for k, v in self.self_times_ms().items()},
               "root": self.root.to_dict()}
        try:
            from geomesa_tpu.cluster.runtime import event_dims
            out.update(event_dims())   # process/shard on an active cluster
        except Exception:
            pass
        if self.parent is not None:
            out["parent"] = self.parent.to_dict()
        if self.error is not None:
            out["error"] = self.error
        return out


class TraceRing:
    """Bounded process-global buffer of completed traces (the audit ring;
    ≙ the reference's in-memory audit trail the `_queries` surface reads)."""

    def __init__(self, keep: int = 256):
        self._ring: deque = deque(maxlen=keep)

    def append(self, t: QueryTrace) -> None:
        # lockless: deque appends are GIL-atomic, and this sits on the
        # trace-close hot path; readers retry the mutated-mid-copy race
        self._ring.append(t)

    def recent(self, limit: Optional[int] = None) -> List[dict]:
        """Most-recent-first trace dicts, bounded by ``limit``."""
        while True:
            try:
                items = list(self._ring)
                break
            except RuntimeError:  # mutated during the copy — retry
                continue
        items.reverse()
        if limit is not None:
            items = items[: max(0, int(limit))]
        return [t.to_dict() for t in items]

    def clear(self) -> None:
        self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)


RING = TraceRing()

# -- observability hooks (obs/ installs these; trace.py stays import-light) --
#
# Close hooks fire once per ROOT trace at close (tail sampling + flight-
# recorder derivation, obs/flight.py / obs/sampling.py); the device hook
# fires per device_fetch with (dispatch_s, wait_s) so per-kernel attribution
# (obs/attrib.py) can charge device time to the kernel an ambient label
# names. Both are None/empty by default — the hot path pays one read.

_close_hooks: List = []
_device_hook = None


def add_close_hook(fn) -> None:
    """Register ``fn(QueryTrace)`` to run at every root-trace close (after
    the trace landed in RING). A raising hook is dropped from that close,
    never the query. Idempotent per function object."""
    if fn not in _close_hooks:
        _close_hooks.append(fn)


def remove_close_hook(fn) -> None:
    if fn in _close_hooks:
        _close_hooks.remove(fn)


def set_device_hook(fn) -> None:
    """Install ``fn(dispatch_s, wait_s)`` called from ``device_fetch`` —
    the per-kernel device-cost attribution slot. None uninstalls."""
    global _device_hook
    _device_hook = fn


def current_trace() -> Optional[QueryTrace]:
    return _local.trace


def mark_root(**attrs) -> None:
    """Set attributes on the active trace's ROOT from a stage nested in it:
    what close hooks decide on (``scheduled``) is read from the root."""
    tr = _local.trace
    if tr is not None:
        if tr.root.attrs is None:
            tr.root.attrs = attrs
        else:
            tr.root.attrs.update(attrs)


class span:
    """Context manager timing one stage. Attaches to the active trace (when
    one exists) and feeds the metrics registry under ``name`` either way —
    the drop-in replacement for ``REGISTRY.time(name)``. ~µs overhead when
    enabled; a no-op under ``disabled()``."""

    __slots__ = ("name", "kind", "attrs", "_node", "_t0")

    def __init__(self, name: str, kind: Optional[str] = None, **attrs):
        self.name = name
        self.kind = kind
        self.attrs = attrs or None

    def __enter__(self):
        if not _state.enabled:
            self._t0 = None
            return self
        tr = _local.trace
        if tr is not None:
            node = Span(self.name, self.kind, self.attrs)
            stack = _local.stack
            stack[-1].add_child(node)
            stack.append(node)
            self._node = node
        else:
            self._node = None
        self._t0 = _pcn()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only once the stage has run."""
        node = self._node if self._t0 is not None else None
        if node is not None:
            node.attrs = {**(node.attrs or {}), **attrs}

    def __exit__(self, *exc):
        t0 = self._t0
        if t0 is None:
            return False
        dt = _pcn() - t0
        node = self._node
        if node is not None:
            # under an active trace the registry feed is DEFERRED to trace
            # close (one batched lock acquisition for the whole span tree),
            # keeping per-span exit cost to pure bookkeeping
            node.start_ns = t0
            node.duration_ms = dt / 1e6
            _local.stack.pop()
        else:
            _REGISTRY.observe(self.name, dt / 1e9)
        return False


def enabled() -> bool:
    return _state.enabled


class detached:
    """Stages a worker thread times on behalf of a request whose trace is
    open on its caller's thread: inside, ``span`` / ``record`` /
    ``device_fetch`` hang under a root of this thread's own that lands
    nowhere (no ring, no close hook, no registry feed). Yields that root, or
    None with tracing off; the owner moves its ``children`` under a span of
    the caller's trace, whose close feeds them to the registry once."""

    __slots__ = ("_on",)

    def __enter__(self) -> Optional[Span]:
        self._on = _state.enabled and _local.trace is None
        if not self._on:
            return None
        t = _local.trace = QueryTrace("detached", None)
        _local.stack = [t.root]
        return t.root

    def __exit__(self, *exc):
        if self._on:
            _local.trace = None
            _local.stack = None
        return False


def _leaf(name: str, kind: str, duration_ms: float,
          start_ns: Optional[int] = None,
          attrs: Optional[dict] = None) -> Span:
    """Allocate a completed leaf span without the __init__ frame (hot path)."""
    s = Span.__new__(Span)
    s.name = name
    s.kind = kind
    s.attrs = attrs
    s.start_ns = start_ns
    s.duration_ms = duration_ms
    s.children = None
    s.span_id = None
    return s


def record(name: str, kind: str, seconds: float,
           end_ns: Optional[int] = None, attrs: Optional[dict] = None,
           parent: Optional[Span] = None) -> Optional[Span]:
    """Record an already-timed stage without context manager dispatch — the
    minimal-overhead hook for µs-scale hot paths. Callers gate their own
    timing on ``enabled()``. ``end_ns`` is the ``perf_counter_ns`` reading
    the caller already took at the stage's end: the start is that minus the
    duration, so a start costs no clock call here. The span hangs under
    ``parent`` (a span an earlier ``record`` returned), else under the
    innermost open span; with neither, the seconds go straight to the
    registry and None is returned."""
    if parent is None:
        if _local.trace is None:
            _REGISTRY.observe(name, seconds)
            return None
        parent = _local.stack[-1]
    node = _leaf(name, kind, seconds * 1000,
                 None if end_ns is None else end_ns - int(seconds * 1e9),
                 attrs)
    parent.add_child(node)
    return node


def device_fetch(block, dispatch, *args):
    """Fused device_scan + device_wait recorder for the kernel hot path:
    ``block(dispatch(*args))`` with both stages timed through ONE function
    call instead of two context managers (the per-query span overhead budget
    is single-digit µs — see tests/test_perf_budget.py)."""
    if not _state.enabled:
        return block(dispatch(*args))
    t0 = _pcn()
    out = dispatch(*args)
    t1 = _pcn()
    out = block(out)
    t2 = _pcn()
    hook = _device_hook
    if hook is not None:
        hook((t1 - t0) / 1e9, (t2 - t1) / 1e9)
    tr = _local.trace
    if tr is not None:
        parent = _local.stack[-1]
        parent.add_child(_leaf("device_scan", "device_scan",
                               (t1 - t0) / 1e6, t0))
        parent.add_child(_leaf("device_wait", "device_wait",
                               (t2 - t1) / 1e6, t1))
    else:
        _REGISTRY.observe_batch(
            [("device_scan", (t1 - t0) / 1e9),
             ("device_wait", (t2 - t1) / 1e9)])
    return out


_annotation_cls = None


def annotation(name: str, **kw):
    """``jax.profiler.TraceAnnotation(name, **kw)``: a host span in the
    profiler's own trace, on the device trace's clock. Imported on first use
    (this module stays free of jax at import); outside a profiler session
    entering one is a flag check."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls(name, **kw)


class trace:
    """Root context manager: opens a QueryTrace, lands it in ``RING`` on
    exit, and feeds the registry timer under ``name``. Re-entrant: under an
    already-active trace it degrades to a nested span (so a datastore-level
    root composes with planner-level instrumentation). Yields the QueryTrace
    (root) or Span (nested) — both expose ``to_dict()`` — or None when
    tracing is disabled."""

    __slots__ = ("name", "attrs", "_t0", "_trace", "_span")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs or None

    def __enter__(self):
        self._trace = self._span = None
        if not _state.enabled:
            self._t0 = None
            return None
        if _local.trace is not None:
            self._span = span(self.name, kind="trace",
                              **(self.attrs or {}))
            return self._span.__enter__()._node
        t = QueryTrace(self.name, self.attrs)
        remote = _local.remote
        if remote is not None:
            # this root is the remote parent's child: adopt its global id
            # (ONE cross-process trace) and its sampling decision, and
            # consume the context so nested/subsequent roots on this
            # thread don't re-parent under it
            t.parent = remote
            t._global_id = remote.trace_id
            t.sampled_hint = remote.sampled
            _local.remote = None
        _local.trace = t
        _local.stack = [t.root]
        self._trace = t
        self._t0 = t.root.start_ns = _pcn()
        return t

    def __exit__(self, *exc):
        if self._span is not None:
            return self._span.__exit__(*exc)
        if self._t0 is None:
            return False
        t = self._trace
        t.root.duration_ms = (_pcn() - self._t0) / 1e6
        if exc and exc[0] is not None:
            t.error = exc[0].__name__
        _local.trace = None
        _local.stack = None
        RING.append(t)
        for hook in _close_hooks:
            try:
                hook(t)
            except Exception:
                pass  # observability must never fail the query
        # deferred feed: the whole span tree drains into the histograms at
        # the next snapshot — trace close pays one list append. The trace id
        # rides along so retained traces become bucket exemplars at drain
        # (after the hooks: the tail sampler has to know the trace by then,
        # should this feed be the one that drains).
        _REGISTRY.feed_tree(t.root, trace_id=t.trace_id)
        return False
