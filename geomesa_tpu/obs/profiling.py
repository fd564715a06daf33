"""Device-level kernel profiling: XLA cost analysis, compile telemetry,
recompilation detection, and index-build phase progress.

PR 5's attribution (obs/attrib.py) charges *observed* device time to
kernels; this module adds what XLA itself knows about each kernel and —
crucially — when XLA is asked to compile the *same logical kernel again*
for a new shape. BENCH history shows why that matters:
``cfg1_index_build_s`` swings 170–495 s and cfg4 KNN regressed 472→614 ms
with no telemetry explaining either; plan-shape churn (a padded batch
tier flipping between adjacent powers of two) silently turns steady-state
serving into a compile loop, and nothing counted it.

Three instruments, all of which cost nothing on the steady-state dispatch
path (everything lands at compile/build time):

  recompile detection
      ``note_signature`` is called by ``ScanKernels._get`` on every cache
      miss, keyed by a crc32 hash of the kernel's structural signature
      (mode, primary, residual structure, box/window/capacity tiers — the
      exact key XLA compiles one program per). The FIRST signature for a
      kernel id is its cold compile; any LATER distinct signature — or a
      re-jit of an LRU-evicted one — increments ``kernels.recompiles``
      and drops a ``kernel.recompile`` wide event into the flight
      recorder carrying the triggering shape, so `debug events
      --kind kernel.recompile` answers "what shape churned?".

  cost analysis + compile telemetry
      ``kernel_probe`` wraps each freshly-jitted kernel: the first
      invocation (where XLA traces + compiles) is timed into the
      existing ``kernel.<id>.b<tier>.compile`` series (obs/attrib), then
      a second trace-only lowering feeds ``Lowered.cost_analysis()``
      into ``kernel.<id>.b<tier>.flops`` / ``.hbm_bytes`` gauges — the
      analytic cost model `debug kernels` shows next to the measured
      dispatch/wait times.

  build phase progress
      ``PROGRESS.phase(...)`` wraps the long-running index-build stages
      (encode/upload/sort, plus the mesh-parallel/incremental stages
      ``shard_sort`` / ``splitter_exchange`` / ``merge`` and the online
      reindex's ``swap_install``) with row throughput; live phases and a
      bounded history surface at ``GET /progress``, finished phases emit
      ``progress`` flight events and ``build.<phase>`` registry timers,
      and ``explain`` carries the owning index's stage breakdown.
      Background reindex runs set ``op="reindex"`` and additionally emit
      ``reindex`` flight events (build_started/aborted/installed/failed).

A deterministic fault hook (``arm_kernel_handicap``) stretches matching
kernels' device time by a factor — the fault the doctor's soak
(obs/soak.py) and the trend drill (obs/trenddrill.py) inject.
"""

from __future__ import annotations

import threading
import time
import zlib
from collections import deque
from typing import Dict, List, Optional

from geomesa_tpu import config
from geomesa_tpu.metrics import REGISTRY as _metrics

_pc = time.perf_counter


def enabled() -> bool:
    return bool(config.PROFILING_ENABLED.get()
                and config.OBS_ENABLED.get())


# -- recompile detection ------------------------------------------------------


def signature_hash(key) -> str:
    """Stable short hash of a kernel's structural signature (the jit cache
    key). crc32 of the repr — not ``hash()``, so two processes agree and a
    flight event's hash can be grepped across runs."""
    return format(zlib.crc32(repr(key).encode()), "08x")


def note_signature(seen: Dict[str, set], kernel_id: str, key,
                   shape: Optional[dict] = None) -> None:
    """Record that ``kernel_id`` is being jitted for signature ``key``
    (called by ScanKernels._get on every compiled-cache miss; ``seen`` is
    the owning instance's kernel_id -> signature-hash set, so two indexes
    each compiling their own kernels never read as churn).

    First signature per kernel id = the cold compile. Anything later is a
    RECOMPILE: a new shape (plan-shape churn — the index-build-variance
    suspect) or a re-jit of an evicted signature. Both increment
    ``kernels.recompiles`` and leave the triggering shape in the flight
    recorder."""
    sig = signature_hash(key)
    sigs = seen.get(kernel_id)
    if sigs is None:
        seen[kernel_id] = {sig}
        return
    reason = "evicted" if sig in sigs else "new_shape"
    sigs.add(sig)
    _metrics.inc("kernels.recompiles")
    try:
        from geomesa_tpu.obs.flight import RECORDER
        RECORDER.record({
            "kind": "kernel.recompile",
            "kernel": kernel_id,
            "signature": sig,
            "reason": reason,
            "shape": shape or {},
            "known_signatures": len(sigs),
        })
    except Exception:
        pass  # observability must never fail the compile


# -- deterministic kernel handicap (the soaks' fault hook) --------------------

_handicap: Optional[tuple] = None  # (substring, factor)


def arm_kernel_handicap(match: str, factor: float) -> None:
    """Stretch every dispatch of kernels whose id contains ``match`` by
    ``factor`` (sleep (factor-1) x the measured call time after it). The
    deterministic injection obs/soak.py and obs/trenddrill.py use to prove
    an in-kernel slowdown is flagged AND attributed to the right kernel.
    Applies to kernels compiled after arming."""
    global _handicap
    _handicap = (match, float(factor)) if factor and factor > 1.0 else None


def reset_kernel_handicap() -> None:
    global _handicap
    _handicap = None


def kernel_handicap() -> Optional[tuple]:
    return _handicap


# -- cost analysis + compile probe -------------------------------------------


def _record_cost_analysis(fn, args, kw, kernel_id: str, tier: int) -> None:
    """Best-effort XLA cost model for one compiled kernel: a trace-only
    lowering (no second XLA compile) feeds flops / bytes-accessed gauges
    under the kernel's attribution prefix. Backends that report nothing
    leave the gauges unset."""
    try:
        ca = fn.lower(*args, **kw).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not ca:
            return
        prefix = f"kernel.{kernel_id}.b{int(tier)}."
        flops = ca.get("flops")
        if flops is not None and flops >= 0:
            _metrics.set_gauge(prefix + "flops", float(flops))
        nbytes = ca.get("bytes accessed")
        if nbytes is not None and nbytes >= 0:
            _metrics.set_gauge(prefix + "hbm_bytes", float(nbytes))
    except Exception:
        pass  # cost analysis is advisory; never fail the query


def kernel_probe(fn, kernel_id: str, tier: int):
    """Wrap a freshly-jitted kernel (the profiling-enabled superset of
    obs/attrib.compile_probe): the FIRST invocation times the XLA
    trace+compile into the kernel's compile series and captures its cost
    analysis; later invocations pay one list check — plus the armed
    handicap stretch when the deterministic fault hook matches."""
    from geomesa_tpu.obs import attrib as _attrib
    state: list = []
    h = _handicap
    stretch = h[1] - 1.0 if h is not None and h[0] in kernel_id else 0.0

    def call(*args, **kw):
        if state:
            if stretch:
                t0 = _pc()
                out = fn(*args, **kw)
                import jax
                jax.block_until_ready(out)
                time.sleep(stretch * (_pc() - t0))
                return out
            return fn(*args, **kw)
        t0 = _pc()
        out = fn(*args, **kw)
        state.append(1)
        _attrib.record_compile(kernel_id, tier, _pc() - t0)
        _record_cost_analysis(fn, args, kw, kernel_id, tier)
        return out

    return call


# -- build phase progress -----------------------------------------------------


class _Phase:
    __slots__ = ("op", "phase", "type_name", "rows", "t0", "ts_ms")

    def __init__(self, op, phase, type_name, rows):
        self.op = op
        self.phase = phase
        self.type_name = type_name
        self.rows = rows
        self.t0 = _pc()
        self.ts_ms = int(time.time() * 1000)

    def to_dict(self, done_s: Optional[float] = None) -> dict:
        dt = done_s if done_s is not None else (_pc() - self.t0)
        out = {"op": self.op, "phase": self.phase, "type": self.type_name,
               "ts_ms": self.ts_ms, "rows": self.rows,
               "duration_ms": round(dt * 1000, 1),
               "done": done_s is not None}
        if self.rows and dt > 0:
            out["rows_per_s"] = round(self.rows / dt, 0)
        return out


class BuildProgress:
    """Live + recent phase registry for long-running operations (index
    builds foremost: a 100M-point build is minutes of silence without it).
    ``phase()`` is a context manager; active phases list at GET /progress
    with elapsed time and running row throughput, finished phases keep a
    bounded history, emit a ``progress`` flight event and feed a
    ``build.<phase>`` registry timer (so phase p50/p99 ride /metrics)."""

    def __init__(self, keep: int = 64):
        self._lock = threading.Lock()
        self._active: List[_Phase] = []
        self._recent: deque = deque(maxlen=keep)

    def phase(self, phase: str, rows: Optional[int] = None,
              op: str = "index_build", type_name: Optional[str] = None):
        return _PhaseCtx(self, _Phase(op, phase, type_name, rows))

    def _start(self, p: _Phase) -> None:
        with self._lock:
            self._active.append(p)

    def _finish(self, p: _Phase) -> None:
        dt = _pc() - p.t0
        with self._lock:
            try:
                self._active.remove(p)
            except ValueError:
                pass
            self._recent.append(p.to_dict(done_s=dt))
        _metrics.observe(f"build.{p.phase}", dt)
        try:
            from geomesa_tpu.obs.flight import RECORDER
            ev = dict(self._recent[-1])
            ev["kind"] = "progress"
            RECORDER.record(ev)
        except Exception:
            pass

    def recent(self, type_name: Optional[str] = None,
               limit: Optional[int] = None) -> List[dict]:
        with self._lock:
            items = list(self._recent)
        items.reverse()
        if type_name is not None:
            items = [e for e in items if e.get("type") == type_name]
        return items[: limit] if limit is not None else items

    def snapshot(self) -> dict:
        with self._lock:
            active = [p.to_dict() for p in self._active]
            recent = list(self._recent)
        recent.reverse()
        return {"active": active, "recent": recent}

    def clear(self) -> None:
        with self._lock:
            self._active.clear()
            self._recent.clear()


class _PhaseCtx:
    __slots__ = ("_progress", "_phase", "_span")

    def __init__(self, progress: BuildProgress, phase: _Phase):
        self._progress = progress
        self._phase = phase

    def __enter__(self):
        from geomesa_tpu import trace as _trace
        self._progress._start(self._phase)
        # under an active trace the phase shows as a span too (a traced
        # ingest that triggers a rebuild attributes the build stages)
        self._span = _trace.span(f"build.{self._phase.phase}",
                                 kind="build_phase")
        self._span.__enter__()
        return self._phase

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._progress._finish(self._phase)
        return False


PROGRESS = BuildProgress()
