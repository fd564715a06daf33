"""Request-centric observability: flight recorder, tail-sampled traces
with exemplars, per-kernel device cost attribution, SLO burn rates —
plus the performance observatory (ISSUE 6): device-level kernel
profiling with recompile detection and build-phase progress
(obs/profiling.py).

Layered ON TOP of trace.py/metrics.py (which stay import-light and
hook-based): ``install()`` wires

  - a trace close hook: every closed root trace is offered to the tail
    sampler (obs/sampling.py) and — unless the scheduler already emitted
    a richer event for it — derived into a flight-recorder wide event
    (obs/flight.py);
  - the registry's exemplar filter: only tail-retained trace ids become
    /metrics bucket exemplars;
  - the trace device hook: per-kernel attribution of dispatch/wait time
    (obs/attrib.py);
  - the default SLOs (obs/slo.py) when none are registered.

``install()`` is idempotent and called from TpuDataStore/QueryScheduler
construction, so any store-bearing process is observable by default;
GEOMESA_TPU_OBS=0 turns the per-request work off at runtime without
uninstalling.

Import discipline: obs submodules import only config/metrics/trace —
never the planner/scheduler/datastore layers — so hot paths (index/scan,
serve/scheduler) can import them without cycles. The close hook computes
the per-stage breakdown ONCE and shares it between the sampling decision
and the wide event (the hot-path budget is guarded by
tests/test_perf_budget.py's obs overhead bar).
"""

from __future__ import annotations

from geomesa_tpu import config as _config
from geomesa_tpu.obs import flight as _flight
from geomesa_tpu.obs import sampling as _sampling

_INSTALLED = False

# cached GEOMESA_TPU_OBS verdict for the close hook (an env read per trace
# close is measurable on µs-scale queries); re-read every _ENABLED_REFRESH
# closes so flipping the knob at runtime still takes effect promptly
_enabled_cache = [True, 0]
_ENABLED_REFRESH = 64


def _obs_enabled() -> bool:
    c = _enabled_cache
    c[1] -= 1
    if c[1] <= 0:
        c[0] = bool(_config.OBS_ENABLED.get())
        c[1] = _ENABLED_REFRESH
    return c[0]


def install() -> None:
    """Wire the observability hooks (idempotent)."""
    global _INSTALLED
    if _INSTALLED:
        return
    _INSTALLED = True
    from geomesa_tpu import trace as _trace
    from geomesa_tpu.metrics import REGISTRY as _metrics
    from geomesa_tpu.obs import attrib as _attrib
    from geomesa_tpu.obs import slo as _slo
    _trace.add_close_hook(_on_trace_close)
    _metrics.set_exemplar_filter(_retained_filter)

    # the sampler's deferred retention decisions, the device hook's
    # pending fetch attributions, and the workload plane's pending event
    # queue all settle right before any snapshot-ish registry read, so
    # surfaces stay accurate without the per-query hot path paying for any
    def _pre_drain(reader: bool = True):
        _sampling.SAMPLER.drain()
        _attrib.flush()
        if not reader:
            # the registry folding its own backlog at a trace close: the
            # roll-ups and the history tick stay a reader's to pay for (both
            # are bounded on their own)
            return
        from geomesa_tpu.obs import history as _history
        from geomesa_tpu.obs import workload as _workload
        _workload.WORKLOAD.drain()
        # history sampler LAST, so a tick retains the just-drained state;
        # self-throttled to the finest tier interval and reentrancy-guarded
        # (taking a sample reads the registry, which re-enters this hook)
        _history.HISTORY.maybe_sample()

    _metrics.set_pre_drain_hook(_pre_drain)
    _metrics.set_gauge("obs.flight_depth", lambda: len(_flight.RECORDER))
    _attrib.install()
    if not _slo.ENGINE.objectives():
        for obj in _slo.default_objectives():
            _slo.ENGINE.add(obj)


def _retained_filter(trace_id: int) -> bool:
    return _sampling.SAMPLER.is_retained(trace_id)


def _on_trace_close(t) -> None:
    """Root-trace close: enqueue for the tail sampler's DEFERRED retention
    decision and for lazy wide-event derivation — the hot path pays two
    appends; decisions and event dicts materialize when somebody reads
    /events, /traces?retained=1, or a metrics snapshot. Scheduled counts
    skip the event (their requests emit richer ones with cache/batch/
    admission fields — see serve/scheduler.py)."""
    if not _obs_enabled():
        return
    _sampling.SAMPLER.enqueue(t)
    attrs = t.root.attrs
    if attrs is not None and attrs.get("scheduled"):
        return
    _flight.RECORDER.record_trace(t)


def installed() -> bool:
    return _INSTALLED
