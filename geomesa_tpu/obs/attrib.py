"""Per-kernel device cost attribution.

The trace layer splits ``device_scan`` (dispatch) from ``device_wait``
(block_until_ready) per QUERY; this module attributes the same costs per
KERNEL — (kernel id, batch tier) — so a fleet-wide p99 regression can be
charged to the one fused kernel that got slower, not just "the device".

Attributed series (all land in metrics.REGISTRY under ``kernel.<id>.b<tier>.*``
so they ride the existing /metrics + Prometheus surfaces and
``snapshot_prefixed("kernel.")`` filtering):

  .dispatches        device dispatch count (counter)
  .device_wait       block-until-ready seconds (histogram timer → p50/p99)
  .dispatch          host-side enqueue seconds (histogram timer)
  .transfer_bytes    host→device bytes shipped for the dispatch (counter)
  .compiles          XLA compilations triggered (counter)
  .compile           compilation seconds (histogram timer)

Kernel ids are ``<mode>.<primary_kind>`` (e.g. ``count_multi_blocks.
point_boxes``); the tier is the padded batch size the dispatch shipped
(the shape XLA actually compiled for).

Wiring:

  - ``ScanKernels._get`` wraps every newly-jitted kernel in
    ``compile_probe`` → first invocation records compile count/time;
  - the scheduler measures the completer's device wait per fused batch
    directly (``record_dispatch``) and the upload bytes per group
    (``record_transfer``);
  - direct-path entry points label the ambient thread
    (``with kernel("count.point_boxes", 1): ...``) and the trace layer's
    device hook charges each ``device_fetch`` to that label.

Everything no-ops when GEOMESA_TPU_OBS is off.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Optional

from geomesa_tpu import config
from geomesa_tpu.metrics import REGISTRY as _metrics

_pc = time.perf_counter


def enabled() -> bool:
    return bool(config.OBS_ENABLED.get())


@functools.lru_cache(maxsize=4096)
def _series(kernel_id: str, tier: int, metric: str) -> str:
    # cached: the hot device hook would otherwise build 2-3 f-strings per
    # dispatch; the set of (kernel, tier, metric) names is small and stable
    return f"kernel.{kernel_id}.b{int(tier)}.{metric}"


def record_dispatch(kernel_id: str, tier: int, wait_s: float,
                    dispatch_s: float = 0.0, n: int = 1) -> None:
    """Charge one device round trip to (kernel id, batch tier)."""
    if not enabled():
        return
    _metrics.inc(_series(kernel_id, tier, "dispatches"), n)
    _metrics.observe(_series(kernel_id, tier, "device_wait"), wait_s)
    if dispatch_s > 0:
        _metrics.observe(_series(kernel_id, tier, "dispatch"), dispatch_s)


def record_transfer(kernel_id: str, tier: int, nbytes: int) -> None:
    """Charge host→device bytes (query constants, block ids, table planes)."""
    if nbytes and enabled():
        _metrics.inc(_series(kernel_id, tier, "transfer_bytes"), int(nbytes))


def record_compile(kernel_id: str, tier: int, seconds: float) -> None:
    # the probes call this from the thread that made a kernel's first call;
    # the scheduler's cycle record takes the seconds from there
    _local.first_call_s = seconds
    if not enabled():
        return
    _metrics.inc(_series(kernel_id, tier, "compiles"))
    _metrics.observe(_series(kernel_id, tier, "compile"), seconds)


def compile_probe(fn, kernel_id: str, tier: int):
    """Wrap a freshly-jitted kernel: its FIRST invocation (where XLA
    traces + compiles) is timed and recorded as the kernel's compile cost;
    later invocations pass straight through (one list check)."""
    state: list = []

    def call(*args, **kw):
        if state:
            return fn(*args, **kw)
        t0 = _pc()
        out = fn(*args, **kw)
        state.append(1)
        record_compile(kernel_id, tier, _pc() - t0)
        return out

    return call


# -- ambient labeling for the direct (unscheduled) path -----------------------


class _Local(threading.local):
    label = None  # (kernel_id, tier) | None
    first_call_s = None  # seconds of the last first call on this thread


_local = _Local()


def take_first_call() -> Optional[float]:
    """Seconds of a kernel's first call (trace + compile, or a load from the
    compile cache) made on this thread since the last take, else None — as
    ``compile_probe`` / ``kernel_probe`` timed it, not timed again."""
    s = _local.first_call_s
    if s is not None:
        _local.first_call_s = None
    return s


class kernel:
    """Context manager labeling this thread's device fetches with a
    (kernel id, batch tier) — the trace layer's device hook charges each
    ``device_fetch`` inside to the label. Nesting keeps the innermost."""

    __slots__ = ("_label", "_prev")

    def __init__(self, kernel_id: str, tier: int = 1):
        self._label = (kernel_id, tier) if enabled() else None

    def __enter__(self):
        self._prev = _local.label
        if self._label is not None:
            _local.label = self._label
        return self

    def __exit__(self, *exc):
        _local.label = self._prev
        return False


# labeled fetches awaiting their registry feed: the device hook sits on the
# per-query hot path, so it pays ONE list append (GIL-atomic) and the
# histogram math happens at the next flush (registry pre-drain / reader)
_pending_fetches: list = []
_PENDING_FETCH_MAX = 4096
_flush_lock = threading.Lock()


def _on_device_fetch(dispatch_s: float, wait_s: float) -> None:
    """trace.set_device_hook slot: charge an ambient-labeled fetch. The
    enabled() gate was already paid when the label was installed; the
    registry feed is deferred (see flush)."""
    lab = _local.label
    if lab is None:
        return
    _pending_fetches.append((lab, dispatch_s, wait_s))
    if len(_pending_fetches) > _PENDING_FETCH_MAX:
        flush()


def flush() -> None:
    """Fold pending labeled fetches into the registry (wait + dispatch
    timers per (kernel id, tier); the wait histogram's count IS the
    dispatch count). Runs from the registry's pre-drain hook and any
    attribution reader."""
    if not _pending_fetches:
        return
    with _flush_lock:
        pending = _pending_fetches[:]
        # concurrent appends land past the copied prefix and survive
        del _pending_fetches[: len(pending)]
    batch = []
    for (kid, tier), dispatch_s, wait_s in pending:
        batch.append((_series(kid, tier, "device_wait"), wait_s))
        batch.append((_series(kid, tier, "dispatch"), dispatch_s))
    _metrics.observe_batch(batch)


def install() -> None:
    """Wire the device hook into the trace layer (idempotent)."""
    from geomesa_tpu import trace as _trace
    _trace.set_device_hook(_on_device_fetch)


def snapshot() -> dict:
    """The per-kernel attribution series (counters/timers under
    ``kernel.``) — the CLI/web summary feed."""
    flush()
    return _metrics.snapshot_prefixed("kernel.")


# -- explain(analyze=True) annotation ----------------------------------------


def annotate_tree(node: dict) -> float:
    """Annotate a trace-tree dict in place: each span gains ``device_ms``
    (device time in its subtree) and ``cached: False`` on plan/
    range_decompose spans (a span that RAN was, by construction, not
    served from a cache — cache hits show as ABSENT spans). Returns the
    node's subtree device ms."""
    kind = node.get("kind")
    own = node.get("self_ms", node.get("duration_ms", 0.0)) \
        if kind in ("device_scan", "device_wait") else 0.0
    dev = own + sum(annotate_tree(c) for c in node.get("children", ()))
    node["device_ms"] = round(dev, 3)
    if kind in ("plan", "range_decompose"):
        node["cached"] = False
    return dev
