"""Cluster bring-up + topology: the one home for jax.distributed state.

Bring-up order matters and is easy to get wrong, so it lives here once:

  1. On the CPU backend, cross-process collectives need the gloo
     implementation selected BEFORE ``jax.distributed.initialize`` —
     without it every multi-process jit fails with "Multiprocess
     computations aren't implemented on the CPU backend".
  2. ``jax.distributed.initialize(coordinator, num_processes, process_id)``
     with a bounded rendezvous timeout (a missing peer fails the
     bring-up instead of hanging the fleet).
  3. The mesh device order is ``sorted(devices, key=(process_index, id))``
     so process p's devices form one contiguous block of the ``rows``
     axis — process p owns rows [p*per_proc, (p+1)*per_proc) under
     ``NamedSharding(P("rows"))``, which is what makes rank order ==
     key order for the ordered select merge.

Topology is first-class config (GEOMESA_TPU_CLUSTER_TOPOLOGY):
``flat`` is one process-contiguous ``rows`` axis (CPU dryruns, single
slice); ``hybrid`` builds ``create_hybrid_device_mesh`` with a ``dcn``
axis across slices and ICI-contiguous ``rows`` within one; ``auto``
picks hybrid iff >1 slice is detected. ``hybrid`` without multiple
slices raises — a misconfigured mesh fails loudly (same discipline as
the create_mesh fix in parallel/mesh.py).
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from geomesa_tpu import config


def note_collective(op: str, seconds: float,
                    payload_bytes: int = 0) -> None:
    """Record one collective round: a ``cluster.collective.<op>`` timer
    (a leaf span under an active trace, a registry histogram otherwise)
    plus a payload-bytes counter. Never raises into the collective."""
    try:
        from geomesa_tpu import trace as _trace
        _trace.record(f"cluster.collective.{op}", "collective", seconds)
        if payload_bytes:
            from geomesa_tpu.metrics import REGISTRY
            REGISTRY.inc(f"cluster.collective.{op}.bytes",
                         int(payload_bytes))
    except Exception:
        pass


class ClusterConfigError(ValueError):
    """A cluster knob combination that cannot work (fail loudly)."""


def _slice_index(dev) -> int:
    return int(getattr(dev, "slice_index", 0) or 0)


@dataclass
class ClusterRuntime:
    """Process-global cluster state (one per process, like the Federator)."""

    coordinator: str = ""
    num_processes: int = 1
    process_id: int = 0
    topology: str = "auto"
    initialized: bool = False
    psum_rounds: int = 0
    # type_name -> {"proc_rows": [...], "key_ranges": [...], ...}
    tables: Dict[str, dict] = field(default_factory=dict)
    _mesh_cache: Dict[str, object] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    # -- bring-up -------------------------------------------------------------

    def initialize(self) -> "ClusterRuntime":
        """Join the cluster (idempotent). Inactive (num_processes == 1,
        no coordinator) is a successful no-op: every cluster code path
        degrades to the single-process behavior."""
        import jax

        if self.initialized:
            return self
        self.coordinator = config.CLUSTER_COORDINATOR.get().strip()
        self.num_processes = max(1, config.CLUSTER_NUM_PROCESSES.get())
        self.process_id = config.CLUSTER_PROCESS_ID.get()
        self.topology = config.CLUSTER_TOPOLOGY.get().strip().lower()
        if self.topology not in ("auto", "flat", "hybrid"):
            raise ClusterConfigError(
                f"GEOMESA_TPU_CLUSTER_TOPOLOGY={self.topology!r} "
                "(want auto|flat|hybrid)")
        if self.num_processes <= 1 or not self.coordinator:
            if self.num_processes > 1 and not self.coordinator:
                raise ClusterConfigError(
                    "GEOMESA_TPU_CLUSTER_NUM_PROCESSES > 1 needs "
                    "GEOMESA_TPU_CLUSTER_COORDINATOR")
            self.initialized = True
            return self
        if not (0 <= self.process_id < self.num_processes):
            raise ClusterConfigError(
                f"process_id {self.process_id} outside "
                f"[0, {self.num_processes})")
        # CPU collectives: gloo must be selected before initialize (the
        # default CPU backend rejects multi-process programs outright).
        # Backend must NOT be initialized yet, so sniff the platform from
        # config/env instead of jax.default_backend().
        import os
        plats = (os.environ.get("JAX_PLATFORMS")
                 or getattr(jax.config, "jax_platforms", None) or "")
        if str(plats).split(",")[0].strip().lower() == "cpu":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        kwargs = {"coordinator_address": self.coordinator,
                  "num_processes": self.num_processes,
                  "process_id": self.process_id}
        n_local = config.CLUSTER_LOCAL_DEVICES.get()
        if n_local and n_local > 0:
            kwargs["local_device_ids"] = list(range(n_local))
        jax.distributed.initialize(
            initialization_timeout=int(config.CLUSTER_INIT_TIMEOUT_S.get()),
            **kwargs)
        self.initialized = True
        return self

    def active(self) -> bool:
        return self.initialized and self.num_processes > 1

    # -- topology -------------------------------------------------------------

    def devices(self) -> List:
        """Global device list in process-contiguous order: sorted by
        (process_index, id) so each process's devices are one block."""
        import jax
        return sorted(jax.devices(),
                      key=lambda d: (d.process_index, d.id))

    def local_device_count(self) -> int:
        import jax
        return jax.local_device_count()

    def mesh(self, axis: str = "rows"):
        """The cluster mesh for ``axis``. Flat: one named axis over the
        process-contiguous device order. Hybrid (multi-slice): ``dcn``
        across slices x ``axis`` ICI-contiguous within a slice."""
        key = axis
        with self._lock:
            if key in self._mesh_cache:
                return self._mesh_cache[key]
        from jax.sharding import Mesh
        devs = self.devices()
        slices = sorted({_slice_index(d) for d in devs})
        want_hybrid = (self.topology == "hybrid"
                       or (self.topology == "auto" and len(slices) > 1))
        if self.topology == "hybrid" and len(slices) <= 1:
            raise ClusterConfigError(
                "topology=hybrid needs >1 slice "
                f"(detected {len(slices)}); use flat/auto")
        if want_hybrid and len(slices) > 1:
            from jax.experimental.mesh_utils import \
                create_hybrid_device_mesh
            per_slice = len(devs) // len(slices)
            mesh_devs = create_hybrid_device_mesh(
                (per_slice,), (len(slices),), devices=devs)
            m = Mesh(mesh_devs, ("dcn", axis))
        else:
            m = Mesh(np.array(devs), (axis,))
        with self._lock:
            self._mesh_cache[key] = m
        return m

    def data_spec_axes(self, axis: str = "rows"):
        """Axis name(s) the row dimension shards over in ``mesh(axis)``:
        a hybrid mesh shards rows over BOTH dcn and ici axes so shard
        order stays process-contiguous."""
        m = self.mesh(axis)
        return tuple(m.axis_names) if len(m.axis_names) > 1 else axis

    # -- host-side exchange ---------------------------------------------------

    def exchange(self, payload: dict, op: str = "allgather") -> List[dict]:
        """All-gather one small JSON payload per process (rank order).
        Inactive clusters return ``[payload]`` — callers never branch.
        Each active round records a ``cluster.collective.<op>`` timer
        with total payload bytes, and (shardwatch on) one extra tiny
        gather of per-rank round timings for straggler attribution."""
        if not self.active():
            return [payload]
        import jax.numpy as jnp
        from jax.experimental import multihost_utils
        t0 = time.perf_counter()
        raw = json.dumps(payload, sort_keys=True).encode("utf-8")
        n = np.asarray([len(raw)], dtype=np.int32)
        lens = np.asarray(multihost_utils.process_allgather(
            jnp.asarray(n))).reshape(self.num_processes)
        cap = int(lens.max())
        buf = np.zeros(cap, dtype=np.uint8)
        buf[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        blobs = np.asarray(multihost_utils.process_allgather(
            jnp.asarray(buf))).reshape(self.num_processes, cap)
        dt = time.perf_counter() - t0
        note_collective(op, dt, payload_bytes=int(lens.sum()))
        if config.SHARDWATCH_ENABLED.get():
            # symmetric on every rank (same env across the cluster):
            # gather each rank's round wall time; the LAST arriver made
            # everyone else wait, so it measured the SHORTEST round —
            # the slowest rank is the argmin
            durs = np.asarray(multihost_utils.process_allgather(
                jnp.asarray(np.asarray([dt * 1000.0],
                                       dtype=np.float32)))
            ).reshape(self.num_processes)
            self._note_straggler(op, [float(d) for d in durs])
        return [json.loads(bytes(blobs[p, :int(lens[p])]).decode("utf-8"))
                for p in range(self.num_processes)]

    def _note_straggler(self, op: str, durs_ms: List[float]) -> None:
        """Per-round straggler attribution: name the slowest rank, count
        over-bar rounds against it (the doctor's collective_straggler
        feed), and flight-record the round with cluster dims."""
        try:
            from geomesa_tpu.metrics import REGISTRY
            spread = max(durs_ms) - min(durs_ms)
            slowest = int(min(range(len(durs_ms)),
                              key=lambda p: (durs_ms[p], p)))
            REGISTRY.inc("cluster.collective.rounds")
            if spread < float(config.DOCTOR_STRAGGLER_MS.get()):
                return
            REGISTRY.inc(f"cluster.collective.straggler.rank{slowest}")
            REGISTRY.observe("cluster.collective.straggler_spread",
                             spread / 1000.0)
            from geomesa_tpu.obs import flight as _flight
            _flight.RECORDER.record({
                "ts_ms": int(time.time() * 1000), "kind": "collective",
                "type": op, "duration_ms": round(spread, 3),
                "slowest_rank": slowest,
                "round_ms": [round(d, 3) for d in durs_ms],
                **event_dims()})
        except Exception:
            pass

    def barrier(self, name: str = "cluster") -> None:
        if not self.active():
            return
        from jax.experimental import multihost_utils
        t0 = time.perf_counter()
        multihost_utils.sync_global_devices(name)
        note_collective("barrier", time.perf_counter() - t0)

    # -- integration hooks ----------------------------------------------------

    def note_psum_round(self, n: int = 1) -> None:
        """Count one psum-reduced global dispatch (the /cluster and
        debug-cluster 'psum round' surface + a fleet metric)."""
        with self._lock:
            self.psum_rounds += n
        try:
            from geomesa_tpu.metrics import REGISTRY
            REGISTRY.inc("cluster.psum_rounds", n)
        except Exception:
            pass

    def register_table(self, type_name: str, summary: dict) -> None:
        with self._lock:
            self.tables[type_name] = summary

    def register_web(self, port: int, host: str = "127.0.0.1") -> Optional[dict]:
        """Exchange this process's web address across the cluster and
        install a Federator over ALL of them on every rank — cluster
        nodes auto-register in /fleet with no manual --addr lists."""
        if not config.CLUSTER_WEB_REGISTER.get():
            return None
        from geomesa_tpu import trace as _trace
        from geomesa_tpu.obs import federation
        me = {"proc": self.process_id, "addr": f"{host}:{port}",
              "node_id": _trace.node_id()}
        peers = self.exchange(me)
        nodes = {p.get("node_id") or f"proc{p['proc']}": p["addr"]
                 for p in peers}
        federation.configure(nodes)
        return nodes

    # -- state surfaces -------------------------------------------------------

    def state(self) -> dict:
        """The /cluster + ``debug cluster`` payload."""
        import jax
        out = {
            "active": self.active(),
            "process_id": self.process_id,
            "num_processes": self.num_processes,
            "coordinator": self.coordinator or None,
            "topology": self.topology,
            "psum_rounds": self.psum_rounds,
            "tables": dict(self.tables),
        }
        if self.initialized:
            try:
                devs = self.devices()
                slices = sorted({_slice_index(d) for d in devs})
                m = self.mesh()
                out["mesh"] = {
                    "axes": {k: int(v)
                             for k, v in zip(m.axis_names,
                                             m.devices.shape)},
                    "devices": len(devs),
                    "local_devices": jax.local_device_count(),
                    "slices": len(slices),
                    "ici_shape": [len(devs) // max(1, len(slices))],
                    "dcn_shape": [len(slices)],
                    "backend": jax.default_backend(),
                }
            except Exception as e:  # noqa: BLE001 - state must not raise
                out["mesh"] = {"error": str(e)}
        return out


_RUNTIME: Optional[ClusterRuntime] = None
_RT_LOCK = threading.Lock()


def runtime(init: bool = True) -> ClusterRuntime:
    """The process-global runtime; ``init=True`` joins the cluster on
    first use when the knobs say so."""
    global _RUNTIME
    with _RT_LOCK:
        if _RUNTIME is None:
            _RUNTIME = ClusterRuntime()
    if init and not _RUNTIME.initialized and _enabled():
        _RUNTIME.initialize()
    return _RUNTIME


def _enabled() -> bool:
    return bool(config.CLUSTER.get()
                or config.CLUSTER_COORDINATOR.get().strip())


def cluster_active() -> bool:
    """True iff this process is part of an initialized >1-process
    cluster. Cheap and safe to call from hot paths (no bring-up side
    effects unless the knobs ask for it)."""
    if _RUNTIME is not None:
        return _RUNTIME.active()
    if not _enabled():
        return False
    return runtime().active()


def event_dims() -> dict:
    """``process``/``shard`` dims for flight events and traces (empty
    outside a cluster, so single-process event shapes are unchanged)."""
    if _RUNTIME is None or not _RUNTIME.active():
        return {}
    return {"process": _RUNTIME.process_id,
            "shard": f"{_RUNTIME.process_id}/{_RUNTIME.num_processes}"}


def _reset_for_tests() -> None:
    global _RUNTIME
    with _RT_LOCK:
        _RUNTIME = None
