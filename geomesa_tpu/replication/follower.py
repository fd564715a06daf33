"""Follower: a read replica kept consistent by applying shipped WAL frames.

The follower opens its OWN durable store directory (an independent copy of
the data), connects to the primary's LogShipper, and for every shipped
frame: re-verifies the CRC, appends the identical bytes to its local WAL
(log-then-apply — a follower crash at any boundary recovers through the
ordinary recovery path and resumes from its durable seq), then applies the
record through the same replay mutation paths recovery uses. Generations
and cache epochs therefore advance exactly as on the primary, so
plan/cover/result caches invalidate identically.

Role discipline: the local DurabilityManager is marked ``read_only`` — any
direct mutation raises FencedError; only the apply loop (which flips the
manager's ``replaying`` flag around each record, exactly like recovery)
may change state. ``promote()`` lifts the restriction, claims a new
fencing epoch, and turns the node into a primary with its own LogShipper.

Lag accounting: heartbeats carry the primary's last seq;
``replication.lag_seqs`` is how many records behind the apply point is,
``replication.lag_ms`` how long the replica has continuously been behind.
Every heartbeat and ack scores a bounded-staleness check
(``replication.staleness_checks`` / ``.staleness_exceeded``) feeding the
burn-rate SLO registered in obs/slo.py."""

from __future__ import annotations

import os
import shutil
import socket
import threading
import time
from typing import Optional

from geomesa_tpu import config
from geomesa_tpu.durability import faults, rotation
from geomesa_tpu.durability import snapshot as _snap
from geomesa_tpu.durability import wal as _wal
from geomesa_tpu.durability.faults import InjectedCrash
from geomesa_tpu.metrics import REGISTRY as _metrics
from geomesa_tpu.replication import fence as _fence
from geomesa_tpu.replication import protocol as _p


class _Resync(Exception):
    """Drop the connection and reconnect from the durable acked seq (a
    CRC-rejected or out-of-order shipped frame)."""


class Follower:
    """One read replica: local durable store + apply loop."""

    def __init__(self, directory: str, primary_addr,
                 follower_id: Optional[str] = None,
                 params: Optional[dict] = None,
                 connect: bool = True):
        from geomesa_tpu.datastore import TpuDataStore
        self.dir = str(directory)
        self.primary_addr = _p.parse_addr(primary_addr)
        self.id = follower_id or os.path.basename(os.path.abspath(directory))
        self.role = "replica"
        p = {"wal.fsync": "off"}  # the primary's log is authoritative
        p.update(params or {})
        self._params = p
        self.store = TpuDataStore.open(self.dir, params=p)
        self.store.durability.read_only = True
        self.store.replication = self
        self.epoch = _fence.load_epoch(self.dir)
        self.applied_seq = self.store.durability.wal.last_seq
        self.primary_seq = self.applied_seq
        self.dead = False            # a drill-injected "process death"
        self.connected = False
        self.snapshot_installs = 0
        self.crc_rejects = 0
        self.fenced_rejects = 0
        self.applied_records = 0
        self._rows_applied = 0       # local snapshot trigger accounting
        self._caught_up = time.monotonic()
        self._lag_ms = 0.0
        self._acked_seq = 0
        self._stop = threading.Event()
        self._sock: Optional[socket.socket] = None
        self._lock = threading.Lock()
        self._snap_tmp: Optional[str] = None
        self._snap_meta: Optional[dict] = None
        # replication-pipeline telemetry: the newest applied ship stamp
        # (echoed in acks for the primary's e2e timing), the retained
        # apply-trace id riding along as the e2e exemplar, and the
        # traced-apply cadence countdown
        self._last_ship_ts = 0.0
        self._last_apply_trace: Optional[str] = None
        self._until_traced_apply = 1
        from geomesa_tpu import trace as _trace
        _trace.set_node_role("replica")
        _metrics.set_gauge("replication.lag_seqs", lambda: self.lag_seqs)
        _metrics.set_gauge("replication.lag_ms",
                           lambda: round(self.lag_ms, 1))
        self._install_slo()
        from geomesa_tpu.obs import hostcpu
        self._thread = hostcpu.thread("program.other", target=self._run,
                                      name=f"geomesa-repl-{self.id}",
                                      daemon=True)
        if connect:
            self._thread.start()

    # -- state ---------------------------------------------------------------

    @property
    def lag_seqs(self) -> int:
        return max(0, self.primary_seq - self.applied_seq)

    @property
    def lag_ms(self) -> float:
        """How long this replica has been unable to PROVE freshness.
        ``_caught_up`` advances only when the apply loop demonstrably
        reaches the primary's last seq (an applied frame or a processed
        heartbeat), so a stalled apply loop, a dropped link, or a genuine
        seq backlog all age identically — the router can't be fooled by a
        replica too stuck to notice it is behind. Two heartbeat intervals
        of grace keep a healthy, chatty replica at 0."""
        grace_ms = 2.0 * float(config.REPL_HEARTBEAT_MS.get())
        age_ms = (time.monotonic() - self._caught_up) * 1000.0
        return max(0.0, age_ms - grace_ms)

    def stats(self) -> dict:
        return {"role": self.role,
                "id": self.id,
                "primary": f"{self.primary_addr[0]}:{self.primary_addr[1]}",
                "connected": self.connected,
                "dead": self.dead,
                "epoch": self.epoch,
                "applied_seq": self.applied_seq,
                "acked_seq": self._acked_seq,
                "primary_seq": self.primary_seq,
                "lag_seqs": self.lag_seqs,
                "lag_ms": round(self.lag_ms, 1),
                "staleness_budget_ms": float(config.REPL_STALENESS_MS.get()),
                "applied_records": self.applied_records,
                "snapshot_installs": self.snapshot_installs,
                "crc_rejects": self.crc_rejects,
                "fenced_rejects": self.fenced_rejects}

    def wait_for_seq(self, seq: int, timeout: float = 10.0) -> bool:
        """Block until the apply point reaches ``seq`` (tests/drills)."""
        deadline = time.monotonic() + timeout
        while self.applied_seq < seq:
            if time.monotonic() >= deadline or self.dead:
                return False
            time.sleep(0.005)
        return True

    def _install_slo(self) -> None:
        from geomesa_tpu.obs import slo as _slo
        if not any(o.name == "replication_staleness"
                   for o in _slo.ENGINE.objectives()):
            _slo.ENGINE.add(_slo.replication_objective())

    # -- connection loop ------------------------------------------------------

    def _run(self) -> None:
        backoff_s = float(config.REPL_RECONNECT_MS.get()) / 1000.0
        while not self._stop.is_set():
            sock = None
            try:
                sock = socket.create_connection(self.primary_addr,
                                                timeout=5.0)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                with self._lock:
                    self._sock = sock
                acked = self.store.durability.wal.last_seq
                _p.send_json(sock, _p.HELLO,
                             {"id": self.id, "acked_seq": acked,
                              "epoch": self.epoch})
                self.connected = True
                self._consume(sock)
            except InjectedCrash:
                # drill semantics: the replica process died mid-apply. The
                # in-flight record is dropped exactly where the crash hit;
                # releasing the file handles here (instead of leaking a
                # zombie whose buffered writes could land later) makes the
                # "restart on the same directory" step well-defined.
                self.dead = True
                self.connected = False
                try:
                    self.store.close()
                except BaseException:
                    pass
                return
            except (_Resync, OSError, _p.ProtocolError):
                _metrics.inc("replication.reconnects")
            except Exception:
                # a flaky-link / injected error mid-apply: reconnect and
                # resume from the durable acked seq like any drop
                _metrics.inc("replication.reconnects")
            finally:
                self.connected = False
                with self._lock:
                    self._sock = None
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass
            if not self._stop.is_set():
                self._stop.wait(backoff_s)

    def _consume(self, sock: socket.socket) -> None:
        hb_s = float(config.REPL_HEARTBEAT_MS.get()) / 1000.0
        sock.settimeout(max(1.0, hb_s * 20))
        last_acked = self.store.durability.wal.last_seq
        ack_every = max(1, int(config.REPL_ACK_EVERY.get()))
        while not self._stop.is_set():
            m = _p.recv_msg(sock)
            if m is None:
                return
            mtype, payload = m
            if mtype == _p.FRAME:
                epoch, ship_ts, frame = _p.unpack_frame(payload)
                if not self._epoch_ok(sock, epoch):
                    return
                seq = self._apply_frame(frame, ship_ts=ship_ts)
                if seq is not None and seq - last_acked >= ack_every:
                    self._ack(sock)
                    last_acked = seq
            elif mtype == _p.HEARTBEAT:
                hb = _p.parse_json(payload)
                if not self._epoch_ok(sock, int(hb.get("epoch", 0))):
                    return
                self.primary_seq = max(self.primary_seq,
                                       int(hb.get("last_seq", 0)))
                if self.applied_seq >= self.primary_seq:
                    self._caught_up = time.monotonic()
                self._staleness_tick()
                self._ack(sock)
                last_acked = self.store.durability.wal.last_seq
            elif mtype == _p.SNAP_BEGIN:
                meta = _p.parse_json(payload)
                if not self._epoch_ok(sock, int(meta.get("epoch", 0))):
                    return
                self._snap_begin(meta)
            elif mtype == _p.SNAP_FILE:
                self._snap_file(*_p.unpack_file(payload))
            elif mtype == _p.SNAP_END:
                self._snap_end(_p.parse_json(payload))
                self._ack(sock)
                last_acked = self.store.durability.wal.last_seq
            elif mtype == _p.FENCE:
                # the primary demoted itself mid-session; adopt the epoch
                # it named and wait for a new primary at this address
                self._adopt_epoch(int(_p.parse_json(payload)
                                      .get("epoch", 0)))
                return

    def _epoch_ok(self, sock: socket.socket, epoch: int) -> bool:
        """Enforce the fencing invariant on every primary->follower
        message: a stale epoch is rejected and answered with the higher
        one (never applied — split-brain writes stop here)."""
        if epoch < self.epoch:
            self.fenced_rejects += 1
            _metrics.inc("replication.fenced_rejects")
            try:
                _p.send_json(sock, _p.FENCE, {"epoch": self.epoch})
            except OSError:
                pass
            return False
        if epoch > self.epoch:
            self._adopt_epoch(epoch)
        return True

    def _adopt_epoch(self, epoch: int) -> None:
        if epoch > self.epoch:
            self.epoch = _fence.save_epoch(self.dir, epoch)

    # -- applying -------------------------------------------------------------

    def _apply_frame(self, frame: bytes,
                     ship_ts: float = 0.0) -> Optional[int]:
        """Verify, locally log, then apply one shipped frame; returns its
        seq (None when it was an already-held duplicate). Every
        REPL_TRACE_EVERY-th apply runs under a RETAINED root trace whose
        global id rides the next ack back to the primary as the
        ``repl.e2e`` exemplar — the fleet p99 links to a concrete remote
        apply a reader can pull up."""
        faults.serve_gate("repl.apply")
        from geomesa_tpu import trace as _trace
        try:
            seq, kind_name, payload = _wal.verify_frame(frame)
        except ValueError as e:
            self._reject_crc(str(e))
        wal = self.store.durability.wal
        if seq <= wal.last_seq:
            return None  # duplicate after an ack-lagged resume
        try:
            wal.append_frame(frame)
        except ValueError as e:
            self._reject_crc(str(e))
        traced = False
        every = int(config.REPL_TRACE_EVERY.get())
        if every > 0 and _trace.enabled():
            self._until_traced_apply -= 1
            traced = self._until_traced_apply <= 0
        if traced:
            self._until_traced_apply = every
            with _trace.trace("repl.apply", seq=seq,
                              kind=kind_name) as t:
                if t is not None:
                    t.sampled_hint = True  # pin it in the tail ring
                    self._last_apply_trace = t.global_id
                self._apply_record(kind_name, payload)
        else:
            self._apply_record(kind_name, payload)
        if ship_ts:
            # per-hop ship→apply latency (shared wall clock): the
            # follower half of the replication-pipeline breakdown
            self._last_ship_ts = max(self._last_ship_ts, ship_ts)
            _metrics.observe("repl.ship_to_apply",
                             max(0.0, time.time() - ship_ts))
        self.applied_seq = seq
        self.applied_records += 1
        self._acked_seq = wal.last_seq
        _metrics.inc("replication.applied_records")
        _metrics.inc("replication.applied_bytes", len(frame))
        if self.applied_seq >= self.primary_seq:
            self.primary_seq = self.applied_seq
            self._caught_up = time.monotonic()
        self._maybe_local_snapshot()
        return seq

    def _reject_crc(self, why: str) -> None:
        self.crc_rejects += 1
        _metrics.inc("replication.crc_rejects")
        raise _Resync(f"rejected shipped frame: {why}")

    def _apply_record(self, kind: str, payload: bytes) -> None:
        """Apply through the recovery replay paths with local logging
        suppressed (the shipped frame is already in the local WAL)."""
        from geomesa_tpu.durability.recovery import _apply_record
        mgr = self.store.durability
        mgr.replaying = True
        try:
            _apply_record(self.store, kind, payload)
            if kind in ("append", "upsert"):
                meta = _wal.peek_meta(payload)
                self._rows_applied += int(meta.get("rows", 0)) or 0
        except Exception:
            _metrics.inc("replication.apply_errors")
        finally:
            mgr.replaying = False

    def _maybe_local_snapshot(self) -> None:
        """Bound the replica's own restart-replay horizon: snapshot
        locally on the manager's ordinary thresholds (its row/byte
        accounting is suppressed while replaying, so the follower keeps
        its own)."""
        mgr = self.store.durability
        if self._rows_applied >= mgr._snapshot_rows:
            self._rows_applied = 0
            mgr.snapshot()

    def _staleness_tick(self) -> None:
        self._lag_ms = self.lag_ms
        _metrics.inc("replication.staleness_checks")
        if self._lag_ms > float(config.REPL_STALENESS_MS.get()):
            _metrics.inc("replication.staleness_exceeded")

    def _ack(self, sock: socket.socket) -> None:
        faults.serve_gate("repl.ack")
        wal = self.store.durability.wal
        self._acked_seq = wal.last_seq
        ack = {"id": self.id, "acked_seq": wal.last_seq,
               "applied_seq": self.applied_seq,
               "ts_ms": time.time() * 1000.0}
        if self._last_ship_ts:
            # echo the newest applied ship stamp (+ the retained apply
            # trace, once) so the primary times ship→apply→ack and pins
            # the repl.e2e exemplar to a fetchable remote trace
            ack["ship_ts"] = self._last_ship_ts
            if self._last_apply_trace is not None:
                ack["apply_trace"] = self._last_apply_trace
                self._last_apply_trace = None
        _p.send_json(sock, _p.ACK, ack)
        _metrics.inc("replication.acks_sent")
        self._staleness_tick()

    # -- snapshot catch-up ----------------------------------------------------

    def _snap_begin(self, meta: dict) -> None:
        seq = int(meta["wal_seq"])
        self._snap_meta = meta
        self._snap_tmp = os.path.join(self.dir, f".tmp-snapshot-{seq:020d}")
        shutil.rmtree(self._snap_tmp, ignore_errors=True)
        os.makedirs(self._snap_tmp)

    def _snap_file(self, name: str, data: bytes) -> None:
        if self._snap_tmp is None:
            raise _p.ProtocolError("SNAP_FILE before SNAP_BEGIN")
        with open(os.path.join(self._snap_tmp, name), "wb") as fh:
            fh.write(data)
            rotation.fsync_file(fh)

    def _snap_end(self, meta: dict) -> None:
        """Install the shipped snapshot and restart the local store from
        it: the local WAL and older snapshots are discarded (the shipped
        image supersedes this replica's whole lineage) and shipping
        resumes at wal_seq + 1."""
        from geomesa_tpu.datastore import TpuDataStore
        if self._snap_tmp is None:
            raise _p.ProtocolError("SNAP_END before SNAP_BEGIN")
        seq = int(meta["wal_seq"])
        old = self.store
        old.replication = None
        old.close()
        shutil.rmtree(os.path.join(self.dir, "wal"), ignore_errors=True)
        for _s, p in _snap.snapshot_dirs(self.dir):
            shutil.rmtree(p, ignore_errors=True)
        rotation.atomic_install(
            self._snap_tmp, os.path.join(self.dir, f"snapshot-{seq:020d}"))
        self._snap_tmp = self._snap_meta = None
        self.store = TpuDataStore.open(self.dir, params=self._params)
        self.store.durability.read_only = True
        self.store.replication = self
        self.applied_seq = self.store.durability.wal.last_seq
        self._acked_seq = self.applied_seq
        self.snapshot_installs += 1
        self._rows_applied = 0
        _metrics.inc("replication.snapshot_installs")

    # -- lifecycle ------------------------------------------------------------

    def promote(self, host: str = "127.0.0.1", port: int = 0):
        """Failover: stop following, claim a fresh fencing epoch strictly
        above everything witnessed, lift the read-only fence, and start
        shipping as the new primary. Returns the new LogShipper."""
        from geomesa_tpu.replication.shipper import LogShipper
        self.close(keep_store=True)
        self.store.durability.read_only = False
        self.epoch = _fence.bump_epoch(self.dir, at_least=self.epoch)
        self.role = "promoted"
        self.store.replication = None
        _metrics.inc("replication.promotions")
        return LogShipper(self.store, host=host, port=port)

    def close(self, keep_store: bool = False) -> None:
        self._stop.set()
        with self._lock:
            sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        if not keep_store:
            if getattr(self.store, "replication", None) is self:
                self.store.replication = None
            self.store.close()
