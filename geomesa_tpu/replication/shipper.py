"""LogShipper: the primary side of the WAL-shipping pipeline.

≙ the replication story the reference inherits from its key-value backends
(HBase region replication / Accumulo table replication both tail the WAL
and ship edits to peers): here the contiguous-global-seq, CRC-framed WAL
(durability/wal.py) IS the replication log. The shipper accepts follower
connections, resumes each one from its acked sequence — falling back to a
snapshot-catchup (reusing the installed incremental snapshots) when the
acked seq was garbage-collected out of the log — and then tails the live
WAL, forwarding frames **verbatim** so the follower re-verifies the same
CRC the primary wrote.

One session thread per follower sends; a paired reader thread consumes
ACKs (per-follower acked/applied seq → the router's promote-by-highest-
acked input) and FENCE messages (a follower that has witnessed a higher
fencing epoch demotes this node: ``fenced`` flips and the
DurabilityManager refuses every subsequent write — split-brain writes are
impossible, see replication/fence.py)."""

from __future__ import annotations

import os
import socket
import threading
import time
from typing import Dict, Optional

from geomesa_tpu import config
from geomesa_tpu.durability import faults
from geomesa_tpu.durability import snapshot as _snap
from geomesa_tpu.durability import wal as _wal
from geomesa_tpu.metrics import REGISTRY as _metrics
from geomesa_tpu.obs import hostcpu
from geomesa_tpu.replication import fence as _fence
from geomesa_tpu.replication import protocol as _p

# frames shipped per tail poll before a heartbeat/ack interleave
_SHIP_BATCH = 256


class LogShipper:
    """Primary-side replication endpoint: a TCP server shipping WAL
    frames + snapshot catch-ups to N followers."""

    role = "primary"

    def __init__(self, store, host: str = "127.0.0.1", port: int = 0):
        if getattr(store, "durability", None) is None:
            raise ValueError("replication requires a durable store "
                             "(TpuDataStore.open)")
        self.store = store
        self.dur = store.durability
        self.path = self.dur.path
        self.epoch = _fence.load_epoch(self.path)
        if self.epoch == 0:
            self.epoch = _fence.save_epoch(self.path, 1)
        self.fenced = False
        self.fenced_by: Optional[int] = None
        self._lock = threading.Lock()
        self.followers: Dict[str, dict] = {}
        self._conns: list = []
        self._closed = False
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, int(port)))
        self._srv.listen(16)
        self.host, self.port = self._srv.getsockname()[:2]
        self._accept_thread = hostcpu.thread(
            "program.other", target=self._accept_loop,
            name="geomesa-repl-ship", daemon=True)
        self._accept_thread.start()
        store.replication = self
        from geomesa_tpu import trace as _trace
        _trace.set_node_role("primary")
        _metrics.set_gauge("replication.followers",
                           lambda: len([f for f in self.followers.values()
                                        if f.get("connected")]))

    # -- surfaces ------------------------------------------------------------

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def min_acked_seq(self) -> int:
        with self._lock:
            acked = [f["acked_seq"] for f in self.followers.values()
                     if f.get("connected")]
        return min(acked) if acked else 0

    def stats(self) -> dict:
        wal = self.dur.wal
        now = time.monotonic()
        with self._lock:
            followers = {
                fid: {
                    "addr": f.get("addr"),
                    "connected": bool(f.get("connected")),
                    "acked_seq": f["acked_seq"],
                    "applied_seq": f["applied_seq"],
                    "lag_seqs": max(0, wal.last_seq - f["acked_seq"]),
                    "last_ack_age_ms":
                        round((now - f["last_ack"]) * 1000.0, 1)
                        if f.get("last_ack") else None,
                    "snapshots_shipped": f.get("snapshots", 0),
                }
                for fid, f in self.followers.items()}
        return {"role": "fenced" if self.fenced else "primary",
                "epoch": self.epoch,
                "fenced": self.fenced,
                "fenced_by": self.fenced_by,
                "address": self.address,
                "last_seq": wal.last_seq,
                "synced_seq": wal.synced_seq,
                "followers": followers}

    # -- fencing -------------------------------------------------------------

    def _fence_self(self, higher_epoch: int) -> None:
        """A peer witnessed a higher epoch: this node lost primaryship.
        Durably witness the epoch (a restart must not silently reclaim the
        role) and refuse every subsequent write via the manager's fence
        check."""
        with self._lock:
            if self.fenced and (self.fenced_by or 0) >= higher_epoch:
                return
            self.fenced = True
            self.fenced_by = int(higher_epoch)
        _fence.save_epoch(self.path, higher_epoch)
        _metrics.inc("replication.fence_events")

    # -- server --------------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, addr = self._srv.accept()
            except OSError:
                return  # server socket closed
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                self._conns.append(conn)
            hostcpu.thread("program.other", target=self._session,
                           args=(conn, addr), name="geomesa-repl-session",
                           daemon=True).start()

    def _session(self, conn: socket.socket, addr) -> None:
        fid = None
        try:
            conn.settimeout(30.0)
            m = _p.recv_msg(conn)
            if m is None or m[0] != _p.HELLO:
                return
            hello = _p.parse_json(m[1])
            fid = str(hello.get("id") or f"{addr[0]}:{addr[1]}")
            acked = int(hello.get("acked_seq", 0))
            their_epoch = int(hello.get("epoch", 0))
            if their_epoch > self.epoch:
                # the connecting node has seen a NEWER primary than us: we
                # are the stale side of a partition — demote immediately
                self._fence_self(their_epoch)
                _p.send_json(conn, _p.FENCE, {"epoch": their_epoch})
                return
            wal = self.dur.wal
            if acked > wal.last_seq:
                # divergent history (the follower outran this log): refuse
                # rather than ship a conflicting lineage
                _metrics.inc("replication.divergent_hellos")
                return
            with self._lock:
                st = self.followers.setdefault(
                    fid, {"acked_seq": acked, "applied_seq": acked,
                          "last_ack": None, "snapshots": 0})
                st["addr"] = f"{addr[0]}:{addr[1]}"
                st["connected"] = True
                st["acked_seq"] = max(st["acked_seq"], acked)
            reader = hostcpu.thread("program.other", target=self._ack_loop,
                                    args=(conn, fid),
                                    name="geomesa-repl-acks", daemon=True)
            reader.start()
            self._ship(conn, fid, acked)
        except (OSError, _p.ProtocolError):
            pass
        finally:
            if fid is not None:
                with self._lock:
                    if fid in self.followers:
                        self.followers[fid]["connected"] = False
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)

    def _ack_loop(self, conn: socket.socket, fid: str) -> None:
        """Consume follower -> primary traffic for one session."""
        try:
            conn.settimeout(None)
            while not self._closed:
                m = _p.recv_msg(conn)
                if m is None:
                    return
                mtype, payload = m
                if mtype == _p.ACK:
                    ack = _p.parse_json(payload)
                    with self._lock:
                        st = self.followers.get(fid)
                        if st is not None:
                            st["acked_seq"] = max(
                                st["acked_seq"], int(ack.get("acked_seq", 0)))
                            st["applied_seq"] = max(
                                st["applied_seq"],
                                int(ack.get("applied_seq", 0)))
                            st["last_ack"] = time.monotonic()
                    _metrics.inc("replication.acks_received")
                    self._score_pipeline(fid, ack)
                elif mtype == _p.FENCE:
                    self._fence_self(int(_p.parse_json(payload)
                                         .get("epoch", 0)))
                    return
        except (OSError, _p.ProtocolError):
            return

    def _score_pipeline(self, fid: str, ack: dict) -> None:
        """Replication-pipeline telemetry from one ACK: the follower
        echoes the newest ship stamp it applied (``ship_ts``) plus its
        measured apply latency, so the primary observes the full
        ship→apply→ack pipeline on ITS clock pair: ``repl.ship_to_ack``
        (wire + apply + ack wire) and the end-to-end ``repl.e2e`` — the
        histogram the fleet surface reads, exemplar-linked to the
        follower's retained apply trace when one rode along."""
        ship_ts = ack.get("ship_ts")
        if not ship_ts:
            return
        e2e_s = max(0.0, time.time() - float(ship_ts))
        _metrics.observe("repl.ship_to_ack", e2e_s)
        apply_trace = ack.get("apply_trace")
        if apply_trace:
            # fleet p99 -> this exemplar -> the follower's apply trace
            _metrics.observe_exemplar("repl.e2e", e2e_s, str(apply_trace))
        else:
            _metrics.observe("repl.e2e", e2e_s)

    # -- shipping ------------------------------------------------------------

    def _oldest_wal_seq(self) -> Optional[int]:
        segs = _wal.segments(self.dur.wal.dir, self.dur.wal.name)
        return _wal.segment_first_seq(segs[0]) if segs else None

    def _ship(self, conn: socket.socket, fid: str, acked: int) -> None:
        conn.settimeout(None)
        wal = self.dur.wal
        start = acked
        oldest = self._oldest_wal_seq()
        if oldest is not None and acked + 1 < oldest:
            # the follower's resume point was GC'd past: snapshot catch-up
            start = self._ship_snapshot(conn, fid)
            if start is None:
                return
        tailer = _wal.WalTailer(wal.dir, wal.name, after_seq=start)
        hb_s = float(config.REPL_HEARTBEAT_MS.get()) / 1000.0
        sent = start
        while not self._closed:
            if self.fenced:
                _p.send_json(conn, _p.FENCE, {"epoch": self.fenced_by})
                return
            wal.flush_to_os()
            frames = tailer.poll(limit=_SHIP_BATCH)
            for seq, _kind, frame in frames:
                faults.serve_gate("repl.ship.frame")
                frame = faults.repl_corrupt(frame)
                # ship-time stamp: the pipeline-latency anchor the
                # follower scores apply latency against and echoes in acks
                _p.send_msg(conn, _p.FRAME,
                            _p.pack_frame(self.epoch, frame,
                                          ship_ts=time.time()))
                sent = seq
                _metrics.inc("replication.shipped_frames")
                _metrics.inc("replication.shipped_bytes", len(frame))
            if len(frames) == _SHIP_BATCH:
                continue  # still draining a backlog: no idle wait yet
            _p.send_json(conn, _p.HEARTBEAT,
                         {"last_seq": wal.last_seq,
                          "ts_ms": time.time() * 1000.0,
                          "epoch": self.epoch})
            wal.wait_for_seq(sent + 1, timeout=hb_s)

    def _ship_snapshot(self, conn: socket.socket, fid: str) -> Optional[int]:
        """Transfer the newest installed snapshot; returns the WAL seq it
        covers (shipping resumes past it), or None when no snapshot can
        bridge the gap."""
        faults.serve_gate("repl.ship.snapshot")
        snaps = _snap.snapshot_dirs(self.path)
        if not snaps:
            _metrics.inc("replication.catchup_impossible")
            return None
        snap_seq, snap_dir = snaps[-1]
        files = sorted(fn for fn in os.listdir(snap_dir)
                       if fn == "catalog.json" or fn.endswith(".npz"))
        _p.send_json(conn, _p.SNAP_BEGIN,
                     {"wal_seq": snap_seq, "epoch": self.epoch,
                      "files": files})
        for fn in files:
            with open(os.path.join(snap_dir, fn), "rb") as fh:
                _p.send_msg(conn, _p.SNAP_FILE, _p.pack_file(fn, fh.read()))
        _p.send_json(conn, _p.SNAP_END, {"wal_seq": snap_seq})
        with self._lock:
            st = self.followers.get(fid)
            if st is not None:
                st["snapshots"] = st.get("snapshots", 0) + 1
        _metrics.inc("replication.snapshots_shipped")
        return snap_seq

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._srv.close()
        except OSError:
            pass
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        if self.store is not None and \
                getattr(self.store, "replication", None) is self:
            self.store.replication = None
