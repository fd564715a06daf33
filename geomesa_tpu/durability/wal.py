"""Append-only, CRC-framed write-ahead log with group-commit fsync.

≙ the reference's storage-tier WALs (Accumulo/HBase write-ahead logs under
the GeoMesa index tables — every mutation is durable before it is
acknowledged) transplanted onto the in-process TPU store: each logical
mutation (append batch / upsert / delete / update / age-off / schema op /
hot-tier GeoMessage) is encoded as one compact framed record and appended to
a numbered segment file.

Framing (all little-endian)::

    segment header:  b"GTW1" + u64 first_seq                    (12 bytes)
    record frame:    u32 crc | u32 len | u64 seq | u8 kind | payload

``crc`` is crc32 over (len, seq, kind, payload), so a torn tail — a frame
cut short by a crash mid-write — fails verification and recovery truncates
the log at the last whole record (the reference's WAL recovery discipline).
Sequence numbers are global and contiguous across segments; a gap is treated
as corruption.

Fsync policy (``GEOMESA_TPU_WAL_FSYNC``):

  off      never fsync (OS page cache only; survives process death, not
           power loss) — the bulk-load setting
  batch    group commit: appends buffer and a background syncer fsyncs once
           per commit window (``GEOMESA_TPU_WAL_INTERVAL_MS``); bounded
           data-at-risk, near-zero per-append cost (default)
  always   every append is durable before it returns, with cross-thread
           group commit (concurrent appenders piggyback on one fsync —
           the classic log-manager optimization)

Payload codecs: JSON records for metadata ops, npz (uncompressed — the WAL
is throughput-critical) reusing io/checkpoint's columnar table codec for
feature batches. Fault-injection hooks (faults.py) thread through every
write/fsync boundary."""

from __future__ import annotations

import json
import os
import re
import struct
import threading
import time
import zlib
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from geomesa_tpu.durability import faults
from geomesa_tpu.durability.faults import InjectedCrash

_MAGIC = b"GTW1"
_HEADER = struct.Struct("<4sQ")          # magic, first seq in segment
_FRAME = struct.Struct("<IIQB")          # crc, payload len, seq, kind
_CRC_PART = struct.Struct("<IQB")        # the crc-covered frame fields

# -- record kinds -------------------------------------------------------------

KINDS: Dict[str, int] = {
    # cold-store logical mutations (datastore.py hooks)
    "append": 1, "upsert": 2, "remove": 3, "update": 4, "age_off": 5,
    "create_schema": 6, "remove_schema": 7, "update_schema": 8,
    # hot-tier journal (stream/live.py) — GeoMessages + persist fencing
    "hot_put": 16, "hot_delete": 17, "hot_clear": 18, "hot_expire": 19,
    "persist_begin": 20, "persist_commit": 21,
}
KIND_NAMES = {v: k for k, v in KINDS.items()}


# -- payload codecs -----------------------------------------------------------


def _json_default(o):
    if isinstance(o, np.datetime64):
        return str(o)
    if isinstance(o, np.generic):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def encode_json(meta: dict) -> bytes:
    return json.dumps(meta, separators=(",", ":"),
                      default=_json_default).encode()


def decode_json(payload: bytes) -> dict:
    return json.loads(payload.decode())


def encode_table(meta: dict, table=None,
                 arrays: Optional[Dict[str, np.ndarray]] = None) -> bytes:
    """Compact raw-buffer payload: a JSON header (meta + column
    descriptors) followed by the concatenated array bytes. Deliberately NOT
    npz: zipfile framing pays a crc32 + copy per member and ~20% of the
    ingest budget — the WAL's outer frame already carries the CRC, so the
    payload is a straight memcpy of each column. String columns (fids,
    dictionary vocabs) ship as a length array + one utf-8 blob (no numpy
    unicode-dtype conversion, which dominates npz encode at scale).
    Snapshots keep the compressed npz codec (io/checkpoint) instead."""
    header_cols: list = []
    bufs: list = []

    def add_arr(key: str, arr) -> None:
        arr = np.ascontiguousarray(arr)
        b = arr.tobytes()
        header_cols.append({"k": key, "dt": arr.dtype.str,
                            "sh": list(arr.shape), "n": len(b)})
        bufs.append(b)

    def add_strs(key: str, values) -> None:
        # fast path: one join + one encode (no per-string python work).
        # The unit separator can only under-count if a VALUE contains it —
        # detected by the count check, which falls back to length-prefixed.
        try:
            joined = "\x1f".join(values)
        except TypeError:
            values = [str(v) for v in values]
            joined = "\x1f".join(values)
        n_vals = len(values)
        if n_vals == 0 or joined.count("\x1f") == n_vals - 1:
            blob = joined.encode("utf-8")
            header_cols.append({"k": key, "dt": "sepblob", "c": n_vals,
                                "n": len(blob)})
            bufs.append(blob)
            return
        enc = [str(v).encode("utf-8") for v in values]
        add_arr(key + ":lens",
                np.fromiter((len(e) for e in enc), dtype=np.int32,
                            count=len(enc)))
        blob = b"".join(enc)
        header_cols.append({"k": key, "dt": "blob", "n": len(blob)})
        bufs.append(blob)

    if table is not None:
        add_strs("__fids__", table.fids)
        if table.visibility is not None:
            add_arr("__vis__:codes", table.visibility.codes)
            add_strs("__vis__:vocab", table.visibility.vocab)
        from geomesa_tpu.features.geometry import GeometryArray
        from geomesa_tpu.features.table import StringColumn
        for attr in table.sft.attributes:
            col = table.columns[attr.name]
            k = f"col:{attr.name}"
            if isinstance(col, GeometryArray):
                add_arr(k + ":types", col.type_codes)
                add_arr(k + ":geom_off", col.geom_offsets)
                add_arr(k + ":part_off", col.part_offsets)
                add_arr(k + ":ring_off", col.ring_offsets)
                add_arr(k + ":coords", col.coords)
            elif isinstance(col, StringColumn):
                add_arr(k + ":codes", col.codes)
                add_strs(k + ":vocab", col.vocab)
            else:
                add_arr(k, np.asarray(col))
    for k, v in (arrays or {}).items():
        add_arr(f"x:{k}", np.asarray(v))
    header = encode_json({"meta": meta, "cols": header_cols})
    return struct.pack("<I", len(header)) + header + b"".join(bufs)


def peek_meta(payload: bytes) -> dict:
    """Just the meta dict of an ``encode_table`` payload — no array or
    string-column decode (recovery uses it to resolve the target schema
    before paying for the full decode)."""
    (hlen,) = struct.unpack_from("<I", payload)
    return json.loads(payload[4:4 + hlen].decode())["meta"]


def decode_table(payload: bytes, sft=None):
    """(meta, table | None, arrays) from an ``encode_table`` payload; the
    table decodes only when ``sft`` is given and table columns are present."""
    from geomesa_tpu.features.geometry import GeometryArray
    from geomesa_tpu.features.table import FeatureTable, StringColumn

    (hlen,) = struct.unpack_from("<I", payload)
    header = json.loads(payload[4:4 + hlen].decode())
    off = 4 + hlen
    vals: Dict[str, object] = {}
    for c in header["cols"]:
        b = payload[off:off + c["n"]]
        off += c["n"]
        if c["dt"] == "sepblob":
            vals[c["k"]] = b.decode("utf-8").split("\x1f") if c["c"] else []
        elif c["dt"] == "blob":
            lens = vals.pop(c["k"] + ":lens")
            ends = np.cumsum(lens)
            starts = ends - lens
            vals[c["k"]] = [b[s:e].decode("utf-8")
                            for s, e in zip(starts, ends)]
        else:
            vals[c["k"]] = np.frombuffer(b, dtype=np.dtype(c["dt"])) \
                .reshape(c["sh"])
    meta = header["meta"]
    table = None
    if sft is not None and "__fids__" in vals:
        data: Dict[str, object] = {}
        for attr in sft.attributes:
            k = f"col:{attr.name}"
            if attr.is_geometry:
                data[attr.name] = GeometryArray(
                    vals[k + ":types"], vals[k + ":geom_off"],
                    vals[k + ":part_off"], vals[k + ":ring_off"],
                    np.array(vals[k + ":coords"]))
            elif attr.type_name == "String":
                data[attr.name] = StringColumn(
                    np.array(vals[k + ":codes"]), vals[k + ":vocab"])
            else:
                data[attr.name] = np.array(vals[k])  # writable copy
        fids = np.asarray(vals["__fids__"], dtype=object)
        table = FeatureTable.build(sft, data, fids=fids)
        if "__vis__:codes" in vals:
            table.visibility = StringColumn(
                np.array(vals["__vis__:codes"]), vals["__vis__:vocab"])
    arrays = {k[2:]: v for k, v in vals.items() if k.startswith("x:")}
    return meta, table, arrays


# -- segment scanning ---------------------------------------------------------


_SEG_RE = re.compile(r"^(?P<name>.+)-(?P<seq>\d{20})\.log$")


def segment_path(directory: str, name: str, first_seq: int) -> str:
    return os.path.join(directory, f"{name}-{first_seq:020d}.log")


def segments(directory: str, name: str = "wal") -> List[str]:
    """Segment paths for ``name`` in ``directory``, oldest first."""
    if not os.path.isdir(directory):
        return []
    out = []
    for fn in os.listdir(directory):
        m = _SEG_RE.match(fn)
        if m and m.group("name") == name:
            out.append(os.path.join(directory, fn))
    return sorted(out)


def segment_first_seq(path: str) -> int:
    return int(_SEG_RE.match(os.path.basename(path)).group("seq"))


def scan_segment(path: str):
    """Parse one segment: ``(records, valid_end_offset, error)`` where
    records are ``(seq, kind_name, payload, offset)`` tuples, in order.
    Stops at the first torn/corrupt frame: ``valid_end_offset`` is where the
    intact prefix ends (recovery truncates there) and ``error`` says why
    (None = clean to EOF)."""
    records: List[Tuple[int, str, bytes, int]] = []
    with open(path, "rb") as fh:
        head = fh.read(_HEADER.size)
        if len(head) < _HEADER.size:
            return records, 0, "truncated segment header"
        magic, first_seq = _HEADER.unpack(head)
        if magic != _MAGIC:
            return records, 0, "bad segment magic"
        pos = _HEADER.size
        expect = first_seq
        while True:
            hdr = fh.read(_FRAME.size)
            if not hdr:
                return records, pos, None
            if len(hdr) < _FRAME.size:
                return records, pos, "torn frame header"
            crc, length, seq, kind = _FRAME.unpack(hdr)
            payload = fh.read(length)
            if len(payload) < length:
                return records, pos, "torn frame payload"
            if zlib.crc32(_CRC_PART.pack(length, seq, kind) + payload) != crc:
                return records, pos, "bad crc"
            if seq != expect:
                return records, pos, f"sequence gap (want {expect}, got {seq})"
            records.append((seq, KIND_NAMES.get(kind, f"kind{kind}"),
                            payload, pos))
            pos += _FRAME.size + length
            expect += 1


def iter_records(directory: str, name: str = "wal",
                 after_seq: int = 0) -> Iterator[Tuple[int, str, bytes]]:
    """Records with seq > ``after_seq`` across all segments, in order;
    stops silently at the first torn/corrupt frame (recovery handles the
    truncation separately via scan_segment)."""
    for seg in segments(directory, name):
        records, _, error = scan_segment(seg)
        for seq, kind, payload, _off in records:
            if seq > after_seq:
                yield seq, kind, payload
        if error is not None:
            return


_TORN_ERRORS = ("torn frame header", "torn frame payload", "bad crc",
                "truncated segment header")


def contiguity(directory: str, name: str = "wal") -> dict:
    """Whole-log contiguity diagnosis: where (if anywhere) the global
    sequence breaks, and what kind of break it is. Shippers and recovery
    both need the distinction a silent stop-at-first-error hides:

      torn_tail        the LAST segment ends in a cut/corrupt frame — a
                       mid-write crash; everything before it is intact and
                       nothing recoverable is lost
      missing_segment  records exist PAST the break (a deleted/corrupt
                       middle segment, or a first_seq jump between
                       segments) — later records can never be ordered and
                       ``unreachable_records`` of them would be dropped

    ``first_gap_seq`` is the first sequence number that should exist but
    cannot be read (None when the log is contiguous to its end)."""
    out = {"first_seq": None, "last_contiguous_seq": None,
           "first_gap_seq": None, "gap_kind": None, "gap_error": None,
           "unreachable_records": 0, "unreachable_segments": 0}
    segs = segments(directory, name)
    expect: Optional[int] = None
    unreachable_from: Optional[int] = None  # index of first stranded segment
    for i, seg in enumerate(segs):
        first = segment_first_seq(seg)
        if out["first_seq"] is None:
            out["first_seq"] = first
        if expect is not None and first > expect:
            # a whole segment's worth of seqs is missing between i-1 and i
            out["first_gap_seq"] = expect
            out["gap_kind"] = "missing_segment"
            out["gap_error"] = (f"segment starting at {first} follows "
                                f"last readable seq {expect - 1}")
            unreachable_from = i
            break
        records, _end, error = scan_segment(seg)
        if records:
            out["last_contiguous_seq"] = records[-1][0]
            expect = records[-1][0] + 1
        elif expect is None:
            expect = first
        if error is not None:
            out["first_gap_seq"] = expect
            out["gap_error"] = error
            # a break in the FINAL segment is the ordinary torn tail a
            # crash leaves; a break with segments after it strands them
            out["gap_kind"] = ("torn_tail" if i == len(segs) - 1
                               and error in _TORN_ERRORS
                               else "missing_segment")
            unreachable_from = i + 1
            break
    if unreachable_from is not None:
        for later in segs[unreachable_from:]:
            out["unreachable_segments"] += 1
            out["unreachable_records"] += len(scan_segment(later)[0])
    return out


def read_raw_frames(path: str, offset: int = 0, after_seq: int = 0):
    """Raw CRC-verified frames from one segment starting at byte
    ``offset`` (0 = start, past the header): yields ``(seq, kind_name,
    frame_bytes, end_offset)`` where ``frame_bytes`` is the exact on-disk
    ``crc|len|seq|kind|payload`` encoding — a shipper forwards it verbatim
    so the receiver re-verifies the SAME crc. Stops (without raising) at
    the first torn/corrupt frame; the caller may retry from the returned
    end_offset once more bytes exist (a torn live head is just a frame
    still being written)."""
    with open(path, "rb") as fh:
        if offset <= _HEADER.size:
            head = fh.read(_HEADER.size)
            if len(head) < _HEADER.size or head[:4] != _MAGIC:
                return
            offset = _HEADER.size
        fh.seek(offset)
        while True:
            hdr = fh.read(_FRAME.size)
            if len(hdr) < _FRAME.size:
                return
            crc, length, seq, kind = _FRAME.unpack(hdr)
            payload = fh.read(length)
            if len(payload) < length:
                return
            if zlib.crc32(_CRC_PART.pack(length, seq, kind) + payload) != crc:
                return
            offset += _FRAME.size + length
            if seq > after_seq:
                yield (seq, KIND_NAMES.get(kind, f"kind{kind}"),
                       hdr + payload, offset)


def verify_frame(frame: bytes):
    """Validate one raw frame's structure + CRC; returns
    ``(seq, kind_name, payload)`` or raises ValueError — the follower-side
    receipt check for shipped frames (runs BEFORE any duplicate-skip, so a
    corrupted frame can never masquerade as an already-held record)."""
    if len(frame) < _FRAME.size:
        raise ValueError("short frame")
    crc, length, seq, kind = _FRAME.unpack_from(frame)
    if len(frame) != _FRAME.size + length:
        raise ValueError(f"frame length mismatch ({len(frame)} != "
                         f"{_FRAME.size + length})")
    payload = frame[_FRAME.size:]
    if zlib.crc32(_CRC_PART.pack(length, seq, kind) + payload) != crc:
        raise ValueError(f"bad frame crc at seq {seq}")
    return seq, KIND_NAMES.get(kind, f"kind{kind}"), payload


class WalTailer:
    """Incremental raw-frame reader for the log shipper: tracks (segment,
    byte offset, next expected seq) so each ``poll()`` reads only NEW
    frames instead of rescanning the log. Follows size-based rotation; a
    torn live head (a frame mid-write) simply ends the poll and retries at
    the same offset next time. Raises FileNotFoundError when the needed
    segment was garbage-collected out from under the tail (the follower
    is then too far behind and must snapshot-catchup)."""

    def __init__(self, directory: str, name: str = "wal",
                 after_seq: int = 0):
        self.dir = directory
        self.name = name
        self.next_seq = int(after_seq) + 1
        self._seg: Optional[str] = None
        self._off = 0

    def _locate(self) -> Optional[str]:
        """Segment that should contain ``next_seq`` (newest first_seq <=
        next_seq); None when the log has nothing at or before it yet."""
        best = None
        for seg in segments(self.dir, self.name):
            if segment_first_seq(seg) <= self.next_seq:
                best = seg
        if best is None and segments(self.dir, self.name):
            raise FileNotFoundError(
                f"wal segment containing seq {self.next_seq} was "
                f"garbage-collected")
        return best

    def poll(self, limit: Optional[int] = None):
        """All newly readable ``(seq, kind_name, frame_bytes)`` in order
        (up to ``limit``)."""
        out = []
        while True:
            if self._seg is None:
                self._seg = self._locate()
                self._off = 0
                if self._seg is None:
                    return out
            if not os.path.exists(self._seg):
                raise FileNotFoundError(self._seg)
            advanced = False
            for seq, kind, frame, end in read_raw_frames(
                    self._seg, self._off, after_seq=self.next_seq - 1):
                self._off = end
                advanced = True
                if seq != self.next_seq:
                    # pre-existing intra-segment gap: unreachable past here
                    return out
                out.append((seq, kind, frame))
                self.next_seq = seq + 1
                if limit is not None and len(out) >= limit:
                    return out
            if not advanced and self._off == 0:
                # skipped records before next_seq count as progress too
                recs = list(read_raw_frames(self._seg, 0, after_seq=0))
                if recs:
                    self._off = recs[-1][3]
            # rotation: a successor segment owns next_seq now
            succ = None
            for seg in segments(self.dir, self.name):
                if seg != self._seg and \
                        segment_first_seq(seg) == self.next_seq:
                    succ = seg
                    break
            if succ is not None:
                self._seg, self._off = succ, 0
                continue
            return out


def inspect(directory: str, name: str = "wal") -> dict:
    """Debug dump for the CLI ``debug wal`` inspector: per-segment record
    listing (seq, kind, bytes), torn-tail diagnostics, and the whole-log
    contiguity diagnosis (first_gap_seq + torn-tail vs missing-segment
    classification)."""
    out: dict = {"dir": directory, "name": name, "segments": []}
    for seg in segments(directory, name):
        records, valid_end, error = scan_segment(seg)
        size = os.path.getsize(seg)
        out["segments"].append({
            "path": seg,
            "first_seq": segment_first_seq(seg),
            "bytes": size,
            "records": len(records),
            "seq_range": [records[0][0], records[-1][0]] if records else None,
            "kinds": {k: sum(1 for r in records if r[1] == k)
                      for k in {r[1] for r in records}},
            "torn": None if error is None else
                    {"error": error, "valid_end": valid_end,
                     "trailing_bytes": size - valid_end},
        })
    out["contiguity"] = contiguity(directory, name)
    return out


# -- the log ------------------------------------------------------------------


import weakref

# live WriteAheadLog instances (weak: a closed+dropped store frees its WAL)
# — the `wal.open_segments` gauge sums on-disk segment counts over these
_LIVE_WALS: "weakref.WeakSet" = weakref.WeakSet()


def open_segment_count() -> int:
    """Total on-disk segments across every live (unclosed) WAL in this
    process — the observability-buffer-pressure gauge feed."""
    total = 0
    for w in list(_LIVE_WALS):
        if not w._closed:
            total += len(segments(w.dir, w.name))
    return total


class WriteAheadLog:
    """One append-only log (a directory of numbered segments). Thread-safe;
    mutators call ``append`` before applying their mutation in memory
    (log-then-apply), recovery replays via ``iter_records``."""

    def __init__(self, directory: str, name: str = "wal",
                 fsync: Optional[str] = None,
                 segment_bytes: Optional[int] = None,
                 interval_ms: Optional[float] = None,
                 start_seq: int = 1):
        from geomesa_tpu import config
        self.dir = directory
        self.name = name
        self.policy = (fsync or config.WAL_FSYNC.get()).lower()
        if self.policy not in ("off", "batch", "always"):
            raise ValueError(f"unknown WAL fsync policy {self.policy!r}")
        self.segment_bytes = int(segment_bytes
                                 or config.WAL_SEGMENT_BYTES.get())
        self.interval_s = (interval_ms if interval_ms is not None
                           else config.WAL_INTERVAL_MS.get()) / 1000.0
        os.makedirs(directory, exist_ok=True)
        # a pre-existing break in the on-disk log (recovery normally cleans
        # one up first, but a follower/shipper-facing WAL may still carry
        # it): diagnosed once at open — live appends can never create one
        self._initial_gap = (contiguity(directory, name)
                             if segments(directory, name) else None)
        self._lock = threading.RLock()
        self._sync_cond = threading.Condition()
        self._sync_leader = False
        self._tail_cond = threading.Condition()
        self._tail_waiters = 0
        self._next_seq = int(start_seq)
        self._last_seq = int(start_seq) - 1
        self._synced_seq = self._last_seq
        self._written_bytes = 0
        self._synced_bytes = 0
        self._n_fsyncs = 0
        self._fh = None
        self._seg_size = 0
        self._closed = False
        self._syncer: Optional[threading.Thread] = None
        self._syncer_stop = threading.Event()
        self._open_segment()
        _LIVE_WALS.add(self)

    # -- state ---------------------------------------------------------------

    @property
    def last_seq(self) -> int:
        return self._last_seq

    @property
    def synced_seq(self) -> int:
        return self._synced_seq

    @property
    def unsynced_bytes(self) -> int:
        return max(0, self._written_bytes - self._synced_bytes)

    def stats(self) -> dict:
        gap = self._initial_gap or {}
        return {
            "policy": self.policy,
            "last_seq": self._last_seq,
            "synced_seq": self._synced_seq,
            "unsynced_bytes": self.unsynced_bytes,
            "fsyncs": self._n_fsyncs,
            "segments": len(segments(self.dir, self.name)),
            "segment_bytes": self._seg_size,
            # explicit contiguity break (None = contiguous): shippers and
            # recovery distinguish "torn tail" from "missing segment"
            # instead of silently dropping everything past the break
            "first_gap_seq": gap.get("first_gap_seq"),
            "gap_kind": gap.get("gap_kind"),
        }

    # -- writing -------------------------------------------------------------

    def append(self, kind: str, payload: bytes) -> int:
        """Append one record; returns its sequence number. Under policy
        ``always`` the record is fsync-durable on return (group commit);
        under ``batch`` within one commit window; under ``off`` whenever
        the OS flushes."""
        from geomesa_tpu import trace as _trace
        from geomesa_tpu.metrics import REGISTRY as _metrics
        k = KINDS[kind]
        t0 = time.perf_counter()
        with self._lock:
            if self._closed:
                raise ValueError("WAL is closed")
            faults.crash_point("wal.append.before")
            seq = self._next_seq
            # incremental crc: no header+payload concat copy on the hot path
            crc = zlib.crc32(payload,
                             zlib.crc32(_CRC_PART.pack(len(payload), seq, k)))
            hdr = _FRAME.pack(crc, len(payload), seq, k)
            frame_len = _FRAME.size + len(payload)
            cut = faults.torn_cut(frame_len)
            if cut is not None:
                # simulated power loss mid-write: persist the torn prefix so
                # recovery actually faces it, then die
                self._fh.write((hdr + payload)[:cut])
                self._fh.flush()
                os.fsync(self._fh.fileno())
                raise InjectedCrash("wal.append.torn")
            self._fh.write(hdr)
            self._fh.write(payload)
            self._next_seq = seq + 1
            self._last_seq = seq
            self._seg_size += frame_len
            self._written_bytes += frame_len
            need_rotate = self._seg_size >= self.segment_bytes
        _metrics.inc("wal.records")
        _metrics.observe_value("wal.append_bytes", frame_len)
        if self.policy == "always":
            self._group_sync(seq)
        elif self.policy == "batch":
            self._ensure_syncer()
        if _trace.enabled():
            _trace.record("wal.append", "wal_append",
                          time.perf_counter() - t0)
        if self._tail_waiters:
            with self._tail_cond:
                self._tail_cond.notify_all()
        if need_rotate:
            self.rotate()
        faults.crash_point("wal.append.after")
        return seq

    def append_frame(self, frame: bytes) -> int:
        """Append one pre-framed record (``crc|len|seq|kind|payload``)
        verbatim — the follower-side ingestion of a shipped frame. The
        frame's CRC is re-verified and its seq must be exactly the next
        expected (shipped logs stay byte-identical to the primary's,
        modulo segment boundaries). Durability policy applies as for
        ``append``."""
        if len(frame) < _FRAME.size:
            raise ValueError("short frame")
        crc, length, seq, kind = _FRAME.unpack_from(frame)
        if len(frame) != _FRAME.size + length:
            raise ValueError(
                f"frame length mismatch ({len(frame)} != "
                f"{_FRAME.size + length})")
        if zlib.crc32(_CRC_PART.pack(length, seq, kind)
                      + frame[_FRAME.size:]) != crc:
            raise ValueError(f"bad frame crc at seq {seq}")
        from geomesa_tpu.metrics import REGISTRY as _metrics
        with self._lock:
            if self._closed:
                raise ValueError("WAL is closed")
            if seq != self._next_seq:
                raise ValueError(
                    f"non-contiguous frame seq {seq} (expect "
                    f"{self._next_seq})")
            self._fh.write(frame)
            self._next_seq = seq + 1
            self._last_seq = seq
            self._seg_size += len(frame)
            self._written_bytes += len(frame)
            need_rotate = self._seg_size >= self.segment_bytes
        _metrics.inc("wal.records")
        _metrics.observe_value("wal.append_bytes", len(frame))
        if self.policy == "always":
            self._group_sync(seq)
        elif self.policy == "batch":
            self._ensure_syncer()
        if self._tail_waiters:
            with self._tail_cond:
                self._tail_cond.notify_all()
        if need_rotate:
            self.rotate()
        return seq

    def flush_to_os(self) -> None:
        """Push the userspace write buffer to the OS page cache (no fsync)
        so on-disk readers — the log shipper's tail — observe every
        appended frame immediately, regardless of fsync policy."""
        with self._lock:
            if self._fh is not None and not self._closed:
                self._fh.flush()

    def wait_for_seq(self, seq: int, timeout: Optional[float] = None) -> bool:
        """Block until a record with sequence >= ``seq`` has been appended
        (True) or ``timeout`` seconds pass (False). The shipper's idle
        wait: appends wake it immediately; the capped internal wait bounds
        the cost of any missed notify."""
        if self._last_seq >= seq:
            return True
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._tail_cond:
            self._tail_waiters += 1
            try:
                while self._last_seq < seq and not self._closed:
                    remaining = None if deadline is None \
                        else deadline - time.monotonic()
                    if remaining is not None and remaining <= 0:
                        return False
                    self._tail_cond.wait(0.1 if remaining is None
                                         else min(remaining, 0.1))
            finally:
                self._tail_waiters -= 1
        return self._last_seq >= seq

    def append_json(self, kind: str, meta: dict) -> int:
        return self.append(kind, encode_json(meta))

    def append_table(self, kind: str, meta: dict, table=None,
                     arrays=None) -> int:
        return self.append(kind, encode_table(meta, table, arrays))

    def sync(self) -> None:
        """Force a group fsync covering everything appended so far."""
        with self._lock:
            target = self._last_seq
        self._group_sync(target)

    def _group_sync(self, seq: int) -> None:
        """Group commit: make records up to ``seq`` durable. One thread
        leads (flush+fsync); concurrent callers piggyback on its fsync and
        return as soon as their seq is covered."""
        from geomesa_tpu import trace as _trace
        from geomesa_tpu.metrics import REGISTRY as _metrics
        with self._sync_cond:
            while True:
                if self._synced_seq >= seq:
                    return
                if not self._sync_leader:
                    self._sync_leader = True
                    break
                self._sync_cond.wait()
        try:
            with self._lock:
                fh = self._fh
                target = self._last_seq
                written = self._written_bytes
            t0 = time.perf_counter()
            faults.crash_point("wal.fsync")
            from geomesa_tpu import config
            from geomesa_tpu.durability.rotation import fsync_file
            attempts = int(config.RETRY_WAL_FSYNC.get())
            if attempts <= 1:
                fsync_file(fh)
            else:
                # transient-EIO absorption behind the shared capped-backoff
                # wrapper (GEOMESA_TPU_RETRY_WAL_FSYNC > 1 opts in; the
                # default stays strict so 'always' surfaces the first
                # failure to the writer that demanded durability)
                from geomesa_tpu.serve.resilience.breaker import retry_call
                retry_call(lambda: fsync_file(fh), attempts=attempts,
                           counter="wal.fsync_retries")
            dt = time.perf_counter() - t0
        except OSError:
            _metrics.inc("wal.fsync_errors")
            raise
        finally:
            with self._sync_cond:
                self._sync_leader = False
                self._sync_cond.notify_all()
        with self._sync_cond:
            group = max(0, target - self._synced_seq)
            self._synced_seq = max(self._synced_seq, target)
            self._sync_cond.notify_all()
        with self._lock:
            self._synced_bytes = max(self._synced_bytes, written)
            self._n_fsyncs += 1
        _metrics.inc("wal.fsyncs")
        if group:
            _metrics.observe_value("wal.group_size", group)
        if _trace.enabled():
            _trace.record("wal.fsync", "wal_fsync", dt)
        # callers whose seq landed after our target retry via recursion
        # (bounded: each level covers strictly more of the log)
        if seq > self._synced_seq:
            self._group_sync(seq)

    def _ensure_syncer(self) -> None:
        if self._syncer is not None:
            return
        with self._lock:
            if self._syncer is not None or self._closed:
                return
            from geomesa_tpu.obs import hostcpu
            t = hostcpu.thread("program.other", target=self._sync_loop,
                               name=f"geomesa-wal-sync-{self.name}",
                               daemon=True)
            self._syncer = t
        t.start()

    def _sync_loop(self) -> None:
        from geomesa_tpu.metrics import REGISTRY as _metrics
        while not self._syncer_stop.wait(self.interval_s):
            if self._closed:
                return
            if self.unsynced_bytes or self._synced_seq < self._last_seq:
                try:
                    self.sync()
                except OSError:
                    # injected/real fsync failure: counted (in _group_sync),
                    # retried next window — the batch policy's contract
                    continue
                except Exception:
                    _metrics.inc("wal.fsync_errors")
                    continue

    # -- segment lifecycle ---------------------------------------------------

    def _open_segment(self) -> None:
        path = segment_path(self.dir, self.name, self._next_seq)
        # "wb": a same-named leftover can only be an empty (header-only)
        # segment from a prior recover-then-crash — records would have
        # advanced the seq past it
        self._fh = open(path, "wb")
        self._fh.write(_HEADER.pack(_MAGIC, self._next_seq))
        self._fh.flush()
        self._seg_size = _HEADER.size
        self._written_bytes += _HEADER.size
        from geomesa_tpu.metrics import REGISTRY as _metrics
        _metrics.inc("wal.segments")

    def rotate(self) -> None:
        """Close the live segment (fsynced unless policy ``off``) and open
        its successor. Called on size overflow and after each snapshot."""
        # become the sync leader so no in-flight group fsync holds the old fh
        with self._sync_cond:
            while self._sync_leader:
                self._sync_cond.wait()
            self._sync_leader = True
        try:
            with self._lock:
                if self._closed:
                    return
                if self._seg_size <= _HEADER.size:
                    return  # empty segment: nothing to rotate
                faults.crash_point("wal.rotate")
                if self.policy != "off":
                    from geomesa_tpu.durability.rotation import fsync_file
                    fsync_file(self._fh)
                    self._synced_seq = self._last_seq
                    self._synced_bytes = self._written_bytes
                else:
                    self._fh.flush()
                self._fh.close()
                self._open_segment()
        finally:
            with self._sync_cond:
                self._sync_leader = False
                self._sync_cond.notify_all()

    def gc(self, upto_seq: int) -> int:
        """Delete segments made fully redundant by a snapshot covering
        ``upto_seq`` (every record with seq <= upto_seq is in the snapshot).
        A segment dies only when its successor proves it holds no later
        records. Returns segments removed."""
        faults.crash_point("wal.gc")
        segs = segments(self.dir, self.name)
        removed = 0
        with self._lock:
            current = self._fh.name if self._fh else None
        for i in range(len(segs) - 1):
            if segs[i] == current:
                continue
            if segment_first_seq(segs[i + 1]) <= upto_seq + 1:
                try:
                    os.remove(segs[i])
                    removed += 1
                except OSError:
                    pass
        return removed

    def close(self) -> None:
        self._syncer_stop.set()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            try:
                self._fh.flush()
                if self.policy != "off":
                    os.fsync(self._fh.fileno())
            except (OSError, ValueError):
                pass
            self._fh.close()
        if self._syncer is not None:
            self._syncer.join(timeout=2)
