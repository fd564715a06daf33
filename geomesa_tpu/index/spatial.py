"""Spatial index implementations: Z2 / Z3 / XZ2 / XZ3.

≙ reference index.index.{z2,z3} key spaces (Z3IndexKeySpace.scala:34 etc.).
Each index owns a device-resident projection of the table sorted in its key
order (epoch-major for the temporal variants — the epoch bin is the row-key
prefix exactly as in the reference's ``[shard][epoch:2][z:8]`` layout), plus
host-side sorted key arrays for range pruning, and produces IndexScanPlans:

  - spatial constraint → padded int31 boxes, loose (cell cover) + strict
    (cell interior) — the contained/overlapping-range distinction
  - temporal constraint → exact (bin, offset) windows (Z3Filter.timeInBounds)
  - leftover predicates → device residual (compiled) + host residual

The scan itself is a full-table fused mask (bandwidth-bound, fast on TPU);
the sorted layout + host key arrays enable block-range pruning (searchsorted
over the reference-style z-range cover) which the planner can enable for
low-selectivity queries.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from geomesa_tpu.curves.binnedtime import (TimePeriod, binned_time_to_millis,
                                           max_offset, time_to_binned_time)
from geomesa_tpu.curves.normalize import NormalizedLat, NormalizedLon
from geomesa_tpu.curves.sfc import Z2SFC, Z3SFC
from geomesa_tpu.curves.xz import XZ2SFC, XZ3SFC
from geomesa_tpu.curves import zorder
from geomesa_tpu.features.table import FeatureTable, StringColumn
from geomesa_tpu.filter import extract, ir
from geomesa_tpu.filter.extract import extract_bboxes, extract_intervals
from geomesa_tpu.index.api import IndexScanPlan
from geomesa_tpu.index.device import DeviceTable, fp62_lat, fp62_lon, host_planes
from geomesa_tpu.index.scan import (ModuleKernelCache, ScanKernels, pad_boxes,
                                    pad_windows, split_residual,
                                    compile_residual)

# Above this row count the index-key sort and row reorder run on the
# accelerator (3×21-bit int32 key planes through lax.sort + one fused gather)
# instead of a single-core host lexsort — ~80× faster at 100M rows.
# DEVICE_SORT_MIN_ROWS resolves through the config registry on each access
# (PEP 562) so runtime overrides apply; tests may monkeypatch it directly.
from geomesa_tpu import config as _config


def __getattr__(name: str):
    if name == "DEVICE_SORT_MIN_ROWS":
        return _config.DEVICE_SORT_MIN.get()
    raise AttributeError(name)

_MASK21 = (1 << 21) - 1


def _stream_encode_upload(encode_chunk, n: int, chunk_rows: int,
                          key_names: Optional[List[str]] = None,
                          shard_devices=None):
    """Chunked native encode overlapped with host→device upload.

    ≙ the latency-hiding of the reference's ``AbstractBatchScan`` pipeline
    (SURVEY §2.12 row 8), applied to the build path: a background thread
    streams chunk i's planes to the device while the C++ encoder (which
    releases the GIL) works on chunk i+1, so encode time and transfer time
    overlap instead of summing. Per-plane chunks concatenate ON DEVICE
    (transient ~2x HBM for the planes, freed before the sort gather).

    With ``shard_devices`` (≥2) the sort-key planes of chunk i additionally
    land round-robin on ``shard_devices[i % D]`` so the mesh-sharded sort
    starts with its inputs already distributed — upload and shard-sort
    pipeline instead of re-scattering after a single-device concat. The
    sort-only planes (zhi/zlo) then skip the default device entirely.

    ``encode_chunk(lo, hi)`` → plane dict or None (native decline).
    Returns ({plane: device array}, [host-kept chunk dicts], key_shards)
    where key_shards is None without sharding, else a per-device list of
    ``(row_offset, [key plane arrays])`` chunks; returns None when any
    chunk declines — the caller falls back to the single-shot path.
    """
    import queue
    import threading

    import jax
    import jax.numpy as jnp

    sharding = (shard_devices is not None and len(shard_devices) >= 2
                and key_names is not None)
    key_shards: Optional[List[list]] = \
        [[] for _ in shard_devices] if sharding else None

    q: "queue.Queue" = queue.Queue(maxsize=2)
    uploaded: List[dict] = []
    state = {"error": None}

    def uploader():
        # a device_put failure (e.g. HBM OOM) must record the error and KEEP
        # DRAINING: exiting early leaves the producer blocked forever on the
        # bounded queue (deadlocked build, exception swallowed)
        while True:
            item = q.get()
            if item is None:
                return
            if state["error"] is not None:
                continue
            off, enc = item
            try:
                if sharding:
                    d = (off // chunk_rows) % len(shard_devices)
                    key_shards[d].append((off, [
                        jax.device_put(enc[k], shard_devices[d])
                        for k in key_names]))
                    uploaded.append({k: jax.device_put(v)
                                     for k, v in enc.items()
                                     if k not in ("zhi", "zlo")})
                else:
                    uploaded.append({k: jax.device_put(v)
                                     for k, v in enc.items()})
            except BaseException as e:  # noqa: BLE001 - re-raised below
                state["error"] = e

    th = threading.Thread(target=uploader, daemon=True)
    th.start()
    host_kept: List[dict] = []
    failed = False
    try:
        for a in range(0, n, chunk_rows):
            if state["error"] is not None:
                break
            enc = encode_chunk(a, min(n, a + chunk_rows))
            if enc is None:
                failed = True
                break
            # z (and bin16 where present) stay host-side for range pruning;
            # keep refs BEFORE the device put consumes the dict
            host_kept.append({k: enc[k] for k in ("z", "bin16")
                              if k in enc})
            enc.pop("z", None)
            q.put((a, enc))
    finally:
        q.put(None)
        th.join()
    if state["error"] is not None:
        raise state["error"]
    if failed or not uploaded:
        return None
    dev = {k: (uploaded[0][k] if len(uploaded) == 1
               else jnp.concatenate([u[k] for u in uploaded]))
           for k in uploaded[0]}
    return dev, host_kept, key_shards


def _split63(v: np.ndarray) -> List[np.ndarray]:
    """Split non-negative int64 keys (< 2^63) into three 21-bit int32 planes
    (major → minor) so the device sort never needs 64-bit lanes."""
    v = np.asarray(v, dtype=np.int64)
    return [((v >> 42) & _MASK21).astype(np.int32),
            ((v >> 21) & _MASK21).astype(np.int32),
            (v & _MASK21).astype(np.int32)]


def _sort_perm_fn(ks):
    """Stable sort permutation from padded key planes (row iota rides as the
    final key, making the order total == a stable host lexsort)."""
    import jax.numpy as jnp
    from jax import lax

    iota = lax.iota(jnp.int32, ks[0].shape[0])
    out = lax.sort(tuple(ks) + (iota,), num_keys=len(ks) + 1)
    return out[-1]


# Build-path jit caches: previously bare module globals that pinned one
# compilation per padded signature forever; now bounded shape-keyed LRUs
# (GEOMESA_TPU_KERNEL_CACHE) counted in the kernels.compiled gauge.
_SORT_PERM_CACHE = ModuleKernelCache("build.sort_perm")
_ROW_GATHER_CACHE = ModuleKernelCache("build.row_gather")
_SORT_GATHER_CACHE = ModuleKernelCache("build.sort_gather")


def _sort_perm(padded_keys):
    """Shape-keyed jit shared across every index build in the process (the
    per-call-closure version re-traced on each build); one cache entry per
    (plane count, padded length) signature."""
    import jax
    key = (len(padded_keys), int(padded_keys[0].shape[0]))
    fn = _SORT_PERM_CACHE.get(key, lambda: jax.jit(_sort_perm_fn))
    return fn(tuple(padded_keys))


def device_sort_perm(keys: List[np.ndarray], type_name: Optional[str] = None):
    """Sort permutation computed on device from int32 key planes.

    On a multi-device mesh (and above GEOMESA_TPU_SHARD_SORT_MIN rows) the
    sort shards across devices (parallel.dist.mesh_sort_perm) — bitwise the
    same permutation; a 1-device mesh takes the single-device path below.
    Keys are padded to a power of two with int32-max sentinels (shared jit
    signatures across sizes).
    """
    import jax.numpy as jnp

    n = len(keys[0])
    from geomesa_tpu.parallel import dist as _dist
    if _dist.mesh_sort_enabled(n):
        return _dist.mesh_sort_perm([np.ascontiguousarray(k) for k in keys],
                                    type_name=type_name)
    cap = 1 << max(0, (n - 1)).bit_length()
    padded = []
    for k in keys:
        p = np.full(cap, np.iinfo(np.int32).max, dtype=np.int32)
        p[:n] = k
        padded.append(jnp.asarray(p))
    return _sort_perm(padded)[:n]


def _row_gather(dev_perm, idx: np.ndarray) -> np.ndarray:
    """Gather table rows for sorted positions on device (pow2-padded so
    compilations and transfer shapes are shared across result sizes)."""
    import jax
    import jax.numpy as jnp

    if len(idx) == 0:
        return np.empty(0, dtype=np.int64)
    cap = max(8, 1 << max(0, len(idx) - 1).bit_length())
    pad = np.zeros(cap, np.int32)
    pad[: len(idx)] = idx
    key = (int(dev_perm.shape[0]), cap)
    fn = _ROW_GATHER_CACHE.get(key, lambda: jax.jit(lambda p, i: p[i]))
    out = np.asarray(fn(dev_perm, jnp.asarray(pad)))
    return out[: len(idx)].astype(np.int64)


def _as_query_column(name: str, gathered, xp):
    """Shared build-plane → device-column rename/cast rule (one home for both
    the host small-table gather and the traced device gather): bin16 lands as
    an int32 ``bin`` column; sort-key planes (zhi/zlo) are not query columns."""
    if name in ("zhi", "zlo"):
        return None, None
    if name == "bin16":
        return "bin", gathered.astype(xp.int32)
    return name, gathered


def _native_sort_gather(keys, cols, n: int):
    """One fused device program: sort padded keys → perm, gather every query
    column through it, cast bin16 → int32. Cached per (shapes, n) signature
    so repeated builds share compilations without pinning every size tier."""
    import functools

    import jax
    import jax.numpy as jnp

    def build():
        @functools.partial(jax.jit, static_argnames=("n",))
        def fn(keys, cols, n):
            cap = 1 << max(0, (n - 1)).bit_length()
            padded = tuple(
                jnp.pad(k, (0, cap - n),
                        constant_values=np.array(np.iinfo(k.dtype).max,
                                                 dtype=k.dtype))
                for k in keys)
            perm = _sort_perm_fn(padded)[:n]
            out = {}
            for name, v in cols.items():
                out_name, g = _as_query_column(name, v[perm], jnp)
                if out_name is not None:
                    out[out_name] = g
            return perm, out

        return fn

    key = (n, len(keys),
           tuple(sorted((name, str(v.dtype)) for name, v in cols.items())))
    return _SORT_GATHER_CACHE.get(key, build)(keys, cols, n)


def _perm_gather_cols(dev_perm, cols, n: int):
    """Gather query columns through an already-computed device permutation
    (the mesh-sharded sort path, where the perm comes from
    parallel.dist.mesh_sort_perm instead of the fused sort_gather program)."""
    import jax
    import jax.numpy as jnp

    def build():
        def fn(perm, cols):
            out = {}
            for name, v in cols.items():
                out_name, g = _as_query_column(name, v[perm], jnp)
                if out_name is not None:
                    out[out_name] = g
            return out

        return jax.jit(fn)

    key = ("perm_gather", n,
           tuple(sorted((name, str(v.dtype)) for name, v in cols.items())))
    return _SORT_GATHER_CACHE.get(key, build)(dev_perm, cols)


def _strip_handled(f: ir.Filter, geom: Optional[str], dtg: Optional[str],
                   points: bool) -> Optional[ir.Filter]:
    """Residual after removing predicates the primary boxes/windows enforce
    exactly.

    A spatial node drops when its box extraction IS the predicate: BBox
    (envelope-overlap semantics, exact for points and extents alike via the
    fp62 envelope planes) and, for point layers only, exact-extracting
    Intersects (point/rectangle literals). Temporal nodes on ``dtg`` always
    drop (windows are exact). OR-rooted filters keep the whole filter as
    residual (the boxes/windows become a superset prefilter) — the
    conservative analogue of the reference's DNF expansion fallback
    (FilterSplitter.scala:61-103).
    """
    if isinstance(f, ir.Or):
        return f
    children = f.children if isinstance(f, ir.And) else (f,)
    rest: List[ir.Filter] = []
    for c in children:
        if isinstance(c, (ir.BBox, ir.Intersects, ir.Contains, ir.Within, ir.Dwithin)) \
                and (geom is None or c.attr == geom):
            if isinstance(c, ir.BBox):
                continue  # envelope semantics: primary boxes are exact
            if points and extract_bboxes(c, geom).exact:
                continue  # point-in-rectangle: primary boxes are exact
            rest.append(c)
        elif isinstance(c, ir.During) and c.attr == dtg:
            continue  # exact via windows
        elif isinstance(c, ir.Cmp) and c.attr == dtg and isinstance(c.value, (int, np.integer)):
            continue  # exact via windows
        else:
            rest.append(c)
    return ir.and_filters(rest) if rest else None


def _boxes_fp62(boxes) -> np.ndarray:
    """User-space boxes → (B, 8) int32 fp62 query planes:
    [qxlo_hi, qxlo_lo, qxhi_hi, qxhi_lo, qylo_hi, qylo_lo, qyhi_hi, qyhi_lo].
    Device comparisons against these reproduce f64 bounds exactly (device.fp62)."""
    out = np.empty((len(boxes), 8), dtype=np.int32)
    for i, (xmin, ymin, xmax, ymax) in enumerate(boxes):
        xlo = fp62_lon(xmin)
        xhi = fp62_lon(xmax)
        ylo = fp62_lat(ymin)
        yhi = fp62_lat(ymax)
        out[i] = (xlo[0], xlo[1], xhi[0], xhi[1], ylo[0], ylo[1], yhi[0], yhi[1])
    return out


class _DeltaKeyShim:
    """Minimal stand-in passed to an index class's ``_sort_keys`` to compute
    a delta run's key planes without building a full index over the delta
    table (``_sort_keys`` reads table/sft/dtg/period/geom and writes its key
    arrays — ``_z``/``_xz``/``_bins``/``_sfc`` — onto ``self``)."""

    def __init__(self, sft, table, geom, dtg, period):
        self.sft = sft
        self.table = table
        self.geom = geom
        self.dtg = dtg
        self.period = period


class BaseSpatialIndex:
    """Shared machinery: device table, kernels, plan construction."""

    name: str = "base"
    temporal: bool = False
    points: bool = True

    def __init__(self, sft, table: FeatureTable):
        self.sft = sft
        self.table = table
        self.geom = sft.geometry_attribute.name if sft.geometry_attribute else None
        dtg = sft.dtg_attribute
        self.dtg = dtg.name if dtg else None
        self.period = TimePeriod.parse(sft.z3_interval) if self.dtg else None
        self._perm_cache: Optional[np.ndarray] = None
        self._dev_perm = None
        n = len(table)
        from geomesa_tpu.obs.profiling import PROGRESS as _progress
        if not self._build_native():
            keys = self._sort_keys()
            if keys is None:
                self._perm_cache = np.arange(n, dtype=np.int64)
                self.device = DeviceTable.build(table, self._perm_cache, self.period)
            elif n >= sys.modules[__name__].DEVICE_SORT_MIN_ROWS and all(
                    k.dtype == np.int32 for k in keys):
                with _progress.phase("device_sort", rows=n,
                                     type_name=sft.name):
                    self._dev_perm = device_sort_perm(keys,
                                                      type_name=sft.name)
                with _progress.phase("upload_gather", rows=n,
                                     type_name=sft.name):
                    self.device = DeviceTable.build_on_device(
                        table, self._dev_perm, self.period)
                self._prefetch_perm()
            else:
                # np.lexsort sorts by LAST key first → reverse to major-first
                with _progress.phase("host_sort", rows=n,
                                     type_name=sft.name):
                    self._perm_cache = np.lexsort(
                        tuple(reversed(keys))).astype(np.int64)
                with _progress.phase("upload_gather", rows=n,
                                     type_name=sft.name):
                    self.device = DeviceTable.build(
                        table, self._perm_cache, self.period)
        self._build_segment_pool()
        self.kernels = ScanKernels(self.device.columns)
        self.vocabs = {
            name: col.vocab for name, col in table.columns.items()
            if isinstance(col, StringColumn)
        }

    def _join_prefetch(self) -> None:
        """Wait for the background perm/keys prefetch (if any) to finish.
        Every lazy accessor calls this first — otherwise a query arriving
        while the prefetch is mid-gather would see a not-yet-set cache and
        redo the same multi-hundred-ms gather synchronously (the r4
        plan-stage regression at 10M scale)."""
        import threading
        t = getattr(self, "_perm_thread", None)
        if t is not None and t is not threading.current_thread():
            t.join()
            self._perm_thread = None

    @property
    def perm(self) -> np.ndarray:
        """Host copy of the index sort permutation (sorted pos → table row);
        downloaded from the device lazily on the large-table build path (a
        background prefetch started at build time usually has it ready)."""
        if self._perm_cache is None:
            self._join_prefetch()
        if self._perm_cache is None:
            self._perm_cache = np.asarray(self._dev_perm).astype(np.int64)
        return self._perm_cache

    def _host_sorted_keys(self) -> None:
        """Derive the sorted host pruning keys WITHOUT downloading the
        device perm. The index order is (bin, key, row); row only breaks
        ties between EQUAL keys, so the sorted key *values* are exactly
        np.sort per bin segment — host sorts that overlap whatever follows
        the build, instead of a 400MB device→host perm download at 100M."""
        bins = getattr(self, "_bins", None)
        order = None
        if bins is not None:
            # one stable argsort of the (small-dtype) bins, then per-segment
            # value sorts — O(N log N) regardless of how many bins exist
            order = np.argsort(bins, kind="stable")
            self._sorted_bins = np.asarray(bins)[order]
            segs = self._bin_segments()
        for attr, src in (("_sorted_z", getattr(self, "_z", None)),
                          ("_sorted_xz", getattr(self, "_xz", None))):
            if src is None:
                continue
            if order is None:
                setattr(self, attr, np.sort(src))
            else:
                out = src[order]
                for i in range(len(segs.bins)):
                    out[segs.starts[i]: segs.starts[i + 1]].sort()
                setattr(self, attr, out)

    def _prefetch_perm(self) -> None:
        """Overlap the derived host pruning keys (sorted z/bins + bin
        segments) with whatever the caller does next after the build, so
        the first query's prepare is ~ms. The device perm itself is NOT
        downloaded here — ``map_rows`` gathers small result sets on device
        and the ``perm`` property downloads in full only on demand."""
        import threading

        def fetch():
            try:
                self._host_sorted_keys()
            except Exception:
                pass  # the lazy properties will retry synchronously

        self._perm_thread = threading.Thread(target=fetch, daemon=True)
        self._perm_thread.start()

    def map_rows(self, idx: np.ndarray) -> np.ndarray:
        """Sorted-position → table-row mapping for query results. Prefers
        the cached host perm; small sets gather against the device-resident
        perm (a full perm download is 100s of MB through the slow downlink
        — only huge hydrations warrant it)."""
        idx = np.asarray(idx, dtype=np.int64)
        if self._perm_cache is not None or self._dev_perm is None \
                or len(idx) > (1 << 20):
            return self.perm[idx]
        return _row_gather(self._dev_perm, idx)

    # subclasses supply the sort keys ---------------------------------------

    def _sort_keys(self) -> Optional[List[np.ndarray]]:
        """Int32 key planes, major → minor (None = natural table order)."""
        raise NotImplementedError

    def _build_native(self) -> bool:
        """Fused native-encode build (geomesa_tpu.native): the host runs one
        C++ pass producing every device plane + sort key, so the table builds
        with a single upload + one device sort/gather program. Returns False
        when unsupported — the numpy path runs instead."""
        return False

    def _stream_build(self, encode_chunk, key_names: List[str], n: int,
                      extra: Dict[str, np.ndarray]):
        """Streamed native build when ``n`` crosses the chunk and
        device-sort thresholds. True = built, False = a chunk declined the
        native path (caller falls back to numpy), None = below thresholds
        (caller runs the single-shot native path)."""
        from geomesa_tpu import config as _cfg
        chunk = _cfg.BUILD_STREAM_CHUNK.get()
        if not (n > chunk
                and n >= sys.modules[__name__].DEVICE_SORT_MIN_ROWS):
            return None
        import time as _time
        from geomesa_tpu.obs.profiling import PROGRESS as _progress
        from geomesa_tpu.parallel import dist as _dist
        shard_devices = _dist.shard_devices() \
            if _dist.mesh_sort_enabled(n) else None
        t0 = _time.perf_counter()
        with _progress.phase("encode_upload", rows=n,
                             type_name=self.sft.name):
            res = _stream_encode_upload(encode_chunk, n, chunk,
                                        key_names=key_names,
                                        shard_devices=shard_devices)
        if res is None:
            return False
        dev, host_kept, key_shards = res
        self._z = np.concatenate([h["z"] for h in host_kept])
        if "bin16" in host_kept[0]:
            self._bins = np.concatenate([h["bin16"] for h in host_kept])
        self.build_stages = {"encode_upload_overlap_s": round(
            _time.perf_counter() - t0, 2)}
        self._finish_native(dev, key_names, extra, key_shards=key_shards)
        return True

    def _finish_native(self, enc: dict, key_names: List[str],
                       extra: Dict[str, np.ndarray],
                       key_shards=None) -> None:
        """Upload native-encoded planes, sort on device, gather.

        ``enc``: native encode output; ``key_names``: sort-key entries of
        ``enc`` major→minor (padded host-side to a power of two with max
        sentinels so jit signatures are shared per size tier); ``extra``:
        remaining host planes (attributes, visibility); ``key_shards``:
        key planes already distributed across the sort mesh by the streamed
        upload (round-robin chunks) — triggers the mesh-sharded sort."""
        import jax
        import jax.numpy as jnp

        n = len(self.table)
        upload = dict(enc)
        upload.pop("z", None)  # host-only (range-pruning searchsorted)
        upload.update(extra)

        if n < sys.modules[__name__].DEVICE_SORT_MIN_ROWS:
            # small tables: host lexsort + host gather (device sort overhead
            # isn't worth it; keeps the native path exercised by unit tests)
            keys = [upload[name] for name in key_names]
            perm = np.lexsort(tuple(reversed(keys)))
            self._perm_cache = perm.astype(np.int64)
            cols = {}
            for name, v in upload.items():
                out_name, g = _as_query_column(name, v[perm], np)
                if out_name is not None:
                    cols[out_name] = jnp.asarray(g)
            self.device = DeviceTable(n, cols)
            return

        from geomesa_tpu.parallel import dist as _dist
        if key_shards is not None or _dist.mesh_sort_enabled(n):
            self._finish_native_mesh(upload, key_names, key_shards, n)
            return

        keys = [upload.pop(name) if name in ("zhi", "zlo") else upload[name]
                for name in key_names]
        # async uploads: dispatch all puts UNPADDED (the build program pads
        # to the power-of-two sort shape on DEVICE — ~28% less key traffic
        # through the host link and no host pad pass; the program is keyed
        # by n already, so device-side padding adds no compilations)
        import time as _time
        from geomesa_tpu.obs.profiling import PROGRESS as _progress
        t0 = _time.perf_counter()
        with _progress.phase("upload", rows=n, type_name=self.sft.name):
            dev_keys = [jax.device_put(k) for k in keys]
            dev_cols = {k: jax.device_put(v) for k, v in upload.items()}
            jax.block_until_ready(dev_keys + list(dev_cols.values()))
        t1 = _time.perf_counter()
        with _progress.phase("sort_gather", rows=n, type_name=self.sft.name):
            self._dev_perm, cols = _native_sort_gather(
                tuple(dev_keys), dev_cols, n)
            jax.block_until_ready(self._dev_perm)
        t2 = _time.perf_counter()
        # per-stage build timings (≙ the profile the reference exposes via
        # MethodProfiling around its writers); chip_smoke.py prints these so a
        # slow build is attributable: upload is host→device bandwidth, sort
        # is device + compile (persistent-cached after the first run)
        mb = sum(k.nbytes for k in keys) / 1e6 \
            + sum(v.nbytes for v in upload.values()) / 1e6
        self.build_stages = dict(getattr(self, "build_stages", {}))
        self.build_stages.update({
            "upload_s": round(t1 - t0, 2), "upload_mb": round(mb, 1),
            "sort_gather_s": round(t2 - t1, 2)})
        self.device = DeviceTable(n, cols)
        self._prefetch_perm()

    def _finish_native_mesh(self, upload: dict, key_names: List[str],
                            key_shards, n: int) -> None:
        """Mesh-sharded variant of the native finish: the sort permutation
        comes from parallel.dist.mesh_sort_perm (per-shard lax.sort +
        splitter exchange + per-partition merge), then the query columns
        gather through it on the default device. Bitwise the same
        permutation as the single-device program."""
        import time as _time

        import jax

        from geomesa_tpu.obs.profiling import PROGRESS as _progress
        from geomesa_tpu.parallel import dist as _dist

        stages: Dict[str, float] = {}
        if key_shards is not None:
            # streamed path: key planes are already shard-resident; zhi/zlo
            # never touched the default device
            upload.pop("zhi", None)
            upload.pop("zlo", None)
            perm = _dist.mesh_sort_perm(shards=key_shards, n=n,
                                        type_name=self.sft.name,
                                        stages=stages)
        else:
            planes = [np.asarray(upload.pop(name)) if name in ("zhi", "zlo")
                      else np.asarray(upload[name]) for name in key_names]
            perm = _dist.mesh_sort_perm(planes, type_name=self.sft.name,
                                        stages=stages)
        t0 = _time.perf_counter()
        with _progress.phase("upload", rows=n, type_name=self.sft.name):
            dev_cols = {k: jax.device_put(v) for k, v in upload.items()}
            jax.block_until_ready(list(dev_cols.values()))
        t1 = _time.perf_counter()
        with _progress.phase("upload_gather", rows=n,
                             type_name=self.sft.name):
            self._dev_perm = perm
            cols = _perm_gather_cols(perm, dev_cols, n)
            jax.block_until_ready(self._dev_perm)
        t2 = _time.perf_counter()
        mb = sum(v.nbytes for v in upload.values()) / 1e6
        self.build_stages = dict(getattr(self, "build_stages", {}))
        self.build_stages.update(stages)
        self.build_stages.update({
            "upload_s": round(t1 - t0, 2), "upload_mb": round(mb, 1),
            "mesh_gather_s": round(t2 - t1, 2)})
        self.device = DeviceTable(n, cols)
        self._prefetch_perm()

    # incremental merge builds ----------------------------------------------

    @classmethod
    def merge_from(cls, old: "BaseSpatialIndex", merged_table: FeatureTable,
                   n_old: int) -> "BaseSpatialIndex":
        """Incremental (LSM-merge) build: ``merged_table`` = ``old.table``
        followed by ``n_delta`` appended rows. Instead of re-sorting all
        ``n_old + n_delta`` keys, sort only the delta run, rank it into the
        resident sorted run (per-bin searchsorted — only the touched bin
        segments are walked), and scatter both runs into the merged layout:
        host block metadata by direct placement, device columns through one
        merge-scatter program that moves only delta-sized data over the
        host link. The result is bitwise identical (perm, sorted planes,
        device columns) to a full rebuild, because the merged order equals
        the stable lexsort of the concatenated keys: residents keep their
        relative order, delta rows keep theirs, and ties go to residents
        (smaller original row index)."""
        from geomesa_tpu.obs.profiling import PROGRESS as _progress

        import time as _time

        n_new = len(merged_table)
        n_delta = n_new - n_old
        sft = old.sft

        self = cls.__new__(cls)
        self.sft = sft
        self.table = merged_table
        self.geom = old.geom
        self.dtg = old.dtg
        self.period = old.period
        self._perm_cache = None
        self._dev_perm = None
        self._bin_segs = None

        with _progress.phase("merge", rows=n_new, type_name=sft.name):
            t0 = _time.perf_counter()
            delta_table = merged_table.take(
                np.arange(n_old, n_new, dtype=np.int64))
            shim = _DeltaKeyShim(sft, delta_table, old.geom, old.dtg,
                                 old.period)
            keys_d = cls._sort_keys(shim)
            if hasattr(shim, "_sfc"):
                self._sfc = shim._sfc

            touched_bins = 0
            if keys_d is None:
                # natural order (FullScanIndex): delta appends after residents
                p_d = np.arange(n_delta, dtype=np.int64)
                r = np.full(n_delta, n_old, dtype=np.int64)
            else:
                p_d = np.lexsort(tuple(reversed(keys_d))).astype(np.int64)
                z_d = getattr(shim, "_z", None)
                xz_d = getattr(shim, "_xz", None)
                sec_d = np.asarray(z_d if z_d is not None else xz_d)
                sec_sorted_d = sec_d[p_d]
                bins_d = getattr(shim, "_bins", None)
                old_sec = old.sorted_z if z_d is not None else old.sorted_xz
                if bins_d is not None:
                    bins_d = np.asarray(bins_d)
                    bins_sorted_d = bins_d[p_d]
                    old_bins = old.sorted_bins
                    r = np.empty(n_delta, dtype=np.int64)
                    ub = np.unique(bins_sorted_d)
                    touched_bins = len(ub)
                    for b in ub:
                        ds = np.searchsorted(bins_sorted_d, b, side="left")
                        de = np.searchsorted(bins_sorted_d, b, side="right")
                        rs = np.searchsorted(old_bins, b, side="left")
                        re_ = np.searchsorted(old_bins, b, side="right")
                        r[ds:de] = rs + np.searchsorted(
                            old_sec[rs:re_], sec_sorted_d[ds:de],
                            side="right")
                else:
                    r = np.searchsorted(old_sec, sec_sorted_d,
                                        side="right").astype(np.int64)

            # merged positions: resident i shifts by the count of delta rows
            # ranked at-or-before it; delta j lands right after its rank
            shift = np.searchsorted(r, np.arange(n_old, dtype=np.int64),
                                    side="right")
            pos_res = np.arange(n_old, dtype=np.int64) + shift
            pos_del = r + np.arange(n_delta, dtype=np.int64)

            if keys_d is not None:
                if z_d is not None:
                    self._z = np.concatenate([np.asarray(old._z), sec_d])
                else:
                    self._xz = np.concatenate([np.asarray(old._xz), sec_d])
                sorted_sec = np.empty(n_new, dtype=old_sec.dtype)
                sorted_sec[pos_res] = old_sec
                sorted_sec[pos_del] = sec_sorted_d
                setattr(self, "_sorted_z" if z_d is not None else
                        "_sorted_xz", sorted_sec)
                if bins_d is not None:
                    self._bins = np.concatenate(
                        [np.asarray(old._bins), bins_d])
                    sorted_bins = np.empty(n_new, dtype=old_bins.dtype)
                    sorted_bins[pos_res] = old_bins
                    sorted_bins[pos_del] = bins_sorted_d
                    self._sorted_bins = sorted_bins

            # permutation: merged on device when the resident perm is
            # device-resident (avoids an O(n_old) download), else on host
            perm_pair = None
            if old._perm_cache is None and old._dev_perm is not None:
                perm_pair = (old._dev_perm,
                             (n_old + p_d).astype(np.int32))
            else:
                new_perm = np.empty(n_new, dtype=np.int64)
                new_perm[pos_res] = old.perm
                new_perm[pos_del] = n_old + p_d
                self._perm_cache = new_perm

            # dictionary columns whose vocab grew under the union-vocab
            # concat: resident device codes are invalid — rebuild those
            # columns from the merged full plane (everything else merges
            # with delta-sized uploads only)
            merged_vocabs = {
                name: col.vocab
                for name, col in merged_table.columns.items()
                if isinstance(col, StringColumn)}
            stale = set()
            full_codes: Dict[str, np.ndarray] = {}
            for name in old.device.columns:
                if name in merged_vocabs \
                        and old.vocabs.get(name) != merged_vocabs[name]:
                    stale.add(name)
                    full_codes[name] = np.asarray(
                        merged_table.columns[name].codes, dtype=np.int32)
            old_vis = old.table.visibility
            new_vis = merged_table.visibility
            if new_vis is not None and (
                    "__vis__" not in old.device.columns
                    or old_vis is None or old_vis.vocab != new_vis.vocab):
                stale.add("__vis__")
                full_codes["__vis__"] = np.asarray(new_vis.codes,
                                                   dtype=np.int32)

            # device columns live in SORTED order — gather the delta planes
            # into delta-sorted order so pos_del scatters rows against the
            # right keys
            delta_planes = {k: np.asarray(v)[p_d]
                            for k, v in host_planes(delta_table,
                                                    old.period).items()}
            self.device, new_dev_perm = DeviceTable.merge_scatter(
                old.device, delta_planes, r, stale=stale,
                full_codes=full_codes, perm_pair=perm_pair,
                host_perm=self._perm_cache)
            if new_dev_perm is not None:
                self._dev_perm = new_dev_perm

            self._build_segment_pool()
            self.kernels = ScanKernels(self.device.columns)
            self.vocabs = merged_vocabs
            self.build_stages = {
                "merge_s": round(_time.perf_counter() - t0, 3),
                "merge_rows": int(n_delta),
                "merge_fraction": round(n_delta / max(1, n_old), 4),
                "merge_touched_bins": int(touched_bins),
                "merge_stale_cols": sorted(stale),
            }
        return self

    @classmethod
    def supports(cls, sft) -> bool:
        raise NotImplementedError

    # planning ---------------------------------------------------------------

    def plan(self, f: ir.Filter) -> Optional[IndexScanPlan]:
        ext = extract_bboxes(f, self.geom) if self.geom else extract.Extraction(
            (extract.WHOLE_WORLD,), False)
        iv = extract_intervals(f, self.dtg) if self.dtg else None

        if len(ext.boxes) == 0 or (iv is not None and len(iv.intervals) == 0):
            return IndexScanPlan(self, "none", empty=True, full_filter=f, cost=0.0)

        residual = _strip_handled(f, self.geom, self.dtg, self.points)

        boxes_loose = None
        kind = "none"
        if not ext.unconstrained:
            kind = "point_boxes" if self.points else "bbox_overlap"
            boxes_loose = pad_boxes(_boxes_fp62(ext.boxes))

        windows = None
        if iv is not None and not iv.unconstrained:
            windows = self.time_windows(iv.intervals)

        avail = set(self.device.columns)
        dev_res, host_res = split_residual(residual, self.sft, self.vocabs,
                                           avail)
        compiled = compile_residual(dev_res, self.sft, self.vocabs, avail) \
            if dev_res else None

        cost = self._cost(ext, iv)
        return IndexScanPlan(
            index=self,
            primary_kind=kind,
            boxes_loose=boxes_loose,
            windows=windows,
            residual_device=compiled,
            residual_host=host_res,
            full_filter=f,
            cost=cost,
            explain={"index": self.name, "boxes": ext.boxes,
                     "intervals": None if iv is None else iv.intervals,
                     "residual_device": dev_res, "residual_host": host_res},
        )

    def time_windows(self, intervals, strict: bool = False) -> np.ndarray:
        """(T, 4) int32 [bin_lo, off_lo, bin_hi, off_hi] device windows of
        inclusive epoch-ms ``intervals``, padded. The device keeps a row's
        time as (bin, offset) in the period's offset unit (a second, for
        weeks), so an end that falls inside a unit cannot be told on the
        device: the plan's windows hold that whole unit, a superset, and the
        ``strict`` ones only the units that lie wholly inside the interval,
        a subset. A caller that has to be exact at such an end takes the
        rows between the two to the host (planner.join_polygons)."""
        w = np.empty((len(intervals), 4), dtype=np.int32)
        i32 = (1 << 31) - 1  # open-ended intervals overflow the bin i32
        for i, (lo, hi) in enumerate(intervals):
            blo, olo = (int(v) for v in time_to_binned_time(lo, self.period))
            bhi, ohi = (int(v) for v in time_to_binned_time(hi, self.period))
            if strict and abs(blo) < i32 and abs(bhi) < i32:
                olo += int(binned_time_to_millis(blo, olo, self.period)) < lo
                ohi -= int(binned_time_to_millis(bhi, ohi + 1,
                                                 self.period)) - 1 > hi
            w[i] = (max(-i32, blo), olo, min(i32, bhi), ohi)
        return pad_windows(w)

    def _cost(self, ext, iv) -> float:
        """Heuristic strategy cost (≙ StrategyDecider index heuristics —
        lower is better; spatio-temporal beats spatial beats full scan)."""
        spatial = not ext.unconstrained
        temporal = iv is not None and not iv.unconstrained
        if self.temporal and spatial and temporal:
            return 1.0
        if spatial:
            return 2.0 if not self.temporal else 2.5
        if temporal and self.temporal:
            return 3.0
        return 10.0  # full scan

    # certified segment predicates ------------------------------------------

    def _build_segment_pool(self) -> None:
        """Every feature's segments onto the device in this index's row
        order (columns ``__seg__`` and ``__way__``), for the banded refine
        (scan.intersects_band_blocks); ``seg_off`` stays on the host too,
        where a query's blocks become spans of the pool. Extent indexes
        only. Built whole at every build and merge: an append to an extent
        type uploads the pool again. A polygon type's gets a twin,
        ``__segy__``, each row's segments in the order of their lower end,
        for the join (scan.join_band_blocks), with the host's search keys
        ``seg_ykey`` and each row's tallest segment ``seg_rise``."""
        self.seg_off = None
        if self.points or self.geom is None:
            return
        from geomesa_tpu.index.device import (SEG, SEGY, segment_pool,
                                              segments_by_y)
        from geomesa_tpu.index.scan import POOL_TILE
        from geomesa_tpu.obs.profiling import PROGRESS as _progress
        with _progress.phase("segment_pool", rows=len(self.table),
                             type_name=self.sft.name):
            built = segment_pool(self.table.geometry(), self.perm, POOL_TILE)
            if built is not None:
                planes, self.seg_off = built
                self.device.columns.update(planes)
                if self.sft.geometry_attribute.type_name in (
                        "Polygon", "MultiPolygon"):
                    # a polygon type can be a join's polygon side
                    self.device.columns[SEGY], self.seg_ykey, self.seg_rise \
                        = segments_by_y(planes[SEG], self.seg_off)

    def join_envelopes(self, block: int, tile: int) -> dict:
        """Host copies of what the join's gate reads of this point index
        (prune.gate_slabs), reduced on the device once an index and kept.
        Per ``block``-row run, in row order: ``tmin``/``tmax``, its first and
        last instant as bin * 2^32 + offset (a run that straddles two bins
        holds all of both; a temporal index only). Per tile, ``tile`` rows of
        a run in ascending y as the join's kernel sorts them
        (scan._sort_tiles), (runs, tiles a run): ``xmin``/``xmax``/``ymin``/
        ``ymax``, the f32 planes' own extremes, an empty tile's +inf/-inf."""
        cached = getattr(self, "_join_env", None)
        if cached is not None and cached[0] == (block, tile):
            return cached[1]
        import jax.numpy as jnp
        from geomesa_tpu.index.scan import _sort_tiles
        cols = self.device.columns
        n = int(cols["xf"].shape[0])
        nb = -(-n // block)
        wide = -(-block // tile) * tile
        valid = cols.get("__valid__")

        def blocked(name, fill, width=block):
            c = cols[name]
            if valid is not None:
                c = jnp.where(valid, c, fill)
            c = jnp.pad(c, (0, nb * block - n), constant_values=fill)
            return jnp.pad(c.reshape(nb, block), ((0, 0), (0, width - block)),
                           constant_values=fill)

        y, x = _sort_tiles(blocked("yf", np.inf, wide),
                           blocked("xf", 0.0, wide))
        real = (y < np.inf).reshape(nb, -1, tile)
        y, x = y.reshape(nb, -1, tile), x.reshape(nb, -1, tile)
        ext = {"ymin": jnp.min(jnp.where(real, y, np.inf), axis=2),
               "ymax": jnp.max(jnp.where(real, y, -np.inf), axis=2),
               "xmin": jnp.min(jnp.where(real, x, np.inf), axis=2),
               "xmax": jnp.max(jnp.where(real, x, -np.inf), axis=2)}
        if "bin" in cols:
            i31 = (1 << 31) - 1
            for name, plane in (("b", "bin"), ("o", "off")):
                ext[name + "min"] = jnp.min(blocked(plane, i31), axis=1)
                ext[name + "max"] = jnp.max(blocked(plane, -i31), axis=1)
        env = {k: np.asarray(v) for k, v in ext.items()}
        if "bin" in cols:
            one = env["bmin"] == env["bmax"]
            lo = np.where(one, env.pop("omin").astype(np.int64), 0)
            hi = np.where(one, env.pop("omax").astype(np.int64),
                          (1 << 32) - 1)
            env["tmin"] = (env.pop("bmin").astype(np.int64) << 32) + lo
            env["tmax"] = (env.pop("bmax").astype(np.int64) << 32) + hi
        self._join_env = ((block, tile), env)
        return env

    def polygon_envelopes(self) -> np.ndarray:
        """(rows, 4) f64 [xmin, ymin, xmax, ymax] of this extent index's
        features in its row order, kept on the host for the join's gate."""
        cached = getattr(self, "_row_env", None)
        if cached is None:
            cached = self._row_env = self.table.geometry().bboxes()[self.perm]
        return cached

    # range pruning ---------------------------------------------------------

    def candidate_blocks(self, plan: IndexScanPlan):
        """Sorted unique gather-block ids covering every possibly-matching
        row, or None when pruning doesn't apply or wouldn't pay (the device
        re-applies the full exact mask to gathered blocks, so this only ever
        needs to be a superset). The plan-level entry of ``cover_blocks``:
        one plan's boxes and intervals, at most 16 boxes."""
        if plan.empty or plan.boxes_loose is None:
            return None  # no spatial constraint → nothing to cover
        boxes = plan.explain.get("boxes")
        if not boxes or len(boxes) > 16:
            return None
        blocks, stats = self.cover_blocks(list(boxes),
                                          self.cover_intervals(plan))
        plan.explain.update(stats)
        return blocks

    @staticmethod
    def cover_intervals(plan: IndexScanPlan):
        """The plan's time intervals as ``cover_blocks`` takes them.
        plan.windows is None iff the temporal extraction was unconstrained —
        the explain intervals then hold the open-ended sentinel, which must
        read as "no temporal constraint", not as a 146-million-bin interval."""
        return plan.explain.get("intervals") if plan.windows is not None \
            else None

    def cover_blocks(self, boxes, intervals) -> Tuple[Optional[np.ndarray],
                                                     dict]:
        """One range decomposition for the union of ``boxes`` (user-space
        (xmin, ymin, xmax, ymax), from one plan or from every plan of a
        scheduler group) under shared time ``intervals``: (sorted unique
        int32 block ids or None, explain stats). None = scan the table: the
        index has no cover, the table is tiny, or the union's rows pass
        ``PRUNE_MAX_FRACTION`` of it, which is what a gather would read. An
        empty array = provably nothing to scan. The range budget is
        ``SCAN_RANGES_TARGET`` for the whole cover, however many boxes
        (≙ the reference's ≤2000-range scan plans,
        Z3IndexKeySpace.getRanges:162-189; the decision threshold mirrors
        full-table-scan avoidance, QueryProperties.BlockFullTableScans)."""
        from geomesa_tpu.index import prune as _p

        n = len(self.table)
        if n < 4 * _p.BLOCK_SIZE:
            return None, {}  # tiny tables: full mask is a single fused pass
        cover = self._row_slices(boxes, intervals)
        if cover is None:
            return None, {}
        slices, n_ranges = cover
        stats = {"cover_boxes": len(boxes), "cover_ranges": n_ranges}
        total = int((slices[:, 1] - slices[:, 0]).sum()) if len(slices) else 0
        if total > _p.PRUNE_MAX_FRACTION * n:
            return None, stats
        blocks = _p.slices_to_blocks(slices, n)
        if blocks is not None and len(blocks) * _p.BLOCK_SIZE > _p.PRUNE_MAX_FRACTION * n:
            return None, stats
        stats.update(_p.candidate_stats(slices, blocks, n))
        if blocks is None:
            # provably empty candidate set — still exact (superset of nothing)
            blocks = np.empty(0, dtype=np.int32)
        return blocks, stats

    def _row_slices(self, boxes, intervals
                    ) -> Optional[Tuple[np.ndarray, int]]:
        """(candidate [lo, hi) row slices in this index's sorted order, a
        superset of matches; key ranges the decomposition came back with),
        or None when unsupported."""
        return None

    def _bin_segments(self):
        from geomesa_tpu.index.prune import BinSegments
        if getattr(self, "_bin_segs", None) is None:
            self._join_prefetch()
        if getattr(self, "_bin_segs", None) is None:
            self._bin_segs = BinSegments(self.sorted_bins)
        return self._bin_segs

    def _sorted_plane(self, attr: str, src: np.ndarray) -> np.ndarray:
        """Sorted host key plane, preferring the build-time background
        prefetch result over a synchronous (100s-of-ms at 10M+) gather."""
        cached = getattr(self, attr, None)
        if cached is None:
            self._join_prefetch()
            cached = getattr(self, attr, None)
        if cached is None:
            cached = src[self.perm]
            setattr(self, attr, cached)
        return cached

    def _binned_row_slices(self, boxes, intervals, sorted_keys,
                           cover_fn) -> Optional[Tuple[np.ndarray, int]]:
        """Shared epoch-major pruning: per-bin segments × per-window covers
        (covers dedup by in-bin window, so a multi-bin interval costs at most
        three distinct covers: head, whole-period, tail). Each cover is of
        all ``boxes`` together."""
        from geomesa_tpu.index import prune as _p
        from geomesa_tpu.curves.binnedtime import max_offset

        segs = self._bin_segments()
        mo = max_offset(self.period) - 1
        if intervals:
            bw = _p.bin_windows(intervals, self.period)
            if bw is None:
                return None
        else:
            bins = segs.all_bins()
            if len(bins) > _p.MAX_BINS:
                return None
            bw = [(int(b), (0, mo)) for b in bins]
        covers = {}
        out = []
        for b, w in bw:
            lo, hi = segs.segment(b)
            if lo >= hi:
                continue
            if w not in covers:
                covers[w] = cover_fn(boxes, w)
            out.append(_p.ranges_to_slices(sorted_keys, covers[w], lo=lo, hi=hi))
        slices = np.concatenate(out) if out \
            else np.empty((0, 2), dtype=np.int64)
        # a cover is (lo, hi, contained) arrays or a list of IndexRange
        return slices, sum(len(c[0]) if isinstance(c, tuple) else len(c)
                           for c in covers.values())

    # explain ---------------------------------------------------------------

    def key_ranges(self, plan: IndexScanPlan, max_ranges: int = 2000):
        """Reference-style z/xz range decomposition for this plan (explain/
        pruning; not needed for the full-scan execution path)."""
        raise NotImplementedError


class Z3Index(BaseSpatialIndex):
    """Point + time: epoch-major (bin, z3) order (≙ Z3IndexKeySpace.scala:34,
    row layout [shard][epoch:2][z:8])."""

    name = "z3"
    temporal = True
    points = True

    @classmethod
    def supports(cls, sft) -> bool:
        g = sft.geometry_attribute
        return g is not None and g.type_name == "Point" and sft.dtg_attribute is not None

    def _sort_keys(self) -> List[np.ndarray]:
        garr = self.table.geometry()
        x, y = garr.point_xy()
        ms = np.asarray(self.table.columns[self.dtg], dtype=np.int64)
        bins, offs = time_to_binned_time(ms, self.period)
        sfc = Z3SFC.apply(self.period)
        self._sfc = sfc
        self._z = sfc.index(x, y, np.minimum(offs, int(sfc.time.max)),
                            lenient=True)
        self._bins = bins
        return [np.asarray(bins, dtype=np.int32)] + _split63(self._z)

    def _build_native(self) -> bool:
        from geomesa_tpu import native
        garr = self.table.geometry()
        if not (garr.is_points and native.available()):
            return False
        x, y = garr.point_xy()
        ms = np.asarray(self.table.columns[self.dtg], dtype=np.int64)
        import time as _time

        self._sfc = Z3SFC.apply(self.period)
        extra = host_planes(self.table, self.period,
                            skip_geom=True, skip_dtg=True)
        streamed = self._stream_build(
            lambda a, b: native.z3_encode(x[a:b], y[a:b], ms[a:b],
                                          self.period.value),
            ["bin16", "zhi", "zlo"], len(x), extra)
        if streamed is not None:
            return streamed
        t0 = _time.perf_counter()
        enc = native.z3_encode(x, y, ms, self.period.value)
        if enc is None:  # calendar periods stay on the numpy path
            return False
        self.build_stages = {"encode_s": round(_time.perf_counter() - t0, 2)}
        self._z = enc["z"]
        self._bins = enc["bin16"]
        self._finish_native(enc, ["bin16", "zhi", "zlo"], extra)
        return True

    @property
    def sorted_z(self) -> np.ndarray:
        return self._sorted_plane("_sorted_z", self._z)

    @property
    def sorted_bins(self) -> np.ndarray:
        return self._sorted_plane("_sorted_bins", self._bins)

    def key_ranges(self, plan, max_ranges: int = 2000):
        ext = extract_bboxes(plan.full_filter, self.geom)
        iv = extract_intervals(plan.full_filter, self.dtg)
        ranges = []
        for lo, hi in iv.intervals[:8] if not iv.unconstrained else []:
            blo, olo = time_to_binned_time(lo, self.period)
            bhi, ohi = time_to_binned_time(hi, self.period)
            for b in range(int(blo), int(bhi) + 1):
                t0 = int(olo) if b == int(blo) else 0
                t1 = int(ohi) if b == int(bhi) else max_offset(self.period) - 1
                rs = self._sfc.ranges(list(ext.boxes), [(t0, t1)], max_ranges=max_ranges)
                ranges.append((b, rs))
        return ranges

    def _row_slices(self, boxes, intervals):
        from geomesa_tpu.index.prune import MAX_RANGES
        return self._binned_row_slices(
            boxes, intervals, self.sorted_z,
            lambda bx, w: self._sfc.ranges_arrays(bx, [w],
                                                  max_ranges=MAX_RANGES))


class Z2Index(BaseSpatialIndex):
    """Point, no time: z2 order (≙ Z2IndexKeySpace.scala:29)."""

    name = "z2"
    temporal = False
    points = True

    @classmethod
    def supports(cls, sft) -> bool:
        g = sft.geometry_attribute
        return g is not None and g.type_name == "Point"

    def _sort_keys(self) -> List[np.ndarray]:
        x, y = self.table.geometry().point_xy()
        self._z = Z2SFC().index(x, y, lenient=True)
        return _split63(self._z)

    def _build_native(self) -> bool:
        from geomesa_tpu import native
        garr = self.table.geometry()
        if not (garr.is_points and native.available()):
            return False
        x, y = garr.point_xy()
        extra = host_planes(self.table, self.period, skip_geom=True)
        streamed = self._stream_build(
            lambda a, b: native.z2_encode(x[a:b], y[a:b]),
            ["zhi", "zlo"], len(x), extra)
        if streamed is not None:
            return streamed
        enc = native.z2_encode(x, y)
        if enc is None:
            return False
        self._z = enc["z"]
        self._finish_native(enc, ["zhi", "zlo"], extra)
        return True

    @property
    def sorted_z(self) -> np.ndarray:
        return self._sorted_plane("_sorted_z", self._z)

    def _row_slices(self, boxes, intervals):
        from geomesa_tpu.index.prune import MAX_RANGES, ranges_to_slices
        rs = Z2SFC().ranges_arrays(boxes, max_ranges=MAX_RANGES)
        return ranges_to_slices(self.sorted_z, rs), len(rs[0])


class XZ3Index(BaseSpatialIndex):
    """Extent + time: (bin, xz3) order (≙ XZ3IndexKeySpace.scala:33)."""

    name = "xz3"
    temporal = True
    points = False

    @classmethod
    def supports(cls, sft) -> bool:
        g = sft.geometry_attribute
        return g is not None and g.type_name != "Point" and sft.dtg_attribute is not None

    def _sort_keys(self) -> List[np.ndarray]:
        bb = self.table.geometry().bboxes()
        ms = np.asarray(self.table.columns[self.dtg], dtype=np.int64)
        bins, offs = time_to_binned_time(ms, self.period)
        sfc = XZ3SFC.apply(self.sft.xz_precision, self.period)
        mins = np.stack([bb[:, 0], bb[:, 1], offs.astype(np.float64)], axis=1)
        maxs = np.stack([bb[:, 2], bb[:, 3], offs.astype(np.float64)], axis=1)
        self._xz = sfc.index(mins, maxs, lenient=True)
        self._bins = bins
        return [np.asarray(bins, dtype=np.int32)] + _split63(self._xz)

    @property
    def sorted_xz(self) -> np.ndarray:
        return self._sorted_plane("_sorted_xz", self._xz)

    @property
    def sorted_bins(self) -> np.ndarray:
        return self._sorted_plane("_sorted_bins", self._bins)

    def _row_slices(self, boxes, intervals):
        from geomesa_tpu.index.prune import MAX_RANGES
        sfc = XZ3SFC.apply(self.sft.xz_precision, self.period)

        def cover(bx, w):
            qs = [(xmin, ymin, float(w[0]), xmax, ymax, float(w[1]))
                  for xmin, ymin, xmax, ymax in bx]
            return sfc.ranges_arrays(qs, max_ranges=MAX_RANGES)

        return self._binned_row_slices(boxes, intervals, self.sorted_xz, cover)


class XZ2Index(BaseSpatialIndex):
    """Extent, no time: xz2 order (≙ XZ2IndexKeySpace.scala:28)."""

    name = "xz2"
    temporal = False
    points = False

    @classmethod
    def supports(cls, sft) -> bool:
        g = sft.geometry_attribute
        return g is not None and g.type_name != "Point"

    def _sort_keys(self) -> List[np.ndarray]:
        bb = self.table.geometry().bboxes()
        sfc = XZ2SFC.apply(self.sft.xz_precision)
        self._xz = sfc.index(bb[:, [0, 1]], bb[:, [2, 3]], lenient=True)
        return _split63(self._xz)

    @property
    def sorted_xz(self) -> np.ndarray:
        return self._sorted_plane("_sorted_xz", self._xz)

    def _row_slices(self, boxes, intervals):
        from geomesa_tpu.index.prune import MAX_RANGES, ranges_to_slices
        sfc = XZ2SFC.apply(self.sft.xz_precision)
        rs = sfc.ranges_arrays(boxes, max_ranges=MAX_RANGES)
        return ranges_to_slices(self.sorted_xz, rs), len(rs[0])


class S2Index(BaseSpatialIndex):
    """Point, no time, S2 (Hilbert-on-cube) order — opt-in via
    ``geomesa.indices=s2`` (≙ S2IndexKeySpace.scala:34; the reference's S2
    indexes are likewise configured, not default)."""

    name = "s2"
    temporal = False
    points = True
    # measured cover slop vs true rows (curves/s2.py _cell_rect): the cost
    # model prices S2 plans above an equally-selective Z cover
    cover_slop = 1.1

    @classmethod
    def supports(cls, sft) -> bool:
        g = sft.geometry_attribute
        names = sft.configured_indices
        return (names is not None and "s2" in names
                and g is not None and g.type_name == "Point")

    def _sort_keys(self) -> List[np.ndarray]:
        from geomesa_tpu.curves.s2 import S2SFC
        x, y = self.table.geometry().point_xy()
        self._z = S2SFC.apply().index(x, y, lenient=True)
        return _split63(self._z)

    @property
    def sorted_z(self) -> np.ndarray:
        return self._sorted_plane("_sorted_z", self._z)

    def _row_slices(self, boxes, intervals):
        from geomesa_tpu.curves.s2 import S2SFC
        from geomesa_tpu.index.prune import MAX_RANGES, ranges_to_slices
        rs = S2SFC.apply().ranges(boxes, max_ranges=MAX_RANGES)
        return ranges_to_slices(self.sorted_z, rs), len(rs)


class S3Index(BaseSpatialIndex):
    """Point + time, epoch-major (bin, s2) order — opt-in via
    ``geomesa.indices=s3`` (≙ S3IndexKeySpace.scala:36 / S3Filter: the S2
    cell id carries no time bits, so temporal pruning lands at bin
    granularity exactly as in the reference's [epoch][s2] layout)."""

    name = "s3"
    temporal = True
    points = True
    cover_slop = 1.1  # see S2Index

    @classmethod
    def supports(cls, sft) -> bool:
        g = sft.geometry_attribute
        names = sft.configured_indices
        return (names is not None and "s3" in names and g is not None
                and g.type_name == "Point" and sft.dtg_attribute is not None)

    def _sort_keys(self) -> List[np.ndarray]:
        from geomesa_tpu.curves.s2 import S2SFC
        x, y = self.table.geometry().point_xy()
        ms = np.asarray(self.table.columns[self.dtg], dtype=np.int64)
        bins, _ = time_to_binned_time(ms, self.period)
        self._z = S2SFC.apply().index(x, y, lenient=True)
        self._bins = bins
        return [np.asarray(bins, dtype=np.int32)] + _split63(self._z)

    @property
    def sorted_z(self) -> np.ndarray:
        return self._sorted_plane("_sorted_z", self._z)

    @property
    def sorted_bins(self) -> np.ndarray:
        return self._sorted_plane("_sorted_bins", self._bins)

    def _row_slices(self, boxes, intervals):
        from geomesa_tpu.curves.s2 import S2SFC
        from geomesa_tpu.index.prune import MAX_RANGES
        # no time dim in the s2 key: one cover shared by every window
        rs = S2SFC.apply().ranges(boxes, max_ranges=MAX_RANGES)
        cover = self._binned_row_slices(boxes, intervals, self.sorted_z,
                                        lambda bx, w: rs)
        return None if cover is None else (cover[0], len(rs))


class FullScanIndex(BaseSpatialIndex):
    """Natural-order fallback for schemas with no usable spatial index or
    queries no index serves (≙ the reference's full-table-scan strategy,
    guarded there by QueryProperties.BlockFullTableScans)."""

    name = "full"
    temporal = False
    points = True

    @classmethod
    def supports(cls, sft) -> bool:
        return True

    def _sort_keys(self) -> Optional[List[np.ndarray]]:
        return None  # natural table order

    def plan(self, f: ir.Filter) -> Optional[IndexScanPlan]:
        avail = set(self.device.columns)
        dev_res, host_res = split_residual(
            f if not isinstance(f, (ir.Include,)) else None, self.sft,
            self.vocabs, avail)
        compiled = compile_residual(dev_res, self.sft, self.vocabs, avail) \
            if dev_res else None
        return IndexScanPlan(
            index=self, primary_kind="none",
            residual_device=compiled, residual_host=host_res, full_filter=f,
            cost=100.0, explain={"index": self.name, "residual_host": host_res},
        )


INDEX_CLASSES = [S3Index, S2Index, Z3Index, XZ3Index, Z2Index, XZ2Index]
