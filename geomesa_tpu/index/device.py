"""DeviceTable: the HBM-resident columnar projection the scan kernels read.

≙ the data a GeoMesa region/tablet server holds for one index table: rows in
index-key order with the serialized values (SURVEY.md §3.2 step 4). Here the
"rows" are structure-of-arrays jnp buffers in index-sorted order:

  - ``xi``/``yi``  int32 31-bit normalized coords (Z2SFC resolution — exact to
                   ~2 cm; the canonical device coordinates for box tests)
  - ``xf``/``yf``  float32 raw coords (aggregations, joins, density)
  - ``bin``/``off`` int32 exact binned time (period bin + integer offset in
                   period units — ms/s/min, exactly representable)
  - bbox columns (extent geometries): f32 xmin/ymin/xmax/ymax
  - the segment pool (extent layers): every feature's segments in row
                   order, ``__seg__`` (4, segments) f32, rows
                   sx1/sy1/sx2/sy2, and ``__way__`` (3, rows) int32, rows
                   seg_off/seg_n/kind, so the ways of a gather block are one
                   contiguous span and a tile of it one slice (the banded
                   intersects refine, scan.intersects_band_blocks). Ragged:
                   added after the build or merge of the per-row columns,
                   never gathered or merged as one of them. A polygon type
                   also keeps ``__segy__``, the pool again with each row's
                   segments sorted by their lower end (the join's slabs,
                   scan.join_band_blocks)
  - attribute columns: numeric as int32/f32; strings as dictionary codes;
                   dates additionally as (bin, off) when they are the primary
                   temporal axis

Only numeric-representable projections live on device; exact f64 coordinates
stay host-side for refinement (the reference's full-filter path).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from geomesa_tpu.curves.binnedtime import TimePeriod, time_to_binned_time
from geomesa_tpu.curves.normalize import NormalizedLat, NormalizedLon
from geomesa_tpu.features.geometry import GeometryArray
from geomesa_tpu.features.table import FeatureTable, StringColumn

LON31 = NormalizedLon(31)
LAT31 = NormalizedLat(31)


def memory_snapshot() -> Dict[str, int]:
    """Live/peak HBM pressure summed over local devices, from each
    backend's ``memory_stats()`` (absent keys are omitted — the CPU
    backend reports nothing, TPU/GPU report live, peak and limit). The
    device-memory gauge feed (metrics.register_device_gauges) and the
    ``debug kernels`` header."""
    import jax
    out: Dict[str, int] = {}
    for d in jax.local_devices():
        stats = getattr(d, "memory_stats", None)
        s = stats() if stats is not None else None
        if not s:
            continue
        for src, dst in (("bytes_in_use", "bytes_in_use"),
                         ("peak_bytes_in_use", "peak_bytes_in_use"),
                         ("bytes_limit", "bytes_limit"),
                         ("num_allocs", "num_allocs")):
            if src in s:
                out[dst] = out.get(dst, 0) + int(s[src])
    return out


def fp62(x, lo: float, hi: float):
    """62-bit fixed-point normalization of a coordinate, split into two int32
    planes (hi = top 31 bits, lo = bottom 31).

    The quantum is (hi-lo)/2^62 ≈ 8e-17 degrees for lon — finer than the f64
    ulp of any real coordinate — so lexicographic (hi, lo) comparison on
    device reproduces the host's f64 predicate exactly up to ties at the f64
    rounding quantum (~4e-14 deg ≈ 4 nm), eliminating the need for any host
    boundary refinement on box predicates. This is the TPU answer to the
    reference's decode-and-compare Z3Filter plus residual exact filter: one
    int compare plane pair instead of two passes.
    """
    x = np.asarray(x, dtype=np.float64)
    if x.ndim == 1 and len(x) >= 65536:
        # bulk encodes take the native one-pass path (bit-identical —
        # tests/test_native.py pins parity); the numpy path below is the
        # canonical semantics and the fallback
        from geomesa_tpu import native
        planes = native.fp62_planes(x, float(lo), float(hi))
        if planes is not None:
            return planes
    frac = np.clip((x - lo) / (hi - lo), 0.0, 1.0)
    # clamp in int64: float(2^62 - 1) rounds UP to 2^62, so a float-side min
    # would let the domain edge overflow the 31-bit hi plane
    v = np.minimum(np.floor(np.ldexp(frac, 62)).astype(np.int64), (1 << 62) - 1)
    return (v >> 31).astype(np.int32), (v & ((1 << 31) - 1)).astype(np.int32)


def fp62_lon(x):
    return fp62(x, -180.0, 180.0)


def fp62_lat(y):
    return fp62(y, -90.0, 90.0)


@dataclass
class DeviceTable:
    """Device-resident columns for one index, in index-sorted row order."""

    n: int
    columns: Dict[str, jnp.ndarray] = field(default_factory=dict)

    def __getitem__(self, name: str) -> jnp.ndarray:
        return self.columns[name]

    def __contains__(self, name: str) -> bool:
        return name in self.columns

    @classmethod
    def build(
        cls,
        table: FeatureTable,
        perm: np.ndarray,
        period: Optional[TimePeriod] = None,
    ) -> "DeviceTable":
        """Project ``table`` rows (reordered by host ``perm``) onto the device.

        period: when set, the default dtg column is decomposed into exact
        (bin, off) int32 pairs for temporal predicates.
        """
        from geomesa_tpu.obs import attrib as _attrib
        planes = host_planes(table, period)
        _attrib.record_transfer(
            "device_table.build", 1,
            sum(int(v.nbytes) for v in planes.values()))
        cols = {k: jnp.asarray(v[perm]) for k, v in planes.items()}
        return cls(len(perm), cols)

    @classmethod
    def build_on_device(
        cls,
        table: FeatureTable,
        dev_perm,
        period: Optional[TimePeriod] = None,
        planes: Optional[Dict[str, np.ndarray]] = None,
    ) -> "DeviceTable":
        """Upload unsorted planes once, then apply the device-resident sort
        permutation with one fused gather — the large-table build path that
        keeps the O(N) reorder on the accelerator instead of the host."""
        import jax

        from geomesa_tpu.obs import attrib as _attrib

        if planes is None:
            planes = host_planes(table, period)
        _attrib.record_transfer(
            "device_table.build_on_device", 1,
            sum(int(v.nbytes) for v in planes.values()))
        unsorted = {k: jnp.asarray(v) for k, v in planes.items()}

        @jax.jit
        def gather(cols, p):
            return {k: v[p] for k, v in cols.items()}

        cols = gather(unsorted, dev_perm)
        return cls(len(table), cols)

    @classmethod
    def merge_scatter(cls, old: "DeviceTable",
                      delta_planes: Dict[str, np.ndarray],
                      r: np.ndarray,
                      stale=(),
                      full_codes: Optional[Dict[str, np.ndarray]] = None,
                      perm_pair=None,
                      host_perm: Optional[np.ndarray] = None):
        """Incremental merge of ``old``'s sorted columns with a sorted delta
        run (the device half of the LSM merge build).

        ``r[j]`` = merged rank of sorted-delta row j among the resident rows
        (count of resident keys ≤ the delta key — residents win ties), host
        int, non-decreasing. The resident shift is derived ON DEVICE from
        ``r`` (searchsorted against iota), so per column only the
        delta-sized values cross the host link — never the resident side.

        ``stale`` columns (dictionary codes whose vocab changed under the
        union-vocab concat) can't reuse the resident device codes; they
        rebuild from ``full_codes`` via one full-length gather through
        ``host_perm`` (host merge) or the merged device perm. ``perm_pair``
        = (old device perm, delta perm values) merges the permutation as
        one more int32 column. Returns (DeviceTable, merged device perm or
        None)."""
        import jax

        from geomesa_tpu.obs import attrib as _attrib

        n_old = old.n
        n_delta = len(r)
        n_new = n_old + n_delta
        full_codes = full_codes or {}

        names = [k for k in old.columns
                 if k in delta_planes and k not in stale]
        old_cols = {k: old.columns[k] for k in names}
        delta_cols = {
            k: jnp.asarray(np.ascontiguousarray(
                np.asarray(delta_planes[k], dtype=old.columns[k].dtype)))
            for k in names}
        if perm_pair is not None:
            old_cols["__perm__"] = perm_pair[0]
            delta_cols["__perm__"] = jnp.asarray(
                np.asarray(perm_pair[1], dtype=np.int32))
        r32 = jnp.asarray(np.asarray(r, dtype=np.int32))
        _attrib.record_transfer(
            "device_table.merge_scatter", 1,
            sum(int(np.asarray(delta_planes[k]).nbytes) for k in names)
            + int(r32.nbytes)
            + sum(int(v.nbytes) for v in full_codes.values()))

        key = (n_old, n_delta,
               tuple(sorted((k, str(v.dtype)) for k, v in old_cols.items())))
        fn = _merge_cache().get(
            key, lambda: _build_merge_scatter(n_old, n_delta))
        out = fn(old_cols, delta_cols, r32)
        new_perm = out.pop("__perm__", None)

        for name in stale:
            codes = full_codes[name]
            if host_perm is not None:
                out[name] = jnp.asarray(codes[host_perm])
            else:
                g = _merge_cache().get(
                    ("stale_gather", n_new, str(codes.dtype)),
                    lambda: jax.jit(lambda c, p: c[p]))
                out[name] = g(jnp.asarray(codes), new_perm)
        return cls(n_new, out), new_perm


# the segment pool's planes among a DeviceTable's columns
SEG, WAY = "__seg__", "__way__"


def segment_pool(garr: GeometryArray, perm: np.ndarray, pad: int):
    """An extent layer's segment pool in ``perm`` (the index's row) order:
    (device planes, the host's ``seg_off``), or None for an empty layer or
    one past int32 offsets. A ring of k vertices gives k - 1 segments (a
    Polygon's rings are stored closed), a lone vertex the degenerate segment
    at it, and no segment bridges two rings. ``way`` says of every row where
    its segments start, how many they are, and its kind: bit 0 for an areal
    geometry (it may hold a query polygon that crosses none of its rings),
    bit 1 for one of several parts (one vertex outside says nothing of the
    other parts). ``seg_off`` has one entry more than rows (the pool's
    length); the planes carry ``pad`` segments beyond it so that a tile read
    from any offset stays inside.

    In table order a row's segments are one run of the vertices that start
    a segment, so in ``perm`` order the run's index goes up by one inside a
    row and jumps where a row begins: a running sum gives it without a
    ragged expansion. The device gathers the four planes from the f32
    vertices."""
    import jax

    from geomesa_tpu.features.geometry import MULTIPOLYGON, POLYGON
    from geomesa_tpu.obs import attrib as _attrib

    if len(garr) == 0 or len(garr.coords) >= 1 << 31:
        return None
    ro = garr.ring_offsets
    ring_len = np.diff(ro)
    if np.any(ring_len == 0):
        return None
    open_ring = ring_len >= 2    # its last vertex starts no segment
    starts = np.ones(len(garr.coords), dtype=bool)
    starts[ro[1:][open_ring] - 1] = False
    a_tab = np.flatnonzero(starts).astype(np.int32)
    # rows → rings → vertices, and the segments before a row's first
    r0 = garr.part_offsets[garr.geom_offsets[:-1]]
    r1 = garr.part_offsets[garr.geom_offsets[1:]]
    rings_before = np.zeros(len(ro), dtype=np.int64)
    np.cumsum(open_ring, out=rings_before[1:])
    seg_first = (ro[r0] - rings_before[r0])[perm]
    seg_n = ((ro[r1] - ro[r0]) - (rings_before[r1] - rings_before[r0]))[perm]
    seg_off = np.zeros(len(perm) + 1, dtype=np.int64)
    np.cumsum(seg_n, out=seg_off[1:])
    total = int(seg_off[-1])
    if total + pad >= 1 << 31:
        return None
    step = np.ones(total + pad, dtype=np.int32)
    step[total:] = 0
    step[seg_off[1:-1]] = seg_first[1:] - (seg_first[:-1] + seg_n[:-1] - 1)
    step[0] = seg_first[0]
    a = a_tab[np.cumsum(step, out=step)]
    # the other end: the next vertex, or the same one for a lone vertex
    b = a + 1
    if not open_ring.all():
        lone = np.zeros(len(garr.coords), dtype=bool)
        lone[ro[:-1][~open_ring]] = True
        b -= lone[a]
    b[total:] = a[total:]
    kind = (np.isin(garr.type_codes, (POLYGON, MULTIPOLYGON)).astype(np.int32)
            | (np.diff(garr.geom_offsets) > 1).astype(np.int32) << 1)[perm]
    xy = [jnp.asarray(garr.coords[:, k].astype(np.float32)) for k in (0, 1)]
    _attrib.record_transfer(
        "device_table.segment_pool", 1,
        sum(int(v.nbytes) for v in (*xy, a, b)) + 12 * len(perm))
    gather = _merge_cache().get(
        ("segment_pool", len(garr.coords), len(a)),
        lambda: jax.jit(lambda x, y, a, b: jnp.stack(
            [x[a], y[a], x[b], y[b]])))
    seg_off = seg_off.astype(np.int32)
    return {SEG: gather(*xy, jnp.asarray(a), jnp.asarray(b)),
            WAY: jnp.asarray(np.stack([seg_off[:-1], seg_n.astype(np.int32),
                                       kind]))}, seg_off


# rows of a polygon type's pool again, each row's segments sorted by their
# lower end, in chunks of SEG_CHUNK that start every SEG_STEP segments: the
# plane the join reads a tile's slab of a polygon from, whole chunks at a
# time (a gather of rows; a slice a pair at its own offset is a loop of some
# 100,000 turns a join). A pair reads from the chunk that starts at most
# SEG_STEP - 1 segments before its slab, then every SEG_CHUNK / SEG_STEP-th.
# The gather's cost follows the chunks it moves more than their segments: on
# a v5e, for the same tests, chunks of 16 took the join's launch 1.23 times
# as long as chunks of 64 and chunks of 32 1.08 times; chunks of 128 came
# within 0.5% of chunks of 64 on JOIN_WIDTHS, at twice the plane. So the
# chunk stays 64 and only where it starts is fine
SEGY = "__segy__"
SEG_CHUNK = 64
SEG_STEP = 16


def segments_by_y(seg, seg_off: np.ndarray):
    """(device plane, ykey, rise) of a pool's segments ``seg`` ((4, n + pad)
    f32 on the device, rows at ``seg_off``): the same segments, every row's
    in ``geom_batch.slab_order`` (by their lower end), so that the segments
    of a row that can reach into a y-range are one span of it, as (chunks,
    4, SEG_CHUNK): chunk q holds segments q * SEG_STEP onwards, so that
    segment i is [q, :, i - q * SEG_STEP] of each chunk q that holds it;
    that order's search keys, ascending over the whole pool, and each row's
    tallest segment. Of the f32 plane's own values: what the kernel
    compares."""
    import jax

    from geomesa_tpu.filter.geom_batch import slab_order
    total = int(seg_off[-1])
    host = np.asarray(seg)
    take = np.arange(host.shape[1])
    take[:total], ykey, rise = slab_order(
        host[1, :total], host[3, :total],
        np.repeat(np.arange(len(seg_off) - 1), np.diff(seg_off)),
        len(seg_off) - 1)
    chunks = -(-host.shape[1] // SEG_STEP)

    def by_chunks(s, t):
        at = (SEG_STEP * jnp.arange(chunks, dtype=jnp.int32)[:, None]
              + jnp.arange(SEG_CHUNK, dtype=jnp.int32))
        return jnp.pad(s[:, t], (
            (0, 0), (0, chunks * SEG_STEP + SEG_CHUNK - s.shape[1]))
        )[:, at].transpose(1, 0, 2)

    gather = _merge_cache().get(("segments_by_y", host.shape[1]),
                                lambda: jax.jit(by_chunks))
    return gather(seg, jnp.asarray(take.astype(np.int32))), ykey, rise


_MERGE_CACHE = None


def _merge_cache():
    # lazy: index.scan imports are deferred so device.py stays import-light
    global _MERGE_CACHE
    if _MERGE_CACHE is None:
        from geomesa_tpu.index.scan import ModuleKernelCache
        _MERGE_CACHE = ModuleKernelCache("build.merge_scatter")
    return _MERGE_CACHE


def _build_merge_scatter(n_old: int, n_delta: int):
    import jax

    def fn(old_cols, delta_cols, r):
        shift = jnp.searchsorted(
            r, jnp.arange(n_old, dtype=jnp.int32),
            side="right").astype(jnp.int32)
        pos_res = jnp.arange(n_old, dtype=jnp.int32) + shift
        pos_del = r + jnp.arange(n_delta, dtype=jnp.int32)
        out = {}
        for k, o in old_cols.items():
            d = delta_cols[k]
            buf = jnp.zeros((n_old + n_delta,) + tuple(o.shape[1:]), o.dtype)
            out[k] = buf.at[pos_res].set(o).at[pos_del].set(d)
        return out

    return jax.jit(fn)


def host_planes(table: FeatureTable,
                period: Optional[TimePeriod] = None,
                skip_geom: bool = False,
                skip_dtg: bool = False) -> Dict[str, np.ndarray]:
    """Unsorted numpy projection of ``table`` onto the device column layout
    (row order = table order; the caller applies the index sort).

    ``skip_geom``/``skip_dtg`` omit the geometry / binned-time planes when the
    caller already produced them (the native fused-encode build path)."""
    cols: Dict[str, np.ndarray] = {}

    geom_attr = table.sft.geometry_attribute
    if skip_geom:
        geom_attr = None
    if geom_attr is not None:
        garr: GeometryArray = table.columns[geom_attr.name]
        if garr.is_points:
            x, y = garr.point_xy()
            xi, xl = fp62_lon(x)
            yi, yl = fp62_lat(y)
            cols["xi"], cols["xl"] = xi, xl
            cols["yi"], cols["yl"] = yi, yl
            cols["xf"] = np.asarray(x, dtype=np.float32)
            cols["yf"] = np.asarray(y, dtype=np.float32)
        else:
            bb = garr.bboxes()
            cols["bxmin"] = np.asarray(bb[:, 0], dtype=np.float32)
            cols["bymin"] = np.asarray(bb[:, 1], dtype=np.float32)
            cols["bxmax"] = np.asarray(bb[:, 2], dtype=np.float32)
            cols["bymax"] = np.asarray(bb[:, 3], dtype=np.float32)
            # fp62 envelope planes: exact envelope-overlap tests on device
            for name, vals, f in (("bxmin", bb[:, 0], fp62_lon),
                                  ("bymin", bb[:, 1], fp62_lat),
                                  ("bxmax", bb[:, 2], fp62_lon),
                                  ("bymax", bb[:, 3], fp62_lat)):
                hi, lo = f(vals)
                cols[name + "_i"] = hi
                cols[name + "_l"] = lo

    dtg_attr = table.sft.dtg_attribute
    if dtg_attr is not None and period is not None and not skip_dtg:
        ms = np.asarray(table.columns[dtg_attr.name], dtype=np.int64)
        bins, offs = time_to_binned_time(ms, period)
        cols["bin"] = np.asarray(bins, dtype=np.int32)
        cols["off"] = np.asarray(offs, dtype=np.int32)

    if table.visibility is not None:
        # dictionary codes; query-time auths shrink to an allowed-code set
        cols["__vis__"] = np.asarray(table.visibility.codes, dtype=np.int32)

    group = table.sft.device_column_group
    for attr in table.sft.attributes:
        if attr.is_geometry:
            continue
        if group is not None and attr.name not in group \
                and not (dtg_attr is not None and attr.name == dtg_attr.name):
            continue  # outside the device column group: host-only attribute
        raw = table.columns[attr.name]
        if isinstance(raw, StringColumn):
            cols[attr.name] = np.asarray(raw.codes, dtype=np.int32)
        elif attr.type_name == "Date":
            if dtg_attr is not None and attr.name == dtg_attr.name \
                    and period is not None:
                continue  # (bin, off) planes carry the primary dtg exactly
            # secondary date attrs: seconds resolution on device (residual
            # date predicates are host-refined; this column is advisory)
            cols[attr.name] = (np.asarray(raw, dtype=np.int64) // 1000).astype(np.int32)
        elif attr.type_name == "Long":
            cols[attr.name] = np.asarray(raw).astype(np.float64).astype(np.float32)
        elif attr.type_name == "Double":
            cols[attr.name] = np.asarray(raw, dtype=np.float32)
        else:
            cols[attr.name] = np.asarray(raw)
    return cols
