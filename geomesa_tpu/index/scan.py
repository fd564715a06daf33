"""Jitted scan kernels: the TPU equivalent of GeoMesa's server-side filters.

≙ the push-down compute contract of SURVEY.md §2.4: ``Z3Filter.inBounds``
(decode z, int box tests — filters/Z3Filter.scala:25-61) plus the residual
CQL evaluation of ``FilterTransformIterator``/``CqlTransformFilter``. Instead
of per-KV decode, the columns are already decoded int32 planes; a scan is one
fused elementwise mask over N rows (bandwidth-bound on HBM), followed by
count / nonzero-compaction / aggregation.

Shape discipline: queries pad their box/window lists to fixed sizes (powers of
two) so XLA compiles one kernel per (primary_kind, n_boxes, n_windows,
residual_structure) — constants ride in arrays, so new query *values* never
recompile.

Exactness contract (mirrors the reference's contained-vs-overlapping ranges +
useFullFilter, Z3IndexKeySpace.scala:235-249):
  - ``strict`` masks use cell-interior bounds → every hit is a definite match
    (like rows in a *contained* range: no further filtering)
  - ``loose`` masks use cell-covering bounds → superset of matches; rows in
    loose∖strict are the boundary band the host refines in f64
"""

from __future__ import annotations

import functools
import re
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from geomesa_tpu import trace as _trace
from geomesa_tpu.filter import ir
from geomesa_tpu.index.device import SEG, SEG_CHUNK, SEG_STEP, SEGY, WAY
from geomesa_tpu.obs import attrib as _attrib
from geomesa_tpu.obs import profiling as _prof


class _RoundLedger:
    """Process-wide host↔device round counter: every kernel dispatch and
    every constant upload is one potential host↔device round trip.
    ``rounds_since`` a snapshot is how the fused-query tests pin the
    dispatches a cold query makes — the fused path must read exactly 1."""

    __slots__ = ("dispatches", "uploads")

    def __init__(self):
        self.dispatches = 0
        self.uploads = 0

    def snapshot(self):
        return (self.dispatches, self.uploads)

    def rounds_since(self, snap) -> int:
        return (self.dispatches - snap[0]) + (self.uploads - snap[1])


ROUNDS = _RoundLedger()


def _fetch(dispatch, *args):
    """Run a kernel dispatch under a ``device_scan`` span (host-side enqueue)
    and block under a ``device_wait`` span — separating the dispatch floor
    from true device time in every trace. Returns the ready device value.
    Variadic so hot paths pass ``(fn, *args)`` without a closure alloc."""
    ROUNDS.dispatches += 1
    return _trace.device_fetch(jax.block_until_ready, dispatch, *args)

# -- primary spatial/temporal masks -----------------------------------------


def _ge62(hi, lo, qhi, qlo):
    """Lexicographic fixed-point (hi, lo) >= (qhi, qlo)."""
    return (hi > qhi) | ((hi == qhi) & (lo >= qlo))


def _le62(hi, lo, qhi, qlo):
    return (hi < qhi) | ((hi == qhi) & (lo <= qlo))


def _point_box_pairwise(cols, boxes: jnp.ndarray) -> jnp.ndarray:
    """(N, B) per-box containment matrix for point layers — EXACT (fp62
    planes). boxes (B, 8) int32: [qxlo_hi, qxlo_lo, qxhi_hi, qxhi_lo,
    qylo_hi, qylo_lo, qyhi_hi, qyhi_lo]. Empty boxes use qlo=max/qhi=0 so
    nothing matches."""
    xi, xl = cols["xi"][:, None], cols["xl"][:, None]
    yi, yl = cols["yi"][:, None], cols["yl"][:, None]
    b = boxes[None, :, :]
    return (
        _ge62(xi, xl, b[..., 0], b[..., 1]) & _le62(xi, xl, b[..., 2], b[..., 3])
        & _ge62(yi, yl, b[..., 4], b[..., 5]) & _le62(yi, yl, b[..., 6], b[..., 7])
    )


def _point_box_mask(cols, boxes: jnp.ndarray) -> jnp.ndarray:
    """Any-box containment for point layers — EXACT (fp62 planes)."""
    return jnp.any(_point_box_pairwise(cols, boxes), axis=1)


def _bbox_overlap_pairwise(cols, boxes: jnp.ndarray) -> jnp.ndarray:
    """(N, B) per-box envelope-overlap matrix for extent layers — EXACT on
    envelopes (geometry-level refinement is the spatial residual's job)."""
    b = boxes[None, :, :]
    return (
        _le62(cols["bxmin_i"][:, None], cols["bxmin_l"][:, None], b[..., 2], b[..., 3])
        & _ge62(cols["bxmax_i"][:, None], cols["bxmax_l"][:, None], b[..., 0], b[..., 1])
        & _le62(cols["bymin_i"][:, None], cols["bymin_l"][:, None], b[..., 6], b[..., 7])
        & _ge62(cols["bymax_i"][:, None], cols["bymax_l"][:, None], b[..., 4], b[..., 5])
    )


def _bbox_overlap_mask(cols, boxes: jnp.ndarray) -> jnp.ndarray:
    """Any-box envelope-overlap for extent layers."""
    return jnp.any(_bbox_overlap_pairwise(cols, boxes), axis=1)


def _time_mask(cols, windows: jnp.ndarray) -> jnp.ndarray:
    """Any-window (bin, off) containment (≙ Z3Filter.timeInBounds semantics,
    exact: offsets are unnormalized period units). windows (T,4) int32
    [bin_lo, off_lo, bin_hi, off_hi]; empty windows bin_lo>bin_hi."""
    b = cols["bin"][:, None]
    o = cols["off"][:, None]
    blo, olo = windows[None, :, 0], windows[None, :, 1]
    bhi, ohi = windows[None, :, 2], windows[None, :, 3]
    after_lo = (b > blo) | ((b == blo) & (o >= olo))
    before_hi = (b < bhi) | ((b == bhi) & (o <= ohi))
    return jnp.any(after_lo & before_hi & (blo <= bhi), axis=1)


PRIMARY_FNS: Dict[str, Callable] = {
    "point_boxes": _point_box_mask,
    "bbox_overlap": _bbox_overlap_mask,
}

# device columns each primary mask reads (batch kernels pre-touch these
# before entering a mapped body — see count_multi_blocks)
_PRIMARY_COLS: Dict[str, tuple] = {
    "point_boxes": ("xi", "xl", "yi", "yl"),
    "bbox_overlap": ("bxmin_i", "bxmin_l", "bxmax_i", "bxmax_l",
                     "bymin_i", "bymin_l", "bymax_i", "bymax_l"),
}


# -- certified f32 geometry predicates ---------------------------------------
#
# The fp62 planes make BOX predicates exact on device; SEGMENT predicates
# (exact intersects for extent features) use f32 with a computed CERTAINTY
# BAND instead: every orientation sign carries an error bound covering both
# the f32 arithmetic and the f64→f32 input rounding, so each feature
# classifies as certain-hit / certain-miss / uncertain — and only the
# uncertain sliver (rows within ~1e-5 deg of a boundary) goes to the host's
# exact f64 refine. This is the strict/loose band discipline applied to
# JTS-style predicates.

_F32_EPS = np.float32(1.2e-7)     # 2^-23 with margin
_IN_DELTA = np.float32(2.5e-5)    # |f64 coord - f32 coord| bound (lon/lat)
_DY_BAND = np.float32(3e-5)       # vertex y-tie band for the crossing rule
_BOX_GAP = 2 * _IN_DELTA          # envelopes this far apart are apart in f64


def _orient_band(px, py, qx, qy, rx, ry):
    """Signed area orientation of (p,q,r) with a conservative error bound."""
    d1x = qx - px
    d1y = qy - py
    d2x = rx - px
    d2y = ry - py
    t1 = d1x * d2y
    t2 = d1y * d2x
    det = t1 - t2
    tol = (8 * _F32_EPS * (jnp.abs(t1) + jnp.abs(t2))
           + 4 * _IN_DELTA * (jnp.abs(d1x) + jnp.abs(d1y)
                              + jnp.abs(d2x) + jnp.abs(d2y)))
    return det, tol


def _pip_edge_band(px, py, ex1, ey1, ex2, ey2):
    """One polygon edge against the +x ray of a point, by the half-open
    crossing rule: (certain crossing, uncertain). Uncertain when the crossing
    decision sits inside its error band or a vertex y ties the ray; an edge
    wholly to the left of the point can neither cross its ray nor make it
    uncertain."""
    cond = (ey1 > py) != (ey2 > py)
    o, t = _orient_band(ex1, ey1, ex2, ey2, px, py)
    cross = cond & jnp.where(ey2 > ey1, o > t, o < -t)
    unc = (cond & (jnp.abs(o) <= t)) \
        | (jnp.abs(ey1 - py) <= _DY_BAND) | (jnp.abs(ey2 - py) <= _DY_BAND)
    return cross, unc & (px <= jnp.maximum(ex1, ex2) + _BOX_GAP)


def _pip_band(px, py, ex1, ey1, ex2, ey2, evalid=None):
    """(certainly-inside, certainly-outside) of points vs polygon edges on
    the last axis. ``evalid`` masks padded edges out of both crossings and
    uncertainty (pair-kernel padded tables)."""
    cross, unc = _pip_edge_band(px, py, ex1, ey1, ex2, ey2)
    if evalid is not None:
        cross = cross & evalid
        unc = unc & evalid
    inside = (jnp.sum(cross, axis=-1) % 2) == 1
    any_unc = jnp.any(unc, axis=-1)
    return inside & ~any_unc, ~inside & ~any_unc


def _segpair_band(ax, ay, bx, by, cx, cy, dx, dy):
    """(certain-intersect, certain-miss) for segment (a,b) vs edge (c,d).
    A pair whose envelopes lie apart by more than the inputs' rounding is a
    certain miss whatever its orientations say: a short segment's own line
    is known too poorly (its direction to within delta / length) to place a
    far edge on one side of it, so without this every segment near an
    edge's *line*, however far from the edge, would stay uncertain."""
    o1, t1 = _orient_band(ax, ay, bx, by, cx, cy)
    o2, t2 = _orient_band(ax, ay, bx, by, dx, dy)
    o3, t3 = _orient_band(cx, cy, dx, dy, ax, ay)
    o4, t4 = _orient_band(cx, cy, dx, dy, bx, by)
    opp12 = ((o1 > t1) & (o2 < -t2)) | ((o1 < -t1) & (o2 > t2))
    opp34 = ((o3 > t3) & (o4 < -t4)) | ((o3 < -t3) & (o4 > t4))
    same12 = ((o1 > t1) & (o2 > t2)) | ((o1 < -t1) & (o2 < -t2))
    same34 = ((o3 > t3) & (o4 > t4)) | ((o3 < -t3) & (o4 < -t4))
    apart = ((jnp.maximum(ax, bx) + _BOX_GAP < jnp.minimum(cx, dx))
             | (jnp.minimum(ax, bx) - _BOX_GAP > jnp.maximum(cx, dx))
             | (jnp.maximum(ay, by) + _BOX_GAP < jnp.minimum(cy, dy))
             | (jnp.minimum(ay, by) - _BOX_GAP > jnp.maximum(cy, dy)))
    return opp12 & opp34, same12 | same34 | apart


_EARTH_R_M = 6371008.8


def _haversine_f32(lon, lat, qlon, qlat):
    """Great-circle distance in meters, f32 (matches process/geo.haversine_m
    up to f32 rounding — callers that need exact ranks re-check in f64)."""
    rad = jnp.float32(np.pi / 180.0)
    la1 = lat * rad
    la2 = qlat * rad
    dla = (qlat - lat) * rad
    dlo = (qlon - lon) * rad
    a = jnp.sin(dla / 2) ** 2 + jnp.cos(la1) * jnp.cos(la2) * jnp.sin(dlo / 2) ** 2
    return jnp.float32(2 * _EARTH_R_M) * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))


# -- residual predicate compiler --------------------------------------------


class Unsupported(Exception):
    """Raised when a predicate subtree can't run on device."""


# attr type names whose device columns are exact representations
_EXACT_DEVICE_TYPES = {"Int", "Integer", "Boolean", "String", "Float"}


def compile_residual(f: Optional[ir.Filter], sft, string_vocabs: Dict[str, list],
                     available: Optional[set] = None):
    """IR → (structure_key, params ndarray list, fn(cols, params) -> mask).

    Raises Unsupported for subtrees that must stay host-side — including
    predicates on attributes OUTSIDE the device column projection
    (``available``, when given: the column-group narrow-scan contract).
    Constants are hoisted into the params list so differing query values
    share one compiled kernel (structure_key captures only the tree shape).
    """
    if f is None:
        return "none", [], None

    def check_available(attr: str) -> None:
        if available is not None and attr not in available:
            raise Unsupported(f"{attr} not in the device column group")

    params: list = []

    def const(v, dtype) -> int:
        params.append(np.asarray(v, dtype=dtype))
        return len(params) - 1

    def walk(node: ir.Filter) -> Tuple[str, Callable]:
        if isinstance(node, ir.Include):
            return "inc", lambda cols, p: jnp.ones(
                next(iter(cols.values())).shape[0], dtype=bool)
        if isinstance(node, ir.Exclude):
            return "exc", lambda cols, p: jnp.zeros(
                next(iter(cols.values())).shape[0], dtype=bool)
        if isinstance(node, ir.And):
            keys, fns = zip(*(walk(c) for c in node.children))
            return "and(" + ",".join(keys) + ")", \
                lambda cols, p, fns=fns: functools.reduce(
                    jnp.logical_and, [g(cols, p) for g in fns])
        if isinstance(node, ir.Or):
            keys, fns = zip(*(walk(c) for c in node.children))
            return "or(" + ",".join(keys) + ")", \
                lambda cols, p, fns=fns: functools.reduce(
                    jnp.logical_or, [g(cols, p) for g in fns])
        if isinstance(node, ir.Not):
            k, g = walk(node.child)
            return f"not({k})", lambda cols, p, g=g: ~g(cols, p)
        if isinstance(node, ir.Cmp):
            check_available(node.attr)
            attr = sft.attribute(node.attr)
            if attr.type_name == "String":
                if node.op not in ("=", "<>"):
                    raise Unsupported("ordered string cmp on device")
                vocab = string_vocabs.get(node.attr)
                if vocab is None:
                    raise Unsupported("no vocab")
                try:
                    code = vocab.index(node.value)
                except ValueError:
                    code = -1  # matches nothing
                i = const(code, np.int32)
                if node.op == "=":
                    return f"seq:{node.attr}", lambda cols, p, i=i, a=node.attr: cols[a] == p[i]
                return f"sne:{node.attr}", lambda cols, p, i=i, a=node.attr: cols[a] != p[i]
            if attr.type_name not in _EXACT_DEVICE_TYPES:
                raise Unsupported(f"{attr.type_name} cmp is inexact on device")
            dtype = np.float32 if attr.type_name == "Float" else np.int32
            i = const(node.value, dtype)
            op = node.op
            key = f"cmp{op}:{node.attr}"

            def g(cols, p, i=i, a=node.attr, op=op):
                c = cols[a]
                v = p[i]
                return {"=": c == v, "<>": c != v, "<": c < v,
                        "<=": c <= v, ">": c > v, ">=": c >= v}[op]
            return key, g
        if isinstance(node, ir.In):
            check_available(node.attr)
            attr = sft.attribute(node.attr)
            if attr.type_name == "String":
                vocab = string_vocabs.get(node.attr)
                if vocab is None:
                    raise Unsupported("no vocab")
                codes = [vocab.index(v) for v in node.values if v in vocab] or [-1]
            elif attr.type_name in ("Int", "Integer"):
                codes = [int(v) for v in node.values]
            else:
                raise Unsupported("IN on non-int/string")
            # pad to pow2 so membership lists of similar size share kernels
            size = max(1, 1 << (len(codes) - 1).bit_length())
            padded = codes + [codes[-1]] * (size - len(codes))
            i = const(padded, np.int32)
            return f"in{size}:{node.attr}", \
                lambda cols, p, i=i, a=node.attr: jnp.any(
                    cols[a][:, None] == p[i][None, :], axis=1)
        if isinstance(node, ir.During):
            dtg = sft.dtg_attribute
            if dtg is None or node.attr != dtg.name:
                raise Unsupported("During on non-dtg attr")
            # exact (bin, off) bounds computed host-side in the planner via
            # params: [bin_lo, off_lo, bin_hi, off_hi] — see plan_residual
            raise Unsupported("During handled by primary time windows")
        raise Unsupported(type(node).__name__)

    key, fn = walk(f)
    return key, params, fn


def split_residual(f: Optional[ir.Filter], sft, string_vocabs,
                   available: Optional[set] = None):
    """Split a residual filter into (device_part, host_part).

    AND trees split per-child; any child the device compiler rejects stays on
    the host (≙ reference splitting between pushed-down filter and client
    post-filter) — including predicates on attributes outside the device
    column group. Returns (device_ir_or_None, host_ir_or_None).
    """
    if f is None or isinstance(f, ir.Include):
        return None, None
    children = f.children if isinstance(f, ir.And) else (f,)
    dev, host = [], []
    for c in children:
        try:
            compile_residual(c, sft, string_vocabs, available)
            dev.append(c)
        except Unsupported:
            host.append(c)
    return (
        ir.and_filters(dev) if dev else None,
        ir.and_filters(host) if host else None,
    )


# -- fused scan entry points ------------------------------------------------


@functools.lru_cache(maxsize=256)
def _mask_kernel(primary_kind: str, has_time: bool, residual_key: str, n_boxes: int, n_windows: int):
    """Build the fused mask fn for one structural signature."""

    def mask(cols, boxes, windows, rparams, residual_fn):
        m = None
        if primary_kind != "none":
            m = PRIMARY_FNS[primary_kind](cols, boxes)
        if has_time:
            tm = _time_mask(cols, windows)
            m = tm if m is None else (m & tm)
        if residual_fn is not None:
            rm = residual_fn(cols, rparams)
            m = rm if m is None else (m & rm)
        if m is None:
            n = next(iter(cols.values())).shape[0]
            m = jnp.ones(n, dtype=bool)
        if "__valid__" in cols:
            m = m & cols["__valid__"]
        return m

    return mask


def _grid_scatter(xs, ys, mask, weight, grid, width: int, height: int):
    """Masked scatter-add onto a (height, width) raster. grid =
    [xmin, ymin, xmax, ymax] f32 (GridSnap.scala:23 snap semantics)."""
    xmin, ymin, xmax, ymax = grid[0], grid[1], grid[2], grid[3]
    fx = (xs - xmin) / (xmax - xmin)
    fy = (ys - ymin) / (ymax - ymin)
    inb = mask & (fx >= 0) & (fx < 1) & (fy >= 0) & (fy < 1)
    ix = jnp.clip((fx * width).astype(jnp.int32), 0, width - 1)
    iy = jnp.clip((fy * height).astype(jnp.int32), 0, height - 1)
    w = jnp.where(inb, weight if weight is not None else 1.0, 0.0).astype(jnp.float32)
    return jnp.zeros((height, width), dtype=jnp.float32).at[iy, ix].add(w)


class _LazyBlockGather:
    """Dict-like view reading candidate blocks of a column on first access,
    so a pruned scan touches only the columns its mask needs.

    Reads are vmapped ``dynamic_slice``s — nb contiguous block_size-row
    slices — which XLA lowers to an efficient slice-gather (one HBM burst per
    block). An elementwise ``col[flat_idx]`` gather here lowers to per-row
    accesses and measured ~75x slower on TPU."""

    def __init__(self, cols: Dict[str, jnp.ndarray], starts: jnp.ndarray,
                 block_size: int, total: int):
        self._cols = cols
        self._starts = starts          # (nb,) clipped int32 row starts
        self._bsz = block_size
        self._total = total            # nb * block_size
        self._cache: Dict[str, jnp.ndarray] = {}

    def __getitem__(self, k: str) -> jnp.ndarray:
        if k not in self._cache:
            from jax import lax, vmap
            v = self._cols[k]
            bsz = self._bsz
            sl = vmap(lambda s: lax.dynamic_slice(v, (s,), (bsz,)))(self._starts)
            self._cache[k] = sl.reshape(self._total)
        return self._cache[k]

    def __contains__(self, k: str) -> bool:
        return k in self._cols

    def values(self):
        # row-count probes (Include/Exclude) only need a .shape[0]
        yield self._starts.repeat(self._bsz)


# segments a tile of the pool holds: the unit a span is gathered and padded in
POOL_TILE = 4096


def _slices(planes, starts, width: int):
    """(len(starts), rows, width): the columns [s, s + width) of a
    (rows, n) array for every start, all rows of a slice in one read (a
    slice a plane is what the device's time goes on, a few µs each)."""
    from jax import lax, vmap
    rows = planes.shape[0]
    return vmap(lambda s: lax.dynamic_slice(
        planes, (0, s), (rows, width)))(starts)


def _classify_segments(ax, ay, bx, by, edges, n_edges):
    """(3, tiles, POOL_TILE) int32 flags of pool segments (a, b) against the
    first ``n_edges`` rows of ``edges`` (E, 4): [certain hit: the segment
    surely crosses an edge or ``a`` is surely inside; pair open: some edge is
    not surely missed; ``a`` surely outside]. One edge a turn, so nothing of
    shape (segments, edges) is ever held."""
    from jax import lax

    def one(i, carry):
        hit, open_, par, punc = carry
        ex1, ey1, ex2, ey2 = (edges[i, k] for k in range(4))
        hit_p, miss_p = _segpair_band(ax, ay, bx, by, ex1, ey1, ex2, ey2)
        cross, unc = _pip_edge_band(ax, ay, ex1, ey1, ex2, ey2)
        return hit | hit_p, open_ | ~miss_p, par ^ cross, punc | unc

    no = jnp.zeros(ax.shape, dtype=bool)
    hit, open_, inside, punc = lax.fori_loop(0, n_edges, one,
                                             (no, no, no, no))
    return jnp.stack([hit | (inside & ~punc), open_,
                      ~inside & ~punc]).astype(jnp.int32)


def _sort_tiles(y, x, *payload):
    """Rows of every block (the last axis) in ascending ``y``: (y, x,
    payload...), sorted together. Stable, so the order follows from ``y``
    alone: the join's kernel and the envelopes its gate reads
    (``BaseSpatialIndex.join_envelopes``) sort each for itself and meet the
    same tiles."""
    from jax import lax
    return lax.sort((y, x) + payload, dimension=1, is_stable=True, num_keys=1)


def _classify_pairs(px, py, m, seg, box, pairs, width: int):
    """One step of the grouped join: ``pairs`` (G, 5) int32 [tile, first
    chunk, first segment in it, past the last, polygon row] of one edge
    bucket (a pad has no segments and so no point), against the tiles'
    points ``px``/``py``/``m`` (tiles, rows) → (G, rows) int32 flags, bit 0
    a point surely inside its pair's polygon, bit 1 one the f32 band cannot
    settle. A pair reads ``width`` segments of ``seg`` ((chunks, 4,
    SEG_CHUNK), a chunk starting every SEG_STEP segments) from its first
    chunk on, whole chunks, those outside its own
    span masked out: the slab of its polygon's edges, sorted by their lower
    end, that can meet the tile's y-range, so the others neither cross a
    point's ray nor tie its y. Edges
    ride the second axis and are summed away (crossings in the low 16 bits,
    uncertain edges above them): nothing of shape (pairs, edges, rows)
    outlives the reduce. A point outside the polygon's envelope by more than
    the inputs' rounding is surely outside whatever the slab says."""
    slot, poly = pairs[:, 0], pairs[:, 4]
    segs = seg[pairs[:, 1:2] + (SEG_CHUNK // SEG_STEP) * jnp.arange(
        width // SEG_CHUNK, dtype=jnp.int32)[None, :]]
    e = jnp.arange(width, dtype=jnp.int32)[None, :]
    evalid = ((e >= pairs[:, 2:3]) & (e < pairs[:, 3:4]))[:, :, None]
    x, y = px[slot][:, None, :], py[slot][:, None, :]
    cross, unc = _pip_edge_band(
        x, y, *(segs[:, :, k, :].reshape(-1, width, 1) for k in range(4)))
    acc = jnp.sum((cross & evalid).astype(jnp.int32)
                  + ((unc & evalid).astype(jnp.int32) << 16), axis=1)
    x, y = x[:, 0], y[:, 0]
    b = box[:, poly]
    near = ((x >= b[0][:, None] - _BOX_GAP) & (x <= b[2][:, None] + _BOX_GAP)
            & (y >= b[1][:, None] - _BOX_GAP) & (y <= b[3][:, None] + _BOX_GAP)
            & m[slot])
    settled = (acc >> 16) == 0
    return ((near & settled & ((acc & 1) == 1)).astype(jnp.int32)
            | ((near & ~settled).astype(jnp.int32) << 1))


def _running_sum(x):
    """Inclusive running sum over the last two axes taken as one: within a
    row, plus the rows before it."""
    inner = jnp.cumsum(x, axis=-1)
    last = inner[..., -1]
    return inner + (jnp.cumsum(last, axis=-1) - last)[..., None]


def _first_set(mask, cap: int):
    """Flat positions of the first ``cap`` set entries of a 2-D mask, the
    mask's size where there are fewer: the j-th is where the running count
    first reaches j + 1. In place of ``jnp.nonzero(size=cap)``, whose scatter
    takes the TPU compiler half a minute at a million rows."""
    count = _running_sum(mask.astype(jnp.int32)).reshape(-1)
    return jnp.searchsorted(count, jnp.arange(1, cap + 1, dtype=jnp.int32),
                            side="left")


def _span_sums(flags, p0, p1):
    """Sums of each flag row over the flat positions [p0, p1) of every way:
    a running sum within a tile plus the tiles before it, read at the two
    ends of the span. Spans of rows that are no candidates may lie anywhere:
    their ends are clipped and their sums masked by the caller."""
    k, tiles, width = flags.shape
    upto = _running_sum(flags).reshape(k, tiles * width)
    # sum of [0, p) = upto[p - 1]
    at = lambda p: jnp.where(
        p > 0, upto[:, jnp.clip(p - 1, 0, tiles * width - 1)], 0)
    return at(p1) - at(p0)


import weakref

# live ScanKernels instances (weak: a dropped index frees its kernels)
_KERNEL_INSTANCES: "weakref.WeakSet" = weakref.WeakSet()


def _register_kernel_gauge() -> None:
    """`kernels.compiled` gauge: compiled scan kernels resident across every
    live ScanKernels instance (the quantity the per-instance LRU bounds)."""
    global _KERNEL_GAUGE_REGISTERED
    if _KERNEL_GAUGE_REGISTERED:
        return
    _KERNEL_GAUGE_REGISTERED = True
    from geomesa_tpu.metrics import REGISTRY
    REGISTRY.set_gauge(
        "kernels.compiled",
        lambda: sum(len(k._jitted) for k in list(_KERNEL_INSTANCES)))


_KERNEL_GAUGE_REGISTERED = False


class ModuleKernelCache:
    """Bounded LRU for module-level jitted kernels (sort / gather / merge).

    The build-path jits in ``index/spatial.py`` used to live in module
    globals keyed by nothing — one padded-shape compile pinned forever, and
    a long-running ingester visiting many pow2 tiers accumulated them all.
    Routing them through this cache bounds residency by
    ``GEOMESA_TPU_KERNEL_CACHE`` (shape-keyed entries, LRU eviction) and —
    because instances register in ``_KERNEL_INSTANCES`` exactly like
    ``ScanKernels`` — counts them in the ``kernels.compiled`` gauge and the
    recompile detector."""

    def __init__(self, kernel_id: str):
        self.kernel_id = kernel_id
        from collections import OrderedDict
        self._jitted: "OrderedDict[tuple, Callable]" = OrderedDict()
        self._sig_seen: Dict[str, set] = {}
        _KERNEL_INSTANCES.add(self)
        _register_kernel_gauge()

    def get(self, key: tuple, builder):
        """Return the cached kernel for ``key`` or build+insert it.

        ``builder`` is a zero-arg callable returning the jitted fn; it runs
        only on a miss. Eviction drops the least-recently-used shape — an
        evicted shape simply recompiles on next use."""
        hit = self._jitted.get(key)
        if hit is not None:
            self._jitted.move_to_end(key)
            return hit
        jitted = builder()
        if _prof.enabled():
            _prof.note_signature(self._sig_seen, self.kernel_id, key)
        self._jitted[key] = jitted
        from geomesa_tpu import config
        lru_cap = max(1, config.KERNEL_CACHE.get())
        while len(self._jitted) > lru_cap:
            self._jitted.popitem(last=False)
        return jitted


class ScanKernels:
    """Compiled-scan cache for one DeviceTable (one index).

    ``_jitted`` is a small LRU (``GEOMESA_TPU_KERNEL_CACHE`` signatures):
    long-lived servers seeing many residual structures stay bounded instead
    of accumulating compiled kernels forever; an evicted signature simply
    recompiles on next use (prepared dispatchers hold their own reference,
    so in-flight handles never lose their kernel)."""

    def __init__(self, device_cols: Dict[str, jnp.ndarray]):
        self.cols = device_cols
        from collections import OrderedDict
        self._jitted: "OrderedDict[tuple, Callable]" = OrderedDict()
        # kernel_id -> signature hashes already compiled by THIS instance:
        # the recompile detector's memory (obs/profiling.note_signature) —
        # per-instance so two indexes compiling their own kernels never
        # read as shape churn
        self._sig_seen: Dict[str, set] = {}
        _KERNEL_INSTANCES.add(self)
        _register_kernel_gauge()

    def _get(self, mode: str, primary_kind: str, has_time: bool,
             residual_key: str, residual_fn, n_boxes: int, n_windows: int,
             capacity: int = 0):
        key = (mode, primary_kind, has_time, residual_key, n_boxes, n_windows, capacity)
        hit = self._jitted.get(key)
        if hit is not None:
            self._jitted.move_to_end(key)
            return hit
        mask_fn = _mask_kernel(primary_kind, has_time, residual_key, n_boxes, n_windows)

        if mode == "count":
            def run(cols, boxes, windows, rparams):
                return jnp.sum(mask_fn(cols, boxes, windows, rparams, residual_fn))
        elif mode == "mask":
            def run(cols, boxes, windows, rparams):
                return mask_fn(cols, boxes, windows, rparams, residual_fn)
        elif mode == "count_at":
            # candidate-pruned scan (attribute index): gather the candidate
            # rows' columns, mask only those (≙ scanning one key range
            # instead of the table)
            def run(cols, boxes, windows, rparams, idxs, nvalid):
                g = {k: v[idxs] for k, v in cols.items()}
                m = mask_fn(g, boxes, windows, rparams, residual_fn)
                m = m & (jnp.arange(idxs.shape[0]) < nvalid)
                return jnp.sum(m)
        elif mode == "select_at":
            def run(cols, boxes, windows, rparams, idxs, nvalid):
                g = {k: v[idxs] for k, v in cols.items()}
                m = mask_fn(g, boxes, windows, rparams, residual_fn)
                m = m & (jnp.arange(idxs.shape[0]) < nvalid)
                sel = jnp.nonzero(m, size=idxs.shape[0], fill_value=idxs.shape[0])[0]
                return jnp.concatenate([
                    jnp.sum(m)[None].astype(jnp.int32), sel.astype(jnp.int32)])
        elif mode == "count_multi":
            # per-box counts in ONE kernel: the non-box constraints evaluate
            # once, then lax.map runs one fused box-count pass per box (B
            # sequential bandwidth-bound scans — no (N, B) materialization).
            # The expanding-radius KNN schedule rides this: every radius
            # costs one extra scan, the whole schedule one round trip.
            from jax import lax

            def run(cols, boxes, windows, rparams):
                base = None
                if has_time:
                    base = _time_mask(cols, windows)
                if residual_fn is not None:
                    rm = residual_fn(cols, rparams)
                    base = rm if base is None else (base & rm)
                if "__valid__" in cols:
                    v = cols["__valid__"]
                    base = v if base is None else (base & v)

                def one(b):
                    m = PRIMARY_FNS[primary_kind](cols, b[None, :])
                    return jnp.sum(m if base is None else (m & base))

                return lax.map(one, boxes)
        elif mode == "density_compact":
            # heat-map over a full-table mask: compact matching rows first
            # (nonzero + gather), THEN scatter-add — a TPU scatter prices per
            # update, so scattering 100M mostly-zero weights (the r3 design)
            # cost ~1s where compact-then-scatter costs ~1ms. Returns
            # (grid, true_count); the caller sizes `cap` from a count so
            # overflow cannot occur on static data.
            cap, width, height, wname = capacity
            n = next(iter(self.cols.values())).shape[0]

            def run(cols, boxes, windows, rparams, grid):
                m = mask_fn(cols, boxes, windows, rparams, residual_fn)
                sel = jnp.nonzero(m, size=cap, fill_value=n)[0]
                ok = sel < n
                seli = jnp.clip(sel, 0, n - 1)
                xs = cols["xf"][seli]
                ys = cols["yf"][seli]
                w = cols[wname][seli].astype(jnp.float32) if wname else None
                out = _grid_scatter(xs, ys, ok, w, grid, width, height)
                return out, jnp.sum(m)
        elif mode in ("count_blocks", "count_multi_blocks", "select_blocks",
                      "density_blocks", "topk_blocks",
                      "intersects_band_blocks", "join_band_blocks"):
            # range-pruned gather scan: block ids (pad = -1) expand to row
            # indices with an iota, candidate rows gather from HBM, and the
            # FULL exact mask re-applies — so the host cover only needs to be
            # a superset (≙ scanning the reference's ≤2000 key ranges instead
            # of the table; block granularity plays the tablet-range role).
            n = next(iter(self.cols.values())).shape[0]
            nblk, bsz, sel_cap = capacity[:3]

            def expand_blocks(cols, block_ids):
                """block ids → (valid membership mask, row ids, lazy gather).
                dynamic_slice clamps out-of-range starts, so the last
                partial block re-reads a suffix of the previous one; the
                membership test (row belongs to ITS intended block) masks
                those re-reads and the -1 pad blocks without double counts.
                Single home for this logic — every block mode goes through it."""
                starts = block_ids * bsz
                astart = jnp.clip(starts, 0, max(0, n - bsz))
                rows = (astart[:, None]
                        + jnp.arange(bsz, dtype=jnp.int32)[None, :])
                valid = ((block_ids >= 0)[:, None]
                         & (rows >= starts[:, None])
                         & (rows < starts[:, None] + bsz)).reshape(-1)
                g = _LazyBlockGather(cols, astart, bsz, astart.shape[0] * bsz)
                return valid, rows.reshape(-1), g

            def blocks_mask(cols, boxes, windows, rparams, block_ids):
                valid, rows, g = expand_blocks(cols, block_ids)
                m = mask_fn(g, boxes, windows, rparams, residual_fn) & valid
                return m, rows, g

            if mode == "count_blocks":
                def run(cols, boxes, windows, rparams, block_ids):
                    m, _, _ = blocks_mask(cols, boxes, windows, rparams, block_ids)
                    return jnp.sum(m)
            elif mode == "count_multi_blocks":
                # batched serving: B independent box-queries against the
                # UNION of their candidate blocks in one dispatch — the
                # gather happens once, then each box is a cheap mask over
                # the resident candidates. Per-query cost collapses to
                # microseconds (the per-dispatch RPC overhead amortizes
                # across the whole batch). The per-box scans run through
                # lax.map with a small vmapped batch_size: loop machinery
                # costs ~0.4ms/iteration on the CPU backend (a fixed ~28ms
                # floor for a 64-query batch regardless of scan size), so
                # chunking 8 boxes per iteration cuts that 8x while keeping
                # the materialized pairwise mask bounded to 8 columns (the
                # full (rows, B) matrix measured SLOWER — broadcast
                # intermediates blow the cache).
                def run(cols, boxes, windows, rparams, block_ids):
                    valid, _, g = expand_blocks(cols, block_ids)
                    base = valid
                    if has_time:
                        base = base & _time_mask(g, windows)
                    if residual_fn is not None:
                        base = base & residual_fn(g, rparams)
                    if "__valid__" in g:
                        base = base & g["__valid__"]
                    # materialize the primary's columns OUTSIDE the mapped
                    # body: the lazy gather caches per column, and a first
                    # touch inside the scan would leak a traced value
                    for k in _PRIMARY_COLS[primary_kind]:
                        g[k]

                    def one(b):
                        return jnp.sum(
                            PRIMARY_FNS[primary_kind](g, b[None, :]) & base)

                    from jax import lax
                    return lax.map(one, boxes,
                                   batch_size=min(8, boxes.shape[0]))
            elif mode == "topk_blocks":
                # pruned KNN: top_k over gathered candidate blocks only.
                # lax.top_k lowers to a full sort of its operand on TPU, so
                # shrinking the operand from N rows to nb*block_size is the
                # entire win (~N/(nb*B) factor); the host drives the radius
                # bound so the candidate set provably contains the true k
                # nearest (guarantee re-check in process/knn.py).
                m_cap = capacity[3]

                def run(cols, boxes, windows, rparams, q, block_ids):
                    m, rowids, g = blocks_mask(cols, boxes, windows, rparams,
                                               block_ids)
                    d = _haversine_f32(g["xf"], g["yf"], q[0], q[1])
                    d = jnp.where(m, d, jnp.inf)
                    vals, idxs = jax.lax.top_k(-d, m_cap)
                    sel = rowids[jnp.clip(idxs, 0, rowids.shape[0] - 1)]
                    return -vals, sel.astype(jnp.int32)
            elif mode == "intersects_band_blocks":
                # exact extent × polygon intersects over the candidate
                # blocks' spans of the segment pool, in f32 with certainty
                # bands: [certain hits, uncertain ways, candidate ways,
                # uncertain row ids...]; the host refines only the uncertain
                # sliver in exact f64 (geom_batch)
                unc_cap, _, ntiles = capacity[3:]

                def run(cols, boxes, windows, rparams, edges, n_edges,
                        block_ids, tile_starts, flat_delta):
                    m, rowids, g = blocks_mask(cols, boxes, windows, rparams,
                                               block_ids)
                    seg = _slices(cols[SEG], tile_starts, POOL_TILE)
                    flags = _classify_segments(
                        *(seg[:, k] for k in range(4)), edges, n_edges)
                    # by way: a way's segments are one span of the flat
                    # tiles, at its pool offset plus its block's shift
                    way = _slices(cols[WAY], g._starts, bsz)
                    seg_n, kind = (way[:, k].reshape(-1) for k in (1, 2))
                    p0 = way[:, 0].reshape(-1) + jnp.repeat(flat_delta, bsz)
                    hits, pair_open, outside = _span_sums(
                        flags, p0, p0 + seg_n)
                    hit = m & (hits > 0)
                    # no pair left open: the way's rings cross nothing, so
                    # each part lies on one side, and a vertex surely
                    # outside settles its part (of a way of several parts,
                    # every vertex). An areal way may still hold the whole
                    # polygon: one that could, by its envelope, stays open
                    holds = ((kind & 1) > 0) \
                        & (g["bxmin"] - _BOX_GAP <= edges[0, 0]) \
                        & (g["bxmax"] + _BOX_GAP >= edges[0, 0]) \
                        & (g["bymin"] - _BOX_GAP <= edges[0, 1]) \
                        & (g["bymax"] + _BOX_GAP >= edges[0, 1])
                    miss = (pair_open == 0) & ~holds & jnp.where(
                        (kind & 2) > 0, outside == seg_n, outside > 0)
                    unc = m & ~hit & ~miss
                    total = m.shape[0]
                    sel = _first_set(unc.reshape(-1, bsz), unc_cap)
                    rows = jnp.where(sel < total,
                                     rowids[jnp.clip(sel, 0, total - 1)], n)
                    return jnp.concatenate([
                        jnp.stack([jnp.sum(hit), jnp.sum(unc),
                                   jnp.sum(m)]).astype(jnp.int32),
                        rows.astype(jnp.int32)])
            elif mode == "join_band_blocks":
                # grouped point-in-polygon: the candidate blocks' points
                # against every polygon slab the gate paired them with, in
                # one launch. A block's rows are sorted by y and cut into
                # tiles of JOIN_TILE (y-narrow, so a tile meets few of a
                # polygon's edges); a pair is a tile against the segments of
                # one polygon, read from ``seg`` (the polygon type's
                # y-sorted twin of its pool), that can meet the tile's
                # y-range. Pairs come sorted by the bucket their segment
                # count falls in (``JOIN_WIDTHS``; ``n_pairs``: a bucket's
                # first pair and its count; padded to whole turns); a bucket
                # runs JOIN_STEP_EDGES / width pairs a turn for as many
                # turns as it has pairs, so the shapes are the tier's and
                # the work the request's.
                # Out: per pair [points surely inside, points uncertain,
                # (low 16 bits, high bits) of each stat's sum over the
                # former], the rows of a tile the filter passed, the
                # uncertain (pair, point) couples compacted to ``unc_cap``
                # (their pairs, then their rows), and the time
                # band: how many rows pass the plan's windows and not the
                # ``strict`` ones (a window's end inside an offset unit),
                # and the first JOIN_TIME_CAP of them. Those are in no
                # tile's count: the host has their milliseconds.
                unc_cap, pair_cap, stat_cols = capacity[3:]
                k_out = 2 + 2 * len(stat_cols)
                tsz = min(JOIN_TILE, bsz)
                wide = -(-bsz // tsz) * tsz      # a block padded to tiles

                def run(cols, boxes, windows, rparams, strict, seg, box,
                        perm, block_ids, pairs, n_pairs):
                    from jax import lax
                    valid, rowids, g = expand_blocks(cols, block_ids)
                    # the rows the host gets are the table's where the
                    # index keeps its permutation on the device: a gather
                    # here, not a round trip behind the next join's launch
                    at_table = (lambda r: r) if perm is None else (
                        lambda r: perm[jnp.clip(r, 0, n - 1)])
                    loose = mask_fn(g, boxes, windows, rparams,
                                    residual_fn) & valid
                    m = loose & _time_mask(g, strict) if has_time else loose
                    band = (loose & ~m).reshape(nblk, bsz)
                    box = jnp.stack(box)
                    blocked = lambda a, fill: jnp.pad(
                        a.reshape(nblk, bsz), ((0, 0), (0, wide - bsz)),
                        constant_values=fill)
                    # rows that are none of the block's sort last; a row
                    # the filter dropped keeps its place, marked
                    y = blocked(jnp.where(valid, g["yf"], jnp.inf), jnp.inf)
                    marked = jnp.where(m, rowids, -1 - rowids)
                    py, px, marked, *vals = _sort_tiles(
                        y, blocked(g["xf"], 0.0), blocked(marked, -1),
                        *(blocked(g[k], 0) for k in stat_cols))
                    py, px, marked = (a.reshape(-1, tsz)
                                      for a in (py, px, marked))
                    vals = [v.reshape(-1, tsz) for v in vals]
                    passed = marked >= 0
                    out = jnp.zeros((pair_cap, k_out), jnp.int32)
                    open_ = jnp.zeros((pair_cap, tsz), bool)
                    for b, width in enumerate(JOIN_WIDTHS):
                        group = JOIN_STEP_EDGES // width
                        first, count = n_pairs[b, 0], n_pairs[b, 1]

                        def step(i, carry, width=width, group=group,
                                 first=first):
                            out, open_ = carry
                            at = first + i * group
                            pr = lax.dynamic_slice(pairs, (at, 0), (group, 5))
                            f = _classify_pairs(px, py, passed, seg, box, pr,
                                                width)
                            inside = (f & 1) == 1
                            rows = [jnp.sum(inside, axis=1),
                                    jnp.sum(f >> 1, axis=1)]
                            for v in vals:
                                v = jnp.where(inside, v[pr[:, 0]], 0)
                                rows += [jnp.sum(v & 0xFFFF, axis=1),
                                         jnp.sum(v >> 16, axis=1)]
                            return (lax.dynamic_update_slice(
                                        out, jnp.stack(rows, axis=1)
                                        .astype(jnp.int32), (at, 0)),
                                    lax.dynamic_update_slice(
                                        open_, (f >> 1) == 1, (at, 0)))

                        out, open_ = lax.fori_loop(
                            0, -(-count // group), step, (out, open_))
                    # the j-th uncertain couple: the pair whose running
                    # count of them first reaches j + 1, and the point of
                    # its tile whose own does
                    upto = jnp.cumsum(out[:, 1])
                    j = jnp.arange(unc_cap, dtype=jnp.int32)
                    at = jnp.clip(jnp.searchsorted(upto, j + 1, side="left"),
                                  0, pair_cap - 1)
                    nth = j - (upto[at] - out[at, 1])
                    lane = jnp.argmax(jnp.cumsum(
                        open_[at].astype(jnp.int32), axis=1)
                        == nth[:, None] + 1, axis=1)
                    row = marked[pairs[at, 0], lane]
                    late = _first_set(band, JOIN_TIME_CAP)
                    return jnp.concatenate([
                        out.reshape(-1),
                        jnp.sum(passed, axis=1).astype(jnp.int32),
                        at.astype(jnp.int32),
                        at_table(row).astype(jnp.int32),
                        jnp.sum(band)[None].astype(jnp.int32),
                        at_table(rowids[jnp.clip(
                            late, 0, nblk * bsz - 1)]).astype(jnp.int32)])
            elif mode == "density_blocks":
                # pruned heat-map: candidate blocks gather (contiguous HBM
                # bursts) + masked scatter of only nb*block_size rows
                width, height, wname = capacity[3:]

                def run(cols, boxes, windows, rparams, grid, block_ids):
                    m, _, g = blocks_mask(cols, boxes, windows, rparams, block_ids)
                    w = g[wname].astype(jnp.float32) if wname else None
                    out = _grid_scatter(g["xf"], g["yf"], m, w, grid,
                                        width, height)
                    return out, jnp.sum(m)
            else:
                def run(cols, boxes, windows, rparams, block_ids):
                    m, rowids, _ = blocks_mask(cols, boxes, windows, rparams, block_ids)
                    total = m.shape[0]
                    sel = jnp.nonzero(m, size=sel_cap, fill_value=total)[0]
                    rows = jnp.where(sel < total,
                                     rowids[jnp.clip(sel, 0, total - 1)], n)
                    return jnp.concatenate([
                        jnp.sum(m)[None].astype(jnp.int32),
                        rows.astype(jnp.int32)])
        elif mode == "topk":
            # device KNN: haversine distance + lax.top_k as ONE fused
            # reduction over the table (the reference's expanding-radius
            # iteration — KNearestNeighborSearchProcess — exists because
            # storage scans price by range; a TPU prices by full-array
            # reductions, so the whole search is a single kernel + one small
            # readback). Distances are f32; callers re-rank the top-m margin
            # exactly on host (m >= 2k makes f32 rank noise harmless).
            m_cap = capacity

            def run(cols, boxes, windows, rparams, q):
                m = mask_fn(cols, boxes, windows, rparams, residual_fn)
                d = _haversine_f32(cols["xf"], cols["yf"], q[0], q[1])
                d = jnp.where(m, d, jnp.inf)
                vals, idxs = jax.lax.top_k(-d, m_cap)
                return -vals, idxs.astype(jnp.int32)
        elif mode == "select_packed":
            # single-roundtrip select: [count, idx...] in ONE int32 array so
            # the host pays a single device-fetch latency (transfers/dispatch
            # are async; only result syncs block).
            n = next(iter(self.cols.values())).shape[0]

            def run(cols, boxes, windows, rparams):
                m = mask_fn(cols, boxes, windows, rparams, residual_fn)
                idx = jnp.nonzero(m, size=capacity, fill_value=n)[0]
                return jnp.concatenate([
                    jnp.sum(m)[None].astype(jnp.int32), idx.astype(jnp.int32)])
        else:
            raise ValueError(mode)

        kid = f"{mode}.{primary_kind}"
        # the XLA module (and every `jit_…` line of a profile) carries the
        # kernel id, not `jit_run`
        run.__name__ = program_name(kid)
        jitted = jax.jit(run)
        if _prof.enabled():
            # recompile detection: a second distinct signature for this
            # kernel id (or a re-jit of an evicted one) is shape churn —
            # counted + flight-evented with the triggering shape. The
            # probe then times the first invocation's XLA compile and
            # captures the kernel's cost analysis (flops/bytes gauges).
            _prof.note_signature(self._sig_seen, kid, key, shape={
                "mode": mode, "primary": primary_kind,
                "residual": residual_key, "n_boxes": n_boxes,
                "n_windows": n_windows, "capacity": repr(capacity)})
            jitted = _prof.kernel_probe(jitted, kid, n_boxes)
        elif _attrib.enabled():
            # per-(kernel, tier) compile attribution: the first invocation
            # is where XLA traces + compiles, and that cost lands on the
            # kernel's labeled series instead of vanishing into one query
            jitted = _attrib.compile_probe(jitted, kid, n_boxes)
        self._jitted[key] = jitted
        from geomesa_tpu import config
        # NB fresh name: the mode closures above capture _get locals (cap,
        # width, …) late — rebinding them here would rewrite the kernel
        lru_cap = max(1, config.KERNEL_CACHE.get())
        while len(self._jitted) > lru_cap:
            self._jitted.popitem(last=False)
        return jitted

    # public API ------------------------------------------------------------

    def count(self, primary_kind, boxes, windows, residual) -> int:
        fn = self._get("count", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0])
        with _attrib.kernel(f"count.{primary_kind}"):
            return int(_fetch(
                fn, self.cols, _dev(boxes), _dev(windows),
                [jnp.asarray(p) for p in residual[1]] if residual else []))

    def mask(self, primary_kind, boxes, windows, residual) -> jnp.ndarray:
        fn = self._get("mask", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0])
        with _trace.span("device_scan"):  # async: consumers block later
            return fn(self.cols, _dev(boxes), _dev(windows),
                      [jnp.asarray(p) for p in residual[1]] if residual else [])

    def count_at(self, primary_kind, boxes, windows, residual,
                 positions: np.ndarray) -> int:
        """Count over candidate positions only (attribute-index pruning)."""
        idxs, nvalid = _pad_positions(positions)
        fn = self._get("count_at", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0],
                       idxs.shape[0])
        return int(_fetch(
            fn, self.cols, _dev(boxes), _dev(windows),
            [jnp.asarray(p) for p in residual[1]] if residual else [],
            jnp.asarray(idxs), nvalid))

    def select_at(self, primary_kind, boxes, windows, residual,
                  positions: np.ndarray):
        """Surviving positions (subset of ``positions``) + count."""
        idxs, nvalid = _pad_positions(positions)
        fn = self._get("select_at", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0],
                       idxs.shape[0])
        out = np.asarray(_fetch(
            fn, self.cols, _dev(boxes), _dev(windows),
            [jnp.asarray(p) for p in residual[1]] if residual else [],
            jnp.asarray(idxs), nvalid))
        cnt = int(out[0])
        sel = out[1: 1 + cnt].astype(np.int64)
        return positions[sel], cnt

    def prepare_counts_multi(self, primary_kind, boxes: np.ndarray, windows,
                             residual):
        """Zero-arg async dispatcher → per-box count device array over the
        FULL table (the batched serving path when range pruning declined).
        B pads to a power of two (EMPTY_BOX rows count zero) to share
        compilations; callers slice the readback to len(boxes)."""
        b = pad_boxes(boxes)
        fn = self._get("count_multi", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       b.shape[0],
                       0 if windows is None else windows.shape[0])
        cols = self.cols
        db, w = _dev(b), _dev(windows)
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        return lambda: fn(cols, db, w, rp)

    def counts_multi(self, primary_kind, boxes: np.ndarray, windows,
                     residual) -> np.ndarray:
        """Per-box counts for a (B, 8) box array: one upload, one kernel,
        one readback — B counts for the price of one round trip."""
        tier = max(1, 1 << max(0, (len(boxes) - 1)).bit_length())
        with _attrib.kernel(f"count_multi.{primary_kind}", tier):
            out = np.asarray(_fetch(self.prepare_counts_multi(
                primary_kind, boxes, windows, residual)))
        return out[: len(boxes)]

    def prepare_count(self, primary_kind, boxes, windows, residual):
        """Zero-arg async count dispatcher with all constants pre-staged on
        device. Repeated dispatches pay no host→device transfer and no
        re-planning; the returned device scalar syncs only when the caller
        reads it (prepared-statement pattern: dispatches pipeline, one
        readback blocks)."""
        fn = self._get("count", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0])
        cols = self.cols
        b, w = _dev(boxes), _dev(windows)
        ROUNDS.uploads += len(residual[1]) if residual else 0
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        return lambda: fn(cols, b, w, rp)

    def prepare_mask(self, primary_kind, boxes, windows, residual):
        """Zero-arg async mask dispatcher (device constants pre-staged)."""
        fn = self._get("mask", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0])
        cols = self.cols
        b, w = _dev(boxes), _dev(windows)
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        return lambda: fn(cols, b, w, rp)

    def _pad_blocks(self, blocks: np.ndarray) -> np.ndarray:
        out = np.full(blocks_tier(len(blocks)), -1, dtype=np.int32)
        out[: len(blocks)] = blocks
        return out

    def count_blocks(self, primary_kind, boxes, windows, residual,
                     blocks: np.ndarray, block_size: int) -> int:
        """Exact count scanning only the candidate blocks (range-pruned)."""
        with _attrib.kernel(f"count_blocks.{primary_kind}"):
            return int(_fetch(self.prepare_count_blocks(
                primary_kind, boxes, windows, residual, blocks, block_size)))

    def prepare_count_blocks(self, primary_kind, boxes, windows, residual,
                             blocks: np.ndarray, block_size: int):
        """Zero-arg async pruned-count dispatcher (constants + block ids
        staged on device once)."""
        b = self._pad_blocks(blocks)
        fn = self._get("count_blocks", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0],
                       (b.shape[0], block_size, 0))
        cols = self.cols
        bx, w = _dev(boxes), _dev(windows)
        ROUNDS.uploads += 1 + (len(residual[1]) if residual else 0)
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        db = jnp.asarray(b)
        return lambda: fn(cols, bx, w, rp, db)

    def select_blocks(self, primary_kind, boxes, windows, residual,
                      blocks: np.ndarray, block_size: int, capacity: int):
        """(sorted-row indices, true count) scanning only candidate blocks.
        Grows capacity and retries on overflow like ``select``."""
        b = self._pad_blocks(blocks)
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        capacity = min(max(1024, capacity), b.shape[0] * block_size)
        while True:
            fn = self._get("select_blocks", primary_kind, windows is not None,
                           residual[0] if residual else "none",
                           residual[2] if residual else None,
                           0 if boxes is None else boxes.shape[0],
                           0 if windows is None else windows.shape[0],
                           (b.shape[0], block_size, capacity))
            out = np.asarray(_fetch(fn, self.cols, _dev(boxes),
                                    _dev(windows), rp, jnp.asarray(b)))
            cnt = int(out[0])
            if cnt <= capacity:
                return out[1: 1 + cnt].astype(np.int64), cnt
            capacity = 1 << int(np.ceil(np.log2(cnt)))

    def prepare_counts_multi_blocks(self, primary_kind, boxes: np.ndarray,
                                    windows, residual, blocks: np.ndarray,
                                    block_size: int):
        """Zero-arg async dispatcher → per-box count device array for a
        whole batch of box-queries over their union candidate blocks (the
        batched serving path — per-query device cost is microseconds once
        the per-dispatch overhead amortizes; pipeline several batches to
        amortize the round trip too)."""
        b = self._pad_blocks(blocks)
        bx = pad_boxes(boxes)
        fn = self._get("count_multi_blocks", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       bx.shape[0],
                       0 if windows is None else windows.shape[0],
                       (b.shape[0], block_size, 0))
        cols = self.cols
        dbx, w = _dev(bx), _dev(windows)
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        db = jnp.asarray(b)
        return lambda: fn(cols, dbx, w, rp, db)

    def counts_multi_blocks(self, primary_kind, boxes: np.ndarray, windows,
                            residual, blocks: np.ndarray,
                            block_size: int) -> np.ndarray:
        """Blocking counterpart of ``prepare_counts_multi_blocks``."""
        tier = max(1, 1 << max(0, (len(boxes) - 1)).bit_length())
        with _attrib.kernel(f"count_multi_blocks.{primary_kind}", tier):
            out = np.asarray(_fetch(self.prepare_counts_multi_blocks(
                primary_kind, boxes, windows, residual, blocks, block_size)))
        return out[: len(boxes)]

    def prepare_density_compact(self, primary_kind, boxes, windows, residual,
                                grid_bbox, width: int, height: int,
                                cap: int, wname: Optional[str]):
        """Zero-arg dispatcher → ((H, W) grid device array, count scalar).
        ``cap`` must be >= the match count (size it from a count query)."""
        fn = self._get("density_compact", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0],
                       (cap, width, height, wname))
        cols = self.cols
        bx, w = _dev(boxes), _dev(windows)
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        g = jnp.asarray(np.asarray(grid_bbox, dtype=np.float32))
        return lambda: fn(cols, bx, w, rp, g)

    def prepare_density_blocks(self, primary_kind, boxes, windows, residual,
                               grid_bbox, width: int, height: int,
                               blocks: np.ndarray, block_size: int,
                               wname: Optional[str]):
        """Zero-arg dispatcher for the range-pruned heat-map."""
        b = self._pad_blocks(blocks)
        fn = self._get("density_blocks", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0],
                       (b.shape[0], block_size, 0, width, height, wname))
        cols = self.cols
        bx, w = _dev(boxes), _dev(windows)
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        g = jnp.asarray(np.asarray(grid_bbox, dtype=np.float32))
        db = jnp.asarray(b)
        return lambda: fn(cols, bx, w, rp, g, db)

    # polygon-edge pad of the fused refine (index/compiled.py): far-away
    # horizontal edges (ey1 == ey2 → no crossing; orientation signs large
    # and same → certain-miss) so padded lanes never create hits or
    # uncertainty
    _EDGE_PAD = np.array([1e9, 1e9, 2e9, 1e9], dtype=np.float32)

    def intersects_band_blocks(self, primary_kind, boxes, windows, residual,
                               edges: np.ndarray, blocks: np.ndarray,
                               block_size: int, seg_off: np.ndarray,
                               unc_cap: int = 16384):
        """(certain_hit_count, uncertain_row_positions, facts) for exact
        extent × polygon intersects over candidate blocks. A block's ways
        are one span of the segment pool (``seg_off``: the host's copy of
        the ways' offsets into it, one more than ways); the spans are read
        in tiles of ``POOL_TILE`` segments, their number padded to a tier as
        the blocks' is, and a way longer than a tile simply runs on into the
        next. The uncertain positions (ways within the f32 certainty band of
        the boundary) need the host's exact f64 refine; None for the
        positions when they overflowed ``unc_cap`` (caller falls back to the
        full host refine). Long block lists go out as several launches
        (``band_launches``), one after another: each has its temporaries to
        itself. ``facts``: ``candidate_ways`` (passed the envelope mask),
        ``uncertain_ways``, and the pool ``segments`` the spans hold."""
        # the edges ride in an array padded to a tier and the loop runs over
        # the real ones: a tier is one program, so polygons of up to
        # BAND_MIN_EDGES edges (a drawn area, a district) share one
        ne = max(BAND_MIN_EDGES, 1 << max(0, (len(edges) - 1)).bit_length())
        ep = np.zeros((ne, 4), dtype=np.float32)
        ep[: len(edges)] = edges
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        dbx, dw, dep = _dev(boxes), _dev(windows), jnp.asarray(ep)
        n_edges = np.int32(len(edges))
        n = len(seg_off) - 1
        facts = {"segments": 0}
        outs = []
        for chunk, starts, delta, n_seg, tiers in band_launches(
                blocks, block_size, seg_off, n):
            b = np.full(tiers[0], -1, dtype=np.int32)
            b[: len(chunk)] = chunk
            tiles = np.zeros(tiers[1], dtype=np.int32)
            tiles[: len(starts)] = starts
            shift = np.zeros(len(b), dtype=np.int32)
            shift[: len(delta)] = delta
            fn = self._get("intersects_band_blocks", primary_kind,
                           windows is not None,
                           residual[0] if residual else "none",
                           residual[2] if residual else None,
                           0 if boxes is None else boxes.shape[0],
                           0 if windows is None else windows.shape[0],
                           (len(b), block_size, 0, unc_cap, ne, len(tiles)))
            with _attrib.kernel(f"intersects_band_blocks.{primary_kind}",
                                len(b)):
                outs.append(np.asarray(_fetch(
                    fn, self.cols, dbx, dw, rp, dep, n_edges,
                    jnp.asarray(b), jnp.asarray(tiles), jnp.asarray(shift))))
            facts["segments"] += n_seg
        certain = sum(int(o[0]) for o in outs)
        facts["candidate_ways"] = sum(int(o[2]) for o in outs)
        facts["uncertain_ways"] = sum(int(o[1]) for o in outs)
        if any(int(o[1]) > unc_cap for o in outs):
            return certain, None, facts
        return certain, np.concatenate(
            [o[3: 3 + int(o[1])] for o in outs]).astype(np.int64), facts

    def join_band_blocks(self, primary_kind, boxes, windows, residual,
                         strict, seg, box, perm, blocks: np.ndarray,
                         block_size: int, pairs: np.ndarray,
                         stat_cols: tuple):
        """The grouped point-in-polygon join over candidate ``blocks``
        (sorted unique ids) and the gate's ``pairs`` (k, 5) [tile, first
        chunk, first segment in it, past the last, polygon row]: a tile is
        ``JOIN_TILE`` rows of a block in ascending y, counted through
        ``blocks`` (tile t of the j-th block is j * tiles-a-block + t); the
        segments are a span of ``seg``, the polygon type's y-sorted segment
        plane, that ends within ``POOL_TILE`` of its first chunk; ``box``: that type's four f32 envelope planes. ``strict``: the
        plan's windows cut to whole offset units (``time_windows``); a row
        between the two is in no pair's numbers. ``perm``: the index's
        permutation on the device, or None where the host has it; with it
        the rows returned are the table's, without it this index's. One
        launch for up to ``JOIN_MAX_BLOCKS`` blocks, whatever the number of
        polygons.

        Returns (inside, open, sums, rows, uncertain, late, launches): per
        pair the points surely inside and the points the f32 band left open,
        (stats, pairs) int64 sums of ``stat_cols`` over the former (widened
        here from the device's 16-bit halves), per pair the rows of its tile
        that the filter passed, the open couples as an (u, 2) array [pair,
        row], or None where a launch's exceeded
        ``JOIN_UNC_CAP``, and the rows between the two sets of windows, or
        None where a launch's exceeded ``JOIN_TIME_CAP``."""
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        dbx, dw, ds = _dev(boxes), _dev(windows), _dev(strict)
        k_out = 2 + 2 * len(stat_cols)
        tsz = min(JOIN_TILE, block_size)
        per = -(-block_size // tsz)
        out = np.zeros((len(pairs), k_out), dtype=np.int64)
        rows = np.zeros(len(pairs), dtype=np.int64)
        bucket = np.searchsorted(JOIN_WIDTHS, pairs[:, 3])
        group = JOIN_STEP_EDGES // np.asarray(JOIN_WIDTHS)
        uncertain, late, launches = [], [], 0
        for lo in range(0, len(blocks), JOIN_MAX_BLOCKS):
            chunk = blocks[lo: lo + JOIN_MAX_BLOCKS]
            mine = np.flatnonzero((pairs[:, 0] >= lo * per)
                                  & (pairs[:, 0] < (lo + len(chunk)) * per))
            mine = mine[np.argsort(bucket[mine], kind="stable")]
            # a bucket's pairs, then pads up to a whole turn of its loop
            count = np.bincount(bucket[mine], minlength=len(group))
            room = -(-count // group) * group
            first = np.cumsum(room) - room
            at = np.arange(len(mine)) + np.repeat(
                first - (np.cumsum(count) - count), count)
            nb = join_tier(len(chunk), 64, 4)
            cap = join_tier(int(room.sum()), 1 << 14, 16)
            ids = np.full(nb, -1, dtype=np.int32)
            ids[: len(chunk)] = chunk
            sent = np.zeros((cap, 5), dtype=np.int32)
            sent[at] = pairs[mine]
            sent[at, 0] -= lo * per
            spans = np.stack([first, count], axis=1).astype(np.int32)
            fn = self._get("join_band_blocks", primary_kind,
                           windows is not None,
                           residual[0] if residual else "none",
                           residual[2] if residual else None,
                           0 if boxes is None else boxes.shape[0],
                           0 if windows is None else windows.shape[0],
                           (nb, block_size, 0, JOIN_UNC_CAP, cap,
                            tuple(stat_cols)))
            with _attrib.kernel(f"join_band_blocks.{primary_kind}", nb):
                got = np.asarray(_fetch(
                    fn, self.cols, dbx, dw, rp, ds, seg, box, perm,
                    jnp.asarray(ids),
                    jnp.asarray(sent), jnp.asarray(spans)))
            launches += 1
            body = cap * k_out
            res = got[:body].reshape(cap, k_out)[at]
            out[mine] = res
            rows[mine] = got[body:][sent[at, 0]]
            tail = got[body + nb * per + 2 * JOIN_UNC_CAP:]
            if late is None or int(tail[0]) > JOIN_TIME_CAP:
                late = None
            else:
                late.append(tail[1: 1 + int(tail[0])].astype(np.int64))
            if uncertain is None or int(res[:, 1].sum()) > JOIN_UNC_CAP:
                uncertain = None
                continue
            n_open = int(res[:, 1].sum())
            back = np.empty(cap, dtype=np.int64)    # a sent pair's own
            back[at] = mine
            pair = got[body + nb * per:][: n_open]
            row = got[body + nb * per + JOIN_UNC_CAP:][: n_open].astype(
                np.int64)
            uncertain.append(np.stack([back[pair], row], axis=1))
        sums = (out[:, 3::2] << 16) + out[:, 2::2]
        if uncertain is not None:
            uncertain = np.concatenate(uncertain) if uncertain \
                else np.empty((0, 2), dtype=np.int64)
        if late is not None:
            late = np.concatenate(late) if late \
                else np.empty(0, dtype=np.int64)
        return out[:, 0], out[:, 1], sums.T, rows, uncertain, late, launches

    def topk_nearest_blocks(self, primary_kind, boxes, windows, residual,
                            qx: float, qy: float, m: int,
                            blocks: np.ndarray, block_size: int):
        """Pruned variant of ``topk_nearest``: distances + top_k over the
        candidate blocks only."""
        b = self._pad_blocks(blocks)
        m = min(m, b.shape[0] * block_size)
        fn = self._get("topk_blocks", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0],
                       (b.shape[0], block_size, 0, m))
        q = jnp.asarray(np.array([qx, qy], dtype=np.float32))
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        with _attrib.kernel(f"topk_blocks.{primary_kind}", b.shape[0]):
            vals, idxs = _fetch(fn, self.cols, _dev(boxes), _dev(windows),
                                rp, q, jnp.asarray(b))
        return np.asarray(vals), np.asarray(idxs)

    def topk_nearest(self, primary_kind, boxes, windows, residual,
                     qx: float, qy: float, m: int):
        """(distances_m f32, sorted-order positions int32) of the m nearest
        masked rows to (qx, qy) — one kernel, one small readback. Distances
        are +inf past the number of matching rows."""
        fn = self._get("topk", primary_kind, windows is not None,
                       residual[0] if residual else "none",
                       residual[2] if residual else None,
                       0 if boxes is None else boxes.shape[0],
                       0 if windows is None else windows.shape[0], m)
        q = jnp.asarray(np.array([qx, qy], dtype=np.float32))
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        with _attrib.kernel(f"topk.{primary_kind}", m):
            vals, idxs = _fetch(fn, self.cols, _dev(boxes), _dev(windows),
                                rp, q)
        return np.asarray(vals), np.asarray(idxs)

    def select(self, primary_kind, boxes, windows, residual, capacity: int):
        """Returns (sorted-row indices ndarray, true_count) in one roundtrip.
        Grows capacity and retries on overflow (fixed-capacity +
        overflow-retry per SURVEY.md §7 hard-parts)."""
        rp = [jnp.asarray(p) for p in residual[1]] if residual else []
        while True:
            fn = self._get("select_packed", primary_kind, windows is not None,
                           residual[0] if residual else "none",
                           residual[2] if residual else None,
                           0 if boxes is None else boxes.shape[0],
                           0 if windows is None else windows.shape[0],
                           capacity)
            out = np.asarray(_fetch(fn, self.cols, _dev(boxes),
                                    _dev(windows), rp))
            cnt = int(out[0])
            if cnt <= capacity:
                return out[1: 1 + cnt].astype(np.int64), cnt
            capacity = 1 << int(np.ceil(np.log2(cnt)))


def _dev(a):
    if a is None:
        return None
    ROUNDS.uploads += 1
    return jnp.asarray(a)


def _pad_positions(positions: np.ndarray):
    """Pad a candidate-position array to the next power of two (shared jit
    signatures across queries); padding rows point at row 0 and are masked
    off by the valid-length compare."""
    n = len(positions)
    cap = max(8, 1 << max(0, (n - 1)).bit_length())
    out = np.zeros(cap, dtype=np.int32)
    out[:n] = positions
    return out, np.int32(n)


# -- padding helpers --------------------------------------------------------

_I31MAX = (1 << 31) - 1
# fp62 empty box: lo bound = +max, hi bound = 0 — matches nothing
EMPTY_BOX = np.array([_I31MAX, _I31MAX, 0, 0, _I31MAX, _I31MAX, 0, 0], dtype=np.int32)
EMPTY_WINDOW = np.array([1, 0, 0, 0], dtype=np.int32)    # bin_lo > bin_hi


def program_name(kernel_id: str) -> str:
    """A kernel id as a function name XLA accepts in a module name:
    ``count_multi_blocks.point_boxes`` → ``count_multi_blocks_point_boxes``."""
    return re.sub(r"\W", "_", kernel_id)


def blocks_tier(n_blocks: int) -> int:
    """Padded length of a candidate-block list: one compiled program per
    tier, so this is the shape half of a pruned kernel's identity."""
    return max(8, 1 << max(0, (n_blocks - 1)).bit_length())


# the smallest tier a polygon's edges are padded to
BAND_MIN_EDGES = 16

# candidate blocks one launch of the banded refine takes: bounds its
# temporaries (some 50 B a segment) and the tiers it can meet
BAND_MAX_BLOCKS = 256


# the grouped join (``join_band_blocks``). The rows of a gather block, sorted
# by y, are cut into tiles of JOIN_TILE: a tile of a Z-ordered block spans
# tens of degrees of x and under one of y, so of a polygon of 1,000 edges it
# can meet the ~100 whose y-range reaches into its own, and those are one
# span of the polygon's segments sorted by their lower end (``SEGY``). A
# pair reads the bucket of JOIN_WIDTHS at or over its span (``POOL_TILE`` at
# most: the pool's pad); a turn of the kernel's loop takes JOIN_STEP_EDGES /
# width pairs. A bucket's time follows the chunks it gathers as much as its
# tests (v5e): buckets of 16, 32 and 48 read from a chunk of 64 took the
# launch 1.06 times as long as running those pairs at 64, so the widths are
# whole chunks in steps of x1.5 (x2 at the first), and a pair runs at most
# 1.5 times what it reads, not 2 times as on powers of two
JOIN_TILE = 128
JOIN_WIDTHS = (64, 128, 192, 256, 384, 512, 768, 1024, 1536, 2048, 3072,
               4096)
JOIN_STEP_EDGES = 1 << 16
JOIN_MAX_BLOCKS = 1 << 10    # blocks a launch: 4.2M rows
JOIN_UNC_CAP = 1 << 15       # uncertain (point, polygon) couples a launch
JOIN_TIME_CAP = 1 << 12      # rows of the windows' boundary units a launch


def join_tier(n: int, floor: int, step: int) -> int:
    """Padded length of a join launch's block list (64, 256, 1,024) and of
    its pair list (16,384, 262,144, ...): ``floor`` times a power of
    ``step``, wide enough apart that the 1.0-3.3M rows of a week or two of
    events, and their 45,000-150,000 pairs with 256 polygons, are one
    program."""
    tier = floor
    while tier < n:
        tier *= step
    return tier



def band_launches(blocks: np.ndarray, block_size: int, seg_off: np.ndarray,
                  n: int):
    """Sorted unique candidate ``blocks`` cut into the banded refine's
    launches: (blocks, tile starts, shifts, segments, (block tier, tile
    tier)) each. The two tiers name one compiled program, and a pair that
    traffic rarely forms is first compiled in the middle of the load (1.3 s
    behind which every single waits: PR 27's builder, on the chip). So past
    smallest block tier the tile tier follows from the block tier: ``per``
    tiles a block, the layer's own mean rounded up to a power of two. Spans
    that need more take a wider block tier, and past the widest launch are
    cut in two; only a lone block of very long ways keeps a tile tier of its
    own."""
    per = 1 << max(0, int(np.ceil(np.log2(max(
        1.0, int(seg_off[-1]) * block_size / max(1, n) / POOL_TILE)))))
    todo = [blocks[i: i + BAND_MAX_BLOCKS]
            for i in range(0, len(blocks), BAND_MAX_BLOCKS)]
    while todo:
        chunk = todo.pop(0)
        starts, delta, n_seg = pool_tiles(chunk, block_size, seg_off, n)
        bt, need = blocks_tier(len(chunk)), blocks_tier(len(starts))
        while bt * per < need and bt < BAND_MAX_BLOCKS:
            bt *= 2
        if bt * per < need and len(chunk) > 1:
            todo[:0] = [chunk[: len(chunk) // 2], chunk[len(chunk) // 2:]]
            continue
        tt = need if bt == blocks_tier(1) else max(need, bt * per)
        yield chunk, starts, delta, n_seg, (bt, tt)


def pool_tiles(blocks: np.ndarray, block_size: int, seg_off: np.ndarray,
               n: int):
    """The segment-pool tiles that hold the spans of sorted unique candidate
    ``blocks``: (tile starts into the pool, each block's shift from pool
    offset to flat tile position, segments the spans hold). Neighbouring
    blocks are one run of the pool and share tiles; a run's last tile runs on
    into segments of ways that are no candidates, which no span reads."""
    lo = seg_off[np.minimum(blocks.astype(np.int64) * block_size, n)]
    hi = seg_off[np.minimum((blocks.astype(np.int64) + 1) * block_size, n)]
    first = np.ones(len(blocks), dtype=bool)
    first[1:] = blocks[1:] != blocks[:-1] + 1
    run = np.cumsum(first) - 1
    run_lo = lo[first].astype(np.int64)
    run_hi = hi[np.append(np.flatnonzero(first)[1:] - 1, len(blocks) - 1)]
    tiles = -(-(run_hi - run_lo) // POOL_TILE)
    before = np.cumsum(tiles) - tiles
    starts = np.repeat(run_lo - before * POOL_TILE, tiles) \
        + np.arange(int(tiles.sum())) * POOL_TILE
    delta = (before * POOL_TILE - run_lo)[run]
    return (starts.astype(np.int32), delta.astype(np.int32),
            int((run_hi - run_lo).sum()))


def pad_boxes(boxes: np.ndarray, min_size: int = 1) -> np.ndarray:
    """Pad (B,8) int32 fp62 box array to the next power-of-two count."""
    b = max(min_size, len(boxes))
    size = 1 << (b - 1).bit_length()
    out = np.tile(EMPTY_BOX, (size, 1))
    if len(boxes):
        out[: len(boxes)] = boxes
    return out


def pad_windows(windows: np.ndarray, min_size: int = 1) -> np.ndarray:
    b = max(min_size, len(windows))
    size = 1 << (b - 1).bit_length()
    out = np.tile(EMPTY_WINDOW, (size, 1))
    if len(windows):
        out[: len(windows)] = windows
    return out
