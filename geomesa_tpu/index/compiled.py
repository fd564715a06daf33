"""Single-dispatch query compilation: one plan *shape* → ONE device program.

≙ the reference's server-side push-down taken to its limit: instead of the
host orchestrating plan → range-decompose → scan → refine as separate device
rounds (each paying one host↔device round trip, counted by
``scan._RoundLedger``), a qualifying plan shape compiles into a
single jitted program that does cover/block selection, the primary scan, the
lowered residual predicate, and the aggregate in ONE dispatch with ONE
host→device round trip.

Three layers:

1. **IR lowering** (``_lower_residual``): walks the filter-IR tree and emits
   the residual mask directly into the program, mirroring
   ``scan.compile_residual``'s structure-key grammar EXACTLY (the lowered key
   must reproduce the interpreted key, or we fall back) — but constants land
   in ONE packed int32 vector instead of a params list, so a whole query
   ships as a single warm-shaped transfer inside the dispatch.

2. **In-kernel cover selection**: per-block f32 coordinate (and time-bin)
   summaries live on device; the program gates blocks against the query's
   f32 envelope (slack-expanded superset — the exact fp62 mask re-applies to
   every gathered row) and counts the blocks that stay alive. ONE
   ``lax.switch`` (``_over_alive``, shared by every mode and by the union
   program) then picks, on the device, the first rung of a ladder of block
   capacities (``_ladder``: powers of two from ``_RUNG_FLOOR`` up to CAP,
   CAP = the table's blocks × ``PRUNE_MAX_FRACTION``) that holds the alive
   blocks, and gathers, masks and compacts that many blocks: the work
   follows the alive blocks, at most twice them, not CAP. Past CAP the last
   branch masks the full table *inside the same program*. The program is
   total (no host-visible overflow round trip for counts), still one
   dispatch and, the ladder being a function of CAP alone, still one compile
   a shape. ``n_alive`` comes back beside the result: ``_Program.fetch``
   adds it to the counter ``fused.blocks_alive``, the serving branch's
   blocks to ``fused.blocks_gathered`` and the rung to ``STATS``.

3. **Shape-keyed caching + recipe fast path**: programs key by the same
   normalized structure signature discipline as the plan cache (geometry is
   data, shape is structure — N distinct bboxes of one shape compile ONCE),
   bounded in a ``ModuleKernelCache`` LRU and counted in ``kernels.compiled``.
   A per-planner recipe cache additionally maps (filter shape, auths) →
   bind instructions, so a repeat *shape* skips ``planner.plan()`` and range
   decomposition entirely: extract boxes/windows, pack, dispatch.

Union (OR-of-covers) plans lower too when every branch is a device-exact
point_boxes scan on one index: the per-branch masks OR inside a single
program (``_build_union``), so union selects and density grids are one
dispatch with inherent dedup instead of per-branch scans + host unions.

Geometry-catalog residuals (geom/catalog.py st_* calls) ride the refine
modes: ``st_contains(POLYGON, geom)`` / ``st_intersects(geom, POLYGON)``
lower to the certainty-band point-in-polygon classifier and
``st_distance(geom, POINT) < r`` to a banded radial test (``_refine_spec``);
the uncertain sliver re-evaluates on host in exact f64 either way.

Fallback rules (always exact — the staged path is the oracle): attribute
-index plans, FID filters, union plans with host residuals or mixed
indexes, vocab-less string predicates, host residuals other than the
single-predicate refine shapes above over point layers, tables under 4
gather blocks, and any structure-key drift between the lowered and
interpreted residuals.

Knobs: ``GEOMESA_TPU_FUSED_QUERY`` (master switch),
``GEOMESA_TPU_FUSED_SHAPE_CACHE`` (recipe LRU bound),
``GEOMESA_TPU_KERNEL_CACHE`` (compiled program LRU bound).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from geomesa_tpu import config
from geomesa_tpu import trace as _trace
from geomesa_tpu.filter import ir
from geomesa_tpu.index import prune as _prune
from geomesa_tpu.index.scan import (EMPTY_BOX, EMPTY_WINDOW, PRIMARY_FNS,
                                    ModuleKernelCache, ScanKernels,
                                    Unsupported, _LazyBlockGather, _fetch,
                                    _grid_scatter, _pip_band, _time_mask,
                                    pad_boxes, pad_windows, program_name)
from geomesa_tpu.index.bind import EMPTY as _EMPTY_BIND
from geomesa_tpu.index.bind import ShapeBinder, _pow2
from geomesa_tpu.index.bind import collect_values as _collect_values
from geomesa_tpu.index.bind import shape_key as _shape_key
from geomesa_tpu.metrics import REGISTRY
from geomesa_tpu.obs import attrib as _attrib
from geomesa_tpu.serve.resilience import deadline as _rdl

# module-level program cache: LRU-bounded by GEOMESA_TPU_KERNEL_CACHE,
# registered in _KERNEL_INSTANCES so fused programs count in the
# kernels.compiled gauge and the PR-6 recompile detector exactly like the
# staged scan kernels they replace
_PROGRAMS = ModuleKernelCache("fused_query")

# observable ledger for tests and the debug/healthz surfaces
STATS: Dict[str, int] = {
    "queries": 0,          # dispatches served by a fused program
    "fallbacks": 0,        # qualification declines (staged path served)
    "programs_built": 0,   # distinct program compiles
    "shape_hits": 0,       # recipe fast-path binds (no planner.plan at all)
    "shape_misses": 0,     # shapes seen before a recipe existed
    "bind_failures": 0,    # recipe present but the new values didn't bind
    "overflow_retries": 0, # select capacity regrows
    # + "rung.<blocks>" / "rung.full": dispatches read back by the branch
    # that served them (``_Program.fetch``), keyed as they first occur
}

REGISTRY.set_gauge("fused.programs", lambda: len(_PROGRAMS._jitted))

# block-gate slack in degrees: the per-block summaries are f32 reductions of
# the f32 coordinate planes and the gate envelopes are f32 roundings of f64
# query bounds — both within _IN_DELTA (2.5e-5) of exact. 1e-3 deg is >>
# both, so a gated-out block provably contains no match (the exact fp62 mask
# re-applies inside the gathered blocks either way).
_GATE_SLACK = np.float32(1e-3)

# select-capacity tiers shared with planner._SELECT_TIERS (each distinct
# capacity is its own compile; hints quantize UP)
_SELECT_TIERS = (1 << 10, 1 << 13, 1 << 16, 1 << 19, 1 << 22)

_UNC_CAP = 4096  # refine-mode uncertain-row capacity (host fallback past it)

# the ladder of block capacities a program's pruned branches gather at
# (``_ladder``): powers of two (step 1) from this floor up to the table's
# ``cap``. Set from what the chip reads at 10M rows (PERF.md §6, PR 29: device
# ms a select by rung, compile seconds by ladder length).
_RUNG_FLOOR = 32
_RUNG_STEP = 1

# radial-distance certainty band (degrees) for the "dist" refine kind: must
# exceed the f32 error of hypot over the f32 coordinate planes — coordinate
# rounding ≤ 2.5e-5 per axis plus a few ulp of arithmetic at |coord| ≤ 360
# (< 5e-4 total) plus the radius literal's own f32 cast (≤ 2.2e-5). Rows
# inside the band re-evaluate on host in exact f64.
_DIST_BAND = np.float32(1e-3)


def _tier(capacity: Optional[int]) -> int:
    if capacity is None:
        return 1 << 16
    for t in _SELECT_TIERS:
        if capacity <= t:
            return t
    return _pow2(capacity)


# -- packed constant layout ---------------------------------------------------


class _Layout:
    """Every per-query constant (boxes, gate, windows, residual values, vis
    codes, edges, grid) packs into ONE pow2-padded int32 vector — one warm
    transfer shape per program, shipped with the dispatch. f32 slots ride as
    bit patterns (``view``/``bitcast_convert_type``)."""

    def __init__(self):
        self.slots: List[tuple] = []   # (offset, size, shape, is_f32)
        self._n = 0

    def add(self, shape: tuple, f32: bool = False) -> int:
        size = 1
        for d in shape:
            size *= int(d)
        self.slots.append((self._n, size, tuple(shape), bool(f32)))
        self._n += size
        return len(self.slots) - 1

    @property
    def padded(self) -> int:
        return _pow2(max(8, self._n))

    def signature(self) -> tuple:
        """Value-free structural signature (part of the program key)."""
        return tuple((size, shape, f32) for _, size, shape, f32 in self.slots)

    def pack(self, values: list) -> np.ndarray:
        out = np.zeros(self.padded, dtype=np.int32)
        for (off, size, shape, f32), v in zip(self.slots, values):
            if f32:
                a = np.ascontiguousarray(v, dtype=np.float32)
                out[off:off + size] = a.reshape(-1).view(np.int32)
            else:
                out[off:off + size] = np.asarray(
                    v, dtype=np.int32).reshape(-1)
        return out


def _make_get(slots: tuple) -> Callable:
    """In-kernel unpack: static slices + bitcast, so unpacking fuses away."""
    import jax
    import jax.numpy as jnp

    def get(packed, i: int):
        off, size, shape, f32 = slots[i]
        v = packed[off:off + size]
        if f32:
            v = jax.lax.bitcast_convert_type(v, jnp.float32)
        return v.reshape(shape) if shape else v[0]

    return get


# -- residual IR lowering -----------------------------------------------------

# attr type names whose device columns are exact (mirrors scan.py)
_EXACT_DEVICE_TYPES = {"Int", "Integer", "Boolean", "String", "Float"}


def _lower_residual(f: Optional[ir.Filter], sft, string_vocabs,
                    available: Optional[set], layout: _Layout, values: list):
    """``compile_residual``'s twin: same structure-key grammar and the same
    ``Unsupported`` conditions, but constants allocate packed layout slots
    and the emitted fn reads them back through ``get``. Returns
    (structure_key, emit | None) where emit(cols, packed, get) → bool mask.
    """
    import functools

    import jax.numpy as jnp

    if f is None:
        return "none", None

    def check_available(attr: str) -> None:
        if available is not None and attr not in available:
            raise Unsupported(f"{attr} not in the device column group")

    def const(v, f32: bool = False, shape: tuple = ()) -> int:
        values.append(v)
        return layout.add(shape, f32)

    def walk(node: ir.Filter):
        if isinstance(node, ir.Include):
            return "inc", lambda cols, p, get: jnp.ones(
                next(iter(cols.values())).shape[0], dtype=bool)
        if isinstance(node, ir.Exclude):
            return "exc", lambda cols, p, get: jnp.zeros(
                next(iter(cols.values())).shape[0], dtype=bool)
        if isinstance(node, ir.And):
            keys, fns = zip(*(walk(c) for c in node.children))
            return "and(" + ",".join(keys) + ")", \
                lambda cols, p, get, fns=fns: functools.reduce(
                    jnp.logical_and, [g(cols, p, get) for g in fns])
        if isinstance(node, ir.Or):
            keys, fns = zip(*(walk(c) for c in node.children))
            return "or(" + ",".join(keys) + ")", \
                lambda cols, p, get, fns=fns: functools.reduce(
                    jnp.logical_or, [g(cols, p, get) for g in fns])
        if isinstance(node, ir.Not):
            k, g = walk(node.child)
            return f"not({k})", lambda cols, p, get, g=g: ~g(cols, p, get)
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
                i = const(code)
                if node.op == "=":
                    return f"seq:{node.attr}", \
                        lambda cols, p, get, i=i, a=node.attr: \
                        cols[a] == get(p, i)
                return f"sne:{node.attr}", \
                    lambda cols, p, get, i=i, a=node.attr: \
                    cols[a] != get(p, i)
            if attr.type_name not in _EXACT_DEVICE_TYPES:
                raise Unsupported(f"{attr.type_name} cmp is inexact on device")
            i = const(node.value, f32=(attr.type_name == "Float"))
            op = node.op
            key = f"cmp{op}:{node.attr}"

            def g(cols, p, get, i=i, a=node.attr, op=op):
                c = cols[a]
                v = get(p, i)
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
                codes = [vocab.index(v) for v in node.values if v in vocab] \
                    or [-1]
            elif attr.type_name in ("Int", "Integer"):
                codes = [int(v) for v in node.values]
            else:
                raise Unsupported("IN on non-int/string")
            size = max(1, 1 << (len(codes) - 1).bit_length())
            padded = codes + [codes[-1]] * (size - len(codes))
            i = const(padded, shape=(size,))
            return f"in{size}:{node.attr}", \
                lambda cols, p, get, i=i, a=node.attr: jnp.any(
                    cols[a][:, None] == get(p, i)[None, :], axis=1)
        if isinstance(node, ir.During):
            raise Unsupported("During handled by primary time windows")
        raise Unsupported(type(node).__name__)

    return walk(f)


# -- per-block device summaries (the in-kernel cover) -------------------------


def _block_summaries(index, bsz: int):
    """Per-gather-block coordinate (and time-bin) envelopes, resident on
    device and cached on the index. The program's block gate tests query
    envelopes against these — a slack-expanded superset of the block's rows
    (invalid/padded rows fold to ∓inf so they never keep a block alive)."""
    cached = getattr(index, "_fused_summ", None)
    if cached is not None and cached[0] == bsz:
        return cached[1]
    import jax
    import jax.numpy as jnp

    cols = index.device.columns
    n = int(cols["xf"].shape[0])
    nb = -(-n // bsz)
    pad = nb * bsz - n
    valid = cols.get("__valid__")

    def blocked(c, fill):
        if valid is not None:
            c = jnp.where(valid, c, fill)
        if pad:
            c = jnp.concatenate([c, jnp.full((pad,), fill, c.dtype)])
        return c.reshape(nb, bsz)

    inf = jnp.float32(np.inf)
    summ = {
        "bxmin": jnp.min(blocked(cols["xf"], inf), axis=1) - _GATE_SLACK,
        "bxmax": jnp.max(blocked(cols["xf"], -inf), axis=1) + _GATE_SLACK,
        "bymin": jnp.min(blocked(cols["yf"], inf), axis=1) - _GATE_SLACK,
        "bymax": jnp.max(blocked(cols["yf"], -inf), axis=1) + _GATE_SLACK,
    }
    if "bin" in cols:
        lo = jnp.int32(-(1 << 31) + 1)
        hi = jnp.int32((1 << 31) - 1)
        summ["binmin"] = jnp.min(blocked(cols["bin"], hi), axis=1)
        summ["binmax"] = jnp.max(blocked(cols["bin"], lo), axis=1)
    with _trace.waiting():
        jax.block_until_ready(summ)
    index._fused_summ = (bsz, summ)
    return summ


# -- the fused program --------------------------------------------------------


class _Program:
    """A compiled fused program bound to one query's packed constants."""

    __slots__ = ("fn", "cols", "summ", "packed", "mode", "sel_cap",
                 "unc_cap", "n", "res_key", "key", "layout", "rungs", "nb")

    def __init__(self, fn, cols, summ, packed, mode, sel_cap, unc_cap, n,
                 res_key, key, rungs, nb, layout=None):
        self.fn = fn
        self.cols = cols
        self.summ = summ
        self.packed = packed   # host np; ships WITH the dispatch (one round)
        self.mode = mode
        self.sel_cap = sel_cap
        self.unc_cap = unc_cap
        self.n = n
        self.res_key = res_key
        self.key = key
        self.rungs = rungs     # _ladder(cap): the pruned branches' blocks
        self.nb = nb           # the table's blocks: what ``full`` reads
        self.layout = layout   # set by _build; the template-rebind fast path

    def dispatch(self):
        """The single dispatch: packed constants ride into the jit call, the
        returned device values, (result, n_alive), sync only when the caller
        reads them."""
        return self.fn(self.cols, self.summ, self.packed)

    def fetch(self):
        """Dispatch, wait, and read the result back with ``n_alive`` beside
        it (one read-back); the blocks the gate kept alive and the blocks the
        branch that served them gathered go to the counters
        ``fused.blocks_alive`` / ``fused.blocks_gathered`` and the rung to
        ``STATS``. Returns the result on the host."""
        import jax
        ready = _fetch(self.dispatch)
        with _trace.waiting():   # the read-back's copy
            out, n_alive = jax.device_get(ready)
        n_alive = int(n_alive)
        which = sum(n_alive > r for r in self.rungs)   # the switch's index
        gathered = (self.rungs + (self.nb,))[which]
        rung = "rung.full" if which == len(self.rungs) else f"rung.{gathered}"
        STATS[rung] = STATS.get(rung, 0) + 1
        REGISTRY.inc("fused.blocks_alive", n_alive)
        REGISTRY.inc("fused.blocks_gathered", gathered)
        return out


def _ladder(cap: int) -> Tuple[int, ...]:
    """Block capacities of a program's pruned branches: every
    ``_RUNG_STEP``-th power of two from ``_RUNG_FLOOR`` up to ``cap``, and
    ``cap`` itself. A function of ``cap`` alone, which the program key
    already holds: the ladder adds no compile."""
    rungs = []
    r = min(_RUNG_FLOOR, cap)
    while r < cap:
        rungs.append(r)
        r <<= _RUNG_STEP
    return tuple(rungs) + (cap,)


def _gate_alive(summ, gate, windows):
    """(nb,) bool: the blocks whose envelope (and week bins, where
    ``windows`` is given) meet any of the query's gate envelopes."""
    import jax.numpy as jnp

    alive = jnp.any(
        (summ["bxmax"][:, None] >= gate[None, :, 0])
        & (summ["bxmin"][:, None] <= gate[None, :, 2])
        & (summ["bymax"][:, None] >= gate[None, :, 1])
        & (summ["bymin"][:, None] <= gate[None, :, 3]), axis=1)
    if windows is not None:
        blo, bhi = windows[:, 0], windows[:, 2]
        alive = alive & jnp.any(
            (blo <= bhi)[None, :]
            & (summ["binmin"][:, None] <= bhi[None, :])
            & (summ["binmax"][:, None] >= blo[None, :]), axis=1)
    return alive


def _first_set(mask, size: int):
    """``jnp.nonzero(mask.reshape(-1), size=size, fill_value=mask.size)[0]``
    for a (blocks, rows a block) mask: the same prefix sum and scatter-add,
    laid out so that the TPU compiler takes seconds over a ladder of them.
    The prefix sum runs along the rows of a block and then over the blocks'
    totals, and the mask is materialised first: a scan over a rung's rows in
    one line, or one whose every step recomputes the mask from the gathered
    planes, cost the compiler 15-70 s a rung (PERF.md §6, PR 29)."""
    import jax.numpy as jnp
    from jax import lax

    mask = lax.optimization_barrier(mask)
    within = jnp.cumsum(mask.astype(jnp.int32), axis=1)
    totals = within[:, -1]
    upto = (within + (jnp.cumsum(totals) - totals)[:, None]).reshape(-1)
    # the k-th set row's position is the number of rows with under k set
    # rows up to and including them
    first = jnp.cumsum(jnp.bincount(upto, length=size))
    return jnp.where(jnp.arange(size) < upto[-1], first, mask.size)


def _over_alive(alive, cols, n: int, bsz: int, cap: int, mask_of, tail):
    """The program's one conditional: ``tail`` over the alive blocks,
    gathered at the first rung of ``_ladder(cap)`` that holds them, or over
    the whole table when more than ``cap`` are alive. The rung is chosen on
    the device from ``n_alive`` (integer compares, no host round trip), so
    the gather and the compaction run over at most ``_RUNG_STEP`` doublings
    of the alive blocks, never over ``cap`` for a box that keeps 80 alive.

    ``tail(c, m, compact)`` is the mode's aggregate over a column view ``c``
    and its exact mask ``m``; ``compact(mask, size)`` gives the TABLE rows
    of the mask's first ``size`` set rows, padded with ``n``. Returns
    (the tail's result, n_alive)."""
    import jax.numpy as jnp
    from jax import lax

    n_alive = jnp.sum(alive).astype(jnp.int32)
    alive_ids = jnp.nonzero(alive, size=cap, fill_value=-1)[0].astype(
        jnp.int32)

    def pruned(rung: int):
        total = rung * bsz

        def branch(_):
            # scan.py expand_blocks discipline: clamped starts re-read a
            # suffix of the previous block; the membership test masks the
            # re-reads and -1 pads without double counts
            bids = alive_ids[:rung]
            starts = bids * bsz
            astart = jnp.clip(starts, 0, max(0, n - bsz))
            rows = astart[:, None] + jnp.arange(bsz, dtype=jnp.int32)[None, :]
            membership = ((bids >= 0)[:, None]
                          & (rows >= starts[:, None])
                          & (rows < starts[:, None] + bsz)).reshape(-1)
            rowids = rows.reshape(-1)
            g = _LazyBlockGather(cols, astart, bsz, total)

            def compact(mask, size):
                s = _first_set(mask.reshape(rung, bsz), size)
                return jnp.where(
                    s < total, rowids[jnp.clip(s, 0, total - 1)],
                    n).astype(jnp.int32)

            return tail(g, mask_of(g, membership), compact)

        return branch

    def full(_):
        def compact(mask, size):
            return jnp.nonzero(mask, size=size, fill_value=n)[0].astype(
                jnp.int32)

        return tail(cols, mask_of(cols), compact)

    rungs = _ladder(cap)
    which = sum((n_alive > r).astype(jnp.int32) for r in rungs)
    return lax.switch(which, [pruned(r) for r in rungs] + [full], 0), n_alive


def _mode_tail(mode: str, sel_cap: int, unc_cap: int = 0, refine_of=None,
               grid=None, width: int = 0, height: int = 0):
    """What a mode makes of a column view and its mask (``_over_alive``'s
    ``tail``): the same for a gathered rung and for the whole table."""
    import jax.numpy as jnp

    def count(m):
        return jnp.sum(m).astype(jnp.int32)

    if mode == "count":
        return lambda c, m, compact: count(m)
    if mode == "select":
        return lambda c, m, compact: jnp.concatenate(
            [count(m)[None], compact(m, sel_cap)])
    if mode in ("count_refine", "select_refine"):
        def refined(c, m, compact):
            hit, unc = refine_of(c, m)
            parts = [count(hit)[None], count(unc)[None]]
            if mode == "select_refine":
                parts.append(compact(hit, sel_cap))
            parts.append(compact(unc, unc_cap))
            return jnp.concatenate(parts)
        return refined
    if mode == "density":
        return lambda c, m, compact: (
            _grid_scatter(c["xf"], c["yf"], m, None, grid, width, height),
            count(m))
    raise ValueError(mode)


def _jit_program(mode: str, slots: tuple, six: Dict[str, int], emit,
                 T: int, n: int, bsz: int, cap: int, sel_cap: int,
                 unc_cap: int, has_bin: bool,
                 width: int, height: int, refine: str = "pip"):
    """Build + jit one fused program. Everything here is structure; values
    arrive through the packed vector at dispatch time."""
    import jax
    import jax.numpy as jnp

    get = _make_get(slots)

    def run(cols, summ, packed):
        boxes = get(packed, six["boxes"])
        windows = get(packed, six["windows"]) if T else None

        # -- in-kernel cover: which blocks can possibly match -------------
        alive = _gate_alive(summ, get(packed, six["gate"]),
                            windows if has_bin else None)

        def mask_of(c, membership=None):
            m = PRIMARY_FNS["point_boxes"](c, boxes)
            if T:
                m = m & _time_mask(c, windows)
            if emit is not None:
                m = m & emit(c, packed, get)
            if "vis" in six:
                codes = get(packed, six["vis"])
                m = m & jnp.any(
                    c["__vis__"][:, None] == codes[None, :], axis=1)
            if "__valid__" in c:
                m = m & c["__valid__"]
            if membership is not None:
                m = m & membership
            return m

        def refine_of(c, m):
            if refine == "dist":
                dz = get(packed, six["dist"])
                d = jnp.sqrt((c["xf"] - dz[0]) ** 2 + (c["yf"] - dz[1]) ** 2)
                cin = d <= dz[2] - _DIST_BAND
                cout = d >= dz[2] + _DIST_BAND
            else:
                edges = get(packed, six["edges"])
                cin, cout = _pip_band(
                    c["xf"][:, None], c["yf"][:, None],
                    edges[None, :, 0], edges[None, :, 1],
                    edges[None, :, 2], edges[None, :, 3])
            return m & cin, m & ~cin & ~cout

        grid = get(packed, six["grid"]) if mode == "density" else None
        return _over_alive(alive, cols, n, bsz, cap, mask_of, _mode_tail(
            mode, sel_cap, unc_cap, refine_of, grid, width, height))

    STATS["programs_built"] += 1
    kid = f"fused_{mode}.point_boxes"
    run.__name__ = program_name(kid)
    jitted = jax.jit(run)
    if _attrib.enabled():
        jitted = _attrib.compile_probe(jitted, kid, cap)
    return jitted


def _gate_of(boxes_geo, B: int) -> np.ndarray:
    """(B, 4) f32 [xmin, ymin, xmax, ymax] block-gate envelopes; padded rows
    are inverted (nothing alive)."""
    gate = np.empty((B, 4), dtype=np.float32)
    gate[:, 0] = 3e38
    gate[:, 1] = 3e38
    gate[:, 2] = -3e38
    gate[:, 3] = -3e38
    for i, (xmin, ymin, xmax, ymax) in enumerate(boxes_geo):
        gate[i] = (xmin, ymin, xmax, ymax)
    return gate


def _build(index, sft, vocabs, mode: str, boxes: np.ndarray,
           gate: np.ndarray, windows: Optional[np.ndarray], dev_ir,
           vis: Optional[np.ndarray],
           refine_spec: Optional[Tuple[str, np.ndarray]],
           grid, width: int, height: int, capacity: Optional[int],
           expected_key: Optional[str] = None) -> Optional[_Program]:
    """Assemble layout + values for one query and fetch (or compile) its
    program. ``boxes``/``windows`` arrive pow2-padded. Returns None when the
    shape doesn't qualify — the staged path is always the fallback."""
    cols = index.device.columns
    if "xf" not in cols or "yf" not in cols:
        return None
    n = int(cols["xf"].shape[0])
    bsz = int(_prune.BLOCK_SIZE)
    if n < 4 * bsz:
        return None  # tiny tables: the staged full mask is already one pass
    T = 0 if windows is None else len(windows)
    if T and ("bin" not in cols or "off" not in cols):
        return None

    layout = _Layout()
    values: list = []
    six: Dict[str, int] = {}
    six["boxes"] = layout.add(boxes.shape)
    values.append(boxes)
    six["gate"] = layout.add(gate.shape, f32=True)
    values.append(gate)
    if T:
        six["windows"] = layout.add(windows.shape)
        values.append(windows)
    try:
        res_key, emit = _lower_residual(dev_ir, sft, vocabs, set(cols),
                                        layout, values)
    except Unsupported:
        return None
    if vis is not None:
        if "__vis__" not in cols:
            return None
        six["vis"] = layout.add((len(vis),))
        values.append(vis)
        res_key = f"vis{len(vis)}&({res_key})"
    if expected_key is not None and res_key != expected_key:
        # structure drift between the lowered and interpreted residuals:
        # stay staged rather than risk a divergent program
        return None
    refine = ""
    if refine_spec is not None:
        refine, rdata = refine_spec
        six["dist" if refine == "dist" else "edges"] = layout.add(
            rdata.shape, f32=True)
        values.append(rdata)
    if grid is not None:
        six["grid"] = layout.add((4,), f32=True)
        values.append(np.asarray(grid, dtype=np.float32))

    nb = -(-n // bsz)
    cap = min(_pow2(max(4, int(np.ceil(
        nb * float(config.PRUNE_MAX_FRACTION.get()))))), _pow2(nb))
    sel_cap = min(_tier(capacity), _pow2(n)) \
        if mode in ("select", "select_refine") else 0
    unc_cap = _UNC_CAP if refine else 0
    has_bin = T > 0 and "bin" in cols

    # value-free program key: geometry/time/residual VALUES ride in the
    # packed vector; only structure lands here, so N distinct bboxes of one
    # shape share one compile (the recompile-churn pin)
    key = ("fq", mode, res_key, refine, layout.signature(), n, bsz, cap,
           sel_cap, unc_cap, has_bin, width, height)
    slots = tuple(layout.slots)
    fn = _PROGRAMS.get(key, lambda: _jit_program(
        mode, slots, dict(six), emit, T, n, bsz, cap, sel_cap, unc_cap,
        has_bin, width, height, refine))
    summ = _block_summaries(index, bsz)
    return _Program(fn, cols, summ, layout.pack(values), mode, sel_cap,
                    unc_cap, n, res_key, key, _ladder(cap), nb, layout)


# -- plan qualification -------------------------------------------------------


def _refine_spec(plan) -> Optional[Tuple[str, np.ndarray]]:
    """(kind, f32 constants) when the host residual is a single predicate
    the fused program can classify with certainty bands over a point layer:

    - ``("pip", edges)`` — point-in-polygon against a padded edge table, for
      ``Intersects`` with a POLYGON literal and for the equivalent catalog
      calls ``st_contains(POLYGON, geom)`` / ``st_intersects(geom, POLYGON)``
      (a point intersects/lies-within a polygon iff it is in the polygon);
    - ``("dist", [cx, cy, r])`` — banded radial distance, for
      ``st_distance(geom, POINT) < r`` (or ``<=``; rows within ``_DIST_BAND``
      of the circle classify uncertain, so the strictness of the comparison
      resolves in the exact host refine).

    None → the staged path serves the plan.
    """
    res = plan.residual_host
    geom_attr = getattr(plan.index, "geom", None)
    from geomesa_tpu.features import geometry as geo
    lit = None
    if isinstance(res, ir.Intersects):
        if res.attr != geom_attr:
            return None
        lit = res.geometry
    elif isinstance(res, ir.Func) and len(res.args) == 2:
        a, b = res.args
        if res.name == "st_contains":
            if isinstance(a, tuple) and b == geom_attr:
                lit = a
        elif res.name == "st_intersects":
            if isinstance(a, tuple) and b == geom_attr:
                lit = a
            elif isinstance(b, tuple) and a == geom_attr:
                lit = b
        if lit is None:
            return None
    elif isinstance(res, ir.FuncCmp) and res.name == "st_distance" \
            and res.op in ("<", "<=") and len(res.args) == 2:
        a, b = res.args
        pt = a if isinstance(a, tuple) else b if isinstance(b, tuple) else None
        attr_arg = b if isinstance(a, tuple) else a
        if pt is None or attr_arg != geom_attr or pt[0] != geo.POINT:
            return None
        r = float(res.value)
        if not r >= 0.0:
            return None
        return "dist", np.array([pt[1][0], pt[1][1], r], dtype=np.float32)
    if lit is None or lit[0] != geo.POLYGON:
        return None
    from geomesa_tpu.filter.geom_numpy import literal_segments
    edges = literal_segments(lit).astype(np.float32)
    ne = max(4, _pow2(len(edges)))
    ep = np.tile(ScanKernels._EDGE_PAD, (ne, 1))
    ep[: len(edges)] = edges
    return "pip", ep


def _from_plan(planner, plan, mode: str, capacity: Optional[int] = None,
               grid=None, width: int = 0, height: int = 0) \
        -> Optional[_Program]:
    """Qualify a staged plan for fused execution. Exactness contract: every
    decline returns None and the caller runs the staged path; every accept
    produces a program whose mask is the SAME primary/time/residual/vis
    conjunction the staged kernels evaluate."""
    if not config.FUSED_QUERY.get():
        return None
    if plan.empty or plan.index is None \
            or plan.primary_kind != "point_boxes" \
            or plan.candidate_slices is not None \
            or plan.boxes_loose is None:
        return None
    cache = getattr(plan, "_fused_cache", None)
    ck = (mode, _tier(capacity) if mode in ("select", "select_refine")
          else 0, width, height)
    if cache is not None and ck in cache:
        return cache[ck]
    boxes_geo = plan.explain.get("boxes")
    if not boxes_geo or len(boxes_geo) > len(plan.boxes_loose):
        return None
    refine_spec = None
    if mode in ("count_refine", "select_refine"):
        refine_spec = _refine_spec(plan)
        if refine_spec is None:
            return None
    elif plan.residual_host is not None:
        return None
    dev_ir = plan.explain.get("residual_device")
    vis = None
    pkey = plan.residual_device[0] if plan.residual_device else "none"
    if plan.explain.get("__vis_applied__") and pkey.startswith("vis"):
        vis = np.asarray(plan.residual_device[1][-1], dtype=np.int32)
    gate = _gate_of(boxes_geo, len(plan.boxes_loose))
    prog = _build(plan.index, planner.sft, plan.index.vocabs, mode,
                  plan.boxes_loose, gate, plan.windows, dev_ir, vis,
                  refine_spec, grid, width, height, capacity,
                  expected_key=pkey)
    try:
        if cache is None:
            cache = {}
            plan._fused_cache = cache   # plans are immutable post-build
        cache[ck] = prog
    except (AttributeError, TypeError):
        pass
    return prog


# -- execution entry points (planner integration) -----------------------------


def prepare_count_program(planner, plan) -> Optional[_Program]:
    """The PreparedQuery hook: a fused count dispatcher for a device-exact
    plan, or None (staged staging takes over)."""
    prog = _from_plan(planner, plan, "count")
    if prog is not None:
        STATS["queries"] += 1
        REGISTRY.inc("fused.queries")
    elif config.FUSED_QUERY.get():
        STATS["fallbacks"] += 1
    return prog


def try_count(planner, plan) -> Optional[int]:
    """One-dispatch count for a device-exact plan, or None."""
    prog = _from_plan(planner, plan, "count")
    if prog is None:
        if config.FUSED_QUERY.get():
            STATS["fallbacks"] += 1
        return None
    _rdl.check_current("fused_dispatch")
    STATS["queries"] += 1
    REGISTRY.inc("fused.queries")
    with _attrib.kernel("fused_count.point_boxes"):
        return int(prog.fetch())


def try_select(planner, plan, capacity: Optional[int]) \
        -> Optional[np.ndarray]:
    """One-dispatch select → index POSITIONS (caller maps + sorts), or None.
    Overflow regrows the capacity tier and re-dispatches (same discipline as
    scan.select)."""
    cap = capacity
    while True:
        prog = _from_plan(planner, plan, "select", capacity=cap)
        if prog is None:
            if config.FUSED_QUERY.get():
                STATS["fallbacks"] += 1
            return None
        _rdl.check_current("fused_dispatch")
        STATS["queries"] += 1
        REGISTRY.inc("fused.queries")
        with _attrib.kernel("fused_select.point_boxes", prog.sel_cap):
            out = prog.fetch()
        cnt = int(out[0])
        if cnt <= prog.sel_cap:
            return out[1: 1 + cnt].astype(np.int64)
        STATS["overflow_retries"] += 1
        cap = _pow2(cnt)


def try_count_refine(planner, plan) -> Optional[int]:
    """Fused scan + certainty-band polygon refine + count in one dispatch;
    only the uncertain sliver re-evaluates on host in exact f64. None when
    the shape doesn't qualify or uncertainty overflowed."""
    prog = _from_plan(planner, plan, "count_refine")
    if prog is None:
        if config.FUSED_QUERY.get():
            STATS["fallbacks"] += 1
        return None
    _rdl.check_current("fused_dispatch")
    STATS["queries"] += 1
    REGISTRY.inc("fused.queries")
    with _attrib.kernel("fused_count_refine.point_boxes"):
        out = prog.fetch()
    certain, n_unc = int(out[0]), int(out[1])
    if n_unc > prog.unc_cap:
        return None  # uncertainty overflow: staged/host refine instead
    if n_unc == 0:
        # the refine stage ran in-kernel (its time is in device_wait);
        # keep the stage visible in the trace contract with 0 host rows
        if _trace.enabled():
            _trace.record("refine", "refine", 0.0)
        return certain
    pos = out[2: 2 + n_unc].astype(np.int64)
    rows = plan.index.map_rows(pos)
    from geomesa_tpu.filter.evaluate import evaluate_at
    with _trace.span("refine", kind="refine", rows=len(rows)):
        return certain + int(np.sum(
            evaluate_at(plan.residual_host, planner.table, rows)))


def try_select_refine(planner, plan, capacity: Optional[int]) \
        -> Optional[np.ndarray]:
    """Fused select with in-kernel polygon refine → FINAL sorted table rows
    (certain hits + host-confirmed uncertain rows), or None."""
    cap = capacity
    while True:
        prog = _from_plan(planner, plan, "select_refine", capacity=cap)
        if prog is None:
            if config.FUSED_QUERY.get():
                STATS["fallbacks"] += 1
            return None
        _rdl.check_current("fused_dispatch")
        STATS["queries"] += 1
        REGISTRY.inc("fused.queries")
        with _attrib.kernel("fused_select_refine.point_boxes", prog.sel_cap):
            out = prog.fetch()
        n_in, n_unc = int(out[0]), int(out[1])
        if n_unc > prog.unc_cap:
            return None
        if n_in > prog.sel_cap:
            STATS["overflow_retries"] += 1
            cap = _pow2(n_in)
            continue
        in_pos = out[2: 2 + n_in].astype(np.int64)
        rows = plan.index.map_rows(in_pos)
        if n_unc:
            unc_pos = out[2 + prog.sel_cap:
                          2 + prog.sel_cap + n_unc].astype(np.int64)
            unc_rows = plan.index.map_rows(unc_pos)
            from geomesa_tpu.filter.evaluate import evaluate_at
            with _trace.span("refine", kind="refine", rows=len(unc_rows)):
                keep = evaluate_at(plan.residual_host, planner.table,
                                   unc_rows)
            rows = np.concatenate([rows, unc_rows[keep]])
        elif _trace.enabled():
            # in-kernel refine resolved every candidate: the stage's time
            # is inside device_wait, but it must stay a visible stage
            _trace.record("refine", "refine", 0.0)
        return np.sort(rows)


def try_density(planner, plan, grid_bbox, width: int, height: int):
    """One-dispatch heat-map: ((H, W) f32 grid, count) or None. Available to
    aggregation callers; the staged density kernels remain the default."""
    prog = _from_plan(planner, plan, "density", grid=grid_bbox, width=width,
                      height=height)
    if prog is None:
        return None
    _rdl.check_current("fused_dispatch")
    STATS["queries"] += 1
    REGISTRY.inc("fused.queries")
    with _attrib.kernel("fused_density.point_boxes"):
        grid, cnt = prog.fetch()
    return grid, int(cnt)


# -- union (Or-of-covers) lowering --------------------------------------------


def _jit_union_program(mode: str, slots: tuple, branches: tuple,
                       six_g: Dict[str, int], n: int, bsz: int, cap: int,
                       sel_cap: int, has_bin: bool, width: int, height: int):
    """One device program for an OR-of-covers plan: per-branch primary/time/
    residual/vis masks OR *inside* the program (dedup is inherent — the OR is
    one mask), so a union select or density render is ONE dispatch instead of
    per-branch scans + host row-set union. ``branches`` is a tuple of
    (slot-index dict, residual emit | None, window count) from
    ``_build_union``; the block gate keeps a block alive when ANY branch's
    envelope set touches it."""
    import functools

    import jax
    import jax.numpy as jnp

    get = _make_get(slots)

    def run(cols, summ, packed):
        alive = functools.reduce(jnp.logical_or, [
            _gate_alive(summ, get(packed, six["gate"]),
                        get(packed, six["windows"]) if T and has_bin
                        else None)
            for six, _, T in branches])

        def mask_of(c, membership=None):
            m = None
            for six, emit, T in branches:
                bm = PRIMARY_FNS["point_boxes"](c, get(packed, six["boxes"]))
                if T:
                    bm = bm & _time_mask(c, get(packed, six["windows"]))
                if emit is not None:
                    bm = bm & emit(c, packed, get)
                if "vis" in six:
                    codes = get(packed, six["vis"])
                    bm = bm & jnp.any(
                        c["__vis__"][:, None] == codes[None, :], axis=1)
                m = bm if m is None else (m | bm)
            if "__valid__" in c:
                m = m & c["__valid__"]
            if membership is not None:
                m = m & membership
            return m

        grid = get(packed, six_g["grid"]) if mode == "density" else None
        return _over_alive(alive, cols, n, bsz, cap, mask_of, _mode_tail(
            mode, sel_cap, grid=grid, width=width, height=height))

    STATS["programs_built"] += 1
    kid = f"fused_union_{mode}"
    run.__name__ = program_name(kid)
    jitted = jax.jit(run)
    if _attrib.enabled():
        jitted = _attrib.compile_probe(jitted, kid, cap)
    return jitted


def _build_union(planner, plan, mode: str, auths,
                 capacity: Optional[int] = None, grid=None, width: int = 0,
                 height: int = 0) -> Optional[_Program]:
    """Qualify an OR-of-covers (UnionScanPlan) for single-dispatch execution:
    every branch must be a device-exact point_boxes scan on ONE shared index
    (the same precondition as the fused OR-of-masks count). Auths fold
    per-branch exactly as the staged union path does — vis code sets ride the
    packed vector. Any decline returns None and the per-branch staged path
    serves the query."""
    if not config.FUSED_QUERY.get():
        return None
    idx = plan.same_index_device_exact()
    if idx is None:
        return None
    cols = idx.device.columns
    if "xf" not in cols or "yf" not in cols:
        return None
    n = int(cols["xf"].shape[0])
    bsz = int(_prune.BLOCK_SIZE)
    if n < 4 * bsz:
        return None
    layout = _Layout()
    values: list = []
    branches: list = []
    res_keys: list = []
    for _, bp in plan.branches:
        bp = planner._apply_auths(bp, auths)
        if bp.empty:
            continue  # auths folded this branch to nothing
        if bp.primary_kind != "point_boxes" \
                or bp.candidate_slices is not None \
                or bp.boxes_loose is None or bp.residual_host is not None:
            return None
        boxes_geo = bp.explain.get("boxes")
        if not boxes_geo or len(boxes_geo) > len(bp.boxes_loose):
            return None
        T = 0 if bp.windows is None else len(bp.windows)
        if T and ("bin" not in cols or "off" not in cols):
            return None
        six: Dict[str, int] = {}
        six["boxes"] = layout.add(bp.boxes_loose.shape)
        values.append(bp.boxes_loose)
        gate = _gate_of(boxes_geo, len(bp.boxes_loose))
        six["gate"] = layout.add(gate.shape, f32=True)
        values.append(gate)
        if T:
            six["windows"] = layout.add(bp.windows.shape)
            values.append(bp.windows)
        dev_ir = bp.explain.get("residual_device")
        try:
            res_key, emit = _lower_residual(dev_ir, planner.sft, idx.vocabs,
                                            set(cols), layout, values)
        except Unsupported:
            return None
        pkey = bp.residual_device[0] if bp.residual_device else "none"
        if bp.explain.get("__vis_applied__") and pkey.startswith("vis"):
            vis = np.asarray(bp.residual_device[1][-1], dtype=np.int32)
            six["vis"] = layout.add((len(vis),))
            values.append(vis)
            res_key = f"vis{len(vis)}&({res_key})"
        if res_key != pkey:
            return None   # lowered/interpreted drift: stay staged
        branches.append((six, emit, T))
        res_keys.append(f"{res_key}|b{len(bp.boxes_loose)}w{T}"
                        + ("v" if "vis" in six else ""))
    if not branches:
        return None
    six_g: Dict[str, int] = {}
    if grid is not None:
        six_g["grid"] = layout.add((4,), f32=True)
        values.append(np.asarray(grid, dtype=np.float32))
    nb = -(-n // bsz)
    cap = min(_pow2(max(4, int(np.ceil(
        nb * float(config.PRUNE_MAX_FRACTION.get()))))), _pow2(nb))
    sel_cap = min(_tier(capacity), _pow2(n)) if mode == "select" else 0
    has_bin = "bin" in cols

    key = ("fqu", mode, tuple(res_keys), layout.signature(), n, bsz, cap,
           sel_cap, has_bin, width, height)
    slots = tuple(layout.slots)
    bspec = tuple(branches)
    fn = _PROGRAMS.get(key, lambda: _jit_union_program(
        mode, slots, bspec, dict(six_g), n, bsz, cap, sel_cap, has_bin,
        width, height))
    summ = _block_summaries(idx, bsz)
    return _Program(fn, cols, summ, layout.pack(values), mode, sel_cap,
                    0, n, "|".join(res_keys), key, _ladder(cap), nb, layout)


def try_union_select(planner, plan, auths,
                     capacity: Optional[int] = None) -> Optional[np.ndarray]:
    """One-dispatch select for an OR-of-covers plan → FINAL sorted table
    rows (branch overlaps dedup in the in-program OR), or None (per-branch
    scans + host union serve instead)."""
    cap = capacity
    while True:
        prog = _build_union(planner, plan, "select", auths, capacity=cap)
        if prog is None:
            if config.FUSED_QUERY.get():
                STATS["fallbacks"] += 1
            return None
        _rdl.check_current("fused_dispatch")
        STATS["queries"] += 1
        REGISTRY.inc("fused.queries")
        with _attrib.kernel("fused_union_select", prog.sel_cap):
            out = prog.fetch()
        cnt = int(out[0])
        if cnt <= prog.sel_cap:
            pos = out[1: 1 + cnt].astype(np.int64)
            idx = plan.same_index_device_exact()
            return np.sort(idx.map_rows(pos))
        STATS["overflow_retries"] += 1
        cap = _pow2(cnt)


def try_union_density(planner, plan, auths, grid_bbox, width: int,
                      height: int):
    """One-dispatch union heat-map: ((H, W) f32 grid, count) or None."""
    prog = _build_union(planner, plan, "density", auths, grid=grid_bbox,
                        width=width, height=height)
    if prog is None:
        if config.FUSED_QUERY.get():
            STATS["fallbacks"] += 1
        return None
    _rdl.check_current("fused_dispatch")
    STATS["queries"] += 1
    REGISTRY.inc("fused.queries")
    with _attrib.kernel("fused_union_density"):
        grid, cnt = prog.fetch()
    return grid, int(cnt)


# -- shape-keyed recipe fast path (skip planning entirely) --------------------


def _auths_key(auths) -> Optional[tuple]:
    return None if auths is None else tuple(sorted(auths))


class _RecipeCache:
    """Small thread-safe LRU for (shape, auths) → Recipe | None (negative).
    Deliberately self-contained — the recipe lookup sits ahead of planning
    on the hottest path and must stay a dict op under one lock."""

    MISS = object()

    def __init__(self):
        self._d: "OrderedDict" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            v = self._d.get(key, self.MISS)
            if v is not self.MISS:
                self._d.move_to_end(key)
            return v

    def put(self, key, value) -> None:
        with self._lock:
            cap = max(1, int(config.FUSED_SHAPE_CACHE.get()))
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > cap:
                self._d.popitem(last=False)

    def __len__(self) -> int:
        return len(self._d)


def _recipes(planner) -> _RecipeCache:
    cache = getattr(planner, "_fused_recipes", None)
    if cache is None:
        cache = _RecipeCache()
        planner._fused_recipes = cache
    return cache


def _rebind(recipe, boxes, gate, windows, dev_ir) -> Optional[_Program]:
    """Hot rebind: pack this query's values straight into the recipe's
    template program — no layout rebuild, no lowering, no cache lookups.
    Every value is size-checked against its template slot; any mismatch
    (vocab-miss IN shrank its pad, a column group reload, table growth)
    returns None and the ordinary ``_build`` re-derives everything."""
    tmpl = recipe.tmpl
    prog, layout = tmpl
    cols = recipe.index.device.columns
    if cols is not prog.cols:
        recipe.tmpl = None   # device table reloaded: template is stale
        return None
    values = [boxes, gate]
    if windows is not None:
        values.append(windows)
    try:
        _collect_values(dev_ir, recipe.sft, recipe.vocabs, values)
    except Unsupported:
        return None
    if recipe.vis is not None:
        values.append(recipe.vis)
    slots = layout.slots
    if len(values) != len(slots):
        return None
    packed = np.zeros(layout.padded, dtype=np.int32)
    for (off, size, shape, f32), v in zip(slots, values):
        if f32:
            a = np.ascontiguousarray(v, dtype=np.float32).reshape(-1)
            if a.size != size:
                return None
            packed[off:off + size] = a.view(np.int32)
        else:
            a = np.asarray(v, dtype=np.int32).reshape(-1)
            if a.size != size:
                return None
            packed[off:off + size] = a
    return _Program(prog.fn, cols, prog.summ, packed, prog.mode,
                    prog.sel_cap, prog.unc_cap, prog.n, prog.res_key,
                    prog.key, prog.rungs, prog.nb)


class Recipe:
    """Bind instructions for one (filter shape, auths): everything needed to
    turn a NEW same-shape filter into a packed fused count dispatch without
    touching ``planner.plan()`` — take its boxes, windows and device
    residual out (``bind.ShapeBinder``, the scheduler's binder too),
    re-lower the residual (values only; the structure key must reproduce),
    pack, go. Any drift (box count, window count, residual key, host
    residual appearing) returns None and the slow path serves the query
    exactly."""

    __slots__ = ("binder", "index", "sft", "vocabs", "res_key", "vis",
                 "template_plan", "tmpl")

    def __init__(self, plan, planner, res_key, vis):
        self.tmpl = None   # (program, layout) after the first full _build
        self.binder = ShapeBinder(plan)
        self.index = plan.index
        self.sft = planner.sft
        self.vocabs = plan.index.vocabs
        self.res_key = res_key
        self.vis = vis
        self.template_plan = plan

    def bind(self, f: ir.Filter):
        """→ (boxes, gate, windows, dev_ir) | _EMPTY_BIND | None."""
        got = self.binder.extract(f)
        if got is None or got is _EMPTY_BIND:
            return got
        ext_boxes, boxes, _, windows, dev_ir = got
        return boxes, _gate_of(ext_boxes, len(boxes)), windows, dev_ir


class FusedPrepared:
    """PreparedQuery-shaped handle from the recipe fast path: the query went
    filter → packed constants → one dispatch, never through
    ``planner.plan()``. ``plan`` exposes the recipe's template plan (its
    box/window VALUES belong to the recipe's exemplar query — audit and
    explain surfaces only)."""

    def __init__(self, planner, recipe: Recipe, f: ir.Filter, auths,
                 prog: Optional[_Program]):
        self.planner = planner
        self.plan = recipe.template_plan
        self.filter = f
        self.auths = auths
        self._prog = prog        # None → provably empty

    @property
    def device_exact(self) -> bool:
        return self._prog is not None

    def count_async(self):
        """Async dispatch → 0-d device array (None for empty binds) — the
        same pipelining contract as PreparedQuery.count_async."""
        if self._prog is None:
            return None
        with _trace.span("device_scan", kind="device_scan"):
            return self._prog.dispatch()[0]

    def count(self) -> int:
        from geomesa_tpu.index.guards import Deadline
        attrs = {"type": self.planner.sft.name, "prepared": True}
        if _trace.enabled():
            attrs["filter"] = str(self.filter)  # ir repr is µs-scale; only
        with _trace.trace("count", **attrs):    # pay it when traces record
            dl = Deadline(self.planner.timeout_ms)
            t0 = time.perf_counter()
            n = 0 if self._prog is None else int(self._prog.fetch())
            dl.check("scan")
            self.planner._write_audit(self.plan, self.filter, 0.0,
                                      (time.perf_counter() - t0) * 1000, n)
            return n

    def select_indices(self) -> np.ndarray:
        # selects replan through the general path (capacity tiers vary);
        # counts are the latency-critical shape the recipe accelerates
        return self.planner.select_indices(self.filter, auths=self.auths)


def fast_prepare(planner, f: ir.Filter, auths) -> Optional[FusedPrepared]:
    """Recipe-keyed prepare: when this (filter shape, auths) has fused
    before, bind the new VALUES straight into the compiled program — no
    parse, no plan, no range decomposition, one dispatch. None sends the
    caller down the ordinary prepare path (which registers the shape)."""
    if not config.FUSED_QUERY.get() or getattr(planner, "interceptors", None):
        return None
    try:
        skey = _shape_key(f)
    except Unsupported:
        return None
    cache = _recipes(planner)
    r = cache.get((skey, _auths_key(auths)))
    if r is _RecipeCache.MISS:
        STATS["shape_misses"] += 1
        return None
    if r is None:   # negative entry: shape known non-fusable
        return None
    bound = r.bind(f)
    if bound is _EMPTY_BIND:
        STATS["shape_hits"] += 1
        return FusedPrepared(planner, r, f, auths, None)
    if bound is None:
        STATS["bind_failures"] += 1
        return None
    boxes, gate, windows, dev_ir = bound
    prog = _rebind(r, boxes, gate, windows, dev_ir) \
        if r.tmpl is not None else None
    if prog is None:
        prog = _build(r.index, r.sft, r.vocabs, "count", boxes, gate,
                      windows, dev_ir, r.vis, None, None, 0, 0, None,
                      expected_key=r.res_key)
        if prog is None:
            STATS["bind_failures"] += 1
            return None
        if prog.layout is not None:
            r.tmpl = (prog, prog.layout)
    STATS["shape_hits"] += 1
    STATS["queries"] += 1
    REGISTRY.inc("fused.queries")
    return FusedPrepared(planner, r, f, auths, prog)


def note_shape(planner, plan, f: ir.Filter, auths,
               prog: Optional[_Program]) -> None:
    """Slow-path epilogue: record how this shape resolved so the NEXT
    same-shape query takes the recipe fast path (or skips the attempt —
    negative entries stop re-qualifying known-staged shapes)."""
    if not config.FUSED_QUERY.get() or getattr(planner, "interceptors", None):
        return
    if getattr(plan, "empty", False):
        return   # emptiness is a property of the values, not the shape
    try:
        skey = _shape_key(f)
    except Unsupported:
        return
    cache = _recipes(planner)
    ck = (skey, _auths_key(auths))
    if cache.get(ck) is not _RecipeCache.MISS:
        return
    if prog is None:
        cache.put(ck, None)
        return
    vis = None
    pkey = plan.residual_device[0] if plan.residual_device else "none"
    if plan.explain.get("__vis_applied__") and pkey.startswith("vis"):
        vis = np.asarray(plan.residual_device[1][-1], dtype=np.int32)
    cache.put(ck, Recipe(plan, planner, prog.res_key, vis))


# -- startup warming ----------------------------------------------------------


def warm_programs(index) -> int:
    """Compile the common fused single-dispatch count shapes for an index
    ahead of traffic (1 box; 1 box + 1 window on temporal layers) and run
    each once, paying the XLA compile at startup instead of on the first
    cold query. A compile failure raises. Returns programs warmed."""
    if not config.FUSED_QUERY.get():
        return 0
    cols = getattr(getattr(index, "device", None), "columns", None)
    if not cols or "xf" not in cols:
        return 0
    if not getattr(index, "points", False):
        return 0
    n = int(cols["xf"].shape[0])
    if n < 4 * int(_prune.BLOCK_SIZE):
        return 0
    warmed = 0
    shapes = [(1, 0)]
    if "bin" in cols and "off" in cols:
        shapes.append((1, 1))
    for nb, nw in shapes:
        boxes = pad_boxes(np.empty((0, 8), dtype=np.int32), min_size=nb)
        windows = pad_windows(np.empty((0, 4), dtype=np.int32),
                              min_size=nw) if nw else None
        prog = _build(index, index.sft, index.vocabs, "count", boxes,
                      _gate_of((), len(boxes)), windows, None, None, None,
                      None, 0, 0, None)
        if prog is None:
            continue
        _fetch(prog.dispatch)   # empty gate: executes, compiles every branch
        warmed += 1
    return warmed


def stats_snapshot() -> Dict[str, int]:
    """STATS + live program count (debug/healthz surfaces)."""
    out = dict(STATS)
    out["programs"] = len(_PROGRAMS._jitted)
    return out
