"""One binder for a filter shape's values.

Two filters of one *shape* (``shape_key``) differ only in the numbers in
them: the box, the time interval, the constants of the residual. The first
plan of a shape settles everything else (which index, which kernel, which
residual program), so a later filter of the shape needs only its values
taken out and put where the first plan had its own. ``ShapeBinder`` takes
them out, the way ``BaseIndex.plan`` does; it serves both users:

- ``compiled.Recipe`` packs them into a fused program's constants
  (``ds.count``, prepared queries);
- ``PlanTemplate`` makes the ``IndexScanPlan`` that
  ``planner._apply_auths(planner._plan(f), auths)`` would make on the
  template's index (the scheduler's batched counts).

Any drift (another number of boxes or windows, an empty extraction, a host
residual appearing, a constant of another type or padded size) is no error:
the binder says None and the caller plans the filter in full.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

from geomesa_tpu.filter import ir
from geomesa_tpu.filter.extract import extract_bboxes, extract_intervals
from geomesa_tpu.index.api import IndexScanPlan
from geomesa_tpu.index.scan import (_EXACT_DEVICE_TYPES, Unsupported,
                                    pad_boxes, split_residual)
from geomesa_tpu.index.spatial import _boxes_fp62, _strip_handled

EMPTY = object()   # extraction result: provably no rows (a count of 0)


def _pow2(n: int) -> int:
    return max(1, 1 << max(0, int(n) - 1).bit_length())


def shape_key(f: ir.Filter) -> str:
    """Value-free structural signature of a filter tree: two queries with
    this key in common differ only in geometry/time/constant VALUES. Raises
    ``Unsupported`` for a node no shape is kept for (FID filters, ...)."""
    if isinstance(f, ir.And):
        return "and(" + ",".join(shape_key(c) for c in f.children) + ")"
    if isinstance(f, ir.Or):
        return "or(" + ",".join(shape_key(c) for c in f.children) + ")"
    if isinstance(f, ir.Not):
        return f"not({shape_key(f.child)})"
    if isinstance(f, ir.Include):
        return "inc"
    if isinstance(f, ir.Exclude):
        return "exc"
    if isinstance(f, ir.BBox):
        return f"bbox:{f.attr}"
    if isinstance(f, ir.Intersects):
        return f"ints:{f.attr}:{f.geometry[0]}"
    if isinstance(f, ir.During):
        return f"during:{f.attr}:{int(f.lo_inclusive)}{int(f.hi_inclusive)}"
    if isinstance(f, ir.Cmp):
        return f"cmp{f.op}:{f.attr}"
    if isinstance(f, ir.In):
        return f"in{_pow2(len(f.values))}:{f.attr}"
    if isinstance(f, ir.Func):
        return f"fn:{f.name}({_func_args_sig(f.args)})"
    if isinstance(f, ir.FuncCmp):
        return f"fc{f.op}:{f.name}({_func_args_sig(f.args)})"
    raise Unsupported(type(f).__name__)


def _func_args_sig(args: tuple) -> str:
    """Value-free signature of st_* call arguments: attributes by name,
    geometry literals by type code, scalars as 'f' — two calls with this
    signature in common differ only in literal VALUES, the same normalization
    the rest of the shape key uses."""
    parts = []
    for a in args:
        if isinstance(a, str):
            parts.append(f"a:{a}")
        elif isinstance(a, tuple):
            parts.append(f"l{a[0]}")
        elif isinstance(a, ir.FuncExpr):
            parts.append(f"{a.name}({_func_args_sig(a.args)})")
        else:
            parts.append("f")
    return ",".join(parts)


def boxes_fp62_fast(boxes) -> Optional[np.ndarray]:
    """Scalar twin of ``spatial._boxes_fp62`` for the handful-of-boxes case:
    pure-python IEEE-754 math (bit-identical to the numpy path — python
    floats ARE C doubles, and floor(ldexp(frac, 62)) of an integral float
    converts to int exactly) without ~40µs of small-array numpy dispatch.
    None on anything unusual (NaN coordinates) → caller uses the array path.
    """
    out = np.empty((len(boxes), 8), dtype=np.int32)
    m62 = (1 << 62) - 1
    m31 = (1 << 31) - 1
    try:
        for i, (xmin, ymin, xmax, ymax) in enumerate(boxes):
            row = out[i]
            for j, (c, lo, hi) in enumerate(
                    ((xmin, -180.0, 360.0), (xmax, -180.0, 360.0),
                     (ymin, -90.0, 180.0), (ymax, -90.0, 180.0))):
                frac = (float(c) - lo) / hi
                frac = 0.0 if frac < 0.0 else (1.0 if frac > 1.0 else frac)
                v = min(math.floor(math.ldexp(frac, 62)), m62)
                row[2 * j] = v >> 31
                row[2 * j + 1] = v & m31
    except (ValueError, OverflowError):   # NaN / inf coordinate
        return None
    return out


def collect_values(f: Optional[ir.Filter], sft, string_vocabs,
                   out: list) -> None:
    """Value-collecting twin of ``scan.compile_residual``'s walk (and of
    ``compiled._lower_residual``'s): appends this query's residual constants
    to ``out`` in the SAME traversal order the compiler hoisted its params
    (the lowering allocated its layout slots), raising ``Unsupported`` for a
    node the device does not evaluate. The caller checks every value against
    the template's slot; any drift goes back to the full path."""
    if f is None:
        return
    if isinstance(f, (ir.Include, ir.Exclude)):
        return
    if isinstance(f, (ir.And, ir.Or)):
        for c in f.children:
            collect_values(c, sft, string_vocabs, out)
        return
    if isinstance(f, ir.Not):
        collect_values(f.child, sft, string_vocabs, out)
        return
    if isinstance(f, ir.Cmp):
        attr = sft.attribute(f.attr)
        if attr.type_name == "String":
            vocab = string_vocabs.get(f.attr)
            if vocab is None:
                raise Unsupported("no vocab")
            try:
                out.append(vocab.index(f.value))
            except ValueError:
                out.append(-1)
            return
        if attr.type_name not in _EXACT_DEVICE_TYPES:
            raise Unsupported("inexact cmp")
        out.append(f.value)
        return
    if isinstance(f, ir.In):
        attr = sft.attribute(f.attr)
        if attr.type_name == "String":
            vocab = string_vocabs.get(f.attr)
            if vocab is None:
                raise Unsupported("no vocab")
            codes = [vocab.index(v) for v in f.values if v in vocab] or [-1]
        elif attr.type_name in ("Int", "Integer"):
            codes = [int(v) for v in f.values]
        else:
            raise Unsupported("IN on non-int/string")
        size = max(1, 1 << (len(codes) - 1).bit_length())
        out.append(codes + [codes[-1]] * (size - len(codes)))
        return
    raise Unsupported(type(f).__name__)


class ShapeBinder:
    """The extraction of ``BaseIndex.plan`` for filters of one shape on one
    index, held to what the shape's first plan came out with: a spatial
    primary of ``n_boxes`` padded boxes, ``n_windows`` padded time windows,
    no host residual."""

    __slots__ = ("index", "n_boxes", "n_windows")

    def __init__(self, plan: IndexScanPlan):
        self.index = plan.index
        self.n_boxes = 0 if plan.boxes_loose is None \
            else len(plan.boxes_loose)
        self.n_windows = 0 if plan.windows is None else len(plan.windows)

    def extract(self, f: ir.Filter):
        """→ (user-space boxes, (B, 8) fp62 boxes, intervals, (T, 4)
        windows, device residual IR) | EMPTY | None. Each is what
        ``index.plan(f)`` would put on its plan, bit for bit."""
        index = self.index
        if index.geom is None:
            return None
        ext = extract_bboxes(f, index.geom)
        if len(ext.boxes) == 0:
            return EMPTY
        if ext.unconstrained:
            return None
        boxes = boxes_fp62_fast(ext.boxes) if len(ext.boxes) <= 4 else None
        if boxes is None:
            boxes = _boxes_fp62(ext.boxes)
        if len(boxes) & (len(boxes) - 1):
            boxes = pad_boxes(boxes)
        if len(boxes) != self.n_boxes:
            return None
        intervals = windows = None
        if index.dtg:
            iv = extract_intervals(f, index.dtg)
            intervals = iv.intervals
            if len(intervals) == 0:
                return EMPTY
            if not iv.unconstrained:
                windows = index.time_windows(intervals)
        if (0 if windows is None else len(windows)) != self.n_windows:
            return None
        residual = _strip_handled(f, index.geom, index.dtg, index.points)
        dev_ir, host_ir = split_residual(
            residual, index.sft, index.vocabs, set(index.device.columns))
        if host_ir is not None:
            return None   # refine shapes go through the planner
        return ext.boxes, boxes, intervals, windows, dev_ir


class PlanTemplate:
    """A shape's first plan with the values taken out: ``bind(f)`` puts a
    same-shape filter's values in and yields the ``IndexScanPlan`` that
    ``planner._apply_auths(planner._plan(f), auths)`` would yield on this
    index, field for field, without asking any index for a plan.

    The strategy is the template's: ``_plan`` prices the candidate indexes
    by the values' estimated selectivity, a bound plan keeps the index the
    shape's first plan chose (as ``compiled``'s recipes always have for
    ``ds.count``). Every strategy answers exactly, so the answer cannot
    differ; which index scans can.

    ``base`` is ``_plan``'s plan, ``folded`` the same after
    ``_apply_auths``: what the fold appended to the residual's parameters
    (the allowed visibility codes) depends on the auths and the table, not
    on the filter, and is carried over as it is. The caller keys the
    template by both."""

    __slots__ = ("binder", "kind", "cost", "explain", "residual", "n_vis",
                 "value_types")

    def __init__(self, base: IndexScanPlan, folded: IndexScanPlan,
                 value_types: tuple):
        self.binder = ShapeBinder(base)
        self.kind = base.primary_kind
        self.cost = base.cost
        # a copy: a plan that runs alone gets its cover's stats written here
        self.explain = dict(folded.explain)
        self.residual = folded.residual_device
        self.n_vis = (len(folded.residual_device[1]) - len(value_types)
                      if folded.residual_device else 0)
        self.value_types = value_types

    @classmethod
    def of(cls, base: IndexScanPlan,
           folded: IndexScanPlan) -> Optional["PlanTemplate"]:
        """The template of a shape's first plan, or None where the plan's
        residual constants are not the ones ``collect_values`` finds (such
        a shape is planned in full every time)."""
        index = base.index
        values: list = []
        try:
            collect_values(base.explain.get("residual_device"), index.sft,
                           index.vocabs, values)
        except Unsupported:
            return None
        rd = base.residual_device
        if len(values) != (len(rd[1]) if rd else 0):
            return None
        return cls(base, folded, tuple(type(v) for v in values))

    def bind(self, f: ir.Filter) -> Optional[IndexScanPlan]:
        binder = self.binder
        got = binder.extract(f)
        if got is None or got is EMPTY:
            return None   # emptiness is planned in full, as any drift is
        ext_boxes, boxes, intervals, windows, dev_ir = got
        index = binder.index
        values: list = []
        try:
            collect_values(dev_ir, index.sft, index.vocabs, values)
        except Unsupported:
            return None
        if tuple(type(v) for v in values) != self.value_types:
            return None   # a literal of another type: compiled anew
        residual = self.residual
        if residual is not None:
            key, tparams, fn = residual
            params = []
            for v, t in zip(values, tparams):
                a = np.asarray(v, dtype=t.dtype)
                if a.shape != t.shape:
                    return None   # an IN list of another padded size
                params.append(a)
            if self.n_vis:
                params.extend(tparams[-self.n_vis:])
            residual = (key, params, fn)
        return IndexScanPlan(
            index=index, primary_kind=self.kind, boxes_loose=boxes,
            windows=windows, residual_device=residual, full_filter=f,
            cost=self.cost,
            explain=dict(self.explain, boxes=ext_boxes, intervals=intervals,
                         residual_device=dev_ir))
