"""Query planning & execution (≙ reference index.planning package:
QueryPlanner.scala:36, FilterSplitter, StrategyDecider).

Flow (mirrors call stack SURVEY.md §3.3):
  1. parse/normalize the filter
  2. ask each index for a strategy + heuristic cost; pick the cheapest
     (CostBasedStrategyDecider:140-168 moral equivalent — stats integration
     arrives with the stats subsystem)
  3. execute: fused device mask scan → (count | nonzero-select) → host
     boundary/residual refinement → hydrate rows

Exactness: results are always exact. The device scan is a superset prune;
definite matches come from strict (cell-interior) masks, and only the
boundary band re-evaluates in f64 on the host.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Union

import numpy as np

from geomesa_tpu import trace as _trace
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.filter.evaluate import evaluate as _evaluate
from geomesa_tpu.filter.evaluate import evaluate_at as _evaluate_at
from geomesa_tpu.filter import ir
from geomesa_tpu.filter.parser import parse_ecql
from geomesa_tpu.index.api import IndexScanPlan, QueryResult, UnionScanPlan
from geomesa_tpu.index import prune as _prune
from geomesa_tpu.metrics import REGISTRY as _metrics
from geomesa_tpu.serve.resilience import deadline as _rdl

_SELECT_CAP = 1 << 16
# select-capacity tiers: each distinct capacity compiles its own packed
# select kernel (seconds of XLA compile time each), so capacity hints
# quantize UP to a coarse tier instead of the exact power of two
_SELECT_TIERS = (1 << 10, 1 << 13, _SELECT_CAP, 1 << 19, 1 << 22)


def _select_tier(capacity) -> int:
    if capacity is None:
        return _SELECT_CAP
    for t in _SELECT_TIERS:
        if capacity <= t:
            return t
    return 1 << max(0, (int(capacity) - 1)).bit_length()


def _pad_pow2(arr: np.ndarray, fill: int) -> np.ndarray:
    size = max(1, 1 << max(0, (len(arr) - 1)).bit_length())
    out = np.full(size, fill, dtype=np.int32)
    out[: len(arr)] = arr
    return out


def tally_join(counts: np.ndarray, sums: np.ndarray, polys: np.ndarray,
               values) -> None:
    """Add matched (point, polygon) couples to a join's running answer:
    ``polys`` the polygon of each, ``values`` the summed attributes' values
    of its point, one array a row of ``sums``."""
    counts += np.bincount(polys, minlength=len(counts))
    for k, v in enumerate(values):
        sums[k] += np.bincount(polys, weights=v,
                               minlength=len(counts)).astype(np.int64)


class QueryPlanner:
    """Planner + executor for one feature type."""

    def __init__(self, sft, table: FeatureTable, indexes: List[object],
                 stats=None, interceptors: Optional[list] = None,
                 audit=None, timeout_ms: Optional[float] = None):
        self.sft = sft
        self.table = table
        self.indexes = indexes
        self.stats = stats  # GeoMesaStats for cost-based strategy selection
        self.interceptors = interceptors if interceptors is not None else []
        self.audit = audit              # AuditWriter | None
        self.timeout_ms = timeout_ms    # cooperative deadline (guards.Deadline)
        self._fid_map: Optional[Dict[str, int]] = None

    # -- fid lookup (≙ IdIndex direct row lookup) ---------------------------

    @property
    def fid_map(self) -> Dict[str, int]:
        if self._fid_map is None:
            self._fid_map = {fid: i for i, fid in enumerate(self.table.fids)}
        return self._fid_map

    # -- planning -----------------------------------------------------------

    def plan(self, f: Union[str, ir.Filter]) -> IndexScanPlan:
        if not _trace.enabled():
            return self._plan(f)
        t0 = time.perf_counter_ns()
        try:
            return self._plan(f)
        finally:
            t1 = time.perf_counter_ns()
            _trace.record("plan", "plan", (t1 - t0) / 1e9, t1)

    def _plan(self, f: Union[str, ir.Filter]) -> IndexScanPlan:
        if isinstance(f, str):
            f = parse_ecql(f)
        for ic in self.interceptors:
            f = ic.rewrite(f, self.sft)  # ≙ QueryInterceptor.rewrite
        if isinstance(f, ir.FidFilter):
            return IndexScanPlan(None, "fid", full_filter=f, cost=0.5,
                                 explain={"index": "id", "fids": f.fids})
        if not self.indexes:
            raise ValueError(f"No indexes for {self.sft.name}")
        plans = [p for p in (idx.plan(f) for idx in self.indexes) if p is not None]
        if self.stats is not None and self.stats.total > 0 and len(plans) > 1:
            # cost-based strategy selection (≙ CostBasedStrategyDecider,
            # StrategyDecider.scala:140-168): price each strategy by the
            # estimated rows its PRIMARY constraints leave to scan; the
            # heuristic cost breaks ties.
            est = self.stats.estimator
            n = self.stats.total

            def priced(p):
                if p.empty:
                    return (0.0, p.cost)
                if p.candidate_slices is not None:
                    # attribute slices: the scanned row count is exact
                    return (float(p.n_candidates), p.cost)
                sel = 1.0
                boxes = p.explain.get("boxes")
                if p.boxes_loose is not None and boxes:
                    s = est.spatial_selectivity(boxes)
                    if s is not None:
                        sel *= s
                intervals = p.explain.get("intervals")
                if p.windows is not None and intervals:
                    s = est.temporal_selectivity(intervals)
                    if s is not None:
                        sel *= s
                # per-curve cover quality: an S2 cover scans ~1.1x the true
                # rows where z-covers scan ~1.02x (measured, curves/s2.py),
                # so equal selectivities must not tie
                slop = getattr(p.index, "cover_slop", 1.0)
                return (sel * n * slop, p.cost)

            chosen = min(plans, key=priced)
        else:
            chosen = min(plans, key=lambda p: p.cost)
        if isinstance(f, ir.Or) and chosen.residual_host is not None:
            # OR → multi-strategy (≙ FilterSplitter.getQueryOptions OR
            # expansion): when every branch plans with real primary
            # constraints, per-branch scans + row-set union beat the
            # union-boxes prefilter + host residual the single plan needs
            union = self._union_plan(f)
            if union is not None:
                chosen = union
        for ic in self.interceptors:   # ≙ query guards veto (QueryPlanner:148)
            msg = ic.guard(chosen, f, self.sft)
            if msg:
                from geomesa_tpu.index.guards import QueryGuardError
                raise QueryGuardError(msg)
        return chosen

    def _union_plan(self, f: ir.Or) -> Optional[UnionScanPlan]:
        """Per-branch plans for an OR filter, or None when any branch would
        degenerate to an unconstrained scan (then the single superset plan
        wins). Branch count is capped like the reference's DNF expansion."""
        if len(f.children) > 8:
            return None
        branches = []
        cost = 0.0
        for c in f.children:
            plans = [p for p in (idx.plan(c) for idx in self.indexes)
                     if p is not None]
            if not plans:
                return None
            bp = min(plans, key=lambda p: p.cost)
            if bp.empty:
                continue
            if bp.primary_kind == "none" and bp.candidate_slices is None:
                return None  # unconstrained branch: union buys nothing
            branches.append((c, bp))
            cost += bp.cost
        return UnionScanPlan(
            branches=branches, full_filter=f, cost=cost,
            empty=not branches,
            explain={"index": "union",
                     "strategies": [p.explain.get("index")
                                    for _, p in branches]})

    def explain(self, f: Union[str, ir.Filter], analyze: bool = False,
                auths=None) -> Dict[str, object]:
        """Hierarchical plan description (≙ Explainer / CLI explain). The
        ``trace`` key carries the span tree of the dry-run (plan + range
        decomposition — no scan executes), so explain shows where planning
        time goes, not just what the plan is.

        ``analyze=True`` (≙ EXPLAIN ANALYZE) additionally EXECUTES the
        plan's count path inside the same trace and annotates each span in
        the returned tree with its device ms and cache provenance, plus an
        ``analyze`` summary: rows scanned/matched, device-vs-host split,
        per-stage self times."""
        with _trace.trace("explain", type=self.sft.name) as t:
            plan = self.plan(f)
            blocks = self._pruned_blocks(plan)  # surface the pruning decision
            n = None
            if analyze:
                plan_x = self._apply_auths(plan, auths)
                n = self._count(
                    plan_x, f if isinstance(f, ir.Filter) else parse_ecql(f),
                    auths)
        out = dict(plan.explain)
        if t is not None:
            tdict = t.to_dict()
            if analyze:
                from geomesa_tpu.obs import attrib as _oattrib
                _oattrib.annotate_tree(tdict["root"])
            out["trace"] = tdict
        out["scan"] = "range-pruned" if blocks is not None else "full-mask"
        out.update({
            "type": self.sft.name,
            "strategy": plan.primary_kind,
            "cost": plan.cost,
            "empty": plan.empty,
            "n_boxes": 0 if plan.boxes_loose is None else len(plan.boxes_loose),
            "n_windows": 0 if plan.windows is None else len(plan.windows),
        })
        # how the serving index was built (the GET /progress history for
        # this type + the owning index's per-stage timings): a slow query
        # on a freshly-built index explains against its build, not a void
        if plan.index is not None:
            build: Dict[str, object] = {}
            stages = getattr(plan.index, "build_stages", None)
            if stages:
                build["stages"] = dict(stages)
            from geomesa_tpu.obs.profiling import PROGRESS
            phases = PROGRESS.recent(type_name=self.sft.name, limit=8)
            if phases:
                build["recent_phases"] = phases
            if build:
                out["build"] = build
        if analyze and t is not None:
            stages = t.self_times_ms()
            device_ms = stages.get("device_scan", 0.0) \
                + stages.get("device_wait", 0.0)
            out["analyze"] = {
                "executed": True,
                "rows_matched": int(n) if n is not None else None,
                "rows_scanned": (len(blocks) * _prune.BLOCK_SIZE
                                 if blocks is not None else len(self.table)),
                "duration_ms": round(t.duration_ms, 3),
                "device_ms": round(device_ms, 3),
                "host_ms": round(max(0.0, t.duration_ms - device_ms), 3),
                "stages_ms": {k: round(v, 3) for k, v in stages.items()},
                # direct-path execution never serves from the scheduler's
                # plan cache; the store-level explain overlays the
                # live scheduler's provenance when one is running
                "provenance": {"plan": "fresh",
                               "cover": "fresh" if blocks is not None
                               else "n/a"},
            }
        return out

    # -- visibility enforcement (≙ VisibilityFilter, geomesa-security) -------

    def _apply_auths(self, plan: IndexScanPlan, auths) -> IndexScanPlan:
        """Fold an auths-derived visibility mask into the plan's device
        residual: each DISTINCT visibility expression evaluates once on the
        host; the device tests dictionary-code membership."""
        if auths is None or self.table.visibility is None or plan.empty \
                or plan.explain.get("__vis_applied__"):
            return plan
        if isinstance(plan, UnionScanPlan):
            # branches fold the auths mask individually at execution time
            return plan
        import dataclasses

        import jax.numpy as jnp

        from geomesa_tpu.security.visibility import allowed_codes

        # the __vis_applied__ marker lands in a COPIED explain dict on the
        # replaced plan only: dataclasses.replace shares the explain dict, so
        # marking the original would make a reused plan (prepared query,
        # plan cache, union branch) silently skip the auths fold on its next
        # execution — exactly the privileged-plan leak the marker guards
        # against double-folding, inverted
        marked = dict(plan.explain, __vis_applied__=True)
        vocab = self.table.visibility.vocab
        allowed = allowed_codes(vocab, auths)
        if len(allowed) == len(vocab):
            # every expression visible — no mask needed, but still mark the
            # handed-back plan so a re-apply is a no-op
            return dataclasses.replace(plan, explain=marked)
        if len(allowed) == 0:
            return dataclasses.replace(plan, empty=True, explain=marked)
        padded = _pad_pow2(allowed, fill=-1)
        key, params, fn = plan.residual_device or ("none", [], None)
        i = len(params)

        def fn2(cols, p, fn=fn, i=i):
            m = jnp.any(cols["__vis__"][:, None] == p[i][None, :], axis=1)
            return m if fn is None else (m & fn(cols, p))

        return dataclasses.replace(
            plan, explain=marked,
            residual_device=(f"vis{len(padded)}&({key})",
                             list(params) + [padded], fn2))

    def _fid_vis_filter(self, rows: np.ndarray, auths) -> np.ndarray:
        if auths is None or self.table.visibility is None or len(rows) == 0:
            return rows
        from geomesa_tpu.security.visibility import allowed_codes
        allowed = allowed_codes(self.table.visibility.vocab, auths)
        return rows[np.isin(self.table.visibility.codes[rows], allowed)]

    # -- range pruning -------------------------------------------------------

    def _pruned_blocks(self, plan: IndexScanPlan):
        """Candidate gather-blocks for a plan (cached on the plan), or None
        when the full-table fused mask is the better scan. ≙ choosing ranged
        scans over a full-table scan (QueryProperties.BlockFullTableScans).
        The scheduler's fused groups do not come here: a group gets one cover
        for all its members' boxes (``cover_blocks``)."""
        from geomesa_tpu import config
        if not config.PRUNE_ENABLED.get():
            return None
        if plan.blocks is False:
            # per-request deadline checkpoint: the range decomposition is
            # the priciest host stage before device dispatch — a request
            # whose budget already lapsed must not start it
            _rdl.check_current("range_decompose")
            blocks = None
            if (not plan.empty and plan.index is not None
                    and plan.candidate_slices is None
                    and hasattr(plan.index, "candidate_blocks")):
                if _trace.enabled():
                    t0 = time.perf_counter_ns()
                    blocks = plan.index.candidate_blocks(plan)
                    t1 = time.perf_counter_ns()
                    _trace.record("range_decompose", "range_decompose",
                                  (t1 - t0) / 1e9, t1)
                else:
                    blocks = plan.index.candidate_blocks(plan)
            plan.blocks = blocks
        return plan.blocks

    # -- execution ----------------------------------------------------------

    def _write_audit(self, plan, f, plan_ms: float, scan_ms: float,
                     hits: int) -> None:
        if self.audit is None:
            return
        from geomesa_tpu.index.guards import QueryEvent
        self.audit.write(QueryEvent(
            type_name=self.sft.name, filter=str(f),
            ts_ms=int(time.time() * 1000), plan_time_ms=round(plan_ms, 3),
            scan_time_ms=round(scan_ms, 3), hits=hits,
            index=str(plan.explain.get("index", ""))))

    def prepare(self, f: Union[str, ir.Filter], auths=None) -> "PreparedQuery":
        """Plan once and stage all query constants on device; the returned
        handle re-executes without re-parsing, re-planning, or re-uploading
        (≙ a configured scan the reference would hand each tablet server;
        also the natural unit for pipelined dispatch).

        When this (filter shape, auths) has fused before, the recipe fast
        path (index/compiled.py) binds the new values straight into the
        compiled single-dispatch program — no planning, no range decompose,
        no per-constant uploads. The ordinary path registers each shape's
        outcome so its NEXT occurrence takes the fast path."""
        from geomesa_tpu.index import compiled as _fused
        f_ir = f if isinstance(f, ir.Filter) else parse_ecql(f)
        fp = _fused.fast_prepare(self, f_ir, auths)
        if fp is not None:
            return fp
        plan = self._apply_auths(self.plan(f_ir), auths)
        pq = PreparedQuery(self, plan, f_ir, auths)
        _fused.note_shape(self, plan, f_ir, auths, pq._fused)
        return pq

    def count(self, f: Union[str, ir.Filter], auths=None) -> int:
        from geomesa_tpu.index.guards import Deadline
        with _trace.trace("count", type=self.sft.name, filter=str(f)):
            dl = Deadline(self.timeout_ms)
            t0 = time.perf_counter()
            plan = self._apply_auths(self.plan(f), auths)
            plan_ms = (time.perf_counter() - t0) * 1000
            dl.check("plan")
            t1 = time.perf_counter()
            n = self._count(plan, f, auths)
            dl.check("scan")
            self._write_audit(plan, f, plan_ms,
                              (time.perf_counter() - t1) * 1000, n)
            return n

    def _count(self, plan: IndexScanPlan, f, auths) -> int:
        if plan.empty:
            return 0
        if isinstance(plan, UnionScanPlan):
            idx = plan.same_index_device_exact()
            if idx is not None:
                # fused OR-of-masks count: branch masks OR on device, one
                # scalar readback (branch overlaps dedup in the OR itself)
                import functools

                import jax.numpy as jnp
                masks = [idx.kernels.mask(
                    bp2.primary_kind, bp2.boxes_loose, bp2.windows,
                    bp2.residual_device)
                    for bp2 in (self._apply_auths(bp, auths)
                                for _, bp in plan.branches)]
                return int(jnp.sum(functools.reduce(
                    lambda a, b: a | b, masks)))
            return len(self._union_select(plan, auths))
        if plan.primary_kind == "fid":
            return len(self._fid_vis_filter(
                self._fid_rows(plan.full_filter), auths))
        from geomesa_tpu.index import compiled as _fused
        if plan.residual_host is None:
            # fully device-exact: one fused reduction, one roundtrip
            if plan.candidate_slices is not None:
                return plan.index.kernels.count_at(
                    plan.primary_kind, plan.boxes_loose, plan.windows,
                    plan.residual_device, plan.candidate_positions())
            fused = _fused.try_count(self, plan)
            if fused is not None:
                return fused
            blocks = self._pruned_blocks(plan)
            if blocks is not None:
                if len(blocks) == 0:
                    return 0
                return plan.index.kernels.count_blocks(
                    plan.primary_kind, plan.boxes_loose, plan.windows,
                    plan.residual_device, blocks, _prune.BLOCK_SIZE)
            return plan.index.kernels.count(
                plan.primary_kind, plan.boxes_loose, plan.windows,
                plan.residual_device)
        fast = self._band_intersects_count(plan)
        if fast is not None:
            return fast
        fused = _fused.try_count_refine(self, plan)
        if fused is not None:
            return fused
        return len(self.select_indices(
            f if isinstance(f, ir.Filter) else parse_ecql(f),
            plan=plan, auths=auths))

    def _band_intersects_count(self, plan) -> Optional[int]:
        """Device certainty-band count for the common extent query shape: a
        single polygon-INTERSECTS residual over an extent layer whose index
        keeps a segment pool. The kernel classifies the candidate blocks'
        ways as certain-hit / certain-miss / uncertain (f32 error bands),
        and only the uncertain sliver refines on host in exact f64. None
        when the shape doesn't apply, or when the uncertain ways overflowed
        the kernel's list (counted; the caller refines every candidate)."""
        res = plan.residual_host
        if not (isinstance(res, ir.Intersects) and plan.index is not None
                and plan.candidate_slices is None
                and plan.primary_kind == "bbox_overlap"):
            return None
        from geomesa_tpu.features import geometry as geo
        if res.geometry[0] != geo.POLYGON:
            return None
        seg_off = getattr(plan.index, "seg_off", None)
        if seg_off is None:
            return None
        blocks, bsz = self._pruned_blocks(plan), _prune.BLOCK_SIZE
        if blocks is None:
            # no cover, or one too wide to pay for a point scan: every block
            # (a table under one block is one block of its own size)
            bsz = min(bsz, len(self.table))
            blocks = np.arange(-(-len(self.table) // bsz), dtype=np.int32)
        if len(blocks) == 0:
            return 0
        from geomesa_tpu.filter.geom_numpy import literal_segments
        edges = literal_segments(res.geometry).astype(np.float32)
        with _trace.span("refine.device", blocks=len(blocks),
                         edges=len(edges)) as sp:
            certain, unc, band = plan.index.kernels.intersects_band_blocks(
                plan.primary_kind, plan.boxes_loose, plan.windows,
                plan.residual_device, edges, blocks, bsz, seg_off)
            sp.set(ways=band["candidate_ways"], segments=band["segments"],
                   certain=certain, uncertain=band["uncertain_ways"])
        _metrics.inc("refine.segments_tested", band["segments"])
        _metrics.inc("refine.ways_candidate", band["candidate_ways"])
        _metrics.inc("refine.ways_uncertain", band["uncertain_ways"])
        if unc is None:
            _metrics.inc("refine.overflow_fallbacks")
            return None  # uncertainty overflow: full host refine instead
        if len(unc) == 0:
            return certain
        from geomesa_tpu.filter.geom_batch import batch_intersects
        with _trace.span("refine", kind="refine", rows=len(unc)):
            rows = plan.index.map_rows(unc)
            return certain + int(batch_intersects(
                self.table.geometry(), rows, res.geometry).sum())

    # -- spatial join (≙ geomesa-spark-sql SpatialJoinStrategy) --------------

    def join_polygons(self, f: Union[str, ir.Filter],
                      other: "QueryPlanner", boundary: bool, stats: tuple,
                      auths=None):
        """This point type's rows that pass ``f``, grouped by the polygons
        of ``other``'s table that hold them: (counts (P,), sums
        (len(stats), P)), int64, in that table's order; ``stats`` names
        Integer attributes. A point on a polygon's boundary counts where
        ``boundary`` (st_intersects) and not where not (st_contains).

        Planned and covered as a count of ``f``. Then the gate pairs the
        candidate blocks' tiles (a block's rows in ascending y) with the
        polygons whose envelope they meet and, of each, the slab of
        segments that can reach the tile's y-range; one grouped launch
        classifies every (point, polygon) couple of those pairs against the
        slabs in ``other``'s y-sorted pool (f32, with certainty bands) and
        reduces the certain ones; the host settles the uncertain couples in
        f64. A plan the device cannot mask alone, or a polygon type without
        a pool, joins on the host."""
        from geomesa_tpu.filter import geom_batch as _gb
        from geomesa_tpu.index import scan as _scan
        f = f if isinstance(f, ir.Filter) else parse_ecql(f)
        polygons = other.table.geometry()
        counts = np.zeros(len(polygons), dtype=np.int64)
        sums = np.zeros((len(stats), len(polygons)), dtype=np.int64)
        values = [np.asarray(self.table.columns[a]) for a in stats]
        px, py = self.table.geometry().point_xy()

        def add(rows, polys):
            tally_join(counts, sums, polys, [v[rows] for v in values])

        plan = self._apply_auths(self.plan(f), auths)
        if plan.empty or len(polygons) == 0:
            return counts, sums
        pool = next((i for i in other.indexes
                     if getattr(i, "seg_ykey", None) is not None), None)

        def on_the_host():
            with _trace.span("join.refine", kind="refine") as sp:
                rows = self.select_indices(f, plan=plan, auths=auths)
                # a window's end inside an offset unit: the plan's windows
                # hold the whole unit (time_windows)
                rows = rows[_evaluate_at(f, self.table, rows)]
                sp.set(pairs=len(rows) * len(polygons))
                i, polys = _gb.points_in_polygons(px[rows], py[rows],
                                                  polygons, boundary)
                add(rows[i], polys)
            return counts, sums

        if pool is None or plan.residual_host is not None \
                or plan.candidate_slices is not None \
                or "xf" not in getattr(plan.index, "device", {}):
            return on_the_host()

        idx = plan.index
        blocks = self._pruned_blocks(plan)
        with _trace.span("join.gate") as sp:
            bsz = min(_prune.BLOCK_SIZE, len(self.table))
            tile = min(_scan.JOIN_TILE, bsz)
            env = idx.join_envelopes(bsz, tile)
            blocks = _prune.gate_blocks(
                env, blocks, plan.windows, plan.explain.get("boxes")
                if plan.boxes_loose is not None else None)
            pairs = _prune.gate_slabs(
                env, blocks, pool.polygon_envelopes(), pool.seg_ykey,
                pool.seg_rise, _scan.SEG_STEP)
            sp.set(blocks=len(blocks), polygons=len(np.unique(pairs[:, 4])),
                   pairs=len(pairs))
        _metrics.inc("join.block_polygon_pairs", len(pairs))
        # a slab of more segments than the pool's pad cannot be read in one
        # slice: such a pair is the host's
        wide = pairs[:, 3] > _scan.POOL_TILE
        host, pairs, unc = pairs[wide], pairs[~wide], None
        late = np.empty(0, dtype=np.int64)
        if len(pairs):
            _rdl.check_current("join.device")
            pcols = pool.device.columns
            with _trace.span("join.device", blocks=len(blocks),
                             pairs=len(pairs)) as sp:
                strict = None if plan.windows is None else idx.time_windows(
                    plan.explain["intervals"], strict=True)
                inside, open_, psums, passed, unc, late, launches = \
                    idx.kernels.join_band_blocks(
                        plan.primary_kind, plan.boxes_loose, plan.windows,
                        plan.residual_device, strict, pcols[_scan.SEGY],
                        [pcols[k] for k in ("bxmin", "bymin", "bxmax",
                                            "bymax")],
                        idx._dev_perm, blocks.astype(np.int32), bsz, pairs,
                        stats)
                if idx._dev_perm is None:
                    late = late if late is None else idx.perm[late]
                    if unc is not None:
                        unc[:, 1] = idx.perm[unc[:, 1]]
                edges = int(np.take(_scan.JOIN_WIDTHS, np.searchsorted(
                    _scan.JOIN_WIDTHS, pairs[:, 3])).sum()) * tile
                slab = int((pairs[:, 3] - pairs[:, 2]).sum())
                sp.set(points=len(blocks) * bsz, edges=edges,
                       certain=int(inside.sum()), uncertain=int(open_.sum()),
                       launches=launches)
            _metrics.inc("join.launches", launches)
            _metrics.inc("join.points_scanned", len(blocks) * bsz)
            _metrics.inc("join.point_pairs", int(passed.sum()))
            _metrics.inc("join.edge_tests", edges)
            # the tests the pairs' own slabs need, of those the kernel ran
            _metrics.inc("join.slab_tests", slab * tile)
            _metrics.inc("join.segments_read", slab)
            _metrics.inc("join.pairs_uncertain", int(open_.sum()))
            if late is None:
                # more rows in the windows' boundary units than the kernel
                # lists: only the host's filter can tell them apart
                return on_the_host()
            if unc is None:
                # more uncertain couples than the kernel lists: every pair
                # that has one is the host's, whole
                _metrics.inc("join.overflow_fallbacks")
                redo = open_ > 0
                host = np.concatenate([host, pairs[redo]])
                pairs, inside, psums = pairs[~redo], inside[~redo], \
                    psums[:, ~redo]
            at = pool.map_rows(pairs[:, 4])
            counts += np.bincount(at, weights=inside,
                                  minlength=len(counts)).astype(np.int64)
            for k in range(len(stats)):
                np.add.at(sums[k], at, psums[k])
        with _trace.span("join.refine", kind="refine") as sp:
            n_refined = 0
            if len(late):
                # rows of a window's boundary unit (a second, for weeks):
                # in no tile's numbers, theirs are the host's
                late = late[_evaluate_at(f, self.table, late)]
                if auths is not None:
                    late = self._fid_vis_filter(late, auths)
                i, polys = _gb.points_in_polygons(px[late], py[late],
                                                  polygons, boundary)
                add(late[i], polys)
                n_refined += len(late) * len(polygons)
            if unc is not None and len(unc):
                rows = unc[:, 1]
                polys = pool.map_rows(pairs[unc[:, 0], 4])
                hit = _gb.pairs_in_polygons(px[rows], py[rows], polys,
                                            polygons, boundary)
                add(rows[hit], polys[hit])
                n_refined += len(rows)
            if len(host):
                # the rows of the host's tiles: a block's, in ascending y
                # of the f32 plane, as the kernel cuts them
                per = -(-bsz // tile)
                which, t = np.divmod(host[:, 0], per)
                theirs = pool.map_rows(host[:, 4])
                rows, polys = [], []
                for j in np.unique(which):
                    lo = int(blocks[j]) * bsz
                    block = idx.map_rows(np.arange(
                        lo, min(lo + bsz, len(self.table))))
                    block = block[np.argsort(py[block].astype(np.float32),
                                             kind="stable")]
                    for k in np.flatnonzero(which == j):
                        mine = block[t[k] * tile: (t[k] + 1) * tile]
                        rows.append(mine)
                        polys.append(np.full(len(mine), theirs[k]))
                rows, polys = np.concatenate(rows), np.concatenate(polys)
                ok = _evaluate_at(f, self.table, rows) & ~np.isin(rows, late)
                if auths is not None:
                    ok &= np.isin(rows, self._fid_vis_filter(rows, auths))
                rows, polys = rows[ok], polys[ok]
                hit = _gb.pairs_in_polygons(px[rows], py[rows], polys,
                                            polygons, boundary)
                add(rows[hit], polys[hit])
                n_refined += len(rows)
            sp.set(pairs=n_refined)
        return counts, sums

    def select_indices(self, f: Union[str, ir.Filter],
                       plan: Optional[IndexScanPlan] = None,
                       auths=None, capacity: Optional[int] = None) -> np.ndarray:
        """Matching row indices (ascending) into the master table.

        ``capacity``: expected match-count hint — sized from a prior count it
        avoids the overflow-retry rescans (index/scan.py select)."""
        if plan is None:
            plan = self.plan(f)
        # "scan" umbrella: its SELF time is constant staging + host glue
        # (pad/upload, map_rows, sort) around the nested device/refine spans
        with _trace.span("scan", kind="scan"):
            plan = self._apply_auths(plan, auths)
            if plan.empty:
                return np.empty(0, dtype=np.int64)
            if isinstance(plan, UnionScanPlan):
                return self._union_select(plan, auths)
            if plan.primary_kind == "fid":
                return self._fid_vis_filter(
                    self._fid_rows(plan.full_filter), auths)
            if plan.candidate_slices is not None:
                idx, _ = plan.index.kernels.select_at(
                    plan.primary_kind, plan.boxes_loose, plan.windows,
                    plan.residual_device, plan.candidate_positions())
            else:
                from geomesa_tpu.index import compiled as _fused
                if plan.residual_host is None:
                    pos = _fused.try_select(self, plan, capacity)
                    if pos is not None:
                        return np.sort(plan.index.map_rows(pos))
                else:
                    rows = _fused.try_select_refine(self, plan, capacity)
                    if rows is not None:
                        return rows
                blocks = self._pruned_blocks(plan)
                if blocks is not None:
                    if len(blocks) == 0:
                        return np.empty(0, dtype=np.int64)
                    idx, _ = plan.index.kernels.select_blocks(
                        plan.primary_kind, plan.boxes_loose, plan.windows,
                        plan.residual_device, blocks, _prune.BLOCK_SIZE,
                        _select_tier(capacity))
                else:
                    idx, _ = plan.index.kernels.select(
                        plan.primary_kind, plan.boxes_loose, plan.windows,
                        plan.residual_device, _select_tier(capacity))
            rows = plan.index.map_rows(idx)
            if plan.residual_host is None:
                return np.sort(rows)
            return np.sort(self._refine(plan, rows))

    def _union_select(self, plan: UnionScanPlan, auths) -> np.ndarray:
        """Union of per-branch row sets (sorted unique — OR-branch overlaps
        dedup here, ≙ the reference's de-duplication across strategies).
        When every branch is a device-exact scan on one index the whole
        union lowers to a single fused dispatch (the OR dedups in-program)."""
        from geomesa_tpu.index import compiled as _fused
        rows = _fused.try_union_select(self, plan, auths)
        if rows is not None:
            return rows
        sets = [self.select_indices(c, plan=bp, auths=auths)
                for c, bp in plan.branches]
        if not sets:
            return np.empty(0, dtype=np.int64)
        return np.unique(np.concatenate(sets))

    def scan_mask(self, f: Union[str, ir.Filter], auths=None):
        """(plan, device mask over the plan index's sorted rows) — None mask
        when the plan needs host refinement or is candidate-pruned. The mask
        stays on device for aggregation kernels to consume (≙ the shared
        AggregatingScan validate step)."""
        plan = self._apply_auths(self.plan(f), auths)
        if isinstance(plan, UnionScanPlan):
            idx = plan.same_index_device_exact()
            if idx is None or plan.empty:
                return plan, None
            import functools
            masks = [idx.kernels.mask(
                bp2.primary_kind, bp2.boxes_loose, bp2.windows,
                bp2.residual_device)
                for bp2 in (self._apply_auths(bp, auths)
                            for _, bp in plan.branches)]
            return plan, functools.reduce(lambda a, b: a | b, masks)
        if not plan.device_exact:
            return plan, None
        return plan, plan.index.kernels.mask(
            plan.primary_kind, plan.boxes_loose, plan.windows, plan.residual_device)

    def query(self, f: Union[str, ir.Filter], auths=None) -> QueryResult:
        from geomesa_tpu.index.guards import Deadline
        with _trace.trace("query", type=self.sft.name, filter=str(f)):
            dl = Deadline(self.timeout_ms)
            t0 = time.perf_counter()
            plan = self.plan(f)
            plan_ms = (time.perf_counter() - t0) * 1000
            dl.check("plan")
            t1 = time.perf_counter()
            rows = self.select_indices(f, plan=plan, auths=auths)
            dl.check("scan")
            self._write_audit(plan, f, plan_ms,
                              (time.perf_counter() - t1) * 1000, len(rows))
            with _trace.span("serialize", kind="serialize", rows=len(rows)):
                table = self.table.take(rows)
            return QueryResult(rows, table, plan)

    # -- helpers ------------------------------------------------------------

    def _fid_rows(self, f: ir.FidFilter) -> np.ndarray:
        rows = [self.fid_map[fid] for fid in f.fids if fid in self.fid_map]
        return np.array(sorted(rows), dtype=np.int64)

    def _refine(self, plan: IndexScanPlan, rows: np.ndarray) -> np.ndarray:
        """Host f64 re-evaluation of device candidates against the residual
        (≙ the reference's full-filter path over overlapping-range rows).
        Evaluates in place at the candidate rows — no sub-table, and geometry
        predicates run batched (geom_batch) rather than per-feature."""
        if len(rows) == 0 or plan.residual_host is None:
            return rows
        _rdl.check_current("refine")
        with _trace.span("refine", kind="refine", rows=len(rows)):
            mask = self._refine_mask(plan.residual_host, rows)
            return rows[mask]

    def _refine_mask(self, res: ir.Filter, rows: np.ndarray) -> np.ndarray:
        """Residual mask over candidate rows. st_* catalog calls in an AND
        residual route through the device kernels when enabled
        (GEOMESA_TPU_GEOM_KERNELS): the banded classify + exact-f64 refine of
        the uncertain sliver produces the SAME mask as the host oracle, so
        the staged path stays exact while the bulk of the predicate runs
        vmapped on device."""
        from geomesa_tpu import config as _cfg
        parts = res.children if isinstance(res, ir.And) else (res,)
        if _cfg.GEOM_KERNELS.get() \
                and any(isinstance(p, (ir.Func, ir.FuncCmp)) for p in parts):
            from geomesa_tpu.geom.functions import eval_filter_node
            mask = np.ones(len(rows), dtype=bool)
            rest = []
            for p in parts:
                if isinstance(p, (ir.Func, ir.FuncCmp)):
                    mask &= eval_filter_node(p, self.table, rows,
                                             kernels=True)
                else:
                    rest.append(p)
            if rest:
                mask &= _evaluate_at(ir.and_filters(rest), self.table, rows)
            return mask
        return _evaluate_at(res, self.table, rows)


class PreparedQuery:
    """A planned query with constants staged on device.

    ``count_async`` dispatches without blocking (returns the device scalar),
    so many queries pipeline over a single host↔device round trip;
    ``count``/``select_indices`` block for the value. Falls back to the
    planner's general execution when the plan needs host refinement,
    candidate pruning, or fid lookup.
    """

    def __init__(self, planner: QueryPlanner, plan: IndexScanPlan,
                 f: ir.Filter, auths):
        self.planner = planner
        self.plan = plan
        self.filter = f
        self.auths = auths
        self._count_disp = None
        self._fused = None
        if plan.device_exact:
            from geomesa_tpu.index import compiled as _fused
            prog = _fused.prepare_count_program(planner, plan)
            if prog is not None:
                # single-dispatch fused program: cover + scan + residual +
                # count in one device round; constants ride with the call
                self._fused = prog
                self._count_disp = lambda: prog.dispatch()[0]
                return
            blocks = planner._pruned_blocks(plan)
            if blocks is not None and len(blocks) > 0:
                self._count_disp = plan.index.kernels.prepare_count_blocks(
                    plan.primary_kind, plan.boxes_loose, plan.windows,
                    plan.residual_device, blocks, _prune.BLOCK_SIZE)
            elif blocks is None:
                self._count_disp = plan.index.kernels.prepare_count(
                    plan.primary_kind, plan.boxes_loose, plan.windows,
                    plan.residual_device)
            else:  # provably-empty candidate set
                self._count_disp = lambda: np.zeros((), dtype=np.int32)

    @property
    def device_exact(self) -> bool:
        """True when the whole query resolves on device (no host refine)."""
        return self._count_disp is not None

    def count_async(self):
        """Async dispatch → 0-d device array (None for empty plans)."""
        if self._count_disp is None:
            if self.plan.empty:
                return None
            raise ValueError("plan needs host execution; use count()")
        with _trace.span("device_scan", kind="device_scan"):
            return self._count_disp()

    def count(self) -> int:
        """Blocking count. Audited like planner.count (plan time 0) and
        subject to the planner's cooperative deadline."""
        from geomesa_tpu.index.guards import Deadline
        from geomesa_tpu.index.scan import _fetch
        attrs = {"type": self.planner.sft.name, "prepared": True}
        if _trace.enabled():
            attrs["filter"] = str(self.filter)
        with _trace.trace("count", **attrs):
            dl = Deadline(self.planner.timeout_ms)
            t0 = time.perf_counter()
            if self.plan.empty:
                n = 0
            elif self._fused is not None:
                n = int(self._fused.fetch())   # tallies its blocks
            elif self._count_disp is not None:
                n = int(_fetch(self._count_disp))
            else:
                n = self.planner._count(self.plan, self.filter, self.auths)
            dl.check("scan")
            self.planner._write_audit(self.plan, self.filter, 0.0,
                                      (time.perf_counter() - t0) * 1000, n)
            return n

    def select_indices(self) -> np.ndarray:
        return self.planner.select_indices(self.filter, plan=self.plan,
                                           auths=self.auths)
