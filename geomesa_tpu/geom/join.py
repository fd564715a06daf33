"""Mesh-sharded spatial joins: polygon literals × the sharded point table.

The distributed face of the geometry catalog. A spatial join here is the
``st_contains``/``st_intersects`` point-in-polygon shape: a small set of
polygon literals (the broadcast side) joined against the feature table
(the sharded side, partitioned by contiguous Morton key range across the
PR-15 cluster mesh). Execution follows the cluster scan discipline:

  - each process evaluates ONLY its local shard — the catalog's banded
    device kernels classify certain-in/certain-out in f32 and the f64
    host oracle refines the uncertain sliver, so every local verdict is
    exact (``geom.functions.eval_filter_node``, the same code path the
    filter IR uses);
  - per-polygon hit counts reduce with a psum round (allgather + sum —
    counted in ``cluster.psum_rounds`` and the collective telemetry,
    same ledger as ClusterScan's count);
  - pair selects (polygon → matching fids) cannot psum (ragged): each
    process compacts its local matches in index key order and the
    results merge host-side in RANK order. Rank order == Morton key
    order (contiguous key-range partitioning), so concatenation IS the
    global sort order — no re-sort, no k-way heap.

The single-process oracle is the identical code path under an inactive
runtime (one code path, two cardinalities), which is what makes the
2-process CPU dryrun's byte-equality check meaningful rather than
merely probable.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from geomesa_tpu.cluster.runtime import ClusterRuntime, note_collective
from geomesa_tpu.features import geometry as geo
from geomesa_tpu.filter import ir

JOIN_OPS = ("st_contains", "st_intersects")


@dataclass
class JoinResult:
    """Global join verdict — identical on every rank (the equality unit)."""

    op: str
    polygons: int
    counts: List[int]                      # per-polygon global hit counts
    pairs: List[List[str]]                 # per-polygon fids, global key order
    rows_local: int                        # this process's shard size
    rows_global: int                       # psum of shard sizes
    num_processes: int
    wall_s: float
    truncated: bool = False                # pairs capped at max_pairs
    meta: dict = field(default_factory=dict)

    def stable(self) -> dict:
        """The rank-invariant portion: identical on every rank AND on the
        single-process oracle — the dryrun's byte-equality surface."""
        return {
            "op": self.op, "polygons": self.polygons,
            "counts": [int(c) for c in self.counts],
            "pairs": [[str(f) for f in p] for p in self.pairs],
            "rows_global": int(self.rows_global),
            "truncated": bool(self.truncated),
        }

    def to_dict(self) -> dict:
        return {
            **self.stable(),
            "rows_local": int(self.rows_local),
            "num_processes": int(self.num_processes),
            "wall_s": round(float(self.wall_s), 3),
        }


def _literal(poly) -> tuple:
    """Accept WKT strings or parsed ``(code, data)`` literals."""
    lit = geo.parse_wkt(poly) if isinstance(poly, str) else poly
    if lit[0] not in (geo.POLYGON, geo.MULTIPOLYGON):
        raise ValueError(f"spatial join literal must be polygonal: {poly!r}")
    return lit


def _join_node(op: str, lit: tuple, attr: str) -> ir.Filter:
    """The filter-IR node one join probe evaluates — the SAME node shape
    the CQL parser produces for ``st_contains(POLYGON(..), geom)``, so
    join probes and filter queries share kernels, caches and parity."""
    if op == "st_contains":
        return ir.Func("st_contains", (lit, attr))
    if op == "st_intersects":
        return ir.Func("st_intersects", (attr, lit))
    raise ValueError(f"unsupported join op {op!r} (want one of {JOIN_OPS})")


def _psum_counts(rt: Optional[ClusterRuntime],
                 local: np.ndarray) -> np.ndarray:
    """psum a small int64 vector across the cluster (allgather + sum over
    the process axis). Inactive runtimes return the input — callers never
    branch, which is exactly what keeps the oracle on the same path."""
    if rt is None or not rt.active():
        return local
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    t0 = time.perf_counter()
    out = np.asarray(multihost_utils.process_allgather(jnp.asarray(local)))
    out = out.reshape(rt.num_processes, -1).sum(axis=0)
    rt.note_psum_round()
    note_collective("psum", time.perf_counter() - t0,
                    payload_bytes=int(local.nbytes) * rt.num_processes)
    return out.astype(np.int64)


def _merge_pairs(rt: Optional[ClusterRuntime],
                 local: List[List[str]]) -> List[List[str]]:
    """Rank-order merge of per-polygon fid lists (ragged → exchange)."""
    if rt is None or not rt.active():
        return local
    peers = rt.exchange({"pairs": local}, op="row_exchange")
    return [[fid for p in peers for fid in p["pairs"][j]]
            for j in range(len(local))]


def _key_order(planner) -> np.ndarray:
    """Local rows in primary index key order — the order whose rank-wise
    concatenation is the global key order (z3 when present, mirroring the
    partitioner's Morton coarsening; first index otherwise)."""
    idx = next((i for i in planner.indexes if i.name == "z3"),
               planner.indexes[0])
    return np.asarray(idx.perm, dtype=np.int64)


def local_matches(planner, polygons: Sequence, op: str = "st_contains",
                  rows: Optional[np.ndarray] = None,
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Shard-local probe: evaluate every polygon against the local table.

    Returns ``(counts, hits)`` — ``counts`` (P,) int64 local hit counts,
    ``hits`` (P, n_local) bool match matrix over ``rows`` (default: the
    primary index's key order, so downstream compaction is already in
    global-mergeable order). Kernel/oracle choice follows the
    ``GEOMESA_TPU_GEOM_KERNELS`` knob via ``eval_filter_node``."""
    from geomesa_tpu.geom.functions import eval_filter_node

    attr = planner.sft.geometry_attribute.name
    if rows is None:
        rows = _key_order(planner)
    nodes = [_join_node(op, _literal(p), attr) for p in polygons]
    hits = np.zeros((len(nodes), len(rows)), dtype=bool)
    for j, node in enumerate(nodes):
        hits[j] = eval_filter_node(node, planner.table, rows)
    return hits.sum(axis=1).astype(np.int64), hits


def spatial_join(planner, polygons: Sequence, op: str = "st_contains",
                 runtime: Optional[ClusterRuntime] = None,
                 fids: Optional[np.ndarray] = None,
                 rows: Optional[np.ndarray] = None,
                 with_pairs: bool = True,
                 max_pairs: Optional[int] = None) -> JoinResult:
    """Distributed ``op(polygon, geom)`` join against the sharded table.

    ``planner`` serves this process's LOCAL shard (on an inactive runtime:
    the whole table — the oracle). ``fids``/``rows`` default to the primary
    index's key order; pass the pair-select payload explicitly when the
    caller already holds it (the dryrun's ``fids_sorted``).

    ``max_pairs`` caps each polygon's pair list AFTER the rank-order merge
    (a global prefix in key order — deterministic, so capped results still
    compare byte-equal across cardinalities)."""
    t0 = time.perf_counter()
    if rows is None:
        rows = _key_order(planner)
    if fids is None:
        fids = np.asarray(planner.table.fids)[rows]
    counts_l, hits = local_matches(planner, polygons, op, rows=rows)

    sizes = _psum_counts(runtime, np.asarray(
        [len(rows)] + list(counts_l), dtype=np.int64))
    rows_global, counts = int(sizes[0]), [int(c) for c in sizes[1:]]

    pairs: List[List[str]] = []
    truncated = False
    if with_pairs:
        local_pairs = [[str(f) for f in np.asarray(fids)[hits[j]]]
                       for j in range(len(hits))]
        pairs = _merge_pairs(runtime, local_pairs)
        if max_pairs is not None:
            truncated = any(len(p) > max_pairs for p in pairs)
            pairs = [p[:max_pairs] for p in pairs]

    nproc = runtime.num_processes if runtime is not None \
        and runtime.active() else 1
    return JoinResult(
        op=op, polygons=len(hits), counts=counts, pairs=pairs,
        rows_local=int(len(rows)), rows_global=rows_global,
        num_processes=nproc, wall_s=time.perf_counter() - t0,
        truncated=truncated)


def func_counts(planner, queries: Sequence[str],
                runtime: Optional[ClusterRuntime] = None) -> Dict[str, int]:
    """st_* function COUNT queries over the sharded table: each shard
    evaluates its local rows through the planner's geometry-kernel refine
    (banded device classify + f64 host refine of the uncertain sliver),
    and the per-query counts psum-reduce. The device-only cluster count
    path cannot host-refine Func residuals, so function queries reduce
    here instead — one psum round for the whole battery."""
    from geomesa_tpu.filter.parser import parse_ecql

    rows = _key_order(planner)
    local = np.asarray(
        [int(planner._refine_mask(parse_ecql(q), rows).sum())
         for q in queries], dtype=np.int64)
    tot = _psum_counts(runtime, local)
    return {q: int(c) for q, c in zip(queries, tot)}


def join_battery(planner, polygons: Sequence,
                 runtime: Optional[ClusterRuntime] = None,
                 fids: Optional[np.ndarray] = None,
                 max_pairs: Optional[int] = None) -> dict:
    """Both join ops over one polygon set — the dryrun unit.
    ``stable`` is identical on every rank (the orchestrator asserts it
    against the single-process oracle verbatim); ``meta`` carries the
    rank-local timings/sizes, excluded from equality."""
    out: dict = {"stable": {}, "meta": {}}
    for op in JOIN_OPS:
        r = spatial_join(planner, polygons, op, runtime=runtime,
                         fids=fids, max_pairs=max_pairs)
        out["stable"][op] = r.stable()
        out["meta"][op] = {"rows_local": int(r.rows_local),
                           "num_processes": int(r.num_processes),
                           "wall_s": round(float(r.wall_s), 3)}
    return out
