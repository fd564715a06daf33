"""Device refine kernel for extent×extent join candidate pairs.

≙ the compute half of the reference's partition join: GeoMesaJoinRelation
evaluates the JTS predicate per candidate pair *inside the executors*
(/root/reference/geomesa-spark/geomesa-spark-sql/src/main/scala/org/
locationtech/geomesa/spark/GeoMesaJoinRelation.scala:41-56). Here the
executors are TPU chips: each candidate pair (left geometry, right geometry)
evaluates the INTERSECTS predicate in f32 with certified error bands —
certain-hit / certain-miss decisions are exact, and only the uncertain
sliver (pairs within ~1e-5 deg of touching) refines on the host in f64.

Data layout: geometries are ragged, devices want fixed shapes — so each
side's *unique* geometries become one padded segment table ``(G, S, 4)``
(S = pow2 of the max boundary-segment count) plus per-geometry segment
counts, uploaded ONCE; the pair lists are just int32 index vectors into
those tables, and the kernel gathers. Pairs are chunked to a fixed pow2
dispatch shape so one compiled program serves any pair count.

Intersects logic per pair, all band-certified:
  hit  = any boundary-segment pair certainly crosses
         OR (right is polygonal AND left's first vertex certainly inside)
         OR (left is polygonal AND right's first vertex certainly inside)
  miss = every segment pair certainly misses
         AND (right not polygonal OR left's first vertex certainly outside)
         AND (left not polygonal OR right's first vertex certainly outside)
  else uncertain → host exact refine (filter/geom_batch).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from geomesa_tpu.features import geometry as geo
from geomesa_tpu.filter import geom_batch

# largest per-geometry boundary segment count the device path accepts;
# pairs involving bigger geometries refine on host (they are rare and one
# giant geometry would inflate every pair's padded shape)
MAX_SEGMENTS = 512
# pair-chunk dispatch shape: bounded so the (chunk, Ls, Rs) band
# intermediates stay well under HBM limits for the largest tier combo
_CHUNK_BUDGET = 1 << 26


def _pow2(n: int) -> int:
    return max(1, 1 << max(0, int(n) - 1).bit_length())


def padded_segment_table(arr: geo.GeometryArray, ids: np.ndarray
                         ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                             np.ndarray, np.ndarray]]:
    """((G, S, 4) f32 padded segments, (G,) int32 counts, (G,) bool
    polygonal, (G,) bool single-part) for the selected geometries, or None
    when any geometry is segment-free (points) or exceeds MAX_SEGMENTS —
    callers fall back to the host refine.

    ``single-part`` drives the miss certification: "first vertex certainly
    outside + no boundary crossing ⇒ disjoint" is only sound for a
    CONNECTED geometry (a polygon's holes don't break connectivity of the
    filled region, but a MULTI* geometry's disconnected parts do — a
    non-first part could sit wholly inside the other geometry).
    """
    ids = np.asarray(ids, dtype=np.int64)
    segs, fid = geom_batch.build_segments(arr, ids)
    counts = np.bincount(fid, minlength=len(ids)).astype(np.int32)
    if len(ids) == 0 or counts.min() == 0 or counts.max() > MAX_SEGMENTS:
        return None
    s_cap = _pow2(int(counts.max()))
    g_cap = _pow2(len(ids))  # pow2 geometry axis: stable jit signatures
    out = np.zeros((g_cap, s_cap, 4), dtype=np.float32)
    pos = np.arange(len(fid)) - np.repeat(
        np.cumsum(counts) - counts, counts)
    out[fid, pos] = segs.astype(np.float32)
    cnt = np.zeros(g_cap, dtype=np.int32)
    cnt[: len(ids)] = counts
    poly = np.zeros(g_cap, dtype=bool)
    poly[: len(ids)] = np.isin(arr.type_codes[ids],
                               (geo.POLYGON, geo.MULTIPOLYGON))
    single = np.zeros(g_cap, dtype=bool)
    single[: len(ids)] = (arr.geom_offsets[ids + 1]
                          - arr.geom_offsets[ids]) == 1
    return out, cnt, poly, single


def _band_core(ls, lc, lpoly, lsingle, rs, rc, rpoly, rsingle):
    """Shared traced body: padded pair segments → (certain_hit, uncertain).

    ls: (P, Ls, 4) f32   lc: (P,) int32   lpoly/lsingle: (P,) bool
    rs: (P, Rs, 4) f32   rc: (P,) int32   rpoly/rsingle: (P,) bool
    Invalid (padded) segments are masked out of both the hit and the
    uncertainty reductions, so padding never flips a verdict.

    Miss certification requires connectivity: "first vertex certainly
    outside + every boundary pair certainly misses ⇒ disjoint" holds only
    for single-part geometries (a MULTI* part other than the first could
    sit wholly inside the other side without any crossing), so multi-part
    pairs that aren't certain hits classify as uncertain → exact host
    refine.
    """
    import jax.numpy as jnp

    from geomesa_tpu.index.scan import _pip_band, _segpair_band

    Ls = ls.shape[1]
    Rs = rs.shape[1]
    lv = jnp.arange(Ls, dtype=jnp.int32)[None, :] < lc[:, None]   # (P, Ls)
    rv = jnp.arange(Rs, dtype=jnp.int32)[None, :] < rc[:, None]   # (P, Rs)
    ax, ay, bx, by = ls[..., 0], ls[..., 1], ls[..., 2], ls[..., 3]
    cx, cy, dx, dy = rs[..., 0], rs[..., 1], rs[..., 2], rs[..., 3]

    hit_p, miss_p = _segpair_band(
        ax[:, :, None], ay[:, :, None], bx[:, :, None], by[:, :, None],
        cx[:, None, :], cy[:, None, :], dx[:, None, :], dy[:, None, :])
    pv = lv[:, :, None] & rv[:, None, :]
    any_hit = jnp.any(hit_p & pv, axis=(1, 2))
    all_miss = jnp.all(miss_p | ~pv, axis=(1, 2))

    # _pip_band broadcasts (P, 1) query points against (P, E) edges and
    # reduces the edge axis → (P,) verdicts
    l_in, l_out = _pip_band(ax[:, 0:1], ay[:, 0:1], cx, cy, dx, dy,
                            evalid=rv)
    r_in, r_out = _pip_band(cx[:, 0:1], cy[:, 0:1], ax, ay, bx, by,
                            evalid=lv)

    hit = any_hit | (rpoly & l_in) | (lpoly & r_in)
    miss = (all_miss
            & (~rpoly | (l_out & lsingle))
            & (~lpoly | (r_out & rsingle)))
    return hit, ~hit & ~miss


_PAIR_JIT = None


def _pair_fn():
    global _PAIR_JIT
    if _PAIR_JIT is None:
        import jax
        import jax.numpy as jnp

        def run(lsegs, lcnt, lpoly, lsingle, redges, rcnt, rpoly, rsingle,
                pl, pr):
            # gather per-pair geometry rows; -1 pads clamp to row 0 and are
            # masked by valid=False
            valid = pl >= 0
            pl = jnp.clip(pl, 0, lsegs.shape[0] - 1)
            pr = jnp.clip(pr, 0, redges.shape[0] - 1)
            hit, unc = _band_core(lsegs[pl], lcnt[pl], lpoly[pl],
                                  lsingle[pl], redges[pr], rcnt[pr],
                                  rpoly[pr], rsingle[pr])
            # bit-packed verdicts: the result readback shrinks 8x
            return (jnp.packbits(hit & valid), jnp.packbits(unc & valid))

        _PAIR_JIT = jax.jit(run)
    return _PAIR_JIT


def _chunk_size(s_l: int, s_r: int) -> int:
    ch = int(np.clip(_CHUNK_BUDGET // max(1, s_l * s_r), 1024, 1 << 20))
    # the packed-verdict concatenation in PreparedPairRefine requires every
    # chunk to fill whole bytes — keep ch a multiple of 8 regardless of how
    # the budget constants evolve
    return max(8, ch & ~7)


class PreparedPairRefine:
    """Pair refine with every input staged on device (the prepared-query
    pattern applied to the join: geometry tables + chunked pair index
    vectors upload once, re-dispatches pay only kernel time + the packed
    verdict readback)."""

    def __init__(self, d_l, d_r, d_pairs, n: int):
        self._d_l = d_l
        self._d_r = d_r
        self._d_pairs = d_pairs
        self.n = n

    def dispatch(self):
        """Async: ONE (2, P/8) packed device array (row 0 = hits, row 1 =
        uncertain) — a single readback syncs the whole refine, so the
        delivered latency floors at one round trip, not one per chunk."""
        import jax.numpy as jnp

        if not self._d_pairs:
            return jnp.zeros((2, 0), jnp.uint8)
        fn = _pair_fn()
        outs = [fn(*self._d_l, *self._d_r, pl, pr)
                for pl, pr in self._d_pairs]
        return jnp.stack([jnp.concatenate([h for h, _ in outs]),
                          jnp.concatenate([u for _, u in outs])])

    def __call__(self) -> Tuple[np.ndarray, np.ndarray]:
        if self.n == 0:
            return np.zeros(0, dtype=bool), np.zeros(0, dtype=bool)
        packed = np.asarray(self.dispatch())
        hit = np.unpackbits(packed[0])[: self.n]
        unc = np.unpackbits(packed[1])[: self.n]
        return hit.astype(bool), unc.astype(bool)


def prepare_refine(left: geo.GeometryArray, right: geo.GeometryArray,
                   li: np.ndarray, rj: np.ndarray
                   ) -> Optional[PreparedPairRefine]:
    """Stage an INTERSECTS pair-refine on device, or None when the workload
    doesn't fit the device path (point/oversized geometries)."""
    try:
        import jax.numpy as jnp
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        return None
    n = len(li)
    if n == 0:  # a legitimately empty join is not "unsupported"
        return PreparedPairRefine([], [], [], 0)
    ul, inv_l = np.unique(li, return_inverse=True)
    ur, inv_r = np.unique(rj, return_inverse=True)
    lt = padded_segment_table(left, ul)
    rt = padded_segment_table(right, ur)
    if lt is None or rt is None:
        return None
    d_l = [jnp.asarray(a) for a in lt]
    d_r = [jnp.asarray(a) for a in rt]
    ch = _chunk_size(lt[0].shape[1], rt[0].shape[1])
    d_pairs = []
    for s in range(0, n, ch):
        e = min(n, s + ch)
        pl = np.full(ch, -1, dtype=np.int32)
        pr = np.zeros(ch, dtype=np.int32)
        pl[: e - s] = inv_l[s:e]
        pr[: e - s] = inv_r[s:e]
        d_pairs.append((jnp.asarray(pl), jnp.asarray(pr)))
    return PreparedPairRefine(d_l, d_r, d_pairs, n)


def device_refine(left: geo.GeometryArray, right: geo.GeometryArray,
                  li: np.ndarray, rj: np.ndarray
                  ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Evaluate INTERSECTS for candidate pairs on the device.

    Returns (certain_hit bool (P,), uncertain bool (P,)) — uncertain pairs
    need the host's exact f64 refine. None when the workload shape doesn't
    fit the device path (point geometries / oversized geometries); callers
    fall back to the host refine for everything.
    """
    prep = prepare_refine(left, right, li, rj)
    return None if prep is None else prep()


def mesh_join_pairs(mesh, left: geo.GeometryArray, right: geo.GeometryArray,
                    li: np.ndarray, rj: np.ndarray):
    """Distributed pair refine over a device mesh: the pair axis shards
    across devices, the (small) geometry segment tables replicate — the
    broadcast-small-side spatial join of SURVEY §2.12 row 7 — and each
    device evaluates its pair slice with the same band kernel. Returns
    (certain_hit (P,), uncertain (P,), per_device_hits (D,)); the hit
    counts come back via a psum-lowered sharded sum so the merge rides ICI,
    not the host.

    None when the workload doesn't fit the device path (point/oversized
    geometries), mirroring ``device_refine``.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = mesh.devices.size
    n = len(li)
    if n == 0:
        return (np.zeros(0, dtype=bool), np.zeros(0, dtype=bool),
                np.zeros(n_dev, dtype=np.int32))
    ul, inv_l = np.unique(li, return_inverse=True)
    ur, inv_r = np.unique(rj, return_inverse=True)
    lt = padded_segment_table(left, ul)
    rt = padded_segment_table(right, ur)
    if lt is None or rt is None:
        return None
    rows = NamedSharding(mesh, P("rows"))
    repl = NamedSharding(mesh, P())
    d_l = [jax.device_put(a, repl) for a in lt]
    d_r = [jax.device_put(a, repl) for a in rt]
    fn = _mesh_fn(mesh, n_dev)

    # chunk the pair axis like device_refine: per-device band intermediates
    # stay within _CHUNK_BUDGET instead of scaling with the join size
    ch = _chunk_size(lt[0].shape[1], rt[0].shape[1]) * n_dev
    hits, uncs = [], []
    per_dev = np.zeros(n_dev, dtype=np.int64)
    for s in range(0, n, ch):
        e = min(n, s + ch)
        # pad to the FULL chunk width (multi-chunk) or a pow2 multiple of
        # n_dev (single chunk): remainder-sized shapes would trigger a fresh
        # XLA trace per distinct tail (-1 sentinels make the slop free), so
        # chunks share compiled programs
        if n > ch:
            n_pad = ch
        else:
            m = (e - s + n_dev - 1) // n_dev
            n_pad = n_dev * (1 << max(0, (m - 1).bit_length()))
        pl = np.full(n_pad, -1, dtype=np.int32)
        pr = np.zeros(n_pad, dtype=np.int32)
        pl[: e - s] = inv_l[s:e]
        pr[: e - s] = inv_r[s:e]
        hit, unc, pd = fn(*d_l, *d_r, jax.device_put(pl, rows),
                          jax.device_put(pr, rows))
        hits.append(np.asarray(hit)[: e - s])
        uncs.append(np.asarray(unc)[: e - s])
        per_dev += np.asarray(pd)
    return np.concatenate(hits), np.concatenate(uncs), per_dev


_MESH_JITS: dict = {}


def _mesh_fn(mesh, n_dev: int):
    """Jitted mesh pair kernel, cached per device set (jit's own cache is
    keyed on callable identity — a fresh closure per call would retrace and
    recompile every invocation)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    key = tuple(int(d.id) for d in mesh.devices.flat)
    if key in _MESH_JITS:
        return _MESH_JITS[key]
    rows = NamedSharding(mesh, P("rows"))
    repl = NamedSharding(mesh, P())

    def run(lsegs, lcnt, lpoly, lsingle, redges, rcnt, rpoly, rsingle,
            pl, pr):
        valid = pl >= 0
        pl = jnp.clip(pl, 0, lsegs.shape[0] - 1)
        pr = jnp.clip(pr, 0, redges.shape[0] - 1)
        hit, unc = _band_core(lsegs[pl], lcnt[pl], lpoly[pl], lsingle[pl],
                              redges[pr], rcnt[pr], rpoly[pr], rsingle[pr])
        hit = hit & valid
        unc = unc & valid
        # per-device hit counts: a sharded segment-sum XLA lowers to local
        # sums + an ICI gather (the FeatureReducer merge as a collective)
        per_dev = jnp.sum(hit.reshape(n_dev, -1), axis=1)
        return hit, unc, per_dev

    fn = jax.jit(run, out_shardings=(rows, rows, repl))
    _MESH_JITS[key] = fn
    return fn
