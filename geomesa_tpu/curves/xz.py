"""XZ-ordering curves for geometries with extent (lines/polygons).

Re-implementation of 'XZ-Ordering: A Space-Filling Curve for Objects with
Spatial Extension' (Böhm, Klump, Kriegel), matching the reference semantics at
/root/reference/geomesa-z3/.../XZ2SFC.scala and XZ3SFC.scala:

  - a bbox is indexed by the sequence code of the *enlarged* tree cell
    (cell doubled in each dim) that contains it; the code-length l is derived
    from the bbox's max extent (l1 or l1+1 via the two-cell predicate)
  - query decomposition is a BFS over tree cells: cells whose enlarged bounds
    are contained in a query window emit a "contained" code interval (lemma 3
    of the paper); overlapping cells emit their single code and recurse
  - ranges are sort-merged (adjacent codes coalesce)

One generic implementation covers both the 2-D quadtree (XZ2) and the 3-D
octree (XZ3, spatial + binned-time). ``index`` is vectorized over numpy bbox
arrays (the write path encodes millions of geometries at once), and the
decomposition takes a whole tree level a step, every cell of it against every
window at once (``ranges_arrays``; at most ``g`` numpy steps a cover). The
reference's cell-by-cell walk is the oracle in tests/test_curves.py.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

from geomesa_tpu.curves.binnedtime import TimePeriod, max_offset
from geomesa_tpu.curves.ranges import (IndexRange, merge_range_arrays,
                                       to_ranges)
from geomesa_tpu.metrics import REGISTRY as _metrics


class XZSFC:
    """Generic D-dimensional XZ curve over user-space bounds per dim."""

    def __init__(self, g: int, bounds: Sequence[Tuple[float, float]]):
        self.g = int(g)
        self.dims = len(bounds)
        self.bounds = [(float(lo), float(hi)) for lo, hi in bounds]
        self._los = np.array([b[0] for b in self.bounds])
        self._sizes = np.array([b[1] - b[0] for b in self.bounds])
        self.fan = 1 << self.dims  # children per cell: 4 (quad) or 8 (oct)
        # child c of a cell sits at bit d of c along dim d, in half-sides
        self._child_offsets = np.array(
            [[(c >> d) & 1 for d in range(self.dims)]
             for c in range(self.fan)], dtype=np.float64)
        # codes below a cell of level i + 1, itself included
        self._subtrees = np.array(
            [self._seq_term(i) for i in range(self.g)], dtype=np.int64)
        # a child's code is its parent's plus row [parent's level] of this
        self._child_terms = 1 + np.arange(self.fan) * self._subtrees[:, None]

    # -- indexing ----------------------------------------------------------

    def _normalize(self, mins: np.ndarray, maxs: np.ndarray, lenient: bool):
        """User-space (N, D) bbox corners → [0,1] normalized."""
        if np.any(mins > maxs):
            raise ValueError("Bounds must be ordered (min <= max per dim)")
        oob = (mins < self._los) | (maxs > self._los + self._sizes)
        if np.any(oob):
            if not lenient:
                raise ValueError("Values out of bounds for xz index")
            mins = np.clip(mins, self._los, self._los + self._sizes)
            maxs = np.clip(maxs, self._los, self._los + self._sizes)
        return (mins - self._los) / self._sizes, (maxs - self._los) / self._sizes

    def _seq_term(self, i) -> "int | np.ndarray":
        """Number of descendants-plus-self below one quadrant at level i:
        (fan^(g-i) - 1) / (fan - 1). Exact in int64 for g <= 21 (2D) / 14 (3D);
        we use Python/object ints via numpy int64 — g defaults keep it safe."""
        return (self.fan ** (self.g - i) - 1) // (self.fan - 1)

    def index(self, mins, maxs, lenient: bool = False) -> np.ndarray:
        """Vectorized: (N, D) bbox min/max corners → (N,) int64 codes."""
        mins = np.atleast_2d(np.asarray(mins, dtype=np.float64))
        maxs = np.atleast_2d(np.asarray(maxs, dtype=np.float64))
        nmins, nmaxs = self._normalize(mins, maxs, lenient)
        n = nmins.shape[0]

        # code length: l1 = floor(log(maxDim)/log(0.5)); maxDim == 0 → g
        ext = np.max(nmaxs - nmins, axis=1)
        with np.errstate(divide="ignore"):
            l1 = np.floor(np.log(ext) / math.log(0.5))
        l1 = np.where(np.isfinite(l1), l1, self.g).astype(np.int64)
        l1 = np.minimum(l1, self.g)

        # two-cell predicate: bump to l1+1 when the bbox spans at most two
        # cells of the finer resolution in every dim (XZ2SFC.scala:66-74)
        w2 = np.power(0.5, (l1 + 1).astype(np.float64))[:, None]
        fits = nmaxs <= np.floor(nmins / w2) * w2 + 2 * w2
        length = np.where((l1 < self.g) & np.all(fits, axis=1), l1 + 1, l1)

        # sequence code: walk the tree `length` levels toward the bbox's min
        # corner (XZ2SFC.sequenceCode, :264-286), all features in lockstep.
        # A cell halves at every level, so "min corner >= the cell's centre"
        # at level i is binary digit i of the corner's normalized position
        # (1.0 reads all ones); times 2^g and floor are exact in f64
        top = (1 << self.g) - 1
        digits = np.minimum(np.floor(np.ldexp(nmins, self.g)), top
                            ).astype(np.int64)
        cs = np.zeros(n, dtype=np.int64)
        for i in range(self.g):
            quadrant = np.zeros(n, dtype=np.int64)
            for d in range(self.dims):
                quadrant |= ((digits[:, d] >> (self.g - 1 - i)) & 1) << d
            cs += np.where(i < length, 1 + quadrant * self._seq_term(i), 0)
        return cs

    # -- query decomposition ----------------------------------------------

    def ranges_arrays(self, queries, max_ranges: Optional[int] = None):
        """Cover query windows with code ranges: merged (lo, hi, contained)
        int64 / int64 / bool arrays, the form ``prune.ranges_to_slices``
        takes with no per-range objects.

        queries: each (min_0..min_D-1, max_0..max_D-1) in user space.

        One step a tree level. The frontier holds the level's candidate
        cells in breadth-first order (lower corners and sequence codes);
        a cell at ``level`` has side 0.5^level, and its *enlarged* element
        doubles that side (XElement semantics). Every cell is tested against
        every window in one comparison. A cell that touches a window emits
        its own code and, unless a window contains it, expands to its
        children; a contained one emits all codes prefixed by its own (lemma
        3). ``max_ranges`` is the budget of entries emitted before merging:
        an overlapping cell met once it is spent covers its whole subtree
        coarsely and expands no further.
        """
        max_ranges = max_ranges or (1 << 62)
        q = np.asarray(queries, dtype=np.float64).reshape(-1, 2 * self.dims)
        wmins, wmaxs = self._normalize(q[:, : self.dims], q[:, self.dims:],
                                       lenient=False)   # (W, D) each

        lo = self._child_offsets * 0.5       # level 1: the root's children
        codes = self._child_terms[0]
        tested = []              # a level's (codes, contained, touches)
        coarse = []              # (lo, hi) of subtrees a spent budget left
        emitted = 0              # entries so far
        for level in range(1, self.g + 1):
            side = 0.5 ** level
            # [0] the cells' lower corners, [1] the upper corners of their
            # enlarged elements: (2, n, 1, D) against the (W, D) windows
            corners = lo[None, :, None, :] + np.array(
                [0.0, 2 * side])[:, None, None, None]
            # window min <= lower and max >= upper: contained; min <= upper
            # and max >= lower: they touch, as a contained cell does too
            contained, touches = ((wmins <= corners)
                                  & (wmaxs >= corners)[::-1]).all(-1).any(-1)
            tested.append((codes, contained, touches))
            if level == self.g:
                break
            expand = touches & ~contained
            entries = np.count_nonzero(touches)
            if emitted + entries >= max_ranges:
                # the budget is tested as each overlapping cell is met,
                # after its own entry: a running count in breadth-first order
                under = emitted + np.cumsum(touches) < max_ranges
                spent = codes[expand & ~under]
                coarse.append((spent, spent + self._subtrees[level - 1]))
                entries += len(spent)
                expand &= under
            emitted += entries
            codes = (codes[expand][:, None] + self._child_terms[level]
                     ).reshape(-1)
            if not len(codes):
                break
            lo = (lo[expand][:, None, :] + self._child_offsets * (side / 2.0)
                  ).reshape(-1, self.dims)

        codes, contained, touches = (np.concatenate(a) for a in zip(*tested))
        _metrics.inc("xz.cover.cells", len(codes))
        subtree = np.repeat(self._subtrees[:len(tested)],
                            [len(t[0]) for t in tested])
        lo = codes[touches]
        cont = contained[touches]
        # NB the reference adds the full subtree size with no -1
        # (XZ2SFC.scala:297-306) — over-inclusive by one code, which the
        # fine filter removes; we match it for parity.
        hi = lo + cont * subtree[touches]
        if coarse:
            spent_lo, spent_hi = (np.concatenate(a) for a in zip(*coarse))
            lo = np.concatenate([lo, spent_lo])
            hi = np.concatenate([hi, spent_hi])
            cont = np.concatenate([cont, np.zeros(len(spent_lo), bool)])
        return merge_range_arrays(lo, hi, cont)

    def ranges(
        self,
        queries: Sequence[Sequence[float]],
        max_ranges: Optional[int] = None,
    ) -> List[IndexRange]:
        """``ranges_arrays`` in the object form."""
        return to_ranges(self.ranges_arrays(queries, max_ranges))


class XZ2SFC(XZSFC):
    """2-D XZ curve over lon/lat (reference XZ2SFC.scala; default g=12)."""

    _cache: dict = {}

    def __init__(self, g: int = 12, x_bounds=(-180.0, 180.0), y_bounds=(-90.0, 90.0)):
        super().__init__(g, [x_bounds, y_bounds])

    @classmethod
    def apply(cls, g: int = 12) -> "XZ2SFC":
        if g not in cls._cache:
            cls._cache[g] = cls(g)
        return cls._cache[g]

    def index_bbox(self, xmin, ymin, xmax, ymax, lenient: bool = False) -> np.ndarray:
        mins = np.stack([np.asarray(xmin, dtype=np.float64), np.asarray(ymin, dtype=np.float64)], axis=-1)
        maxs = np.stack([np.asarray(xmax, dtype=np.float64), np.asarray(ymax, dtype=np.float64)], axis=-1)
        return self.index(mins, maxs, lenient)

    def ranges_bbox(self, queries: Sequence[Tuple[float, float, float, float]],
                    max_ranges: Optional[int] = None) -> List[IndexRange]:
        return self.ranges([(xmin, ymin, xmax, ymax) for xmin, ymin, xmax, ymax in queries], max_ranges)


class XZ3SFC(XZSFC):
    """3-D XZ curve over lon/lat/binned-time (reference XZ3SFC.scala).

    The time dim spans one period bin, [0, max_offset(period)]; callers
    decompose multi-bin intervals per bin as with Z3. Default g=36 exceeds
    what int64 codes can hold for an octree; the reference uses g=36 for XZ3?
    No — the reference XZ3 uses the same g resolution as XZ2 (12) by default
    at the index layer; we keep g configurable and default to 12.
    """

    _cache: dict = {}

    def __init__(self, g: int = 12, period: TimePeriod = TimePeriod.WEEK,
                 x_bounds=(-180.0, 180.0), y_bounds=(-90.0, 90.0)):
        period = TimePeriod.parse(period)
        super().__init__(g, [x_bounds, y_bounds, (0.0, float(max_offset(period)))])
        self.period = period

    @classmethod
    def apply(cls, g: int = 12, period: TimePeriod = TimePeriod.WEEK) -> "XZ3SFC":
        period = TimePeriod.parse(period)
        key = (g, period)
        if key not in cls._cache:
            cls._cache[key] = cls(g, period)
        return cls._cache[key]
