"""Single-dispatch query compilation (index/compiled.py).

The exactness contract: a fused program is an *optimization of execution
shape*, never of semantics — every count and every selected row set must
equal the staged planner path (the oracle) and the host evaluate.py mask,
for randomized filter trees over every supported node type. The perf
contract rides ROUNDS (one host↔device round per fused cold query) and the
program cache (N distinct same-shape bboxes → one compile).
"""

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.filter.evaluate import evaluate
from geomesa_tpu.filter.parser import parse_ecql
from geomesa_tpu.index import bind as _bind
from geomesa_tpu.index import compiled as fused
from geomesa_tpu.index.planner import QueryPlanner
from geomesa_tpu.index.scan import ROUNDS
from geomesa_tpu.index.spatial import Z3Index, _boxes_fp62


def _unshadow_block_size():
    # earlier suites monkeypatch prune.BLOCK_SIZE, which the module serves
    # via PEP 562 __getattr__; monkeypatch teardown re-sets it as a REAL
    # attribute, which then shadows config.PRUNE_BLOCK for the rest of the
    # session. Drop any shadow so the config override governs again.
    from geomesa_tpu.index import prune
    vars(prune).pop("BLOCK_SIZE", None)


@pytest.fixture(autouse=True)
def _small_blocks():
    # the fused path requires n >= 4 gather blocks; shrink blocks so the
    # ~6k-row corpus qualifies the same way a 100M corpus does at 4096
    _unshadow_block_size()
    config.PRUNE_BLOCK.set(512)
    config.FUSED_QUERY.set(True)
    yield
    config.PRUNE_BLOCK.unset()
    config.FUSED_QUERY.unset()


def _corpus(n=6000, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-170, 170, n)
    y = rng.uniform(-80, 80, n)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    dtg = base + rng.integers(0, 30 * 86400000, n)
    name = rng.choice(["alpha", "beta", "gamma", "delta"], n)
    age = rng.integers(0, 100, n).astype(np.int32)
    score = rng.uniform(0, 1, n).astype(np.float32)
    sft = SimpleFeatureType.from_spec(
        "fq", "name:String,age:Int,score:Float,dtg:Date,*geom:Point;"
        "geomesa.z3.interval=week")
    table = FeatureTable.build(sft, {
        "name": name, "age": age, "score": score, "dtg": dtg,
        "geom": (x, y)})
    idx = Z3Index(sft, table)
    return QueryPlanner(sft, table, [idx]), table


@pytest.fixture(scope="module")
def world():
    _unshadow_block_size()
    config.PRUNE_BLOCK.set(512)
    try:
        planner, table = _corpus()
    finally:
        config.PRUNE_BLOCK.unset()
    return planner, table


def _staged(planner, q):
    """The oracle: the same query through the staged path."""
    config.FUSED_QUERY.set(False)
    try:
        return planner.count(q), planner.select_indices(q)
    finally:
        config.FUSED_QUERY.set(True)


def _check_parity(planner, table, q, expect_fused=True):
    sc, ss = _staged(planner, q)
    q0 = fused.STATS["queries"]
    fc = planner.count(q)
    fs = planner.select_indices(q)
    engaged = fused.STATS["queries"] - q0
    assert fc == sc, q
    assert np.array_equal(fs, ss), q
    # and against the host evaluator directly
    host = evaluate(parse_ecql(q), table)
    assert fc == int(host.sum()), q
    assert np.array_equal(fs, np.flatnonzero(host)), q
    if expect_fused:
        assert engaged >= 2, f"fused path did not engage for {q}"
    return fc


# -- randomized IR-lowering parity -------------------------------------------


def _random_tree(rng, depth=0):
    """A random residual subtree over cmp/in/string/float with And/Or/Not
    composition (the device-lowerable node set)."""
    leaves = [
        lambda: f"age > {rng.integers(0, 100)}",
        lambda: f"age <= {rng.integers(0, 100)}",
        lambda: f"score < {rng.uniform(0, 1):.3f}",
        lambda: "name = '%s'" % rng.choice(["alpha", "beta", "zeta"]),
        lambda: "name <> 'gamma'",
        lambda: "name IN ('beta','delta')",
        lambda: "age IN (%d, %d, %d)" % tuple(rng.integers(0, 100, 3)),
    ]
    if depth >= 2 or rng.random() < 0.4:
        return leaves[rng.integers(0, len(leaves))]()
    a = _random_tree(rng, depth + 1)
    b = _random_tree(rng, depth + 1)
    op = rng.integers(0, 3)
    if op == 0:
        return f"({a} AND {b})"
    if op == 1:
        return f"({a} OR {b})"
    return f"NOT ({a})"


def test_randomized_tree_parity(world):
    planner, table = world
    rng = np.random.default_rng(42)
    nonzero = 0
    for i in range(12):
        x0 = float(rng.uniform(-160, 120))
        y0 = float(rng.uniform(-70, 40))
        q = f"BBOX(geom,{x0},{y0},{x0 + rng.uniform(10, 60):.2f}," \
            f"{y0 + rng.uniform(10, 30):.2f})"
        if rng.random() < 0.6:
            d0 = int(rng.integers(1, 20))
            q += (f" AND dtg DURING 2020-01-{d0:02d}T00:00:00Z/"
                  f"2020-01-{min(28, d0 + int(rng.integers(1, 9))):02d}"
                  "T00:00:00Z")
        if rng.random() < 0.8:
            q += f" AND {_random_tree(rng)}"
        nonzero += _check_parity(planner, table, q) > 0
    assert nonzero >= 3  # the corpus actually exercised the masks


def test_polygon_refine_parity(world):
    planner, table = world
    poly = ("INTERSECTS(geom, POLYGON((-10 20, 40 20, 40 60, -10 60, "
            "15 40, -10 20)))")
    n = _check_parity(planner, table, poly)
    assert n > 0
    _check_parity(planner, table,
                  poly + " AND dtg DURING "
                  "2020-01-03T00:00:00Z/2020-01-25T00:00:00Z AND age > 20")


# -- recompile churn + dispatch accounting ------------------------------------


def test_distinct_bboxes_one_shape_one_compile(world):
    planner, _ = world
    shape = ("BBOX(geom,{x0},{y0},{x1},{y1}) AND dtg DURING "
             "2020-01-05T00:00:00Z/2020-01-12T00:00:00Z")
    # seed the shape (slow path registers the recipe + compiles)
    planner.prepare(shape.format(x0=-11, y0=19, x1=41, y1=61)).count()
    built0 = fused.STATS["programs_built"]
    for i in range(20):
        d = 0.37 * i
        pq = planner.prepare(shape.format(
            x0=-12 + d, y0=18 + d / 3, x1=38 + d, y1=58 + d / 3))
        assert isinstance(pq, fused.FusedPrepared)   # recipe fast path
        pq.count()
    assert fused.STATS["programs_built"] == built0  # zero recompiles


def test_fused_cold_query_is_one_round(world):
    planner, table = world
    shape = "BBOX(geom,{x0},20,{x1},60) AND age > 30"
    planner.prepare(shape.format(x0=-10, x1=40)).count()  # register recipe
    snap = ROUNDS.snapshot()
    n = planner.prepare(shape.format(x0=-23.5, x1=31.5)).count()
    assert ROUNDS.rounds_since(snap) == 1   # ONE dispatch, zero uploads
    host = evaluate(parse_ecql(shape.format(x0=-23.5, x1=31.5)), table)
    assert n == int(host.sum())


def test_staged_cold_query_pays_multiple_rounds(world):
    planner, _ = world
    config.FUSED_QUERY.set(False)
    try:
        snap = ROUNDS.snapshot()
        planner.count("BBOX(geom,-17,22,37,57) AND age > 30")
        assert ROUNDS.rounds_since(snap) >= 2  # uploads + dispatch
    finally:
        config.FUSED_QUERY.set(True)


# -- fallback rules stay exact ------------------------------------------------


def test_fallbacks_stay_correct(world):
    planner, table = world
    # Or-rooted (union plan), attribute-only, vocab-miss IN value: all
    # decline fusion and still answer exactly
    for q in ["BBOX(geom,-10,20,40,60) OR BBOX(geom,100,-50,140,-10)",
              "age > 90",
              "BBOX(geom,-10,20,40,60) AND name IN ('nosuch')"]:
        sc, ss = _staged(planner, q)
        assert planner.count(q) == sc
        assert np.array_equal(planner.select_indices(q), ss)
        host = evaluate(parse_ecql(q), table)
        assert sc == int(host.sum())


def test_empty_bind_short_circuits(world):
    planner, _ = world
    shape = "BBOX(geom,{x0},20,{x1},60) AND dtg DURING {t0}/{t1}"
    q = shape.format(x0=-10, x1=40, t0="2020-01-05T00:00:00Z",
                     t1="2020-01-12T00:00:00Z")
    planner.prepare(q).count()   # register recipe
    # same shape, inverted interval -> provably empty at bind time
    empty = shape.format(x0=-10, x1=40, t0="2020-01-12T00:00:00Z",
                         t1="2020-01-05T00:00:00Z")
    pq = planner.prepare(empty)
    assert isinstance(pq, fused.FusedPrepared) and not pq.device_exact
    assert pq.count() == 0 and pq.count_async() is None


def test_select_overflow_regrows_capacity(world):
    planner, table = world
    q = "BBOX(geom,-170,-80,170,80)"   # nearly everything matches
    sc, ss = _staged(planner, q)
    r0 = fused.STATS["overflow_retries"]
    rows = planner.select_indices(q, capacity=10)   # tiny hint: must regrow
    assert np.array_equal(rows, ss) and len(rows) == sc
    assert fused.STATS["overflow_retries"] > r0


def test_disabled_knob_means_staged_only(world):
    planner, _ = world
    config.FUSED_QUERY.set(False)
    try:
        q0 = fused.STATS["queries"]
        planner.count("BBOX(geom,-10,20,40,60)")
        pq = planner.prepare("BBOX(geom,-10,20,40,60)")
        assert not isinstance(pq, fused.FusedPrepared)
        assert fused.STATS["queries"] == q0
    finally:
        config.FUSED_QUERY.set(True)


# -- program cache + warming --------------------------------------------------


def test_programs_counted_and_lru_bounded(world):
    planner, _ = world
    planner.count("BBOX(geom,-10,20,40,60) AND age > 30")
    from geomesa_tpu.metrics import REGISTRY
    snap = REGISTRY.snapshot()["gauges"]
    assert snap.get("fused.programs", 0) >= 1
    # fused programs ride the kernels.compiled gauge like staged kernels
    assert snap.get("kernels.compiled", 0) >= snap.get("fused.programs", 0)
    assert len(fused._PROGRAMS._jitted) <= config.KERNEL_CACHE.get()


def test_warm_programs_precompiles(world):
    planner, _ = world
    idx = planner.indexes[0]
    warmed = fused.warm_programs(idx)
    assert warmed >= 1
    # a second call is cache-served: no new compiles
    built0 = fused.STATS["programs_built"]
    assert fused.warm_programs(idx) == warmed
    assert fused.STATS["programs_built"] == built0


def test_scalar_fp62_matches_array_path():
    # the scalar bind fast path must be bit-identical to spatial._boxes_fp62
    rng = np.random.default_rng(3)
    for _ in range(64):
        k = int(rng.integers(1, 5))
        x0 = rng.uniform(-180, 170, k)
        y0 = rng.uniform(-90, 80, k)
        boxes = np.stack([x0, y0,
                          np.minimum(180, x0 + rng.uniform(0, 50, k)),
                          np.minimum(90, y0 + rng.uniform(0, 40, k))], 1)
        fast = _bind.boxes_fp62_fast(boxes)
        assert fast is not None
        assert np.array_equal(fast, _boxes_fp62(boxes))
    # exact world bounds are representable in both paths
    edge = np.array([[-180.0, -90.0, 180.0, 90.0]])
    assert np.array_equal(_bind.boxes_fp62_fast(edge),
                          _boxes_fp62(edge))
    # NaN coordinates decline the fast path (array path clamps them)
    assert _bind.boxes_fp62_fast(
        np.array([[np.nan, 0.0, 10.0, 10.0]])) is None


def test_template_rebind_matches_full_build(world):
    planner, table = world
    shape = ("BBOX(geom,{x0},{y0},{x1},{y1}) AND dtg DURING "
             "2020-01-{d0:02d}T00:00:00Z/2020-01-{d1:02d}T00:00:00Z AND "
             "age IN (11, 22, 33) AND name <> 'beta'")
    planner.prepare(shape.format(
        x0=-10, y0=20, x1=40, y1=60, d0=5, d1=12)).count()  # seeds template
    built0 = fused.STATS["programs_built"]
    rng = np.random.default_rng(9)
    for _ in range(8):
        x0 = round(float(rng.uniform(-160, 100)), 3)
        y0 = round(float(rng.uniform(-70, 30)), 3)
        d0 = int(rng.integers(1, 14))
        q = shape.format(x0=x0, y0=y0, x1=x0 + 55, y1=y0 + 45,
                         d0=d0, d1=d0 + int(rng.integers(1, 14)))
        pq = planner.prepare(q)
        assert isinstance(pq, fused.FusedPrepared)
        host = evaluate(parse_ecql(q), table)
        assert pq.count() == int(host.sum()), q
    assert fused.STATS["programs_built"] == built0  # rebinds, not rebuilds


def test_density_mode_matches_host_histogram(world):
    planner, table = world
    plan = planner.plan(parse_ecql("BBOX(geom,-60,-40,80,60)"))
    grid_bbox = (-60.0, -40.0, 80.0, 60.0)
    out = fused.try_density(planner, plan, grid_bbox, 32, 16)
    assert out is not None
    grid, cnt = out
    host = evaluate(parse_ecql("BBOX(geom,-60,-40,80,60)"), table)
    assert cnt == int(host.sum())
    assert grid.shape == (16, 32)
    assert int(grid.sum()) == cnt   # every match lands in exactly one cell


# -- the ladder of block capacities -------------------------------------------

_LADDER_BLOCK = 64
_LADDER_WEEK = "dtg DURING 2020-01-09T00:00:00Z/2020-01-15T00:00:00Z"
# boundary → (fewest and most blocks the gate may keep alive, blocks the
# serving branch gathers); the table has 1,024 blocks of 64 rows, cap 256,
# rungs 32 / 64 / 128 / 256
_LADDER_CASES = {"under_floor": (1, 31, 32), "on_rung": (64, 64, 64),
                 "over_rung": (65, 65, 128), "at_cap": (256, 256, 256),
                 "over_cap": (257, 1024, 1024)}
_LADDER_MODES = ("count", "select", "count_refine", "select_refine",
                 "density", "union_select")


@pytest.fixture(scope="module")
def ladder_world():
    """65,536 points of ONE week (one time bin, so the gate is the boxes'),
    and for every boundary a box whose gate keeps that many blocks alive:
    found by a seeded search over the block envelopes in numpy, not
    by the program under test."""
    _unshadow_block_size()
    config.PRUNE_BLOCK.set(_LADDER_BLOCK)
    try:
        n = 1024 * _LADDER_BLOCK
        rng = np.random.default_rng(11)
        base = np.datetime64("2020-01-09T00:00:00", "ms").astype(np.int64)
        sft = SimpleFeatureType.from_spec(
            "fl", "age:Int,dtg:Date,*geom:Point;geomesa.z3.interval=week")
        table = FeatureTable.build(sft, {
            "age": rng.integers(0, 100, n).astype(np.int32),
            "dtg": base + rng.integers(0, 6 * 86400000, n),
            "geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n))})
        planner = QueryPlanner(sft, table, [Z3Index(sft, table)])
        summ = {k: np.asarray(v) for k, v in fused._block_summaries(
            planner.indexes[0], _LADDER_BLOCK).items()}
    finally:
        config.PRUNE_BLOCK.unset()

    def alive(boxes):
        g = np.asarray(boxes, dtype=np.float32).reshape(-1, 4)
        return ((summ["bxmax"][None, :] >= g[:, 0:1])
                & (summ["bxmin"][None, :] <= g[:, 2:3])
                & (summ["bymax"][None, :] >= g[:, 1:2])
                & (summ["bymin"][None, :] <= g[:, 3:4]))

    k = 40000
    c = np.stack([rng.uniform(-150, 150, k), rng.uniform(-70, 70, k)], 1)
    half = np.stack([rng.uniform(0.01, 80, k), rng.uniform(0.01, 50, k)],
                    1) * rng.random((k, 2))
    cand = np.round(np.concatenate([c - half, c + half], 1), 3)
    counts = np.concatenate([alive(cand[i:i + 4000]).sum(1)
                             for i in range(0, k, 4000)])
    boxes = {name: tuple(cand[np.flatnonzero(
        (counts >= lo) & (counts <= hi))[0]].tolist())
        for name, (lo, hi, _) in _LADDER_CASES.items()}
    return planner, table, alive, boxes


@pytest.mark.parametrize("case", sorted(_LADDER_CASES))
@pytest.mark.parametrize("mode", _LADDER_MODES)
def test_ladder_rung_by_alive_blocks(ladder_world, mode, case):
    """Every mode at every boundary of the ladder: the answer is the host
    evaluator's, ``fused.blocks_alive`` grew by the gate's own count and
    ``fused.blocks_gathered`` by the first rung that holds it (the table's
    blocks past ``cap``)."""
    from geomesa_tpu.metrics import REGISTRY
    planner, table, alive, boxes = ladder_world
    lo, hi, gathered = _LADDER_CASES[case]
    x0, y0, x1, y1 = box = boxes[case]
    if mode in ("count_refine", "select_refine"):
        # a concave pentagon with the box's envelope: same gate, host refine
        q = (f"INTERSECTS(geom, POLYGON(({x0} {y0}, {x1} {y0}, {x1} {y1}, "
             f"{x0} {y1}, {(x0 + x1) / 2} {(y0 + y1) / 2}, {x0} {y0}))) "
             f"AND {_LADDER_WEEK}")
    elif mode == "union_select":
        # the second branch lies inside the first: every block it keeps
        # alive the first keeps too, and the OR dedups its rows
        q = (f"BBOX(geom,{x0},{y0},{x1},{y1}) OR BBOX(geom,{x0},{y0},"
             f"{(x0 + x1) / 2},{(y0 + y1) / 2})")
    else:
        q = f"BBOX(geom,{x0},{y0},{x1},{y1}) AND {_LADDER_WEEK} AND age > 20"
    host = evaluate(parse_ecql(q), table)

    def counters():
        snap = REGISTRY.snapshot()["counters"]
        return (snap.get("fused.blocks_alive", 0),
                snap.get("fused.blocks_gathered", 0),
                fused.STATS.get(f"rung.{gathered}", 0),
                fused.STATS.get("rung.full", 0))

    config.PRUNE_BLOCK.set(_LADDER_BLOCK)
    try:
        plan = planner.plan(parse_ecql(q))
        gates = [bp.explain["boxes"] for _, bp in plan.branches] \
            if mode == "union_select" else [plan.explain["boxes"]]
        target = int(np.any([alive(g).any(0) for g in gates], 0).sum())
        assert lo <= target <= hi
        before = counters()
        if mode in ("count", "count_refine"):
            assert planner.count(q) == int(host.sum())
        elif mode == "density":
            grid, cnt = fused.try_density(planner, plan, box, 32, 16)
            assert cnt == int(host.sum()) and int(grid.sum()) == cnt
        else:
            assert np.array_equal(planner.select_indices(q),
                                  np.flatnonzero(host))
        after = counters()
    finally:
        config.PRUNE_BLOCK.set(512)
    assert host.sum() > 0
    assert after[0] - before[0] == target
    assert after[1] - before[1] == gathered
    assert (after[3] - before[3], after[2] - before[2]) == (
        (1, 0) if case == "over_cap" else (0, 1))
