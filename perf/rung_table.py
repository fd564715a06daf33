#!/usr/bin/env python3
"""The fused program's rung table: device milliseconds a dispatch by the rung
that serves it, and compile seconds by the ladder's length.

    chiprun -- python3 perf/rung_table.py            # 10,000,000 rows
    JAX_PLATFORMS=cpu python3 perf/rung_table.py --rows 300000 --block 256

The numbers `index/compiled.py`'s `_RUNG_FLOOR` and `_RUNG_STEP` are set from
(PERF.md §6, PR 29). A GDELT-shaped point table (x, y, dtg of the benchmark's
corpus) under a Z3 index, no store and no server; boxes of rising width in one
week, about a cluster's centre and about an empty corner (few blocks alive:
the low rungs), each dispatched `--reps` times and waited for, so a time is
launch to ready on the host's clock with the device the only worker (a CPU
run's times say nothing of the device). One process, which holds the chip
alone. Prints one JSON line last and writes it to
`chiprun_out/rung_table.json`.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark", "data"))

WEEK = "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
HALF_WIDTHS = (0.05, 0.2, 0.5, 1.0, 2.0, 3.0, 4.5, 6.0, 9.0, 13.0, 20.0,
               30.0, 60.0)
# (floor, step) of the ladders whose compile is timed; None: one rung at cap
LADDERS = ((None, 1), (32, 1), (32, 2), (64, 1), (128, 1))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=10_000_000)
    p.add_argument("--seed", type=int, default=2147486001)
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--block", type=int, default=0,
                   help="rows a gather block (a rehearsal's small table)")
    args = p.parse_args(argv)

    from geomesa_tpu import config
    config.enable_compile_cache()
    if args.block:
        config.PRUNE_BLOCK.set(args.block)
    import jax
    import numpy as np

    import gdelt_events
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.features.table import FeatureTable
    from geomesa_tpu.filter.parser import parse_ecql
    from geomesa_tpu.index import compiled as fused
    from geomesa_tpu.index.planner import QueryPlanner
    from geomesa_tpu.index.spatial import Z3Index

    t = time.perf_counter()
    corpus = gdelt_events.make_corpus(args.rows, args.seed)
    sft = SimpleFeatureType.from_spec(
        "rungs", "dtg:Date,*geom:Point;geomesa.z3.interval=week")
    table = FeatureTable.build(
        sft, {"dtg": corpus["dtg"], "geom": (corpus["x"], corpus["y"])})
    planner = QueryPlanner(sft, table, [Z3Index(sft, table)])
    print(f"table of {args.rows} rows: {time.perf_counter() - t:.1f} s on "
          f"{jax.devices()[0].device_kind}", flush=True)
    centres = (tuple(corpus["centers"][0]), (-176.0, -86.0))

    def program(mode: str, centre, w: float):
        cx, cy = centre
        q = (f"BBOX(geom,{max(-180.0, cx - w)},{max(-90.0, cy - w)},"
             f"{cx + w},{min(90.0, cy + w)}) AND {WEEK}")
        prog = fused._from_plan(planner, planner.plan(parse_ecql(q)), mode)
        if prog is None:
            raise SystemExit(f"rung_table: {q} did not fuse")
        return prog

    def timed(prog):
        """(median ms, n_alive, rows matched) over the repetitions."""
        ms = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(prog.dispatch())
            ms.append(1000.0 * (time.perf_counter() - t0))
        head = out[0] if prog.mode == "count" else out[0][0]
        return statistics.median(ms), int(out[1]), int(head)

    compiles, table_rows = [], []
    for floor, step in LADDERS:
        fused._PROGRAMS._jitted.clear()
        fused._RUNG_FLOOR, fused._RUNG_STEP = floor or 1 << 30, step
        first = {}
        for mode in ("select", "count"):
            prog = program(mode, centres[0], HALF_WIDTHS[0])
            t0 = time.perf_counter()
            jax.block_until_ready(prog.dispatch())
            first[mode] = time.perf_counter() - t0
        compiles.append({"floor": floor, "step": step,
                         "rungs": list(prog.rungs),
                         "first_call_s": first})
        print(f"ladder {compiles[-1]}", flush=True)
        if (floor, step) not in ((None, 1), (32, 1)):
            continue
        for centre, w in [(c, w) for c in centres for w in HALF_WIDTHS]:
            sel_ms, n_alive, rows = timed(program("select", centre, w))
            cnt_ms, _, _ = timed(program("count", centre, w))
            which = sum(n_alive > r for r in prog.rungs)
            table_rows.append({
                "ladder": "one rung" if floor is None else "32, step 1",
                "centre": [round(float(v), 2) for v in centre],
                "half_width_deg": w, "n_alive": n_alive, "rows": rows,
                "blocks": prog.rungs[which] if which < len(prog.rungs)
                else prog.nb,
                "select_ms": round(sel_ms, 3), "count_ms": round(cnt_ms, 3)})
            print(f"  {table_rows[-1]}", flush=True)

    line = {"rows": args.rows, "seed": args.seed, "reps": args.reps,
            "device": jax.devices()[0].device_kind,
            "block": int(config.PRUNE_BLOCK.get()), "nb": prog.nb,
            "compiles": compiles, "table": table_rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "rung_table.json"), "w") as f:
        json.dump(line, f)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
