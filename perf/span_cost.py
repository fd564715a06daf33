#!/usr/bin/env python3
"""What a span costs the host: nanoseconds a root ``trace.trace``, a
``trace.span`` under an open root and one on its own (a flat timer) take to
enter and exit, beside the clocks they read (a root reads the thread's CPU
clock, a span does not).

    python3 perf/span_cost.py                    # this checkout's trace.py
    python3 perf/span_cost.py --root <checkout>  # another checkout's

No device, no store: the host's Python alone, so the number is the machine's,
wherever it runs (the chip's host is the one that counts). ``jax.profiler`` is
imported where it can be, as in a server, so each span pays the annotation
gate. Each cost is the least of ``--rounds`` rounds of ``--n`` spans, less the
same loop with tracing off, so the loop's own cost drops out. Prints one JSON
line.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def per_call_ns(fn, n: int, rounds: int) -> float:
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter_ns()
        fn(n)
        best = min(best, time.perf_counter_ns() - t0)
    return best / n


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--root", default=ROOT,
                   help="the checkout whose geomesa_tpu is measured")
    p.add_argument("--n", type=int, default=200)
    p.add_argument("--rounds", type=int, default=2000)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))
    from geomesa_tpu import trace
    try:
        import jax.profiler  # noqa: F401  (a server has it: the gate's cost)
    except ImportError:
        pass

    def root(n):
        for _ in range(n):
            with trace.trace("span_cost.root"):
                pass

    def nested(n):
        with trace.trace("span_cost.root"):
            for _ in range(n):
                with trace.span("span_cost.leaf"):
                    pass

    def flat(n):
        for _ in range(n):
            with trace.span("span_cost.flat"):
                pass

    def clock(read):
        def loop(n):
            for _ in range(n):
                read()
        return loop

    out = {"python": sys.version.split()[0], "root": args.root}
    for name, fn in (("root", root), ("nested", nested), ("flat", flat)):
        on = per_call_ns(fn, args.n, args.rounds)
        with trace.disabled():
            off = per_call_ns(fn, args.n, args.rounds)
        out[f"span_{name}_ns"] = round(on - off, 1)
    for name, read in (("perf_counter_ns", time.perf_counter_ns),
                       ("thread_time_ns", time.thread_time_ns)):
        out[f"{name}_ns"] = round(per_call_ns(clock(read), args.n,
                                              args.rounds), 1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
