"""What the timers and counters of ``GET /metrics`` gained over the window:
shared by the readers of the scheduler's dispatch-cycle record. No metric of
its own (no entry in BENCHMARK.json names it). A program that has no such
timer or counter, as one from before the cycle record has not, reads None."""


def timer_delta(ctx: dict, name: str):
    """(observations, seconds) the timer gained from `before` to `after`."""
    t0 = ctx["before"]["/metrics"]["timers"].get(name, {})
    t1 = ctx["after"]["/metrics"]["timers"].get(name)
    if not t1:
        return None
    return (t1["count"] - t0.get("count", 0),
            t1["total_s"] - t0.get("total_s", 0.0))


def counter_delta(ctx: dict, name: str):
    c1 = ctx["after"]["/metrics"]["counters"]
    if name not in c1:
        return None
    return c1[name] - ctx["before"]["/metrics"]["counters"].get(name, 0)


def mean_ms(ctx: dict, name: str):
    """Mean milliseconds of the timer's observations inside the window."""
    d = timer_delta(ctx, name)
    if d is None or d[0] <= 0:
        return None
    return 1000.0 * d[1] / d[0]


def stage_seconds(ctx: dict, stages) -> float:
    """Seconds the window's cycles spent in these ``sched.stage.*`` stages.
    A stage is observed when its cycle (or its dispatch) completes: one that
    straddles an edge of the window counts whole, an error of one cycle in
    some hundreds."""
    deltas = [timer_delta(ctx, "sched.stage." + s) for s in stages]
    if any(d is None for d in deltas) or not any(d[0] for d in deltas):
        return None
    return sum(d[1] for d in deltas)


def stage_pct(ctx: dict, stages):
    """The stages' share of the window on the collector thread, which is one
    serial thread: idle+window, plan, cover and group+union+prepare+launch
    make 100 with the loop's own bookkeeping."""
    s = stage_seconds(ctx, stages)
    return None if s is None else 100.0 * s / ctx["seconds"]
