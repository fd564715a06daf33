"""Share of the traced slice in which the host had a program out.

layer: scheduler (serve/scheduler.py) · source: program_counter · moves: qps
Union of [launch_ms, ready_ms] of the slice's ``kind=batch`` events: from
the dispatch call to the read-back's return. Not clipped to the slice: an
error under one in-flight time in three seconds. It reads at least 100 minus
``device.idle_pct``; the distance between the two is launch, transfer and
read-back latency, which neither the device trace nor a counter shows."""


def read(ctx: dict):
    spans = sorted((e["launch_ms"], e["ready_ms"])
                   for e in ctx["batch_events"] or ()
                   if "launch_ms" in e and "ready_ms" in e)
    lo, hi = ctx["slice"]
    if not spans or hi <= lo:
        return None
    out_ms, (start, end) = 0.0, spans[0]
    for a, b in spans[1:]:
        if a > end:
            out_ms += end - start
            start, end = a, b
        else:
            end = max(end, b)
    out_ms += end - start
    return 100.0 * out_ms / (1000.0 * (hi - lo))
