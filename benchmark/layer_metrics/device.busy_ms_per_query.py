"""Device busy milliseconds per request completed in the traced slice.

layer: device, all kernels together · source: device_trace · moves: qps"""


def read(ctx: dict):
    trace, (lo, hi) = ctx["trace"], ctx["slice"]
    done = sum(1 for _, at, good in ctx["requests"] if good and lo <= at <= hi)
    if not trace or trace["busy_s"] <= 0 or not done:
        return None
    return 1000.0 * trace["busy_s"] / done
