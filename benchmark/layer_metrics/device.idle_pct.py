"""Share of the traced slice in which no operation ran on the device.

layer: device · source: device_trace · moves: qps"""


def read(ctx: dict):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0 or trace["idle_share"] is None:
        return None
    return 100.0 * trace["idle_share"]
