"""Mean queries per scheduler dispatch over the window.

layer: scheduler (serve/scheduler.py) · source: program_counter · moves: qps
Reads ``GET /scheduler`` before and after the window: sum(size*n)/sum(n) of
the ``batch_size_hist`` delta. Nothing dispatched: nothing to read."""


def read(ctx: dict):
    before, after = (ctx[k]["/scheduler"]["batch_size_hist"]
                     for k in ("before", "after"))
    delta = {int(k): n - before.get(k, 0) for k, n in after.items()}
    batches = sum(delta.values())
    if batches <= 0:
        return None
    return sum(size * n for size, n in delta.items()) / batches
