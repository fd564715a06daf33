"""The batched count kernel's share of the HBM roofline, in percent.

layer: staged kernels (index/scan.py) · source: device_trace · moves: qps
Bytes the dispatches of the traced slice had to read: the rows of each
dispatch's block union (``rows_scanned`` of ``GET /events?kind=batch``; a
batch reads a row once) times the bytes per row the predicate needs, as the
configuration lists its planes' widths: never what a kernel happens to read.
Least time = bytes / peak HBM bytes/s; share = least time / device busy
seconds of the same slice. While the cell sends one kind of request, device
busy time is this kernel's. HBM-bound by this count: the B x rows compares
run on the VPU, for which no peak is published."""


def bytes_needed(rows_scanned: int, plane_bytes: dict) -> int:
    return rows_scanned * sum(plane_bytes.values())


def read(ctx: dict):
    trace, events = ctx["trace"], ctx["batch_events"]
    if not trace or trace["busy_s"] <= 0 or not events:
        return None
    rows = sum(e["rows_scanned"] for e in events)
    need = bytes_needed(rows, ctx["config"]["predicate_plane_bytes"])
    least_s = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
