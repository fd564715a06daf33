"""Peak bytes in use on the fullest device after the window, in GB (1e9).

layer: table + build (index/device.py) · source: program_counter
moves: setup_s (what is resident is what set-up built and uploaded)"""


def read(ctx: dict):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
