"""Mean milliseconds the completer took to resolve one dispatch's requests.

layer: scheduler (serve/scheduler.py) · source: program_counter
moves: p50_ms
``sched.stage.resolve``: the result-cache offer and set_result of every
request of the batch, done-callbacks included (the flight event, the
admission release); the last request of a batch waits for all of it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    return _window.mean_ms(ctx, "sched.stage.resolve")
