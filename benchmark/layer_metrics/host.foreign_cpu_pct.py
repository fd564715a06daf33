"""The share of the Python threads' CPU time that threads the program did not
start took: in a benchmark run, its client threads and its main thread, in
the server's process and under its interpreter lock.

layer: REST (web/server.py) · source: program_counter · moves: qps
The counters ``host.cpu_us.<role>`` (obs/hostcpu.py), moved when ``/metrics``
is read: ``foreign`` over ``foreign`` and the program's roles ``http``,
``http.listener``, ``sched.collector``, ``sched.completer`` and
``program.other``. The runtime's native threads (``host.cpu_us.process`` holds
them) take no lock and are left out. A program without the counters reads
None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402

ROLES = ("http", "http.listener", "sched.collector", "sched.completer",
         "program.other")


def read(ctx: dict):
    foreign = _window.counter_delta(ctx, "host.cpu_us.foreign")
    if foreign is None:
        return None
    total = foreign + sum(_window.counter_delta(ctx, "host.cpu_us." + r) or 0
                          for r in ROLES)
    return 100.0 * foreign / total if total > 0 else None
