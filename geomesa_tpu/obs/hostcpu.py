"""The process's CPU time by thread role: who holds the interpreter lock.

Every thread the package starts carries a role (``thread(role, ...)`` makes
one, ``mark`` gives one to a thread already made):

  http             a REST handler thread (web/server.py); it adds its own
                   CPU time as it ends — one clock read a request
  http.listener    the thread in ``serve_forever`` (accept, thread starts)
  sched.collector  the scheduler's collector (serve/scheduler.py)
  sched.completer  the scheduler's completer
  program.other    any other thread the package starts

The counters ``host.cpu_us.<role>`` hold their CPU time, in µs, and
``host.cpu_us.foreign`` that of the live Python threads the program did not
start (a client in the same process: the benchmark's client threads and its
main thread). ``host.cpu_us.process`` is the whole process's user + system
time (``resource.getrusage``), the runtime's native threads with it, which
hold no lock. ``sample()`` moves them — every role but ``http``, from the
threads alive at that instant — and runs when ``/metrics`` is read, never on
a request's path. A thread that ends between two samples keeps what it
spent since the last one off its counter.

Imports only metrics (the obs discipline): hot paths may import it.
"""

from __future__ import annotations

import resource
import sys
import threading
import time
from typing import Dict, Optional

from geomesa_tpu.metrics import REGISTRY as _metrics

ROLES = ("http", "http.listener", "sched.collector", "sched.completer",
         "program.other")
_ATTR = "geomesa_role"
_PREFIX = "host.cpu_us."

_lock = threading.Lock()
_seen: Dict[threading.Thread, int] = {}   # thread → CPU ns at last sample
_process_ns = [0]


def thread(role: str, **kw) -> threading.Thread:
    """``threading.Thread(**kw)`` carrying ``role`` (one of ``ROLES``)."""
    return mark(threading.Thread(**kw), role)


def mark(t: threading.Thread, role: str) -> threading.Thread:
    setattr(t, _ATTR, role)
    return t


def role_of(t: threading.Thread) -> Optional[str]:
    """The thread's role, None for a thread the program did not start."""
    return getattr(t, _ATTR, None)


def charge(role: str, cpu_ns: int) -> None:
    """Add CPU time the thread measured itself (a handler at its end)."""
    _metrics.inc(_PREFIX + role, cpu_ns // 1000)


def _cpu_ns(t: threading.Thread) -> Optional[int]:
    """A live thread's CPU time in ns, or None once it has ended. On Linux
    the clock id is built from the kernel's thread id as glibc's
    ``pthread_getcpuclockid`` builds it, so a thread that ends meanwhile
    reads EINVAL instead of a freed ``pthread_t`` being dereferenced."""
    try:
        if sys.platform.startswith("linux"):
            tid = t.native_id
            if tid is None:
                return None
            clock = ((~tid) << 3) | 6   # CPUCLOCK_SCHED | CPUCLOCK_PERTHREAD
        else:
            clock = time.pthread_getcpuclockid(t.ident)
        return time.clock_gettime_ns(clock)
    except (OSError, TypeError, AttributeError):
        return None


def sample() -> None:
    """Move ``host.cpu_us.<role>``, ``.foreign`` and ``.process`` to now."""
    gained = dict.fromkeys(ROLES[1:] + ("foreign",), 0)
    with _lock:
        live = {}
        for t in threading.enumerate():
            role = role_of(t)
            # handlers charge themselves; a native thread that called into
            # Python (a dummy) is no Python thread the program started or not
            if role == "http" or isinstance(t, threading._DummyThread) \
                    or not t.is_alive():
                continue
            ns = _cpu_ns(t)
            if ns is None:
                continue
            key = role or "foreign"
            gained[key] = gained.get(key, 0) + ns - _seen.get(t, 0)
            live[t] = ns
        _seen.clear()
        _seen.update(live)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        process = int((ru.ru_utime + ru.ru_stime) * 1e9)
        gained["process"] = process - _process_ns[0]
        _process_ns[0] = process
        for role, ns in gained.items():
            _metrics.inc(_PREFIX + role, max(0, ns) // 1000)
