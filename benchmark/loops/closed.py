"""Closed loop: each of the mix's ``clients`` sends its next request when the
last is answered.

A loop kind is a module with ``start(traffic, port, op, stream, compiles,
say)``, which warms up and returns with the cell's load running, and whose
result has ``stop()`` → (records, clients still out). A record is (sent, done,
status, answer, args) on ``time.perf_counter()``; ``answer`` is None where the
operation's ``answer()`` may not stand for an exact answer. run.py cuts the
window out of the records, so warm-up runs on into it with no ramp."""

import http.client
import json
import threading
import time

HTTP_TIMEOUT_S = 120
DRAIN_S = 60          # how long a request in flight at the close is waited for


def http_get(port: int, path: str):
    """One request on a connection of its own (the server speaks HTTP/1.0);
    returns (status, parsed JSON body)."""
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=HTTP_TIMEOUT_S)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


class Client(threading.Thread):
    def __init__(self, port: int, stream, op, stop: threading.Event):
        super().__init__(daemon=True)
        self.port, self.stream, self.op, self.stop_ev = port, stream, op, stop
        self.records, self.last_error = [], None

    def run(self):
        for path, args in self.stream:
            if self.stop_ev.is_set():
                return
            sent = time.perf_counter()
            status, answer = 0, None
            try:
                status, body = http_get(self.port, path)
                if status == 200:
                    answer = self.op.answer(body)
            except (OSError, http.client.HTTPException, ValueError) as e:
                status = status or -1
                self.last_error = repr(e)
            self.records.append(
                (sent, time.perf_counter(), status, answer, args))


class Running:
    def __init__(self, pool, stop_ev):
        self.pool, self.stop_ev = pool, stop_ev

    def stop(self):
        """Stop sending and wait for what is in flight."""
        self.stop_ev.set()
        deadline = time.perf_counter() + DRAIN_S
        for c in self.pool:
            c.join(max(0.0, deadline - time.perf_counter()))
        errors = [c.last_error for c in self.pool if c.last_error]
        if errors:
            print(f"closed loop: client errors {errors[:3]}", flush=True)
        return ([r for c in self.pool for r in c.records],
                sum(c.is_alive() for c in self.pool))


def start(traffic: dict, port: int, op, stream, compiles: list, say):
    """The cell's own traffic in closed loops of ``warmup.steps`` = [clients,
    quiet], fewer clients first so that the small dispatch tiers are met. A
    step ends when ``quiet`` requests were answered since the last new
    program appeared (``compiles`` grows with every program compiled or
    loaded). The last step is the cell's clients and is returned running."""
    warm = traffic["warmup"]
    if warm["steps"][-1][0] != traffic["clients"]:
        raise SystemExit("closed loop: the last warm-up step has to be the "
                         "cell's clients")
    t = time.perf_counter()
    for step, (n, quiet) in enumerate(warm["steps"]):
        last = step == len(warm["steps"]) - 1
        running = Running([], threading.Event())
        running.pool = [Client(port, stream(i if last else 1000 * (step + 1)
                                            + i), op, running.stop_ev)
                        for i in range(n)]
        for c in running.pool:
            c.start()
        t_step, seen, mark = time.perf_counter(), len(compiles), 0
        while time.perf_counter() - t_step < warm["max_s"]:
            time.sleep(0.05)
            answered = sum(len(c.records) for c in running.pool)
            if len(compiles) != seen:
                seen, mark = len(compiles), answered
            if answered - mark >= quiet:
                break
        if not last:
            running.stop()
    say(f"phase warmup: {time.perf_counter() - t:.3f} s  "
        f"programs met: {len(compiles)}")
    return running
