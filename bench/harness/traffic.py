"""Load generators: one thread that submits to ``RetrievalServer.submit``.

A mix is a data file (``bench/traffic/<name>.json``) read here:

  * ``{"loop": "closed", "clients": C}``: C callers, each sending its next
    query when its reply arrives. Replies come back in submission order
    (one FIFO batcher, one in-order completer), so one thread keeps C
    requests outstanding by waiting on the oldest.
  * ``{"loop": "open", "rate_qps": R, "arrival_seed": A}``: Poisson
    arrivals at R queries/s. The gaps are one set drawn from A, put in an
    order drawn from the run's seed, so every seed offers the same load.
    Latency runs from each request's scheduled send to the completer's
    ``completed_at`` stamp (the arithmetic of ``launch/serve.py``
    ``_drive_open``), so a late generator counts against the server.

Queries cycle through a pool of ``query_pool`` vectors drawn from the seed.
"""
from __future__ import annotations

import time
from collections import deque

import numpy as np

WAIT_S = 120.0   # a reply that has not come by then never comes


class Log:
    """One row per request: query, scheduled and actual send, the reply."""

    def __init__(self):
        self.query: list[int] = []
        self.t_sched: list[float] = []
        self.t_send: list[float] = []
        self.replies: list = []
        self.payload: list = []

    def send(self, server, Q, i: int, t_sched: float | None = None):
        t = time.perf_counter()
        self.query.append(i % len(Q))
        self.t_sched.append(t if t_sched is None else t_sched)
        self.t_send.append(t)
        r = server.submit(Q[i % len(Q)])
        self.replies.append(r)
        return r

    def collect(self) -> None:
        for r in self.replies[len(self.payload):]:
            try:
                self.payload.append(r.get(timeout=WAIT_S))
            except Exception as e:   # noqa: BLE001 (a reply that never came)
                self.payload.append(e)

    def arrays(self) -> dict:
        done = np.array([r.completed_at if r.completed_at is not None
                         else np.inf for r in self.replies])
        ok = np.array([not isinstance(p, BaseException)
                       for p in self.payload], bool)
        return dict(query=np.array(self.query), t_sched=np.array(self.t_sched),
                    t_send=np.array(self.t_send), t_done=done, ok=ok)


def closed(server, Q: np.ndarray, mix: dict, t0: float, seconds: float,
           seed: int) -> Log:
    """C outstanding requests until ``t0 + seconds``, then drain."""
    log = Log()
    t_end = t0 + seconds
    out: deque = deque()
    for i in range(mix["clients"]):
        out.append(log.send(server, Q, i))
    i = len(out)
    while out:
        r = out.popleft()
        try:
            log.payload.append(r.get(timeout=WAIT_S))
        except Exception as e:   # noqa: BLE001
            log.payload.append(e)
        if time.perf_counter() < t_end:
            out.append(log.send(server, Q, i))
            i += 1
    return log


def schedule(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Send times, relative to the window's start, of every request due in
    the window."""
    rate = float(mix["rate_qps"])
    count = int(rate * seconds * 1.25) + 64
    gaps = np.random.default_rng(mix["arrival_seed"]).exponential(
        1.0 / rate, size=count)
    gaps = np.random.default_rng(seed).permutation(gaps)
    at = np.cumsum(gaps)
    return at[at <= seconds]


def open_loop(server, Q: np.ndarray, mix: dict, t0: float, seconds: float,
              seed: int) -> Log:
    log = Log()
    for i, at in enumerate(schedule(mix, seconds, seed)):
        t = t0 + at
        delay = t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        log.send(server, Q, i, t_sched=t)
    log.collect()
    return log


LOOPS = dict(closed=closed, open=open_loop)
