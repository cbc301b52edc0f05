"""Closed loop: each of `clients` threads sends its next request only when
the previous one has been answered.  A pass sends the mix's queries once,
in an order shuffled from the seed; a client starts passes until `seconds`
are up, and the pass in flight at the end is finished and counted.

So a window is whole passes only: every seed gives the same requests in
the same proportions, in another order, and no window's rate depends on
which of a mix's slow queries the clock happened to cut off (in a mix whose
queries run from 20 ms to 2 s that alone moved the rate by several per
cent).  The window overruns `seconds` by at most one pass.
"""

from __future__ import annotations

import random
import threading
import time
from typing import List

from harness.window import Hooks, Request


def run(system, traffic: dict, seconds: float, seed: int,
        hooks: Hooks) -> List[Request]:
    queries = traffic["queries"]
    clients = int(traffic["clients"])
    out: List[List[Request]] = [[] for _ in range(clients)]
    crashes = []
    t_end = time.perf_counter() + seconds

    def client(ci: int):
        rng = random.Random(seed * 1000003 + ci)
        index = 0
        while time.perf_counter() < t_end:
            order = list(queries)
            rng.shuffle(order)
            # only the first client drives the hooks: one profiler, one trace
            if ci == 0:
                hooks.pass_begins(index)
            first = len(out[ci])
            for q in order:
                with hooks.request(q["name"]):
                    t0 = time.perf_counter()
                    status, body, metrics = system.send(q)
                    t1 = time.perf_counter()
                out[ci].append(
                    Request(q["name"], ci, t0, t1, status, body, metrics)
                )
            if ci == 0:
                hooks.pass_ended(index, out[ci][first:])
            index += 1

    def guarded(ci: int):
        try:
            client(ci)
        except BaseException as e:  # re-raised on the caller's thread
            crashes.append(e)

    threads = [
        threading.Thread(target=guarded, args=(ci,), name=f"client-{ci}")
        for ci in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if crashes:
        raise crashes[0]
    return sorted((r for rs in out for r in rs), key=lambda r: r.sent_s)
