"""Closed loop: ``clients`` callers, each sending its next request as soon
as the answer to its last one is read.

One thread drives every client: it sends the first request of each, then
waits on the oldest answer still out and, whenever the server has
resolved more requests (its ``served_queries`` and ``failed_batches``
counters), collects every answer resolved and sends each of their clients
its next request at once: a client whose batch finished first does not
wait for an older batch. Latency runs from the send to the moment the server resolved the
answer (``result_at``). Requests are sent only inside the window; those
still out when it closes are collected after it.
"""
from __future__ import annotations

import time
from collections import deque

POLL_S = 0.005   # how often the wait on the oldest answer looks at others


def drive(server, requests, traffic: dict, seconds: float, spans,
          grace: float):
    """Run the loop for ``seconds``; returns ``(t0, t1, records)`` with one
    record per request sent: ``[index, t_sched, t_sent, ticket, value,
    t_resolved]`` (``t_sched`` is the send time in a closed loop)."""
    clients = int(traffic["clients"])
    records = []
    out = deque()

    def send():
        i = len(records)
        p = requests.payload(i)
        with spans("bench.send"):
            t = time.perf_counter()
            ticket = requests.submit(server, p)
        rec = [i, t, t, ticket, None, None]
        records.append(rec)
        out.append(rec)

    def collect(rec, timeout):
        try:
            rec[4], rec[5] = server.result_at(rec[3], timeout=timeout)
            return True
        except TimeoutError:
            return False

    def progress():
        return server.served_queries + server.failed_batches

    t0 = time.perf_counter()
    t1 = t0 + seconds
    for _ in range(clients):
        send()
    seen = -1
    while out:
        with spans("bench.collect"):
            got = collect(out[0], POLL_S)
        now = progress()
        if not got and now == seen:
            if time.perf_counter() > t1 + grace:
                break               # what is still out was never answered
            continue
        seen = now
        done = []
        for _ in range(len(out)):   # in send order; the rest keep it
            rec = out.popleft()
            if rec[5] is not None or collect(rec, 0.0):
                done.append(rec)
            else:
                out.append(rec)
        if time.perf_counter() < t1:
            for _ in done:
                send()
    return t0, t1, records
