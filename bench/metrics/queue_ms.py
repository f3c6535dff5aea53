"""Serving tier (``serve/server.py``): milliseconds a request waits from its
enqueue until a worker starts serving its batch, averaged over the window:
the server's counters, Δ``queue_wait_ms`` / Δ``queue_waited``. A program
without the counters reads nothing."""


def read(rec):
    s0, s1 = rec["stats0"], rec["stats1"]
    if "queue_waited" not in s0:
        return None
    waited = s1["queue_waited"] - s0["queue_waited"]
    if waited <= 0:
        return None
    return (s1["queue_wait_ms"] - s0["queue_wait_ms"]) / waited
