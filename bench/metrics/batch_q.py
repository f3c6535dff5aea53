"""Serving tier (``serve/server.py``): queries per engine batch over the
window, the server's own counters (cache misses over served batches)."""


def read(rec):
    s0, s1 = rec["stats0"], rec["stats1"]
    batches = s1["served_batches"] - s0["served_batches"]
    if batches <= 0:
        return None
    return (s1["cache_misses"] - s0["cache_misses"]) / batches
