"""Stages (``core/exec.py``): host milliseconds per call of the cell's
stage that the host did not spend blocked on device results, its self time:
(Δ``wall_ms`` - Δ``wait_ms``) / Δ``calls`` over the window. ``wall_ms`` is
the ``glin.stage.<name>`` span's clock pair and ``wait_ms`` the sum of its
``glin.refine.wait`` spans. A program without ``wait_ms`` reads nothing."""
from bench.harness import stage_delta


def read(rec):
    s0, s1, st = rec["stats0"], rec["stats1"], rec["stage"]
    if "wait_ms" not in s1["engine_stages"].get("device", {}).get(st, {}):
        return None
    calls = stage_delta(s0, s1, st, "calls")
    if calls <= 0:
        return None
    return (stage_delta(s0, s1, st, "wall_ms")
            - stage_delta(s0, s1, st, "wait_ms")) / calls
