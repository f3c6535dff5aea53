"""Stages (``core/exec.py``): host-clock milliseconds per call of the
device refine stage (probe, compact kernel, exact stage, every ladder
attempt), from the engine's stage counters over the window."""
from bench.harness import stage_delta


def read(rec):
    calls = stage_delta(rec["stats0"], rec["stats1"], "refine", "calls")
    if calls <= 0:
        return None
    return stage_delta(rec["stats0"], rec["stats1"], "refine",
                       "wall_ms") / calls
