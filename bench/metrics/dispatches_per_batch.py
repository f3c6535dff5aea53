"""Stages, overflow ladder (``core/exec.py``): jitted programs launched per
call of the cell's stage over the window. One is the least; every budget
escalation and bounds probe adds one."""
from bench.harness import stage_delta


def read(rec):
    st = rec["stage"]
    calls = stage_delta(rec["stats0"], rec["stats1"], st, "calls")
    if calls <= 0:
        return None
    return stage_delta(rec["stats0"], rec["stats1"], st,
                       "dispatches") / calls
