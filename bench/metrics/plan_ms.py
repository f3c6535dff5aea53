"""Planner (``core/engine.py``, ``SpatialIndex.query``): host milliseconds
of one query's prologue (facade lock, republish upkeep, ``plan``,
``compile_plan``), timed by the ``glin.engine.plan`` span's clock pair, over
the window: Δ``plan_ms`` / Δ``plans``. A program without the counters reads
nothing."""


def read(rec):
    s0, s1 = rec["stats0"], rec["stats1"]
    if "plans" not in s0:
        return None
    plans = s1["plans"] - s0["plans"]
    if plans <= 0:
        return None
    return (s1["plan_ms"] - s0["plan_ms"]) / plans
