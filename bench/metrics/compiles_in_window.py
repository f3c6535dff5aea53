"""Stages (``core/exec.py``): backend compiles (persistent-cache hits
included) that the engine's stages triggered inside the window, summed over
every backend and stage: Δ``compiles`` of ``engine_stages``. Set-up warms
every shape the traffic reaches, so anything above 0 is a shape it missed.
A program without the counter reads nothing."""


def _total(stats):
    per = [ent.get("compiles") for stages in stats["engine_stages"].values()
           for ent in stages.values()]
    if not per or None in per:
        return None
    return sum(per)


def read(rec):
    a, b = _total(rec["stats0"]), _total(rec["stats1"])
    if a is None or b is None:
        return None
    return b - a
