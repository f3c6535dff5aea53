"""Device: the share of the traced slice in which no operation ran on the
chip, 1 - (union of device op intervals) / slice, from the profiler
trace."""


def read(rec):
    red = rec.get("reduced")
    if not red or red["window_s"] <= 0 or not red["device_ops"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
