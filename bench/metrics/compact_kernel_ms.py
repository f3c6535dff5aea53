"""Kernels (``kernels/refine.py``): device milliseconds of the compact
kernel's events in the traced slice per engine batch served in it."""
from bench.work import compact

KERNEL = compact.TRACE_NAME


def read(rec):
    red = rec.get("reduced")
    s0, s1 = rec.get("slice_stats0"), rec.get("slice_stats1")
    if not red or s0 is None:
        return None
    batches = s1["served_batches"] - s0["served_batches"]
    ks = red["kernel_s"].get(KERNEL, 0.0)
    if batches <= 0 or ks <= 0:
        return None
    return 1e3 * ks / batches
