"""Kernels (``kernels/refine.py``): the compact kernel's share of its
roofline in the traced slice. The least time is the bytes the requests
need (``bench/work/compact.py``, counted by the reference from the data
and the requests) over the HBM peak of the device's row in
``bench/peaks.json``; the share is that over the kernel's device time.
Only requests sent after the slice began and answered before it ended are
counted, so the share is not overstated by work outside the slice."""
from bench.work import compact

KERNEL = compact.TRACE_NAME


def read(rec):
    red = rec.get("reduced")
    if not red or rec.get("peaks") is None or "slice" not in rec:
        return None
    ks = red["kernel_s"].get(KERNEL, 0.0)
    if ks <= 0:
        return None
    lo, hi = rec["slice"]
    req, ref = rec["requests"], rec["ref"]
    need = sum(req.work_bytes(ref, req.payload(r[0]), r[4])
               for r in rec["records"]
               if r[5] is not None and r[2] >= lo and r[5] <= hi
               and not hasattr(r[4], "reason"))
    if need <= 0:
        return None
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / ks
