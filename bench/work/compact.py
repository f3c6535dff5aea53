"""Work of the compact kernel (``refine_compact`` in ``kernels/refine.py``).

The count comes from the requests and the reference's answers alone, never
from the grid the kernel walks: it is the least any implementation of the
filter-and-compact step must move through HBM. Each record whose MBR meets
the request's probe region is read once (four float32 coordinates, 16 B),
the probe window is read once (16 B), and each returned id is written once
(4 B). A kernel that skips tiles outside the probe run still moves at
least this much, so the share of the roofline cannot pass 100%.

The kernel compares on the vector unit, which has no published peak; the
bound is HBM bandwidth alone.
"""
from __future__ import annotations

# the kernel's op name in the device trace (``%refine_compact.<n> = ...``)
TRACE_NAME = "refine_compact"

MBR_BYTES = 16
WINDOW_BYTES = 16
ID_BYTES = 4


def bytes_needed(mbr_hits: int, ids_returned: int) -> int:
    return MBR_BYTES * int(mbr_hits) + WINDOW_BYTES + ID_BYTES * int(
        ids_returned)
