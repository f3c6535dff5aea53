"""The plain reference: brute-force answers over the benchmark's own store.

It imports nothing of the program and takes nothing the program made. The
semantics are the program's documented ones (``docs/api.md``), written
here independently:

* windows are closed axis-aligned rectangles; a record of one vertex is a
  point, a POLYLINE an open chain, a POLYGON a closed ring whose interior
  belongs to it;
* ``intersects``: the record and the closed window share a point.

Arithmetic is float64 on float32-representable coordinates. The control
(``dtype="bfloat16"``) rounds every coordinate and window to
bfloat16 first and computes in float32: the reference in the nearest
precision below the device's float32.

Candidates are the records whose MBR centre lies near the window
(``cKDTree``).
"""
from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree

from bench.store import POLYGON, Store


def _round(a, dtype):
    a = np.asarray(a, np.float64)
    if dtype == "float64":
        return a
    import ml_dtypes

    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


def _seg_meets_rect(ax, ay, bx, by, w):
    """Closed segments (a, b) against the closed rectangle ``w``: separated
    along x, along y, or by the segment's own line (all four corners
    strictly on one side). Degenerate segments reduce to a point test."""
    x0, y0, x1, y1 = w
    box = ((np.minimum(ax, bx) <= x1) & (np.maximum(ax, bx) >= x0)
           & (np.minimum(ay, by) <= y1) & (np.maximum(ay, by) >= y0))
    dx, dy = bx - ax, by - ay
    s = [dx * (cy - ay) - dy * (cx - ax)
         for cx, cy in ((x0, y0), (x1, y0), (x1, y1), (x0, y1))]
    lo = np.minimum(np.minimum(s[0], s[1]), np.minimum(s[2], s[3]))
    hi = np.maximum(np.maximum(s[0], s[1]), np.maximum(s[2], s[3]))
    return box & (lo <= 0) & (hi >= 0)


def _next_index(nv, width, poly):
    """Index of each vertex's successor: polygons close the ring, polylines
    end on a degenerate segment at their last vertex."""
    idx = np.arange(width)[None, :]
    wrap = np.where(poly[:, None], 0, idx)
    return np.where(idx + 1 < nv[:, None], idx + 1, wrap)


def _inside_ring(px, py, v, nv):
    """Even-odd crossing count of a point against closed rings."""
    nxt = _next_index(nv, v.shape[1], np.ones(len(nv), bool))
    ax, ay = v[..., 0], v[..., 1]
    bx = np.take_along_axis(ax, nxt, 1)
    by = np.take_along_axis(ay, nxt, 1)
    valid = np.arange(v.shape[1])[None, :] < nv[:, None]
    straddle = (ay > py) != (by > py)
    with np.errstate(divide="ignore", invalid="ignore"):
        xint = ax + (py - ay) * (bx - ax) / (by - ay)
    cross = straddle & (px < xint) & valid
    return (cross.sum(axis=1) % 2) == 1



class PlainReference:
    """Answers over a private copy of ``store``."""

    def __init__(self, store: Store, dtype: str = "float64"):
        self.dtype = dtype
        self.pool = _round(store.pool, dtype)
        self.off = store.offsets.astype(np.int64).copy()
        self.nv = store.nverts.astype(np.int64).copy()
        self.poly = store.kinds == POLYGON
        mbrs = np.empty((len(self.nv), 4), self.pool.dtype)
        order = np.argsort(self.off, kind="stable")
        for c, red in enumerate((np.minimum, np.minimum, np.maximum,
                                 np.maximum)):
            mbrs[order, c] = red.reduceat(self.pool[:, c % 2],
                                          self.off[order])
        self.mbrs = mbrs
        self._half = (mbrs[:, 2:] - mbrs[:, :2]).max(axis=0) / 2
        self._centres = cKDTree((mbrs[:, :2] + mbrs[:, 2:]) / 2)

    def __len__(self) -> int:
        return len(self.nv)

    # ------------------------------------------------------------ helpers
    def _near(self, box):
        """Ids of every record whose MBR may meet the closed ``box``: MBR
        centres within the box grown by the largest MBR half-extents."""
        box = np.asarray(box, np.float64)
        c = (box[:2] + box[2:]) / 2
        r = float(((box[2:] - box[:2]) / 2 + self._half).max())
        return np.asarray(self._centres.query_ball_point(c, r, p=np.inf),
                          np.int64)

    def _mbr_hits(self, w):
        ids = self._near(w)
        m = self.mbrs[ids]
        keep = ((m[:, 0] <= w[2]) & (w[0] <= m[:, 2])
                & (m[:, 1] <= w[3]) & (w[1] <= m[:, 3]))
        return np.sort(ids[keep])

    def _rings(self, ids):
        nv = self.nv[ids]
        w = int(nv.max()) if len(ids) else 1
        lane = np.minimum(np.arange(w)[None, :], nv[:, None] - 1)
        return self.pool[self.off[ids][:, None] + lane], nv

    # ------------------------------------------------------------ windows
    def mbr_count(self, w) -> int:
        """Records whose MBR meets the closed window ``w``."""
        return int(len(self._mbr_hits(_round(w, self.dtype))))

    def intersects(self, w) -> np.ndarray:
        """Ids of the records that share a point with the closed window
        ``w``, ascending."""
        w = _round(w, self.dtype)
        cand = self._mbr_hits(w)
        if not len(cand):
            return cand
        v, nv = self._rings(cand)
        poly = self.poly[cand]
        nxt = _next_index(nv, v.shape[1], poly)
        ax, ay = v[..., 0], v[..., 1]
        bx = np.take_along_axis(ax, nxt, 1)
        by = np.take_along_axis(ay, nxt, 1)
        valid = np.arange(v.shape[1])[None, :] < nv[:, None]
        hit = (_seg_meets_rect(ax, ay, bx, by, w) & valid).any(axis=1)
        # no edge meets the window: it lies wholly inside or outside the
        # ring, and one corner tells which
        inside = poly & (nv >= 3) & _inside_ring(w[0], w[1], v, nv)
        return cand[hit | inside]
