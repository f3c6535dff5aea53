"""The plain reference and the work counts against brute force over every
record, in exact rational arithmetic where it decides a predicate."""
import os
from fractions import Fraction

import numpy as np
import pytest

from bench import harness
from bench.reference import PlainReference
from bench.store import POLYGON
from bench.work import compact

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def orient(a, b, c):
    v = (b[0] - a[0]) * (c[1] - a[1]) - (b[1] - a[1]) * (c[0] - a[0])
    return (v > 0) - (v < 0)


def on_seg(a, b, c):
    return (min(a[0], b[0]) <= c[0] <= max(a[0], b[0])
            and min(a[1], b[1]) <= c[1] <= max(a[1], b[1]))


def segs_meet(p1, p2, q1, q2):
    o1, o2 = orient(p1, p2, q1), orient(p1, p2, q2)
    o3, o4 = orient(q1, q2, p1), orient(q1, q2, p2)
    if o1 != o2 and o3 != o4:
        return True
    return ((o1 == 0 and on_seg(p1, p2, q1)) or (o2 == 0 and on_seg(p1, p2, q2))
            or (o3 == 0 and on_seg(q1, q2, p1))
            or (o4 == 0 and on_seg(q1, q2, p2)))


def inside_poly(ring, p):
    odd = False
    for i in range(len(ring)):
        a, b = ring[i], ring[(i + 1) % len(ring)]
        if (a[1] > p[1]) != (b[1] > p[1]):
            x = a[0] + (p[1] - a[1]) * (b[0] - a[0]) / (b[1] - a[1])
            odd ^= p[0] < x
    return odd


def brute_intersects(ring, polygon, w):
    F = [Fraction(float(v)) for v in w]
    pts = [(Fraction(float(x)), Fraction(float(y))) for x, y in ring]
    if any(F[0] <= x <= F[2] and F[1] <= y <= F[3] for x, y in pts):
        return True
    corners = [(F[0], F[1]), (F[2], F[1]), (F[2], F[3]), (F[0], F[3])]
    sides = [(corners[i], corners[(i + 1) % 4]) for i in range(4)]
    n = len(pts)
    segs = [(pts[i], pts[(i + 1) % n]) for i in range(n if polygon else n - 1)]
    if any(segs_meet(a, b, c, d) for a, b in segs for c, d in sides):
        return True
    return polygon and n >= 3 and inside_poly(pts, corners[0])



@pytest.fixture(scope="module")
def mixed():
    cell = harness.Cell(ROOT, "mixed10m-tiles-closed96")
    cell.config["records"] = 2000
    for layer in cell.config["layers"]:       # denser: more contacts
        if "size" in layer:
            layer["size"] = [0.005, 0.03]
        if "step" in layer:
            layer["step"] = [0.002, 0.01]
    store = cell.generator.generate(cell.config)
    return store, PlainReference(store)


def rings(store):
    return [store.pool[store.offsets[i]:store.offsets[i] + store.nverts[i]]
            for i in range(len(store))]


def test_intersects_matches_exact_brute_force(mixed):
    store, ref = mixed
    rng = np.random.default_rng(0)
    rs = rings(store)
    for _ in range(12):
        c = rng.uniform(0.1, 0.9, 2)
        h = rng.uniform(0.005, 0.03, 2)
        w = np.r_[c - h, c + h].astype(np.float32).astype(np.float64)
        m = store.mbrs
        near = np.nonzero((m[:, 0] <= w[2]) & (m[:, 2] >= w[0])
                          & (m[:, 1] <= w[3]) & (m[:, 3] >= w[1]))[0]
        want = [i for i in near
                if brute_intersects(rs[i], store.kinds[i] == POLYGON, w)]
        assert ref.intersects(w).tolist() == want
        assert ref.mbr_count(w) == len(near)


def test_warm_up_counter_matches_brute_force(mixed):
    """The requests' own MBR counter, which sizes the warm-up before the
    reference exists, agrees with a scan of every record."""
    store, ref = mixed
    cell = harness.Cell(ROOT, "mixed10m-tiles-closed96")
    mi = cell.requests.MbrIndex(store.mbrs)
    rng = np.random.default_rng(1)
    c = rng.uniform(0.0, 1.0, (40, 2))
    h = rng.uniform(0.0, 0.05, (40, 2))
    ws = np.concatenate([c - h, c + h], 1)
    m = store.mbrs
    want = [int(((m[:, 0] <= w[2]) & (m[:, 2] >= w[0]) & (m[:, 1] <= w[3])
                 & (m[:, 3] >= w[1])).sum()) for w in ws]
    assert mi.count(ws).tolist() == want
    assert [ref.mbr_count(w) for w in ws] == want


def test_work_count():
    assert compact.bytes_needed(100, 7) == 16 * 100 + 16 + 4 * 7
