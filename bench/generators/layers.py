"""Layered synthetic stores: geometry families mixed in fixed shares.

The family recipes are the program's ``core/datasets.py`` ones (uniform
points, road-like random-walk polylines, concave star and L-shaped
polygons, fixed-width boundary rings), copied here so that the yardstick does not move when the
program's generators change. Which of their numbers a source gives and
which are assumed is listed in each configuration file.

A configuration's geometry is drawn once, from its own ``data_seed``: a
deployment serves one map. (The harness puts the records in an order of
``--seed``'s, so each seed has other ids and the same records, and the
published index, with every shape the device programs see, is the same
for every seed.) Coordinates are rounded to float32 (the device's precision) and the
MBRs are recomputed from the rounded rings.

The geometry is made with vectorised NumPy and kept under the cache
directory the harness gives, keyed by the configuration, so that only
the first run in a checkout draws it.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from bench.store import POLYGON, POLYLINE, Store

# bump when a recipe changes, so that no cached geometry of the old one
# is read back
RECIPE_VERSION = 1

_TOP = 1.0 - 2.0 ** -24          # largest float32 below 1


def _widths(n: int, values, rng) -> np.ndarray:
    """``n`` widths cycling through ``values``, shuffled: the same multiset
    for every seed."""
    v = np.asarray(values, np.int32)
    return rng.permutation(v[np.arange(n) % len(v)])


def _pad_last(verts: np.ndarray, nverts: np.ndarray) -> np.ndarray:
    idx = np.minimum(np.arange(verts.shape[1])[None, :], nverts[:, None] - 1)
    return np.take_along_axis(verts, idx[:, :, None], axis=1)


def points(rng, n, p):
    """Points uniform over the unit square."""
    return rng.uniform(0.0, 1.0, (n, 1, 2)), np.ones(n, np.int32), POLYGON



def roads(rng, n, p):
    """Random-walk polylines with a persistent heading, 2..max_verts
    vertices, anisotropic steps."""
    w = int(p.get("max_verts", 8))
    nv = _widths(n, range(2, w + 1), rng)
    lo, hi = p.get("step", [2e-5, 2e-4])
    starts = rng.uniform(0.02, 0.98, (n, 2))
    steps = rng.uniform(lo, hi, n)
    theta = (rng.uniform(0.0, 2 * np.pi, (n, 1))
             + rng.normal(0.0, 0.25, (n, w)).cumsum(axis=1))
    dx = np.cos(theta) * steps[:, None] * float(p.get("anisotropy", 3.0))
    dy = np.sin(theta) * steps[:, None]
    zero = np.zeros((n, 1))
    vx = starts[:, :1] + np.concatenate([zero, dx[:, :-1].cumsum(1)], 1)
    vy = starts[:, 1:] + np.concatenate([zero, dy[:, :-1].cumsum(1)], 1)
    return _pad_last(np.stack([vx, vy], -1), nv), nv, POLYLINE


def concave(rng, n, p):
    """Simple concave rings: half rotated L-shapes (6 vertices), half star
    polygons with 6..max_verts vertices (radius alternating r, 0.25-0.5 r)."""
    w = int(p.get("max_verts", 12))
    lo, hi = p.get("size", [5e-5, 5e-4])
    ell = rng.permutation(np.arange(n) < n // 2)
    nv = np.full(n, 6, np.int32)
    nv[~ell] = _widths(int((~ell).sum()), range(6, w + 1, 2), rng)
    centres = rng.uniform(0.02, 0.98, (n, 2))
    sizes = rng.uniform(lo, hi, n)
    ang = np.sort(rng.uniform(0.0, 2 * np.pi, (n, w)), axis=1)
    frac = rng.uniform(0.25, 0.5, (n, 1))
    rad = np.where(np.arange(w)[None, :] % 2 == 0, sizes[:, None],
                   sizes[:, None] * frac)
    verts = np.stack([centres[:, :1] + rad * np.cos(ang),
                      centres[:, 1:] + rad * np.sin(ang)], -1)
    t = rng.uniform(0.25, 0.6, n)
    one, nil = np.ones(n), np.zeros(n)
    unit = np.stack([np.stack(c, -1) for c in (
        (nil, nil), (one, nil), (one, t), (t, t), (t, one), (nil, one))], 1)
    th = rng.uniform(0.0, 2 * np.pi, n)
    c, s = np.cos(th)[:, None], np.sin(th)[:, None]
    sh = (unit - 0.5) * (2.0 * sizes[:, None, None])
    lv = np.stack([centres[:, :1] + sh[..., 0] * c - sh[..., 1] * s,
                   centres[:, 1:] + sh[..., 0] * s + sh[..., 1] * c], -1)
    verts[ell, :6] = lv[ell]
    return _pad_last(verts, nv), nv, POLYGON


def rings(rng, n, p):
    """Dense boundary rings of exactly ``verts`` vertices."""
    w = int(p.get("verts", 64))
    lo, hi = p.get("size", [5e-5, 5e-4])
    centres = rng.uniform(0.02, 0.98, (n, 2))
    sizes = rng.uniform(lo, hi, n)
    ang = np.sort(rng.uniform(0.0, 2 * np.pi, (n, w)), axis=1)
    rad = sizes[:, None] * rng.uniform(0.7, 1.0, (n, w))
    verts = np.stack([centres[:, :1] + rad * np.cos(ang),
                      centres[:, 1:] + rad * np.sin(ang)], -1)
    return verts, np.full(n, w, np.int32), POLYGON


FAMILIES = {f.__name__: f for f in (points, roads, concave, rings)}


def layer_counts(n: int, layers) -> list:
    """Records per layer: each layer's share of ``n`` rounded down, the
    remainder to the first layer."""
    counts = [int(n * float(l["share"])) for l in layers]
    counts[0] += n - sum(counts)
    return counts


def generate(cfg: dict, cache_dir: str | None = None) -> Store:
    """The records of configuration ``cfg``, drawn from its ``data_seed``
    (read back from ``cache_dir`` where an earlier run kept them)."""
    if cache_dir is None:
        return draw(cfg)
    key = hashlib.sha256(json.dumps(
        [RECIPE_VERSION, cfg["records"], cfg["layers"], cfg["data_seed"]],
        sort_keys=True).encode()).hexdigest()[:16]
    path = os.path.join(cache_dir, f"{cfg['name']}-{key}")
    if os.path.isdir(path):
        return Store.load(path)
    store = draw(cfg)
    store.save(path)
    return store


def draw(cfg: dict) -> Store:
    """The geometry of configuration ``cfg``, from its ``data_seed``."""
    n = int(cfg["records"])
    rng = np.random.default_rng(int(cfg["data_seed"]))
    blocks = []
    for layer, cnt in zip(cfg["layers"], layer_counts(n, cfg["layers"])):
        v, nv, kind = FAMILIES[layer["family"]](rng, cnt, layer)
        v = np.clip(v, 0.0, _TOP).astype(np.float32)
        xs, ys = np.ascontiguousarray(v[..., 0]), np.ascontiguousarray(v[..., 1])
        mbr = np.stack([xs.min(1), ys.min(1), xs.max(1), ys.max(1)], 1)
        blocks.append((v, nv, np.full(cnt, kind, np.int8), mbr))
    nverts = np.concatenate([b[1] for b in blocks])
    offsets = np.zeros(n, np.int64)
    np.cumsum(nverts[:-1], out=offsets[1:])
    pool = np.concatenate([v[np.arange(v.shape[1])[None, :] < nv[:, None]]
                           for v, nv, _, _ in blocks])
    return Store(pool=pool.astype(np.float64), offsets=offsets,
                 nverts=nverts, kinds=np.concatenate([b[2] for b in blocks]),
                 mbrs=np.concatenate([b[3] for b in blocks]).astype(
                     np.float64))
