"""Generators, request recipes and the closed loop: deterministic by
seed, the same work for every seed, and the window recipe against a
brute-force version."""
import os
import threading
import time

import numpy as np
import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mixed10m-tiles-closed96"


@pytest.fixture
def small(cells_root):
    def make(n):
        cell = harness.Cell(cells_root, CELL)
        cell.config["records"] = n
        return cell
    return make


def records(store):
    """Each record as a tuple of (kind, vertices), in id order."""
    return [(int(k), store.pool[o:o + n].tobytes()) for k, o, n in
            zip(store.kinds, store.offsets, store.nverts)]


def test_store_by_seed(small):
    """One map for every seed; a seed puts its records in an order of its
    own."""
    cell = small(3000)
    a = cell.generator.generate(cell.config)
    b = cell.generator.generate(cell.config)
    assert np.array_equal(a.pool, b.pool) and np.array_equal(a.mbrs, b.mbrs)
    assert np.array_equal(a.pool, a.pool.astype(np.float32))
    assert (a.pool >= 0).all() and (a.pool < 1).all()
    c = a.permuted(np.random.default_rng(8).permutation(len(a)))
    assert not np.array_equal(a.pool[:100], c.pool[:100])
    assert sorted(records(a)) == sorted(records(c))
    for s in (a, c):
        for i in range(0, len(s), 97):
            ring = s.pool[s.offsets[i]:s.offsets[i] + s.nverts[i]]
            assert np.array_equal(s.mbrs[i], np.r_[ring.min(0), ring.max(0)])


def test_store_cache_round_trip(small, tmp_path):
    cell = small(3000)
    fresh = cell.generator.generate(cell.config)
    first = cell.generator.generate(cell.config, str(tmp_path))
    (entry,) = os.listdir(tmp_path)
    assert entry.startswith("mixed-10m-")
    again = cell.generator.generate(cell.config, str(tmp_path))
    for s in (first, again):
        for f in ("pool", "offsets", "nverts", "kinds", "mbrs"):
            got, want = getattr(s, f), getattr(fresh, f)
            assert got.dtype == want.dtype and np.array_equal(got, want), f
    cell.config["data_seed"] = 2           # another map: another entry
    cell.generator.generate(cell.config, str(tmp_path))
    assert len(os.listdir(tmp_path)) == 2


def test_mixed_layers(small):
    cell = small(4000)
    s = cell.generator.generate(cell.config)
    nv = s.nverts
    assert (nv == 1).sum() == 1800 and (nv == 64).sum() == 400
    assert (s.kinds == 1).sum() == 1000 and set(nv[s.kinds == 1]) == set(
        range(2, 9))


def test_every_seed_publishes_the_same_shapes(small):
    """The published index, and so every device program, has the same
    shapes for every seed."""
    import jax

    from repro.core.datasets import GeometrySet
    from repro.core.engine import EngineConfig, SpatialIndex
    from repro.core.index import GLINConfig
    from repro.core.zorder import UNIT

    cell = small(6000)
    cfg = cell.config
    base = cell.generator.generate(cfg)

    def shapes(seed):
        st = base.permuted(np.random.default_rng(seed).permutation(len(base)))
        gs = GeometrySet(pool=st.pool, offsets=st.offsets, nverts=st.nverts,
                         kinds=st.kinds, mbrs=st.mbrs, grid=UNIT, name="t")
        snap = SpatialIndex.build(gs, GLINConfig(**cfg["glin"]),
                                  EngineConfig(**cfg["engine"])).snapshot()
        leaves, tree = jax.tree_util.tree_flatten(snap)
        return str(tree), [(np.shape(x), str(np.asarray(x).dtype))
                           for x in leaves]

    assert shapes(11) == shapes(2**31 + 99)


def brute_windows(mbrs, anchors, ks):
    """§IX-A by brute force: MBR of the k nearest MBR centres
    (Chebyshev) of each anchor record."""
    c = (mbrs[:, :2] + mbrs[:, 2:]) / 2
    out = []
    for a, k in zip(anchors, ks):
        d = np.abs(c - c[a]).max(axis=1)
        near = np.argsort(d, kind="stable")[:k]
        m = mbrs[near]
        out.append([m[:, 0].min(), m[:, 1].min(), m[:, 2].max(),
                    m[:, 3].max()])
    return np.asarray(out, np.float32).astype(np.float64)


def test_window_recipe_matches_brute_force(small):
    cell = small(5000)
    store = cell.generator.generate(cell.config)
    traffic = dict(cell.traffic, pool=64, max_requests=256,
                   selectivity=[2e-4, 8e-3])
    a = cell.requests.Requests(store, traffic, np.random.default_rng(5))
    b = cell.requests.Requests(store, traffic, np.random.default_rng(5))
    c = cell.requests.Requests(store, traffic, np.random.default_rng(6))
    assert np.array_equal(a.windows, b.windows)
    assert len(np.unique(a.windows, axis=0)) == len(a.windows)
    # every seed sends the same views, in another order
    assert not np.array_equal(a.pool, c.pool)
    assert np.array_equal(np.unique(a.pool, axis=0), np.unique(c.pool, axis=0))
    rng = np.random.default_rng(traffic["pool_seed"])
    ks = cell.requests.pool_sizes(5000, traffic["selectivity"], 64, rng)
    anchors = rng.integers(0, len(store), 64)
    assert ks.min() == 1 and ks.max() == 39
    # ties in Chebyshev distance may pick another record of equal rank;
    # with continuous coordinates there are none
    assert np.array_equal(np.unique(a.pool, axis=0), np.unique(
        brute_windows(store.mbrs, anchors, ks), axis=0))
    shift = a.windows - a.pool[np.arange(256) % 64]
    assert np.abs(shift).max() <= traffic["jitter"] * 1.01
    assert np.allclose(shift[:, :2], shift[:, 2:], atol=1e-7)


def test_pool_sizes_same_for_every_seed(small):
    sel = small(1).traffic["selectivity"]
    a = small(1).requests.pool_sizes(10**7, sel, 4096,
                                     np.random.default_rng(1))
    b = small(1).requests.pool_sizes(10**7, sel, 4096,
                                     np.random.default_rng(2))
    assert np.array_equal(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)
    assert a.min() == 16 and a.max() == 400
    # log-uniform: the median at the geometric mean
    assert np.median(a) == np.sqrt(16 * 400)


class FakeServer:
    """Answers each ticket after a delay of its own, on a thread."""

    def __init__(self, delays):
        self.delays, self.done, self.n = delays, {}, 0
        self.cond = threading.Condition()
        self.served_queries = self.failed_batches = 0

    def submit(self, payload):
        t, self.n = self.n, self.n + 1

        def answer():
            time.sleep(self.delays(t))
            with self.cond:
                self.done[t] = (t, time.perf_counter())
                self.served_queries += 1
                self.cond.notify_all()

        threading.Thread(target=answer, daemon=True).start()
        return t

    def result_at(self, t, timeout):
        with self.cond:
            if not self.cond.wait_for(lambda: t in self.done, timeout):
                raise TimeoutError(t)
            return self.done.pop(t)


class Stream:
    def payload(self, i):
        return i

    def submit(self, server, payload):
        return server.submit(payload)


def test_closed_loop_collects_in_completion_order():
    """A client whose answer came first sends again without waiting for
    an older request still out."""
    import contextlib

    loop = harness.load_module(ROOT, "loops", "closed")
    # ticket 0 takes the whole window; the other client answers at once
    srv = FakeServer(lambda t: 0.6 if t == 0 else 0.01)
    t0, t1, recs = loop.drive(srv, Stream(), {"clients": 2}, 0.3,
                              lambda name: contextlib.nullcontext(), 2.0)
    assert recs[0][4] == 0 and recs[0][5] - recs[0][2] >= 0.6
    assert len(recs) > 10          # client 2 kept sending meanwhile
    assert all(r[4] == r[0] for r in recs)
    assert all(r[2] < t1 for r in recs)
