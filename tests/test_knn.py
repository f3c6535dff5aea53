"""Device-complete kNN suite.

Covers the full tentpole contract: oracle parity on a MIXED store across all
four backends (host / device / device+delta / sharded), the k > live-records
exhaustion path, duplicate query points, deterministic ascending
``(distance, id)`` tie-breaking on co-located records, CDF-seed-underestimate
ladder escalation, ``knn_topk`` impl equivalence (the Pallas kernel in
interpret mode == sort, through the engine and on raw tie-heavy tiles), and the
no-host-gather assertion (device and sharded ranking never pull candidate
geometry to the host). The randomized sweep is marked ``property`` (tier-2:
``pytest -q -m property``) and skips gracefully without hypothesis.
"""
import numpy as np
import pytest
from _hyp import given, settings, st
from _oracle import mixed_store

import repro.core.exec as qexec
from repro.core import geometry as geom
from repro.core.datasets import GeometrySet
from repro.core.engine import EngineConfig, QueryBatch, SpatialIndex
from repro.core.index import GLINConfig
from repro.core.index import knn as host_knn

_N = 400
_CACHE = {}


def _fp32(w):
    return np.asarray(w, np.float32).astype(np.float64)


def _cfg(mesh=None):
    return EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1,
                        knn_device_min_batch=1, mesh=mesh,
                        shard_min_records=1)


def _index(key="device"):
    """Module-cached indexes (hypothesis-safe: no function-scoped fixture)."""
    if key in _CACHE:
        return _CACHE[key]
    mesh = None
    if key == "sharded":
        from repro.utils.compat import make_auto_mesh
        mesh = make_auto_mesh((1, 1), ("data", "model"))
    idx = SpatialIndex.build(mixed_store(_N, seed=3),
                             GLINConfig(piece_limitation=500), _cfg(mesh))
    _CACHE[key] = idx
    return idx


def _oracle_knn(gs, p, k, live=None):
    """Brute-force fp64 kNN over every live record, ranked by the canonical
    ascending (distance, id) contract (geometry.rank_knn)."""
    ids = np.arange(len(gs.nverts), dtype=np.int64)
    if live is not None:
        ids = ids[np.asarray(live)[ids]]
    rect = np.array([p[0], p[1], p[0], p[1]], np.float64)
    d2 = geom.rect_geom_sqdist(rect, gs.padded(ids), gs.nverts[ids],
                               gs.kinds[ids], xp=np)
    return geom.rank_knn(ids, np.sqrt(np.maximum(d2, 0.0)), k)


def _pts(seed, n=16):
    rng = np.random.default_rng(seed)
    return _fp32(rng.uniform(0.15, 0.85, (n, 2)))


def _assert_rows(res, idx, pts, k, fp32=True):
    live = idx.glin._live_mask()
    for i, p in enumerate(pts):
        oi, od = _oracle_knn(idx.gs, p, k, live=live)
        np.testing.assert_array_equal(res.ids[i], oi)
        rtol = 2e-4 if fp32 else 1e-9
        np.testing.assert_allclose(res.distances[i], od, rtol=rtol, atol=1e-7)


@pytest.mark.parametrize("backend", ["host", "device", "sharded"])
def test_knn_matches_bruteforce_oracle(backend):
    idx = _index("sharded" if backend == "sharded" else "device")
    pts = _pts(seed=5)
    res = idx.query(QueryBatch.knn(pts, k=5, backend=backend))
    assert res.plan.backend == backend and res.plan.kind == "knn"
    _assert_rows(res, idx, pts, 5, fp32=backend != "host")
    rank = res.stages[-1]
    assert rank.stage == "knn-rank" and "knn-rank" in rank.covers
    if backend != "host":
        # CDF seeding settles the bulk of points at their first radius: the
        # median ladder depth must be <= 2 rungs (the acceptance bar)
        probes = np.repeat(np.arange(1, rank.rungs + 1),
                           np.asarray(rank.rung_hist, np.int64))
        assert np.median(probes) <= 2
        assert rank.seed_radius > 0.0


def test_device_delta_ranks_unpublished_inserts():
    """An insert after publish is rankable WITHOUT a republish; a tombstoned
    record disappears from the ranking even when it was the nearest."""
    idx = SpatialIndex.build(mixed_store(_N, seed=3),
                             GLINConfig(piece_limitation=500), _cfg())
    idx.snapshot()
    p = np.array([0.4321, 0.5678])
    # delete the current nearest record, then insert a point right at p
    nearest, _ = host_knn(idx.glin, p, 1)
    assert idx.delete(int(nearest[0]))
    new = idx.insert(_fp32([[p[0], p[1]]]), 1, 0)
    assert idx.snapshot_is_stale()
    pts = np.concatenate([[p], _pts(seed=8, n=7)])
    res = idx.query(QueryBatch.knn(pts, k=4))
    assert res.plan.backend == "device+delta"
    assert res.ids[0][0] == new                 # unpublished insert ranked
    for row in res.ids:
        assert int(nearest[0]) not in row       # tombstone masked everywhere
    for i, q in enumerate(pts):                 # full host parity, same epoch
        hi, hd = host_knn(idx.glin, q, 4)
        np.testing.assert_array_equal(res.ids[i], np.asarray(hi, np.int64))
        np.testing.assert_allclose(res.distances[i], hd, rtol=2e-4, atol=1e-7)
    assert idx.snapshot_is_stale()              # parity did NOT republish


@pytest.mark.parametrize("backend", ["host", "device"])
def test_k_exceeds_live_records(backend):
    """k > records: every row returns ALL live records, exhaustion-terminated
    (the within >= n_live rule), still in ascending (distance, id) order."""
    idx = _index("device")
    n_live = int(idx.glin._live_mask().sum())
    pts = _pts(seed=11, n=4)
    res = idx.query(QueryBatch.knn(pts, k=n_live + 50, backend=backend))
    live_ids = set(np.nonzero(idx.glin._live_mask())[0].tolist())
    for i in range(len(pts)):
        assert len(res.ids[i]) == n_live
        assert set(res.ids[i].tolist()) == live_ids
        d, rid = res.distances[i], res.ids[i]
        order = np.lexsort((rid, d))
        np.testing.assert_array_equal(order, np.arange(n_live))


def test_duplicate_query_points_identical_rows():
    idx = _index("device")
    p = _fp32([[0.44, 0.61]])
    pts = np.repeat(p, 6, axis=0)
    res = idx.query(QueryBatch.knn(pts, k=7, backend="device"))
    for i in range(1, 6):
        np.testing.assert_array_equal(res.ids[i], res.ids[0])
        np.testing.assert_array_equal(res.distances[i], res.distances[0])


def test_tied_records_break_by_ascending_id():
    """Co-located records (exactly equal distance) resolve by ascending id on
    every backend — the geometry.rank_knn contract."""
    idx = SpatialIndex.build(mixed_store(160, seed=7),
                             GLINConfig(piece_limitation=500), _cfg())
    site = _fp32([[0.5117, 0.5117]])
    dup = [idx.insert(site, 1, 0) for _ in range(5)]
    idx.snapshot()                              # publish the coincident rows
    pts = np.concatenate([site, site + 0.003])
    want = [_oracle_knn(idx.gs, q, 4, live=idx.glin._live_mask())
            for q in pts]
    for backend in ("host", "device"):
        res = idx.query(QueryBatch.knn(pts, k=4, backend=backend))
        for i, (oi, od) in enumerate(want):
            np.testing.assert_array_equal(res.ids[i], oi)
            # the tied block itself must be id-ascending
            d = res.distances[i]
            for j in range(1, len(d)):
                if d[j] == d[j - 1]:
                    assert res.ids[i][j] > res.ids[i][j - 1]
    # the coincident inserts dominate the at-site row, lowest ids first
    assert want[0][0].tolist() == sorted(dup)[:4]


def test_seed_underestimate_escalates_ladder(monkeypatch):
    """A pathologically small CDF seed is a performance event, not a
    correctness one: the doubling backstop walks extra rungs and the result
    still matches the host loop exactly."""
    idx = _index("device")
    monkeypatch.setattr(
        qexec, "knn_seed_radii",
        lambda snap, w, k: np.full(np.asarray(w).shape[0], 1e-6))
    pts = _pts(seed=13, n=8)
    res = idx.query(QueryBatch.knn(pts, k=4, backend="device"))
    rank = res.stages[-1]
    assert rank.rungs > 1 and rank.seed_hits < len(pts)
    for i, q in enumerate(pts):
        hi, _ = host_knn(idx.glin, q, 4)
        np.testing.assert_array_equal(res.ids[i], np.asarray(hi, np.int64))


def test_knn_topk_pallas_matches_sort():
    import dataclasses
    idx = _index("device")
    pts = _pts(seed=17, n=8)
    base = idx.config
    try:
        idx.config = dataclasses.replace(base, knn_topk="sort")
        a = idx.query(QueryBatch.knn(pts, k=6, backend="device"))
        idx.config = dataclasses.replace(base, knn_topk="interpret")
        b = idx.query(QueryBatch.knn(pts, k=6, backend="device"))
    finally:
        idx.config = base
    assert "topk=sort" in a.stages[-1].note
    assert "topk=interpret" in b.stages[-1].note
    for i in range(len(pts)):
        np.testing.assert_array_equal(a.ids[i], b.ids[i])
        np.testing.assert_array_equal(a.distances[i], b.distances[i])


@pytest.mark.parametrize("q,b,k,dup", [
    (3, 40, 6, 4),       # heavy distance ties, k < live lanes
    (9, 130, 10, 1),     # odd Q and B: internal padding on both axes
    (5, 7, 12, 2),       # k > live candidates: (inf, INT32_MAX) padding
    (8, 64, 64, 3),      # k == B: full sort of the tile
])
def test_knn_topk_kernel_matches_two_key_sort(q, b, k, dup):
    """The partial-selection kernel (interpret mode) against the two-key
    ``lax.sort`` reference on raw tiles: repeated distances resolved by id,
    repeated (distance, id) pairs kept as often as the sort keeps them, and
    dead +inf lanes padding past the live candidates."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(q * 1000 + b)
    d = rng.integers(0, 5, (q, b)).astype(np.float32) / dup  # many ties
    ids = rng.integers(0, 3 * b, (q, b)).astype(np.int32)
    ids[:, 1] = ids[:, 0]                       # a repeated id per row
    d[:, 1] = d[:, 0]                           # ...at the same distance
    dead = rng.random((q, b)) < 0.3
    d[dead] = np.inf
    ids[dead] = 2**31 - 1
    want_d, want_i = ops.knn_topk(jnp.asarray(d), jnp.asarray(ids), k=k,
                                  use_pallas=False)
    got_d, got_i = ops.knn_topk(jnp.asarray(d), jnp.asarray(ids), k=k,
                                interpret=True)
    if k > b:                 # the reference cannot slice past the tile
        want_d = np.concatenate(
            [want_d, np.full((q, k - b), np.inf, np.float32)], axis=1)
        want_i = np.concatenate(
            [want_i, np.full((q, k - b), 2**31 - 1, np.int32)], axis=1)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


@pytest.mark.parametrize("backend", ["device", "sharded"])
def test_no_host_candidate_gather(backend):
    """THE device-complete assertion: once warm, ranking never materialises a
    candidate's vertices on the host — GeometrySet.padded (the only dense
    host gather) must not run during the query."""
    idx = _index("sharded" if backend == "sharded" else "device")
    pts = _pts(seed=19, n=8)
    idx.query(QueryBatch.knn(pts, k=5, backend=backend))   # warm + publish
    want = idx.query(QueryBatch.knn(pts, k=5, backend="host"))

    def boom(self, ids):
        raise AssertionError("host candidate gather during device knn")

    orig = GeometrySet.padded
    GeometrySet.padded = boom
    try:
        res = idx.query(QueryBatch.knn(_pts(seed=23, n=8), k=5,
                                       backend=backend))
        res2 = idx.query(QueryBatch.knn(pts, k=5, backend=backend))
    finally:
        GeometrySet.padded = orig
    assert res.stages[-1].stage == "knn-rank"
    for i in range(len(pts)):
        np.testing.assert_array_equal(res2.ids[i], want.ids[i])


def test_server_submit_knn_flush_cache_and_stages():
    """kNN through the serving tier: one flush = one device-complete batch
    per distinct k, duplicate points coalesce, repeats hit the result cache,
    and knn-rank telemetry surfaces in stats()["engine_stages"]."""
    from repro.serve.server import SpatialQueryServer

    idx = _index("device")
    idx.snapshot()
    srv = SpatialQueryServer(idx)
    pts = _pts(seed=29, n=6)
    ref = idx.query(QueryBatch.knn(pts, k=3, backend="device"))
    tickets = [srv.submit_knn(p, 3) for p in pts]
    dup = srv.submit_knn(pts[0], 3)
    out = srv.flush()
    for i, t in enumerate(tickets):
        ids, dists = out[t]
        np.testing.assert_array_equal(ids, ref.ids[i])
        np.testing.assert_allclose(dists, ref.distances[i])
    np.testing.assert_array_equal(out[dup][0], ref.ids[0])
    assert srv.coalesced >= 1
    t2 = srv.submit_knn(pts[1], 3)          # repeat -> result cache
    ids, dists = srv.flush()[t2]
    np.testing.assert_array_equal(ids, ref.ids[1])
    assert srv.cache_hits >= 1
    ent = srv.stats()["engine_stages"]["device"]["knn-rank"]
    assert ent["calls"] >= 1 and ent["rungs"] >= 1 and ent["rung_hist"]
    assert "knn-rank" in idx.explain(QueryBatch.knn(pts, k=3))


@pytest.mark.property
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([1, 3, 8]))
def test_knn_device_matches_host_property(seed, k):
    idx = _index("device")
    rng = np.random.default_rng(seed)
    pts = _fp32(rng.uniform(0.1, 0.9, (6, 2)))
    dev = idx.query(QueryBatch.knn(pts, k=k, backend="device"))
    hst = idx.query(QueryBatch.knn(pts, k=k, backend="host"))
    for i in range(len(pts)):
        np.testing.assert_array_equal(dev.ids[i], hst.ids[i])
        np.testing.assert_allclose(dev.distances[i], hst.distances[i],
                                   rtol=2e-4, atol=1e-7)
