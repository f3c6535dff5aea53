"""SpatialIndex facade: one entry point for all relations + knn, planner
backend selection (host / device / device+delta), epoch-invalidated snapshots
and LSM-style delta patching under interleaved maintenance (split and merge
both exercised), and the GLIN.insert vertex-capacity fix."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geometry as geom
from repro.core.datasets import generate, make_query_windows
from repro.core.engine import EngineConfig, QueryBatch, SpatialIndex
from repro.core.index import GLINConfig
from repro.core.model import GLINModelConfig
from repro.core.relations import RELATIONS as RELATION_REGISTRY
from repro.core.relations import (Relation, get_relation, register_relation,
                                  relation_names)

RELATIONS = ("contains", "intersects", "within", "covers", "disjoint",
             "touches", "crosses", "dwithin:0.003")


def _build(name="cluster", n=4000, pl=200, seed=1, config=None, **kw):
    gs = generate(name, n, seed=seed)
    return SpatialIndex.build(gs, GLINConfig(piece_limitation=pl, **kw),
                              config=config)


def _oracle(idx, w, relation, dtype=np.float64):
    """Brute-force relation oracle over live records at the given precision."""
    gs = idx.gs
    rel = get_relation(relation)
    ok = rel.predicate(np.asarray(w, dtype), gs.verts.astype(dtype),
                       gs.nverts, gs.kinds)
    live = idx.glin._live_mask()
    return np.nonzero(np.asarray(ok) & live)[0].astype(np.int64)


def _big_polygon(rng, c, r=0.02, nv=10):
    ang = np.sort(rng.uniform(0, 2 * np.pi, nv))
    return np.stack([c[0] + r * np.cos(ang), c[1] + r * np.sin(ang)], -1)


# ---------------------------------------------------------------- relations --
@pytest.mark.parametrize("relation", RELATIONS)
def test_all_relations_host_match_bruteforce(relation):
    idx = _build()
    wins = make_query_windows(idx.gs, 0.01, 4, seed=3)
    res = idx.query(wins, relation, backend="host")
    for qi, w in enumerate(wins):
        np.testing.assert_array_equal(res[qi], _oracle(idx, w, relation))


@pytest.mark.parametrize("relation", ["contains", "intersects", "covers",
                                      "disjoint", "touches", "crosses",
                                      "dwithin:0.003"])
def test_all_relations_device_match_fp32_oracle(relation):
    idx = _build()
    wins = make_query_windows(idx.gs, 0.01, 4, seed=3)
    res = idx.query(wins, relation, backend="device")
    for qi, w in enumerate(wins):
        np.testing.assert_array_equal(
            res[qi], _oracle(idx, w.astype(np.float32), relation, np.float32))


def test_within_finds_covering_polygons_on_both_backends():
    idx = _build()
    rng = np.random.default_rng(5)
    centers = [rng.uniform(0.2, 0.8, 2) for _ in range(4)]
    recs = [idx.insert(_big_polygon(rng, c), 10, 0) for c in centers]
    wins = np.array([[c[0] - 1e-3, c[1] - 1e-3, c[0] + 1e-3, c[1] + 1e-3]
                     for c in centers])
    for backend in ("host", "device"):
        res = idx.query(wins, "within", backend=backend)
        dtype = np.float64 if backend == "host" else np.float32
        for qi, w in enumerate(wins):
            assert recs[qi] in res[qi]
            np.testing.assert_array_equal(
                res[qi], _oracle(idx, w.astype(dtype), "within", dtype))


def test_touches_finds_boundary_contact_on_both_backends():
    """Windows flush against a record's MBR left edge: the leftmost vertex
    lies ON the window's right edge, the rest of the ring strictly right of
    it — guaranteed Touches hits (random windows never touch exactly)."""
    idx = _build("concave", n=3000, seed=4)
    # fp64 MBRs verbatim: the leftmost vertex sits exactly on the window's
    # right edge in fp64, and fp32 rounds window edge and vertex to the SAME
    # value, so the contact survives the device precision contract too
    m = idx.gs.mbrs[::379][:6]
    wins = np.stack([m[:, 0] - 2e-3, m[:, 1] - 1e-3,
                     m[:, 0], m[:, 3] + 1e-3], axis=1)
    for backend, dtype in (("host", np.float64), ("device", np.float32)):
        res = idx.query(wins, "touches", backend=backend)
        assert res.total_hits > 0
        for qi, w in enumerate(wins):
            np.testing.assert_array_equal(
                res[qi], _oracle(idx, w.astype(dtype), "touches", dtype))


def test_dwithin_padded_probe_at_domain_edge_device_parity():
    """REGRESSION: the dwithin probe window pads past the Z-grid domain edge
    near (1,1); device-side two-stage quantization used to compute the fine
    limb inside an out-of-range coarse cell, collapsing the probe interval
    and silently dropping every corner record on the device path."""
    rng = np.random.default_rng(8)
    gs = generate("uniform", 2000, seed=1)
    idx = SpatialIndex.build(gs, GLINConfig(piece_limitation=200),
                             EngineConfig(device_min_batch=1))
    recs = []
    for _ in range(20):   # tiny squares hugging the (1, 1) corner
        c = 1.0 - rng.uniform(1e-5, 2.5e-5, 2)
        v = np.array([[c[0], c[1]], [c[0] + 5e-6, c[1]],
                      [c[0] + 5e-6, c[1] + 5e-6], [c[0], c[1] + 5e-6]])
        recs.append(idx.insert(np.clip(v, 0, 1 - 1e-9), 4, 0))
    w = np.tile([0.998, 0.998, 0.999, 0.999], (2, 1))
    host = idx.query(w, "dwithin:0.005", backend="host")
    dev = idx.query(w, "dwithin:0.005", backend="device")
    assert set(recs) <= set(host[0].tolist())
    np.testing.assert_array_equal(host[0], dev[0])


def test_contains_is_proper_covers_is_closed():
    """A point record ON the window boundary is covered but not contained."""
    idx = _build("points", n=500, pl=50)
    p = idx.gs.verts[7, 0]  # an arbitrary record's point
    w = np.array([p[0], p[1] - 1e-4, p[0] + 1e-4, p[1] + 1e-4])  # xmin == px
    covers = idx.query(w, "covers")[0]
    contains = idx.query(w, "contains")[0]
    assert 7 in covers and 7 not in contains
    assert set(contains).issubset(set(covers))


def test_disjoint_complements_intersects():
    idx = _build(n=2000)
    w = make_query_windows(idx.gs, 0.02, 1, seed=9)[0]
    inter = idx.query(w, "intersects")[0]
    disj = idx.query(w, "disjoint")[0]
    assert len(set(inter) & set(disj)) == 0
    assert len(inter) + len(disj) == len(idx)


def test_knn_is_a_query_kind():
    idx = _build(n=3000)
    pts = np.array([[0.3, 0.4], [0.7, 0.2]])
    res = idx.query(QueryBatch.knn(pts, k=7))
    assert res.plan.backend == "host" and res.plan.kind == "knn"
    gs = idx.gs
    for qi, p in enumerate(pts):
        assert res.ids[qi].shape == (7,) and res.distances[qi].shape == (7,)
        rect = np.array([p[0], p[1], p[0], p[1]])
        d = np.sqrt(geom.rect_geom_sqdist(rect, gs.verts, gs.nverts,
                                          gs.kinds))
        np.testing.assert_allclose(res.distances[qi], np.sort(d)[:7],
                                   atol=1e-12)


def test_knn_device_batch_matches_host_loop():
    """A point batch >= knn_device_min_batch plans the device-complete
    CDF-seeded ladder; ids must equal the host loop point-for-point (the
    fp32-representable grid makes both candidate sets identical), while
    distances come from the fp32 device rank vs the host's fp64 (rtol 1e-4)."""
    from repro.core.index import knn as host_knn

    gs = _fp32_grid(generate("cluster", 3000, seed=7))
    idx = SpatialIndex.build(gs, GLINConfig(piece_limitation=200),
                             EngineConfig(knn_device_min_batch=8))
    pts = np.random.default_rng(11).uniform(0.15, 0.85, (24, 2))
    res = idx.query(QueryBatch.knn(pts, k=6))
    assert res.plan.backend == "device" and "device-complete knn" in res.plan.reason
    for qi, p in enumerate(pts):
        hi, hd = host_knn(idx.glin, p, 6)
        np.testing.assert_array_equal(res.ids[qi], hi)
        np.testing.assert_allclose(res.distances[qi], hd, rtol=1e-4)
    # below the threshold (or without the piecewise function) it stays host
    small = idx.query(QueryBatch.knn(pts[:2], k=6))
    assert small.plan.backend == "host"
    for qi in range(2):
        np.testing.assert_array_equal(small.ids[qi], res.ids[qi])


def test_unknown_relation_rejected():
    idx = _build(n=500, pl=50)
    with pytest.raises(ValueError, match="unknown relation"):
        idx.query(np.array([0, 0, 1, 1.0]), "overlaps")
    # parametric families must be queried with a bound parameter
    with pytest.raises(ValueError, match="requires a parameter"):
        idx.query(np.array([0, 0, 1, 1.0]), "dwithin")
    with pytest.raises(ValueError, match="bad parameter"):
        idx.query(np.array([0, 0, 1, 1.0]), "dwithin:huge")
    assert {"contains", "intersects", "within", "covers", "disjoint",
            "touches", "crosses", "dwithin"} <= set(relation_names())
    assert set(RELATION_REGISTRY) == set(relation_names())


# ------------------------------------------------------------------ planner --
def test_planner_picks_host_for_small_device_for_large():
    idx = _build(config=EngineConfig(device_min_batch=16))
    idx.snapshot()   # fresh snapshot: the batch size alone decides
    w = make_query_windows(idx.gs, 0.01, 1, seed=2)
    assert idx.plan(w, "intersects").backend == "host"
    big = np.repeat(w, 32, axis=0)
    assert idx.plan(big, "intersects").backend == "device"
    assert idx.plan(QueryBatch.window(big, "intersects",
                                      collect_stats=True)).backend == "host"
    assert idx.plan(big, "disjoint").base_relation == "intersects"


def test_device_cap_overflow_auto_retries():
    idx = _build(n=3000, config=EngineConfig(initial_cap=64, max_cap=1 << 15))
    whole = np.repeat(np.array([[0.0, 0.0, 1.0, 1.0]]), 2, axis=0)
    res = idx.query(whole, "covers", backend="device")
    np.testing.assert_array_equal(
        res[0], _oracle(idx, whole[0].astype(np.float32), "covers", np.float32))


def test_two_stage_budget_equals_single_stage():
    idx1 = _build(config=EngineConfig(initial_cap=8192))
    idx2 = SpatialIndex(idx1.glin,
                        EngineConfig(initial_cap=8192, exact_budget=512))
    wins = make_query_windows(idx1.gs, 0.005, 6, seed=7)
    r1 = idx1.query(wins, "intersects", backend="device")
    r2 = idx2.query(wins, "intersects", backend="device")
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------- maintenance + epoch invalidation
def test_interleaved_maintenance_parity_with_split_and_merge():
    """Host-vs-device equality through the facade after interleaved
    insert/delete hammering one region (forces leaf splits) followed by a
    deletion storm (forces merges). fp32-representable coordinates keep the
    two precisions comparable."""
    gs = generate("uniform", 1500, seed=11)
    gs.verts = gs.verts.astype(np.float32).astype(np.float64)
    gs.mbrs = geom.mbrs_of_verts(gs.verts, gs.nverts)
    cfg = GLINConfig(model=GLINModelConfig(max_leaf=32, fanout=8),
                     piece_limitation=100)
    idx = SpatialIndex.build(gs, cfg,
                             EngineConfig(stale_rebuild_min_batch=1,
                                          device_min_batch=1))
    n_leaves0 = len(idx.glin.leaves)
    rng = np.random.default_rng(13)
    wins = make_query_windows(gs, 0.02, 3, seed=4)

    def check_parity():
        for rel in ("contains", "intersects", "covers"):
            h = idx.query(wins, rel, backend="host")
            d = idx.query(wins, rel, backend="device")
            for a, b in zip(h, d):
                np.testing.assert_array_equal(a, b)

    for step in range(300):
        c = np.array([0.5, 0.5]) + rng.normal(0, 1e-4, 2)
        v = _big_polygon(rng, c, r=1e-5, nv=6).astype(np.float32).astype(np.float64)
        idx.insert(v, 6, 0)
        if step % 100 == 99:
            check_parity()
    assert len(idx.glin.leaves) > n_leaves0, "no leaf split happened"
    live = np.nonzero(idx.glin._live_mask())[0]
    for d in live[: len(live) * 3 // 4]:
        idx.delete(int(d))
    check_parity()


def test_stale_snapshot_never_served_patched():
    """Every mutation bumps the epoch; any device answer must reflect it.
    With delta patching (the default) the published snapshot is NOT
    republished — the write is patched on top, exactly."""
    idx = _build(n=2000, config=EngineConfig(device_min_batch=1,
                                             stale_rebuild_min_batch=1))
    rng = np.random.default_rng(17)
    snap0 = idx.snapshot()
    assert idx.snapshot_epoch == idx.epoch == 0
    rec = idx.insert(_big_polygon(rng, np.array([0.4, 0.4]), r=1e-3), 10, 0)
    assert idx.snapshot_is_stale() and idx.epoch == 1
    assert idx.delta_size() == 1
    # the query right after the write must see the new record — served from
    # the *old* snapshot plus the added-set patch, no republish
    w = np.array([[0.39, 0.39, 0.41, 0.41]])
    res = idx.query(w, "intersects")
    assert res.plan.backend == "device+delta"
    assert not res.plan.rebuild_snapshot and res.plan.delta_size == 1
    assert rec in res[0] and res.epoch == 1
    assert idx.snapshot_epoch == 0 and idx._snapshot is snap0
    # a delete must disappear from device results immediately; deleting a
    # never-published record just cancels its added-set entry
    assert idx.delete(rec)
    assert idx.delta_size() == 0 and idx.snapshot_is_stale()
    res = idx.query(w, "intersects")
    assert res.plan.backend == "device+delta"
    assert rec not in res[0] and res.epoch == 2 and idx.snapshot_epoch == 0
    # a tombstoned *published* record is masked out of snapshot results
    w2 = np.array([[0.3, 0.3, 0.5, 0.5]])
    victim = int(idx.query(w2, "intersects", backend="host")[0][0])
    assert idx.delete(victim)
    res = idx.query(w2, "intersects")
    assert res.plan.backend == "device+delta" and victim not in res[0]


def test_stale_snapshot_republished_when_patching_disabled():
    """delta_patch_max=0 restores the PR-1 behavior: a stale snapshot is
    republished before any device execution."""
    idx = _build(n=2000, config=EngineConfig(device_min_batch=1,
                                             stale_rebuild_min_batch=1,
                                             delta_patch_max=0))
    rng = np.random.default_rng(17)
    snap0 = idx.snapshot()
    rec = idx.insert(_big_polygon(rng, np.array([0.4, 0.4]), r=1e-3), 10, 0)
    w = np.array([[0.39, 0.39, 0.41, 0.41]])
    res = idx.query(w, "intersects")
    assert res.plan.backend == "device" and res.plan.rebuild_snapshot
    assert rec in res[0] and res.epoch == 1
    assert idx.snapshot_epoch == 1 and idx.snapshot() is not snap0
    assert idx.delete(rec)
    res = idx.query(w, "intersects")
    assert rec not in res[0] and res.epoch == 2 == idx.snapshot_epoch


def test_stale_snapshot_small_batch_falls_back_to_host():
    idx = _build(n=2000, config=EngineConfig(device_min_batch=1,
                                             stale_rebuild_min_batch=64,
                                             delta_patch_max=0))
    idx.snapshot()
    rng = np.random.default_rng(19)
    rec = idx.insert(_big_polygon(rng, np.array([0.6, 0.6]), r=1e-3), 10, 0)
    w = np.array([[0.59, 0.59, 0.61, 0.61]])
    res = idx.query(w, "intersects")    # 1 window < stale_rebuild_min_batch
    assert res.plan.backend == "host" and "stale" in res.plan.reason
    assert rec in res[0]
    assert idx.snapshot_epoch == 0      # snapshot untouched, but never served


def test_stale_small_batch_patches_instead_of_host_fallback():
    """With patching enabled the same small stale batch stays on device:
    patching costs no republish, so stale_rebuild_min_batch does not apply."""
    idx = _build(n=2000, config=EngineConfig(device_min_batch=1,
                                             stale_rebuild_min_batch=64))
    idx.snapshot()
    rng = np.random.default_rng(19)
    rec = idx.insert(_big_polygon(rng, np.array([0.6, 0.6]), r=1e-3), 10, 0)
    w = np.array([[0.59, 0.59, 0.61, 0.61]])
    res = idx.query(w, "intersects")
    assert res.plan.backend == "device+delta" and rec in res[0]
    assert idx.snapshot_epoch == 0


# ----------------------------------------------- delta-patched device serving
def _fp32_grid(gs):
    """Clamp coordinates to fp32-representable values so fp64 host and fp32
    device refinement decide identically (see the interleaved test above)."""
    gs.verts = gs.verts.astype(np.float32).astype(np.float64)
    gs.mbrs = geom.mbrs_of_verts(gs.verts, gs.nverts)
    return gs


def test_write_heavy_parity_stream():
    """The headline maintenance scenario: interleaved insert/delete/query
    with device-delta results equal to host results at EVERY step, crossing
    republish boundaries (small refresh_threshold) and one vertex-store-width
    growth (wide-geometry insert between publishes)."""
    gs = _fp32_grid(generate("cluster", 2000, seed=21))
    idx = SpatialIndex.build(
        gs, GLINConfig(piece_limitation=100),
        EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1,
                     refresh_threshold=24, delta_patch_max=4096))
    idx.snapshot()
    rng = np.random.default_rng(23)
    wins = make_query_windows(gs, 0.02, 3, seed=4)
    wins = wins.astype(np.float32).astype(np.float64)
    width0 = gs.verts.shape[1]
    patched_plans = 0
    for step in range(120):
        if step == 60:  # wide-geometry insert between publishes
            nv = gs.verts.shape[1] + 4
            v = _big_polygon(rng, np.array([0.5, 0.5]), r=3e-3, nv=nv)
            idx.insert(v.astype(np.float32).astype(np.float64), nv, 0)
        elif rng.random() < 0.6:
            c = rng.uniform(0.2, 0.8, 2)
            v = _big_polygon(rng, c, r=3e-4, nv=6)
            idx.insert(v.astype(np.float32).astype(np.float64), 6, 0)
        else:
            live = np.nonzero(idx.glin._live_mask())[0]
            idx.delete(int(rng.choice(live)))
        for rel in ("intersects", "contains", "disjoint"):
            d = idx.query(wins, rel)
            assert d.plan.backend in ("device", "device+delta")
            patched_plans += d.plan.backend == "device+delta"
            h = idx.query(wins, rel, backend="host")
            for a, b in zip(d, h):
                np.testing.assert_array_equal(a, b)
    assert idx.gs.verts.shape[1] > width0        # width growth happened
    assert idx._publishes >= 3                   # republish boundary crossed
    assert patched_plans > 200                   # and patching dominated


def test_delta_path_no_payload_reupload_between_publishes():
    """The added-set patch runs host-side: streaming inserts must NOT force
    per-query re-uploads of the multi-MB geometry payload, and deletes never
    invalidate it. With the CSR pool even a WIDER-than-ever insert keeps the
    cached pods (the added record is served by the delta patch, never
    gathered from the payload); only a compacting republish — the moment the
    device pool should actually shrink — rebuilds it."""
    idx = _build(n=2000, config=EngineConfig(device_min_batch=1))
    wins = make_query_windows(idx.gs, 0.01, 8, seed=3)
    idx.query(wins, "intersects", backend="device")
    pay0 = idx._payload
    rng = np.random.default_rng(31)
    for _ in range(10):
        idx.insert(_big_polygon(rng, rng.uniform(0.2, 0.8, 2), r=3e-4), 10, 0)
        res = idx.query(wins, "intersects")
        assert res.plan.backend == "device+delta"
        assert idx._payload is pay0
    live = np.nonzero(idx.glin._live_mask())[0]
    idx.delete(int(live[0]))
    idx.query(wins, "intersects")
    assert idx._payload is pay0
    # width growth between publishes: the pool appends O(width) bytes, the
    # payload survives untouched and the snapshot is NOT republished
    publishes = idx._publishes
    nv = idx.gs.max_nverts + 4
    idx.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=1e-3, nv=nv), nv, 0)
    res = idx.query(wins, "intersects")
    assert res.plan.backend == "device+delta"
    assert idx._payload is pay0 and idx._publishes == publishes
    # a compacting republish (deletes pending) bumps the store layout
    # generation: the next device query rebuilds the payload once
    idx.snapshot()
    idx.query(wins, "intersects", backend="device")
    assert idx._payload is not pay0


def test_delta_path_shares_adaptive_cap_ladder():
    """A wide window on the patched path must walk the same overflow ladder
    as the rebuild path (no fixed-cap OverflowError), remembering the cap."""
    idx = _build(n=3000, config=EngineConfig(initial_cap=64, max_cap=1 << 15,
                                             device_min_batch=1))
    idx.snapshot()
    rng = np.random.default_rng(37)
    idx.insert(_big_polygon(rng, np.array([0.4, 0.4]), r=1e-3), 10, 0)
    whole = np.repeat(np.array([[0.0, 0.0, 1.0, 1.0]]), 2, axis=0)
    res = idx.query(whole, "covers", backend="device+delta")
    assert res.plan.backend == "device+delta"
    np.testing.assert_array_equal(
        res[0], _oracle(idx, whole[0].astype(np.float32), "covers",
                        np.float32))
    assert idx.device_cap > 64                   # ladder walked and remembered


@pytest.mark.parametrize("relation", RELATIONS)
def test_delta_path_serves_every_registry_relation(relation):
    """The old delta manager's device query crashed on non-device-native
    relations (passed `relation` through instead of probing the base); the
    facade delta path must probe the base and complement-finish for all."""
    idx = _build(n=2000, config=EngineConfig(device_min_batch=1))
    idx.snapshot()
    rng = np.random.default_rng(41)
    for _ in range(5):
        idx.insert(_big_polygon(rng, rng.uniform(0.3, 0.7, 2), r=3e-4), 10, 0)
    live = np.nonzero(idx.glin._live_mask())[0]
    idx.delete(int(live[5]))
    wins = make_query_windows(idx.gs, 0.01, 4, seed=3)
    res = idx.query(QueryBatch.window(wins, relation, backend="device+delta"))
    assert res.plan.backend == "device+delta"
    assert res.plan.base_relation == get_relation(relation).base_name()
    for qi, w in enumerate(wins):
        np.testing.assert_array_equal(
            res[qi], _oracle(idx, w.astype(np.float32), relation, np.float32))


def test_delta_side_table_matches_host_loop_patching():
    """Past delta_device_min the added-set patch runs through the device
    DeltaTable; below it, through the host loop. Both must produce identical
    results, and the table must be rebuilt lazily (once per epoch served),
    not per query batch."""
    def mk(dmin):
        # each index owns its GeometrySet copy: inserts mutate the store
        gs = _fp32_grid(generate("cluster", 2500, seed=61))
        return SpatialIndex.build(
            gs, GLINConfig(piece_limitation=100),
            EngineConfig(device_min_batch=1, delta_patch_max=4096,
                         refresh_threshold=100_000, delta_device_min=dmin))

    idx_dev, idx_host = mk(4), mk(10**9)
    gs = idx_dev.gs
    rng = np.random.default_rng(67)
    for idx in (idx_dev, idx_host):
        idx.snapshot()
    for _ in range(150):
        v = _big_polygon(rng, rng.uniform(0.25, 0.75, 2), r=3e-4, nv=6)
        v = v.astype(np.float32).astype(np.float64)
        for idx in (idx_dev, idx_host):
            idx.insert(v, 6, 0)
    live = np.nonzero(idx_dev.glin._live_mask())[0]
    for victim in live[:4]:
        for idx in (idx_dev, idx_host):
            idx.delete(int(victim))
    wins = make_query_windows(gs, 0.01, 8, seed=5)
    wins = wins.astype(np.float32).astype(np.float64)
    for rel in RELATIONS:
        a = idx_dev.query(wins, rel)
        b = idx_host.query(wins, rel)
        assert a.plan.backend == b.plan.backend == "device+delta"
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert idx_dev._dtable is not None and idx_host._dtable is None
    table = idx_dev._dtable
    idx_dev.query(wins, "intersects")        # same epoch: table reused
    assert idx_dev._dtable is table
    idx_dev.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=3e-4), 10, 0)
    idx_dev.query(wins, "intersects")        # epoch moved: table rebuilt
    assert idx_dev._dtable is not table
    idx_dev.snapshot()                       # publish clears the delta/table
    assert idx_dev._dtable is None


def test_compaction_modes_bit_identical():
    """sort (legacy argsort), scan (jnp reference) and the Pallas compact
    kernel (in interpret mode, asked for by name) must return bit-identical
    hits/counts through batch_query, including on odd batch sizes."""
    from repro.core.device import batch_query

    idx = _build(n=2500, pl=200)
    snap = idx.snapshot()
    payload = idx._device_payload(idx._snapshot_recs)
    wins = make_query_windows(idx.gs, 0.005, 13, seed=9)   # odd Q
    wj = jnp.asarray(wins.astype(np.float32))
    for rel in ("intersects", "contains", "within", "dwithin:0.003"):
        base = get_relation(rel).base_name()
        outs = {}
        for mode in ("sort", "scan", "interpret"):
            h, c = batch_query(snap, wj, *payload, relation=base,
                               cap=1 << 15, exact_budget=64, compaction=mode)
            outs[mode] = (np.asarray(h), np.asarray(c))
        for mode in ("scan", "interpret"):
            np.testing.assert_array_equal(outs["sort"][0], outs[mode][0])
            np.testing.assert_array_equal(outs["sort"][1], outs[mode][1])


def test_forced_compaction_config_parity():
    """EngineConfig.compaction forces the stage-1 implementation end to end
    through the facade; results must not depend on it."""
    idx_auto = _build(n=2000)
    wins = make_query_windows(idx_auto.gs, 0.01, 20, seed=13)
    ref = idx_auto.query(wins, "intersects", backend="device")
    for mode in ("sort", "scan", "interpret"):
        idx = SpatialIndex(idx_auto.glin, EngineConfig(compaction=mode))
        res = idx.query(wins, "intersects", backend="device")
        for a, b in zip(res, ref):
            np.testing.assert_array_equal(a, b)


def test_plan_reason_every_branch():
    """Every QueryPlan.reason branch of the three-backend planner."""
    cfg = EngineConfig(device_min_batch=4, stale_rebuild_min_batch=8,
                       delta_patch_max=2, refresh_threshold=2)
    idx = _build(n=1000, pl=100, config=cfg)
    one = make_query_windows(idx.gs, 0.01, 1, seed=2)
    big = np.repeat(one, 8, axis=0)
    rng = np.random.default_rng(43)

    # knn / forced backends / stats / validation
    assert "knn" in idx.plan(QueryBatch.knn([[0.5, 0.5]], k=3)).reason
    p = idx.plan(QueryBatch.knn(np.tile([0.5, 0.5], (20, 1)), k=3))
    assert p.backend == "device" and "device-complete knn" in p.reason
    for be in ("host", "device", "device+delta"):
        p = idx.plan(QueryBatch.window(big, "intersects", backend=be))
        assert p.backend == be and p.reason == "forced by caller"
    p = idx.plan(QueryBatch.window(big, "intersects", collect_stats=True))
    assert p.backend == "host" and "host-only" in p.reason
    for be in ("device", "device+delta"):
        with pytest.raises(ValueError, match="collect_stats"):
            idx.plan(QueryBatch.window(big, "intersects", backend=be,
                                       collect_stats=True))
    with pytest.raises(ValueError, match="unknown backend"):
        idx.plan(QueryBatch.window(big, "intersects", backend="tpu"))

    # a relation whose base is not device-native always plans host
    register_relation(Relation(
        name="_hostonly", predicate=get_relation("intersects").predicate,
        augment=False, mbr_prefilter=get_relation("intersects").mbr_prefilter,
        device_native=False))
    try:
        p = idx.plan(big, "_hostonly")
        assert p.backend == "host" and "not device-native" in p.reason
    finally:
        del RELATION_REGISTRY["_hostonly"]

    # batch-size and staleness ladder
    p = idx.plan(one, "intersects")
    assert p.backend == "host" and "device_min_batch" in p.reason
    p = idx.plan(big, "intersects")      # nothing published yet
    assert p.backend == "device" and "no published snapshot" in p.reason
    assert p.rebuild_snapshot
    idx.snapshot()
    p = idx.plan(big, "intersects")
    assert p.backend == "device" and "windows on" in p.reason
    assert not p.rebuild_snapshot and p.delta_size == 0
    idx.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=1e-3), 10, 0)
    p = idx.plan(big, "intersects")          # delta of 1 < refresh_threshold
    assert p.backend == "device+delta" and "patching" in p.reason
    assert p.delta_size == 1 and not p.rebuild_snapshot
    idx.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=1e-3), 10, 0)
    p = idx.plan(big, "intersects")          # delta of 2 >= refresh_threshold
    assert p.backend == "device" and "republishing" in p.reason
    assert p.rebuild_snapshot and p.delta_size == 2
    p = idx.plan(np.repeat(one, 5, axis=0), "intersects")
    assert p.backend == "host" and "stale_rebuild_min_batch" in p.reason


def test_delta_cancels_to_empty_after_insert_delete_roundtrip():
    idx = _build(n=1500, config=EngineConfig(device_min_batch=1))
    idx.snapshot()
    rng = np.random.default_rng(47)
    rec = idx.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=1e-3), 10, 0)
    assert idx.delete(rec) and idx.delta_size() == 0
    assert idx.snapshot_is_stale()           # epoch moved ...
    wins = make_query_windows(idx.gs, 0.01, 4, seed=3)
    res = idx.query(wins, "intersects")      # ... but the empty delta patches
    assert res.plan.backend == "device+delta" and res.plan.delta_size == 0
    for qi, w in enumerate(wins):
        np.testing.assert_array_equal(
            res[qi],
            _oracle(idx, w.astype(np.float32), "intersects", np.float32))


# ------------------------------------------------- GLIN.insert capacity fix --
def test_insert_wider_than_store_grows_instead_of_truncating():
    idx = _build(n=800, pl=50, seed=23)
    vmax0 = idx.gs.verts.shape[1]
    rng = np.random.default_rng(29)
    nv = vmax0 + 8
    verts = _big_polygon(rng, np.array([0.3, 0.7]), r=5e-3, nv=nv)
    rec = idx.insert(verts, nv, 0)
    # store grew; no vertex was dropped; MBR covers the full input ring
    assert idx.gs.verts.shape[1] == nv
    assert int(idx.gs.nverts[rec]) == nv
    np.testing.assert_allclose(idx.gs.verts[rec, :nv], verts)
    np.testing.assert_allclose(
        idx.gs.mbrs[rec],
        [verts[:, 0].min(), verts[:, 1].min(),
         verts[:, 0].max(), verts[:, 1].max()])
    # old records keep the pad-with-last-valid-vertex convention
    old = 5
    n_old = int(idx.gs.nverts[old])
    np.testing.assert_array_equal(
        idx.gs.verts[old, n_old:],
        np.repeat(idx.gs.verts[old, n_old - 1][None], nv - n_old, axis=0))
    # and the record is exactly queryable on both backends
    w = np.array(idx.gs.mbrs[rec]) + [-1e-4, -1e-4, 1e-4, 1e-4]
    for backend in ("host", "device"):
        res = idx.query(np.atleast_2d(w), "contains", backend=backend)
        assert rec in res[0]
    np.testing.assert_array_equal(
        idx.query(w, "contains", backend="host")[0],
        _oracle(idx, w, "contains"))


def test_insert_rejects_inconsistent_inputs():
    idx = _build(n=200, pl=50)
    with pytest.raises(ValueError):
        idx.insert(np.zeros((3, 2)), 5, 0)   # nverts > provided rows
    with pytest.raises(ValueError):
        idx.insert(np.zeros((3, 3)), 3, 0)   # not (N, 2)


# ------------------------------------------------------------------- server --
def test_spatial_query_server_mixed_relations():
    from repro.serve.server import SpatialQueryServer

    idx = _build(n=2000)
    server = SpatialQueryServer(idx)
    wins = make_query_windows(idx.gs, 0.01, 4, seed=31)
    tickets = [server.submit(w, rel)
               for w, rel in zip(wins, ("intersects", "touches",
                                        "dwithin:0.004", "covers"))]
    out = server.flush()
    assert set(out) == set(tickets)
    np.testing.assert_array_equal(out[tickets[2]],
                                  idx.query(wins[2], "dwithin:0.004")[0])
    with pytest.raises(ValueError, match="requires a parameter"):
        server.submit(wins[0], "dwithin")   # fail fast at submit time
    assert server.flush() == {}
    # writes go through the facade: epoch moves, next flush is fresh
    rng = np.random.default_rng(37)
    rec = server.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=1e-3), 10, 0)
    t = server.submit(np.array([0.49, 0.49, 0.51, 0.51]), "intersects")
    assert rec in server.flush()[t]
    assert server.write_ops == 1 and server.served_queries >= 5


def test_server_result_cache_hits_and_epoch_invalidation():
    """Repeated windows are served from the (epoch, window-bytes, relation)
    cache without touching the facade; a write bumps the epoch and every
    cached entry stops matching — results stay exact."""
    from repro.serve.server import SpatialQueryServer

    idx = _build(n=2000)
    server = SpatialQueryServer(idx)
    wins = make_query_windows(idx.gs, 0.01, 4, seed=31)
    t1 = [server.submit(w, "intersects") for w in wins]
    out1 = server.flush()
    assert server.cache_hits == 0 and server.cache_misses == 4
    batches0 = server.served_batches
    # identical resubmission: pure cache, no facade query
    t2 = [server.submit(w, "intersects") for w in wins]
    out2 = server.flush()
    assert server.cache_hits == 4 and server.served_batches == batches0
    assert server.backend_counts.get("cache") == 4
    for a, b in zip(t1, t2):
        np.testing.assert_array_equal(out1[a], out2[b])
    # same window under a different relation is a different key
    t3 = server.submit(wins[0], "covers")
    assert server.flush()[t3] is not None and server.cache_hits == 4
    # a write invalidates: next flush recomputes and sees the new record
    rng = np.random.default_rng(41)
    c = np.array([np.mean(wins[0][[0, 2]]), np.mean(wins[0][[1, 3]])])
    rec = server.insert(_big_polygon(rng, c, r=1e-3), 10, 0)
    t4 = server.submit(wins[0], "intersects")
    out4 = server.flush()
    assert rec in out4[t4]
    assert server.cache_hits == 4      # no stale hit happened
    np.testing.assert_array_equal(
        out4[t4], idx.query(wins[0], "intersects", backend="host")[0])


def test_server_write_flush_stream_takes_delta_plan():
    """Interleaved write/flush through the server: exact at every flush, on
    the device+delta backend (no republish per write) until the delta crosses
    refresh_threshold, which republishes — still exact."""
    from repro.serve.server import SpatialQueryServer

    gs = _fp32_grid(generate("cluster", 2000, seed=53))
    idx = SpatialIndex.build(
        gs, GLINConfig(piece_limitation=100),
        EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1,
                     refresh_threshold=16))
    idx.snapshot()
    server = SpatialQueryServer(idx)
    rng = np.random.default_rng(59)
    wins = make_query_windows(gs, 0.02, 4, seed=6)
    wins = wins.astype(np.float32).astype(np.float64)
    for step in range(24):
        v = _big_polygon(rng, rng.uniform(0.3, 0.7, 2), r=3e-4, nv=6)
        server.insert(v.astype(np.float32).astype(np.float64), 6, 0)
        tickets = [server.submit(w, "intersects") for w in wins]
        out = server.flush()
        host = idx.query(wins, "intersects", backend="host")
        for ti, t in enumerate(tickets):
            np.testing.assert_array_equal(out[t], host[ti])
    assert server.backend_counts.get("device+delta", 0) >= 20
    assert idx._publishes >= 2               # crossed a republish boundary
    assert server.write_ops == 24


# ------------------------------------------ budget ladder + survivor counts --
def test_budget_overflow_encodes_survivor_count():
    """Two-stage overflow counts carry -(TOTAL MBR survivors) - 1, so the
    caller can size the budget ladder in one step (all three impls)."""
    from repro.core.device import batch_query

    idx = _build(n=2500, pl=200)
    snap = idx.snapshot()
    payload = idx._device_payload(idx._snapshot_recs)
    wins = make_query_windows(idx.gs, 0.01, 6, seed=9)
    wj = jnp.asarray(wins.astype(np.float32))
    surv = {}
    for mode in ("sort", "scan", "interpret"):
        _, c = batch_query(snap, wj, *payload, relation="intersects",
                           cap=1 << 15, exact_budget=2, compaction=mode)
        surv[mode] = np.asarray(c)
    for mode, c in surv.items():
        over = c < 0
        assert over.any(), mode               # budget of 2 must overflow
        np.testing.assert_array_equal((-c[over] - 1),
                                      _mbr_survivors(idx, wins)[over],
                                      err_msg=mode)


def _mbr_survivors(idx, wins):
    """Oracle stage-1 survivor counts: slots in the probe run whose record
    MBR passes the prefilter."""
    from repro.core.device import batch_query_bounds

    snap = idx.snapshot()
    wj = jnp.asarray(wins.astype(np.float32))
    start, end = batch_query_bounds(snap, wj, relation="intersects")
    start, end = np.asarray(start), np.asarray(end)
    rmbr = np.asarray(snap.slot_rmbr)
    out = np.zeros(len(wins), np.int64)
    for qi, w in enumerate(wins.astype(np.float32)):
        sl = slice(start[qi], end[qi])
        ok = geom.mbr_intersects(rmbr[sl], w[None, :])
        out[qi] = int(np.count_nonzero(ok))
    return out


def test_budget_ladder_grows_geometrically_then_goes_dense():
    """Survivors past a small exact_budget grow the budget geometrically
    (re-running compaction) instead of dropping straight to the dense path;
    only survivors past CAPPED_MAX_BUDGET escalate to dense (the scan
    compaction here is capped)."""
    import repro.core.engine as eng
    from repro.core.exec import CAPPED_MAX_BUDGET

    calls = []
    real_bq = eng.batch_query

    def spy(*a, **kw):
        calls.append((kw.get("cap"), kw.get("exact_budget")))
        return real_bq(*a, **kw)

    idx = _build(n=3000, pl=200,
                 config=EngineConfig(initial_cap=1 << 14, exact_budget=8))
    try:
        eng.batch_query = spy
        # moderately selective: survivors overflow budget=8 but stay well
        # under CAPPED_MAX_BUDGET -> the ladder must stay two-stage
        wins = make_query_windows(idx.gs, 0.02, 4, seed=3)
        res = idx.query(wins, "intersects", backend="device")
        budgets = [b for _, b in calls]
        assert budgets[0] == 8
        assert len(budgets) >= 2 and budgets[-1] > 8, budgets
        assert all(b > 0 for b in budgets), f"dropped to dense: {budgets}"
        for i in range(1, len(budgets)):
            assert budgets[i] >= 2 * budgets[i - 1]   # geometric growth
        for qi, w in enumerate(wins):
            np.testing.assert_array_equal(
                res[qi], _oracle(idx, w.astype(np.float32), "intersects",
                                 np.float32))
        # whole-domain covers: survivors ~ N > CAPPED_MAX_BUDGET -> dense
        calls.clear()
        whole = np.repeat(np.array([[0.0, 0.0, 1.0, 1.0]]), 2, axis=0)
        res = idx.query(whole, "covers", backend="device")
        assert calls[-1][1] == 0, calls       # escalated to single-stage
        assert all(b <= CAPPED_MAX_BUDGET for _, b in calls)
        np.testing.assert_array_equal(
            res[0], _oracle(idx, whole[0].astype(np.float32), "covers",
                            np.float32))
    finally:
        eng.batch_query = real_bq


# ------------------------------------------------- async double-buffering ---
def _slow_build(monkeypatch, delay=0.25):
    """Slow the background snapshot build down so the in-flight window is
    reliably observable."""
    import time

    import repro.core.engine as eng

    real = eng.snapshot_from_capture

    def slow(cap):
        time.sleep(delay)
        return real(cap)

    monkeypatch.setattr(eng, "snapshot_from_capture", slow)


def _fp32_grid(gs):
    from repro.core.geometry import mbrs_of_verts

    gs.verts = gs.verts.astype(np.float32).astype(np.float64)
    gs.mbrs = mbrs_of_verts(gs.verts, gs.nverts)
    return gs


def test_async_republish_streams_exact_across_swap(monkeypatch):
    """The double-buffer race test: queries streamed WHILE a republish builds
    on the background thread never see stale or torn results — including
    writes (and deletes of pending-snapshot records) landing mid-build."""
    import time

    gs = _fp32_grid(generate("cluster", 4000, seed=21))
    idx = SpatialIndex.build(
        gs, GLINConfig(piece_limitation=300),
        EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1,
                     delta_patch_max=8, refresh_threshold=8,
                     async_republish=True))
    wins = make_query_windows(gs, 0.02, 4, seed=6)
    wins = wins.astype(np.float32).astype(np.float64)
    idx.snapshot()
    idx.query(wins, "intersects")
    _slow_build(monkeypatch, delay=0.3)
    rng = np.random.default_rng(23)

    def check_exact():
        res = idx.query(wins, "intersects")
        host = idx.query(wins, "intersects", backend="host")
        for a, b in zip(res, host):
            np.testing.assert_array_equal(a, b)
        return res

    # drive the delta over the threshold: the next query starts the build
    # and keeps serving patched results instead of blocking on it
    for _ in range(9):
        idx.insert(_big_polygon(rng, rng.uniform(0.3, 0.7, 2), r=3e-4, nv=6),
                   6, 0)
    pubs0 = idx._publishes
    res = check_exact()
    # the build is STILL in flight after the query returned: it did not
    # block on the rebuild (a wall-clock bound here flakes under CI load)
    assert idx.republish_inflight()
    assert res.plan.backend == "device+delta"
    assert "async republish in flight" in res.plan.reason

    # mid-build writes: a record the PENDING snapshot contains is deleted
    # (it must come out tombstoned after the swap, not resurrect) and new
    # records are inserted (they must stay in the delta after the swap)
    victim = int(idx.query(wins, "intersects", backend="host")[0][0])
    assert idx.delete(victim)
    late = idx.insert(
        _big_polygon(rng, np.array([np.mean(wins[0][[0, 2]]),
                                    np.mean(wins[0][[1, 3]])]), r=2e-3, nv=6),
        6, 0)
    served_inflight = 0
    for _ in range(200):
        res = check_exact()
        if idx._publishes > pubs0:
            break
        served_inflight += 1
        time.sleep(0.01)
    assert idx._publishes == pubs0 + 1, "swap never landed"
    assert served_inflight >= 1                 # queries ran during the build
    # post-swap: the delta shrank to just the post-capture writes, and the
    # targeted records behave
    assert victim in idx._tombstones and late in idx._added
    res = check_exact()
    ids0 = res[0]
    assert victim not in ids0 and late in ids0
    # converges to a fresh snapshot once the follow-up republish drains
    for _ in range(200):
        if not idx.snapshot_is_stale() and not idx.republish_inflight():
            break
        check_exact()
        time.sleep(0.01)


def test_async_republish_discarded_by_sync_publish(monkeypatch):
    """A forced synchronous publish (count_candidates, forced device) that
    overtakes the in-flight build wins: the stale pending snapshot is
    discarded by the epoch guard, never swapped in."""
    import time

    gs = _fp32_grid(generate("cluster", 2000, seed=29))
    idx = SpatialIndex.build(
        gs, GLINConfig(piece_limitation=200),
        EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1,
                     delta_patch_max=4, refresh_threshold=4,
                     async_republish=True))
    wins = make_query_windows(gs, 0.02, 4, seed=6)
    wins = wins.astype(np.float32).astype(np.float64)
    idx.snapshot()
    _slow_build(monkeypatch, delay=0.3)
    rng = np.random.default_rng(31)
    for _ in range(5):
        idx.insert(_big_polygon(rng, rng.uniform(0.3, 0.7, 2), r=3e-4, nv=6),
                   6, 0)
    idx.query(wins, "intersects")
    assert idx.republish_inflight()
    inflight_epoch = idx._inflight.epoch
    idx.insert(_big_polygon(rng, rng.uniform(0.3, 0.7, 2), r=3e-4, nv=6),
               6, 0)
    snap = idx.snapshot()                      # sync publish at a NEWER epoch
    pubs = idx._publishes
    time.sleep(0.5)                            # let the stale build finish
    idx.query(wins, "intersects")              # poll point
    assert idx._publishes == pubs              # discarded, not swapped
    assert idx._snapshot is snap
    assert idx._snapshot_epoch > inflight_epoch
    res = idx.query(wins, "intersects")
    host = idx.query(wins, "intersects", backend="host")
    for a, b in zip(res, host):
        np.testing.assert_array_equal(a, b)


def test_serving_generation_moves_on_write_and_publish():
    idx = _build(n=1500, config=EngineConfig(device_min_batch=1))
    g0 = idx.serving_generation
    idx.snapshot()
    g1 = idx.serving_generation
    assert g1 != g0
    rng = np.random.default_rng(3)
    idx.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=1e-3), 10, 0)
    assert idx.serving_generation != g1


def test_server_cache_invalidated_by_snapshot_swap(monkeypatch):
    """The result cache keys on the SERVED snapshot identity: an async swap
    (which does not bump the epoch) must stop the old entries from hitting."""
    import time

    from repro.serve.server import SpatialQueryServer

    gs = _fp32_grid(generate("cluster", 2000, seed=37))
    idx = SpatialIndex.build(
        gs, GLINConfig(piece_limitation=200),
        EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1,
                     delta_patch_max=4, refresh_threshold=4))
    server = SpatialQueryServer(idx, async_republish=True)
    assert idx.config.async_republish
    wins = make_query_windows(gs, 0.02, 3, seed=6)
    wins = wins.astype(np.float32).astype(np.float64)
    idx.snapshot()
    rng = np.random.default_rng(39)
    _slow_build(monkeypatch, delay=0.2)
    for _ in range(5):
        server.insert(_big_polygon(rng, rng.uniform(0.3, 0.7, 2), r=3e-4,
                                   nv=6), 6, 0)
    t1 = [server.submit(w, "intersects") for w in wins]
    out1 = server.flush()                     # starts the build, caches at
    gen1 = idx.serving_generation             # generation (epoch, publishes)
    assert idx.republish_inflight()
    # identical resubmission pre-swap: pure cache hits
    t2 = [server.submit(w, "intersects") for w in wins]
    out2 = server.flush()
    assert server.cache_hits == len(wins)
    for a, b in zip(t1, t2):
        np.testing.assert_array_equal(out1[a], out2[b])
    # wait for the swap (no writes: the epoch does NOT move)
    deadline = time.time() + 5
    while idx.republish_inflight() or idx.snapshot_is_stale():
        assert time.time() < deadline, "swap never landed"
        time.sleep(0.02)
        idx.query(wins[:1], "intersects")     # poll point (host-planned)
    assert idx.serving_generation[0] == gen1[0]       # same epoch ...
    assert idx.serving_generation[1] == gen1[1] + 1   # ... new snapshot
    hits0 = server.cache_hits
    t3 = [server.submit(w, "intersects") for w in wins]
    out3 = server.flush()                     # generation moved: cache MISS
    assert server.cache_hits == hits0
    for a, b in zip(t1, t3):                  # swap is invisible in content
        np.testing.assert_array_equal(out1[a], out3[b])


def test_forced_sharded_backend_requires_mesh():
    idx = _build(n=1000)
    wins = make_query_windows(idx.gs, 0.01, 4, seed=2)
    with pytest.raises(ValueError, match="requires EngineConfig.mesh"):
        idx.plan(QueryBatch.window(wins, "intersects", backend="sharded"))


def test_plan_reason_sharded_branches():
    """The sharded planner branches: fresh, stale+patched, async-inflight,
    republishing, and the shard_min_records / device_min_batch gates."""
    from repro.utils.compat import make_auto_mesh

    mesh = make_auto_mesh((1, 1), ("data", "model"))
    cfg = EngineConfig(mesh=mesh, shard_min_records=1, device_min_batch=4,
                       stale_rebuild_min_batch=8, delta_patch_max=2,
                       refresh_threshold=2)
    idx = _build(n=1000, pl=100, config=cfg)
    one = make_query_windows(idx.gs, 0.01, 1, seed=2)
    big = np.repeat(one, 8, axis=0)
    rng = np.random.default_rng(43)

    p = idx.plan(QueryBatch.window(big, "intersects", backend="sharded"))
    assert p.backend == "sharded" and p.reason == "forced by caller"
    p = idx.plan(one, "intersects")
    assert p.backend == "host" and "device_min_batch" in p.reason
    p = idx.plan(big, "intersects")           # nothing published yet
    assert p.backend == "sharded" and "publishing" in p.reason
    assert p.rebuild_snapshot
    idx.snapshot()
    p = idx.plan(big, "intersects")
    assert p.backend == "sharded" and "windows on" in p.reason
    assert not p.rebuild_snapshot
    idx.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=1e-3), 10, 0)
    p = idx.plan(big, "intersects")
    assert p.backend == "sharded" and "patched on top" in p.reason
    idx.insert(_big_polygon(rng, np.array([0.5, 0.5]), r=1e-3), 10, 0)
    p = idx.plan(big, "intersects")           # delta >= refresh_threshold
    assert p.backend == "sharded" and "republishing" in p.reason
    assert p.rebuild_snapshot
    p = idx.plan(np.repeat(one, 5, axis=0), "intersects")
    assert p.backend == "host" and "stale_rebuild_min_batch" in p.reason
    # below shard_min_records the single-device device path wins
    small = SpatialIndex(idx.glin, EngineConfig(mesh=mesh,
                                                shard_min_records=1 << 20))
    small.snapshot()
    p = small.plan(np.repeat(one, 32, axis=0), "intersects")
    assert p.backend == "device"


def test_plan_reason_sharded_async_inflight(monkeypatch):
    """The sharded + async-republish-in-flight branch: the mesh keeps
    serving the published placement + delta while the build runs."""
    from repro.utils.compat import make_auto_mesh

    mesh = make_auto_mesh((1, 1), ("data", "model"))
    gs = _fp32_grid(generate("cluster", 2000, seed=61))
    idx = SpatialIndex.build(
        gs, GLINConfig(piece_limitation=200),
        EngineConfig(mesh=mesh, shard_min_records=1, device_min_batch=1,
                     stale_rebuild_min_batch=1, delta_patch_max=4,
                     refresh_threshold=4, async_republish=True))
    wins = make_query_windows(gs, 0.02, 4, seed=6)
    wins = wins.astype(np.float32).astype(np.float64)
    idx.snapshot()
    _slow_build(monkeypatch, delay=0.3)
    rng = np.random.default_rng(67)
    for _ in range(5):
        idx.insert(_big_polygon(rng, rng.uniform(0.3, 0.7, 2), r=3e-4, nv=6),
                   6, 0)
    res = idx.query(wins, "intersects")      # starts the build, serves patched
    assert idx.republish_inflight()
    assert res.plan.backend == "sharded"
    assert "async republish in flight" in res.plan.reason
    host = idx.query(wins, "intersects", backend="host")
    for a, b in zip(res, host):
        np.testing.assert_array_equal(a, b)


def test_sync_publish_discards_staged_sharded_table(monkeypatch):
    """REGRESSION (review): an async swap stages its sharded table; when a
    synchronous republish immediately follows (post-capture write + forced
    rebuild), the staged table describes the OLD capture and must not be
    served — post-capture records would silently vanish from sharded
    results."""
    import time

    from repro.utils.compat import make_auto_mesh

    mesh = make_auto_mesh((1, 1), ("data", "model"))
    gs = _fp32_grid(generate("cluster", 2000, seed=71))
    idx = SpatialIndex.build(
        gs, GLINConfig(piece_limitation=200),
        EngineConfig(mesh=mesh, shard_min_records=1, device_min_batch=1,
                     stale_rebuild_min_batch=1, delta_patch_max=4,
                     refresh_threshold=4, async_republish=True))
    wins = make_query_windows(gs, 0.02, 4, seed=6)
    wins = wins.astype(np.float32).astype(np.float64)
    idx.snapshot()
    rng = np.random.default_rng(73)
    for _ in range(5):
        idx.insert(_big_polygon(rng, rng.uniform(0.3, 0.7, 2), r=3e-4, nv=6),
                   6, 0)
    idx.query(wins, "intersects")            # starts the async build
    deadline = time.time() + 5
    while not idx._inflight.done.is_set():   # let it finish UN-polled
        assert time.time() < deadline
        time.sleep(0.01)
    # a post-capture record inside window 0, then a synchronous republish
    c = np.array([np.mean(wins[0][[0, 2]]), np.mean(wins[0][[1, 3]])])
    late = idx.insert(
        _big_polygon(rng, c, r=2e-3, nv=6).astype(np.float32)
        .astype(np.float64), 6, 0)
    idx.snapshot()                           # polls (swap), then sync publish
    assert not idx.snapshot_is_stale()
    res = idx.query(wins, "intersects")
    assert res.plan.backend == "sharded"
    assert late in res[0]                    # the staged table was NOT served
    host = idx.query(wins, "intersects", backend="host")
    for a, b in zip(res, host):
        np.testing.assert_array_equal(a, b)


def test_cap_growth_reenables_configured_budget():
    """REGRESSION (review): a budget >= the initial cap is dormant (dense);
    once the overflow ladder grows the cap past it, the configured two-stage
    budget must come back into play instead of staying dense forever."""
    import repro.core.engine as eng

    calls = []
    real_bq = eng.batch_query

    def spy(*a, **kw):
        calls.append((kw.get("cap"), kw.get("exact_budget")))
        return real_bq(*a, **kw)

    idx = _build(n=3000, pl=200,
                 config=EngineConfig(initial_cap=256, exact_budget=512,
                                     max_cap=1 << 15))
    wins = make_query_windows(idx.gs, 0.05, 4, seed=3)  # runs overflow 256
    try:
        eng.batch_query = spy
        res = idx.query(wins, "intersects", backend="device")
    finally:
        eng.batch_query = real_bq
    assert calls[0] == (256, 0)              # dormant budget: dense
    assert calls[-1][0] > 512 and calls[-1][1] == 512, calls
    for qi, w in enumerate(wins):
        np.testing.assert_array_equal(
            res[qi], _oracle(idx, w.astype(np.float32), "intersects",
                             np.float32))
