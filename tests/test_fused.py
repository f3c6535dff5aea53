"""Parity suite for the whole-table fused query and the compact kernel.

``batch_query_fused`` executes probe + capless whole-table mask/compact +
exact refinement as one XLA program (``EngineConfig.fusion="reference"``).
These tests pin it, the compact kernel through ``batch_query(...,
compaction="interpret")`` (the CPU correctness path; same kernel body the
TPU runs) and the staged ``batch_query(compaction="scan")`` baseline
bit-identical on hits AND counts, including the awkward shapes: odd Q/N
(tile padding), empty and inverted probe runs, zero-survivor and
all-survivor rows, the capless ``-(survivors) - 1`` budget-overflow
encoding, and widest-bucket vertex gathers on the heavy-tailed mixed store.
The engine-level tests assert the dispatch telemetry and the planner's
fused-selection / fallback rules."""
import numpy as np
import jax.numpy as jnp
import pytest

from _oracle import mixed_store
from repro.core import relations
from repro.core.datasets import make_query_windows
from repro.core.device import (_compact_kernel_refine, _device_relation,
                               batch_query, batch_query_bounds,
                               batch_query_fused, pods_from_store)
from repro.core.engine import EngineConfig, SpatialIndex
from repro.core.index import GLIN, GLINConfig
from repro.kernels.refine import COMPACT_CHUNK

# relations spanning both static prefilter shapes, augmentation on/off,
# probe pads (dwithin) and a host-predicate-heavy case (crosses)
PARITY_RELATIONS = ("intersects", "contains", "within", "dwithin:0.004",
                    "crosses")


@pytest.fixture(scope="module")
def mixed():
    """Heavy-tailed mixed store (points .. 64-vertex rings), odd N=347,
    published unpadded so slot indices match the raw leaf arrays."""
    gs = mixed_store(347, seed=3)
    g = GLIN.build(gs, GLINConfig(piece_limitation=200))
    s = SpatialIndex(g, EngineConfig(pad_quantum=0)).snapshot()
    return gs, g, s, pods_from_store(gs)


def _staged_scan(s, wj, pods, gs, base, budget, cap=1024):
    mb = jnp.asarray(gs.mbrs.astype(np.float32))
    return batch_query(s, wj, pods, mb, relation=base, cap=cap,
                       exact_budget=budget, compaction="scan")


def _kernel(s, wj, pods, gs, base, budget, cap=1024):
    """The compact kernel (interpret mode) inside the staged program."""
    mb = jnp.asarray(gs.mbrs.astype(np.float32))
    return batch_query(s, wj, pods, mb, relation=base, cap=cap,
                       exact_budget=budget, compaction="interpret")


def _ids(hits):
    return [np.sort(r[r >= 0]) for r in np.asarray(hits)]


# ---------------------------------------------------------------------------
# kernel-level parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("relation", PARITY_RELATIONS)
@pytest.mark.parametrize("q", [1, 13])
def test_fused_parity_odd_shapes(mixed, relation, q):
    """interpret == reference == staged scan, bit-for-bit, on odd Q and N."""
    gs, g, s, pods = mixed
    base = relations.get_relation(relation).base_name()
    wins = make_query_windows(gs, 0.004, q, seed=4)
    wj = jnp.asarray(wins.astype(np.float32))
    h_ref, c_ref = batch_query_fused(s, wj, pods, relation=base,
                                     exact_budget=64)
    h_int, c_int = _kernel(s, wj, pods, gs, base, 64)
    h_scan, c_scan = _staged_scan(s, wj, pods, gs, base, 64)
    np.testing.assert_array_equal(np.asarray(c_int), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(c_int), np.asarray(c_scan))
    np.testing.assert_array_equal(np.asarray(h_int), np.asarray(h_ref))
    for a, b in zip(_ids(h_int), _ids(h_scan)):
        np.testing.assert_array_equal(a, b)


def test_fused_budget_overflow_signalling(mixed):
    """Capless overflow: a negative count is ALWAYS -(MBR survivors) - 1,
    identical across interpret / reference / staged-scan (the staged path's
    cap is settled high enough that only the budget can overflow)."""
    gs, g, s, pods = mixed
    lo = gs.mbrs[:, :2].min(axis=0) - 0.01
    hi = gs.mbrs[:, 2:].max(axis=0) + 0.01
    wins = np.array([
        [lo[0], lo[1], hi[0], hi[1]],          # whole domain: must overflow
        [hi[0] + 1, hi[1] + 1, hi[0] + 2, hi[1] + 2],   # empty region
        list(gs.mbrs[0, :2]) + list(gs.mbrs[0, :2] + 1e-5),  # tiny
    ], np.float32)
    wj = jnp.asarray(wins)
    outs = {
        "interpret": _kernel(s, wj, pods, gs, "intersects", 8),
        "reference": batch_query_fused(s, wj, pods, relation="intersects",
                                       exact_budget=8),
        "scan": _staged_scan(s, wj, pods, gs, "intersects", 8, cap=2048),
    }
    counts = {k: np.asarray(c) for k, (h, c) in outs.items()}
    np.testing.assert_array_equal(counts["interpret"], counts["reference"])
    np.testing.assert_array_equal(counts["interpret"], counts["scan"])
    assert counts["interpret"][0] < 0
    assert -(counts["interpret"][0]) - 1 > 8         # encodes the true need
    assert counts["interpret"][1] == 0
    # non-overflowing rows still return exact hits alongside the signal
    for h, c in outs.values():
        assert (np.asarray(h)[1] == -1).all()


def test_fused_zero_and_all_survivor_rows(mixed):
    """A row with no survivors is all -1 / count 0; a row where EVERY live
    record survives fits when budget >= N and matches the brute oracle."""
    gs, g, s, pods = mixed
    lo = gs.mbrs[:, :2].min(axis=0) - 0.01
    hi = gs.mbrs[:, 2:].max(axis=0) + 0.01
    wins = np.array([
        [hi[0] + 1, hi[1] + 1, hi[0] + 2, hi[1] + 2],   # zero survivors
        [lo[0], lo[1], hi[0], hi[1]],                   # all survivors
    ], np.float32)
    hits, counts = _kernel(s, jnp.asarray(wins), pods, gs, "intersects", 512)
    hits, counts = np.asarray(hits), np.asarray(counts)
    assert counts[0] == 0 and (hits[0] == -1).all()
    bf = g.query_bruteforce(wins[1].astype(np.float64), "intersects")
    assert counts[1] == len(bf) == len(gs.nverts)
    np.testing.assert_array_equal(np.sort(hits[1][hits[1] >= 0]), bf)


def test_fused_empty_and_inverted_probe_runs(mixed):
    """Doctored probe runs through the compact kernel + exact stage: an
    inverted run (start > end) and an off-the-end empty run both yield zero
    survivors; an untouched row is unaffected by its neighbours."""
    gs, g, s, pods = mixed
    rel = _device_relation("contains")      # augment=False: probes stay raw
    wins = make_query_windows(gs, 0.02, 3, seed=7).astype(np.float32)
    wj = jnp.asarray(wins)
    start, end = (np.asarray(a).copy()
                  for a in batch_query_bounds(s, wj, "contains"))
    start[0], end[0] = end[0] + 5, start[0]       # inverted run
    start[1] = end[1] = s.num_slots               # beyond every stored slot
    hits, counts = _compact_kernel_refine(
        rel, wj, s, pods, jnp.asarray(start), jnp.asarray(end), 32,
        interpret=True)
    hits, counts = np.asarray(hits), np.asarray(counts)
    assert counts[0] == 0 and (hits[0] == -1).all()
    assert counts[1] == 0 and (hits[1] == -1).all()
    _, c_ref = batch_query_fused(s, wj, pods, relation="contains",
                                 exact_budget=32)
    assert counts[2] == np.asarray(c_ref)[2]


def test_fused_widest_bucket_gather(mixed):
    """The exact stage's pow2 gather ladder must reach the WIDEST surviving
    bucket: a whole-domain query on the heavy-tailed store pulls the
    64-vertex rings through the top bucket, and stays oracle-exact."""
    gs, g, s, pods = mixed
    assert pods.num_buckets >= 2       # heavy tail actually spans buckets
    assert int(np.asarray(pods.bucket).max()) == pods.num_buckets - 1
    lo = gs.mbrs[:, :2].min(axis=0) - 0.01
    hi = gs.mbrs[:, 2:].max(axis=0) + 0.01
    w = np.array([[lo[0], lo[1], hi[0], hi[1]]], np.float32)
    hits, counts = _kernel(s, jnp.asarray(w), pods, gs, "intersects", 512)
    ids = np.sort(np.asarray(hits)[0][np.asarray(hits)[0] >= 0])
    bf = g.query_bruteforce(w[0].astype(np.float64), "intersects")
    np.testing.assert_array_equal(ids, bf)
    # the survivors really include a top-bucket (widest) record
    assert int(np.asarray(pods.bucket)[ids].max()) == pods.num_buckets - 1


def test_fused_input_validation(mixed):
    gs, g, s, pods = mixed
    wj = jnp.asarray(make_query_windows(gs, 0.004, 2, seed=1)
                     .astype(np.float32))
    with pytest.raises(ValueError, match="device-native"):
        batch_query_fused(s, wj, pods, relation="disjoint")
    with pytest.raises(ValueError, match="exact_budget"):
        batch_query_fused(s, wj, pods, relation="intersects",
                          exact_budget=0)


# ---------------------------------------------------------------------------
# engine-level: planner selection, dispatch telemetry, fallbacks
# ---------------------------------------------------------------------------
def _engine(fusion, n=250, **eng):
    gs = mixed_store(n, seed=5)
    cfg = EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1,
                       fusion=fusion, **eng)
    return SpatialIndex.build(gs, GLINConfig(piece_limitation=200),
                              config=cfg)


def test_engine_fused_one_dispatch():
    """Fused and staged refines are each one jitted program per attempt,
    asserted via telemetry, with identical results."""
    idx = _engine("reference")
    wins = make_query_windows(idx.gs, 0.004, 17, seed=5)
    res = idx.query(wins, "intersects", backend="device")
    refine = {st.stage: st for st in res.stages}["refine"]
    assert refine.impl == "fused"
    assert refine.dispatches == 1
    assert "probe" in refine.covers and "refine" in refine.covers
    agg = idx.stats()["stages"]["device"]["refine"]
    assert agg["impl"] == "fused" and agg["dispatches"] == 1

    off = _engine("off")
    res_off = off.query(wins, "intersects", backend="device")
    r_off = {st.stage: st for st in res_off.stages}["refine"]
    assert r_off.impl == "device" and r_off.dispatches == 1
    for a, b in zip(res.ids, res_off.ids):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("relation", ["intersects", "within", "disjoint"])
def test_engine_fused_matches_staged(relation):
    """End-to-end ids agree across the whole-table fused refine, the staged
    compact kernel (interpret mode) and the staged scan, for plain,
    contains-shaped and complement-finished relations."""
    wins = None
    got = {}
    for name, fusion, comp in (("reference", "reference", None),
                               ("kernel", "off", "interpret"),
                               ("off", "off", None)):
        idx = _engine(fusion, compaction=comp)
        if wins is None:
            wins = make_query_windows(idx.gs, 0.004, 9, seed=6)
        got[name] = idx.query(wins, relation, backend="device").ids
    for name in ("reference", "kernel"):
        for a, b in zip(got[name], got["off"]):
            np.testing.assert_array_equal(a, b)


def test_engine_fused_overflow_ladder():
    """Budget overflow inside the fused path walks the SAME OverflowLadder
    (capless: no disambiguating bounds probe is spent) and ends exact."""
    idx = _engine("reference", exact_budget=4)
    lo = idx.gs.mbrs[:, :2].min(axis=0) - 0.01
    hi = idx.gs.mbrs[:, 2:].max(axis=0) + 0.01
    w = np.array([[lo[0], lo[1], hi[0], hi[1]]])
    res = idx.query(w, "intersects", backend="device")
    refine = {st.stage: st for st in res.stages}["refine"]
    assert refine.escalations >= 1
    # one dispatch per attempt: escalations+1 attempts, nothing extra
    assert refine.dispatches == refine.escalations + 1
    bf = idx.glin.query_bruteforce(w[0], "intersects")
    np.testing.assert_array_equal(res.ids[0], bf)


def test_engine_explain_shows_fused():
    idx = _engine("reference")
    wins = make_query_windows(idx.gs, 0.004, 8, seed=2)
    text = idx.explain(wins, "intersects")
    assert "fused whole-table refine" in text
    assert "impl=fused" in text
    assert idx.plan(wins, "intersects").fused
    off = _engine("off")
    assert not off.plan(wins, "intersects").fused
    assert "fused whole-table refine" not in off.explain(wins, "intersects")


def test_custom_prefilter_falls_back_to_staged():
    """A relation the kernel cannot prefilter (prefilter_kind="custom") is
    planned staged, and the raw fused entry point refuses it loudly."""
    base = relations.get_relation("intersects")
    custom = relations.Relation(
        name="_test_custom", predicate=base.predicate, augment=base.augment,
        mbr_prefilter=base.mbr_prefilter, prefilter_kind="custom")
    relations.register_relation(custom)
    try:
        idx = _engine("reference")
        wins = make_query_windows(idx.gs, 0.004, 8, seed=2)
        assert not idx.plan(wins, "_test_custom").fused
        res = idx.query(wins, "_test_custom", backend="device")
        refine = {st.stage: st for st in res.stages}["refine"]
        assert refine.impl == "device"
        base_res = idx.query(wins, "intersects", backend="device")
        for a, b in zip(res.ids, base_res.ids):
            np.testing.assert_array_equal(a, b)
        s = idx.snapshot()
        pods, _ = idx._device_payload(idx._snapshot_recs)
        with pytest.raises(ValueError, match="custom"):
            batch_query_fused(s, jnp.asarray(wins.astype(np.float32)),
                              pods, relation="_test_custom")
    finally:
        relations.RELATIONS.pop("_test_custom", None)
        relations._BOUND.clear()


def test_fusion_mode_resolution():
    """_fusion_mode: the single gate deciding whole-table vs staged. The
    fused path is capless, so only a dense escalation (budget 0) leaves
    it; the compact kernel is a compaction of the staged path, not a
    fusion mode."""
    idx = _engine("reference")
    assert idx._fusion_mode("intersects") == "reference"
    assert idx._fusion_mode("intersects", budget=0) is None
    assert idx._fusion_mode("intersects",
                            budget=4 * COMPACT_CHUNK) == "reference"
    off = _engine("off")
    assert off._fusion_mode("intersects") is None
    assert _engine(None)._fusion_mode("intersects") is None
    for bogus in ("warp9", "pallas", "interpret"):
        with pytest.raises(ValueError, match="fusion"):
            _engine(bogus)._fusion_mode("intersects")


def test_fusion_vmem_envelope_falls_back():
    """No store size and no budget below max_cap leaves the whole-table
    path: survivors past ``COMPACT_CHUNK`` grow the budget and stay fused
    and exact, with no dense attempt."""
    idx = _engine("reference", n=1500, exact_budget=4)
    lo = idx.gs.mbrs[:, :2].min(axis=0) - 0.01
    hi = idx.gs.mbrs[:, 2:].max(axis=0) + 0.01
    w = np.array([[lo[0], lo[1], hi[0], hi[1]]])  # 1500 survivors > 1024
    res = idx.query(w, "intersects", backend="device")
    refine = {st.stage: st for st in res.stages}["refine"]
    assert refine.impl == "fused"
    assert refine.note == "fusion=reference"
    assert refine.budget > COMPACT_CHUNK
    np.testing.assert_array_equal(
        res.ids[0], idx.glin.query_bruteforce(w[0], "intersects"))
