"""Staged execution pipeline (core.exec): every backend's compiled plan is
oracle-identical on the mixed convex/concave/polyline/point store AND reports
consistent per-stage telemetry (survivor counts, overflow-ladder escalations,
delta sizes); the shared complement-finish stage answers exactly at the
frozen epoch under concurrent writers; explain() renders without executing."""
import os
import pathlib
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.core import exec as qexec
from repro.core.datasets import generate, make_query_windows
from repro.core.engine import EngineConfig, QueryBatch, SpatialIndex
from repro.core.geometry import mbrs_of_verts
from repro.core.index import GLINConfig
from repro.core.relations import get_relation

ROOT = pathlib.Path(__file__).resolve().parents[1]

RELATIONS = ("contains", "intersects", "within", "covers", "disjoint",
             "touches", "crosses", "dwithin:0.003")


def _mixed(n=3000, pl=250, seed=2, **eng):
    """fp32-representable MIXED store (convex/concave polygons, polylines,
    points): host fp64 and device fp32 refinement decide identically, so one
    oracle serves every backend."""
    gs = generate("mixed", n, seed=seed)
    gs.verts = gs.verts.astype(np.float32).astype(np.float64)
    gs.mbrs = mbrs_of_verts(gs.verts, gs.nverts)
    cfg = EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1, **eng)
    return SpatialIndex.build(gs, GLINConfig(piece_limitation=pl), config=cfg)


def _windows(idx, sel=0.01, k=4, seed=3):
    w = make_query_windows(idx.gs, sel, k, seed=seed)
    return w.astype(np.float32).astype(np.float64)


def _oracle(idx, w, relation, dtype=np.float32):
    rel = get_relation(relation)
    gs = idx.gs
    ok = rel.predicate(np.asarray(w, dtype), gs.verts.astype(dtype),
                       gs.nverts, gs.kinds)
    live = idx.glin._live_mask()
    return np.nonzero(np.asarray(ok) & live)[0].astype(np.int64)


def _check_stage_telemetry(res):
    """Structural invariants every executed window pipeline must satisfy."""
    assert res.stages, "QueryResult.stages missing"
    order = {s: i for i, s in enumerate(qexec.PIPELINE_STAGES)}
    covered = [c for s in res.stages for c in s.covers]
    ranks = [order[c] for c in covered]
    assert ranks == sorted(ranks) and len(set(ranks)) == len(ranks), covered
    assert covered[:3] == ["probe", "compact", "refine"], covered
    producing = [s for s in res.stages if not s.skipped and s.survivors >= 0]
    assert producing, [s.stage for s in res.stages]
    assert producing[-1].survivors == res.total_hits
    for s in res.stages:
        assert s.wall_ms >= 0.0
        if s.skipped:
            assert s.note, f"skipped stage {s.stage} gives no reason"


# ------------------------------------------------------------ stage parity --
@pytest.mark.parametrize("relation", RELATIONS)
@pytest.mark.parametrize("backend", ["host", "device"])
def test_stage_parity_mixed_store(backend, relation):
    idx = _mixed()
    wins = _windows(idx)
    res = idx.query(wins, relation, backend=backend)
    for qi, w in enumerate(wins):
        np.testing.assert_array_equal(res[qi], _oracle(idx, w, relation))
    _check_stage_telemetry(res)
    refine = {s.stage: s for s in res.stages}["refine"]
    assert refine.impl == backend
    assert refine.queries == len(wins)


def test_backends_report_identical_survivor_counts():
    """Same frozen store, same windows: host and device pipelines must agree
    on the ids AND on the telemetry that describes them — the final stage's
    survivor count is a backend-independent fact."""
    idx = _mixed()
    wins = _windows(idx, sel=0.02)
    for relation in ("intersects", "disjoint", "dwithin:0.003"):
        h = idx.query(wins, relation, backend="host")
        d = idx.query(wins, relation, backend="device")
        for a, b in zip(h, d):
            np.testing.assert_array_equal(a, b)
        hs = [s.survivors for s in h.stages if not s.skipped][-1]
        ds = [s.survivors for s in d.stages if not s.skipped][-1]
        assert hs == ds == h.total_hits == d.total_hits


def test_complement_stage_skipped_vs_active():
    """The complement-finish stage is compiled into every window pipeline but
    must no-op (with a stated reason) for plain relations and fire exactly
    once for complements, fixing the per-query hit counts."""
    idx = _mixed()
    wins = _windows(idx)
    plain = idx.query(wins, "intersects", backend="host")
    comp = idx.query(wins, "disjoint", backend="host",
                     collect_stats=True)
    p = {s.stage: s for s in plain.stages}["complement-finish"]
    c = {s.stage: s for s in comp.stages}["complement-finish"]
    assert p.skipped and p.note
    assert not c.skipped and c.impl == "shared"
    assert c.survivors == comp.total_hits
    for st, ids in zip(comp.stats, comp.ids):
        assert st.results == len(ids)


# ------------------------------------------------------- ladder telemetry ---
def test_ladder_escalations_surface_in_stage_stats():
    """A tiny exact_budget forces the shared OverflowLadder to escalate; the
    refine StageStats must report the retries and the settled budget, and
    SpatialIndex.stats() must aggregate them."""
    idx = _mixed(initial_cap=1 << 14, exact_budget=8)
    wins = _windows(idx, sel=0.02)
    res = idx.query(wins, "intersects", backend="device")
    for qi, w in enumerate(wins):
        np.testing.assert_array_equal(res[qi], _oracle(idx, w, "intersects"))
    refine = {s.stage: s for s in res.stages}["refine"]
    assert refine.escalations >= 1
    assert refine.budget == 0 or refine.budget > 8  # grew or went dense
    assert refine.cap >= 1 << 14
    agg = idx.stats()["stages"]["device"]["refine"]
    assert agg["escalations"] >= refine.escalations
    assert agg["calls"] >= 1 and agg["wall_ms"] > 0.0


@pytest.mark.parametrize("capless", [True, False])
def test_ladder_budget_ceiling_capless_vs_capped(capless):
    """Capless compaction grows its budget past the cap and past one
    kernel chunk (up to max_cap); capped compaction drops to the dense path
    once the budget would pass CAPPED_MAX_BUDGET or the cap."""
    cfg = EngineConfig(exact_budget=256, max_cap=1 << 20)
    ladder = qexec.OverflowLadder(cfg, cap=4096, capless=capless)
    ladder.on_capless_overflow(np.array([-5001, 3]), ladder.use_budget)
    if capless:
        assert ladder.use_budget == 8192          # >= cap, still two-stage
        ladder.on_capless_overflow(np.array([-(1 << 21) - 1]), 8192)
        assert ladder.use_budget == 0             # past max_cap: dense
    else:
        assert ladder.use_budget == 0             # past the capped ceiling


def test_capless_device_refine_never_goes_dense():
    """A window with more MBR survivors than one compact-kernel chunk and a
    probe run longer than the cap stays on the kernel: the budget grows,
    the cap does not, no dense attempt runs, and the answer is exact (the
    dense fallback would need a cap as long as the run, which on a
    deployment-size store is past max_cap)."""
    from repro.kernels.refine import COMPACT_CHUNK

    idx = _mixed(n=1800, initial_cap=512, exact_budget=64,
                 compaction="interpret")
    lo = idx.gs.mbrs[:, :2].min(axis=0) - 0.01
    hi = idx.gs.mbrs[:, 2:].max(axis=0) + 0.01
    w = np.array([[lo[0], lo[1], hi[0], hi[1]]])
    res = idx.query(w, "intersects", backend="device")
    np.testing.assert_array_equal(res[0], _oracle(idx, w[0], "intersects"))
    refine = {s.stage: s for s in res.stages}["refine"]
    assert refine.note == "compaction=interpret"
    assert refine.budget > COMPACT_CHUNK and refine.cap == 512
    assert refine.escalations >= 1


# ------------------------------------------------------ delta-patch stage ---
def test_delta_patch_stage_stats_and_parity():
    """Writes after a publish route through device+delta: the shared patch
    stage reports the frozen delta's size and the patched ids equal the host
    oracle's."""
    idx = _mixed(refresh_threshold=10_000, delta_patch_max=4096)
    idx.snapshot()
    wins = _windows(idx, sel=0.02)
    rng = np.random.default_rng(9)
    for _ in range(3):
        c = rng.uniform(0.3, 0.7, 2)
        ang = np.sort(rng.uniform(0, 2 * np.pi, 8))
        v = np.stack([c[0] + 1e-3 * np.cos(ang),
                      c[1] + 1e-3 * np.sin(ang)], -1)
        idx.insert(v.astype(np.float32).astype(np.float64), 8, 0)
    assert idx.delete(0)
    res = idx.query(wins, "intersects")   # planner: stale + small delta
    assert res.plan.backend == "device+delta"
    patch = {s.stage: s for s in res.stages}["delta-patch"]
    assert not patch.skipped and patch.impl == "shared"
    assert patch.delta_added == 3 and patch.delta_tombstoned == 1
    host = idx.query(wins, "intersects", backend="host")
    for a, b in zip(res, host):
        np.testing.assert_array_equal(a, b)
    _check_stage_telemetry(res)


# ------------------------------------------------------------- knn stages ---
def test_knn_pipelines_compose_knn_rank():
    idx = _mixed()
    rng = np.random.default_rng(11)
    pts = rng.uniform(0.2, 0.8, (20, 2)).astype(np.float32).astype(np.float64)
    res = idx.query(QueryBatch.knn(pts, k=5))
    assert res.stages and res.stages[-1].stage == "knn-rank"
    assert "knn-rank" in res.stages[-1].covers
    assert res.distances is not None and len(res.distances) == len(pts)
    one = idx.query(QueryBatch.knn(pts[:1], k=5))
    assert one.stages[-1].stage == "knn-rank"
    np.testing.assert_array_equal(one[0], res[0])


# ----------------------------------------------------------------- explain --
def test_explain_renders_without_executing():
    idx = _mixed()
    wins = _windows(idx)
    txt = idx.explain(wins, "disjoint")
    assert "QueryPlan backend=" in txt and "reason:" in txt
    assert "refine" in txt and "complement-finish" in txt
    assert "probe+compact+refine" in txt
    assert idx.stats()["stages"] == {}  # nothing ran


def test_stats_aggregate_per_backend_per_stage():
    idx = _mixed()
    wins = _windows(idx)
    idx.query(wins, "intersects", backend="host")
    idx.query(wins, "intersects", backend="host")
    idx.query(wins, "disjoint", backend="device")
    st = idx.stats()["stages"]
    assert st["host"]["refine"]["calls"] == 2
    assert st["host"]["refine"]["impl"] == "host"
    assert st["device"]["refine"]["calls"] == 1
    assert st["device"]["complement-finish"]["calls"] == 1
    assert st["device"]["complement-finish"]["skipped"] == 0


# --------------------------------------- complement vs concurrent writers ---
def test_complement_finish_exact_at_frozen_epoch_under_writes(monkeypatch):
    """Satellite regression: the device pipeline freezes the live-id set
    under the lock BEFORE its unlocked device compute; records inserted
    while the compute runs must NOT leak into a complement answer (they are
    disjoint from the window, so a non-frozen live set would include them)."""
    import repro.core.engine as eng

    idx = _mixed(n=1500)
    idx.snapshot()
    w = np.array([0.4, 0.4, 0.6, 0.6], np.float32).astype(np.float64)
    base = idx.query(w[None], "intersects", backend="host")[0]
    live0 = np.nonzero(idx.glin._live_mask())[0].astype(np.int64)

    entered, release = threading.Event(), threading.Event()
    real = eng.batch_query

    def slow(*a, **kw):
        entered.set()
        release.wait(10.0)   # hold the freeze->finish window open
        return real(*a, **kw)

    monkeypatch.setattr(eng, "batch_query", slow)
    inserted = []

    def writer():
        entered.wait(10.0)
        rng = np.random.default_rng(13)
        for _ in range(5):   # far from the window -> in its complement
            c = rng.uniform(0.9, 0.95, 2)
            ang = np.sort(rng.uniform(0, 2 * np.pi, 8))
            v = np.stack([c[0] + 5e-4 * np.cos(ang),
                          c[1] + 5e-4 * np.sin(ang)], -1)
            inserted.append(idx.insert(
                v.astype(np.float32).astype(np.float64), 8, 0))
        release.set()

    t = threading.Thread(target=writer)
    t.start()
    try:
        res = idx.query(w[None], "disjoint", backend="device")
    finally:
        release.set()
        t.join(10.0)
    assert len(inserted) == 5, "writer never ran inside the compute window"
    assert not np.isin(inserted, res[0]).any(), \
        "mid-flight inserts leaked into the frozen complement"
    np.testing.assert_array_equal(res[0], np.setdiff1d(live0, base))
    fin = {s.stage: s for s in res.stages}["complement-finish"]
    assert not fin.skipped and fin.survivors == len(res[0])


# --------------------------------------------------------------- sharded ----
def _run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_sharded_capless_budget_past_chunk():
    """Sharded windows whose per-shard survivors pass one kernel chunk
    grow the budget on the compact kernel (interpret mode) and stay exact:
    no dense attempt, the cap untouched (4 fake CPU devices)."""
    out = _run_py("""
        import numpy as np
        from repro.utils.compat import make_auto_mesh
        mesh = make_auto_mesh((4, 1), ("data", "model"))
        from repro.core.datasets import generate
        from repro.core.geometry import mbrs_of_verts
        from repro.core.index import GLIN, GLINConfig
        from repro.core.engine import EngineConfig, SpatialIndex

        gs = generate("mixed", 5000, seed=4)
        gs.verts = gs.verts.astype(np.float32).astype(np.float64)
        gs.mbrs = mbrs_of_verts(gs.verts, gs.nverts)
        idx = SpatialIndex(
            GLIN.build(gs, GLINConfig(piece_limitation=300)),
            EngineConfig(mesh=mesh, device_min_batch=1, initial_cap=512,
                         exact_budget=64, compaction="interpret",
                         stale_rebuild_min_batch=1, shard_min_records=1))
        lo = gs.mbrs[:, :2].min(axis=0) - 0.01
        hi = gs.mbrs[:, 2:].max(axis=0) + 0.01
        w = np.array([[lo[0], lo[1], hi[0], hi[1]]])
        s = idx.query(w, "intersects", backend="sharded")
        h = idx.query(w, "intersects", backend="host")
        assert np.array_equal(s[0], h[0]) and len(s[0]) == 5000
        refine = {st.stage: st for st in s.stages}["refine"]
        assert refine.note == "compaction=interpret", refine.note
        assert refine.budget > 1024 and refine.cap == 512, refine
        print("SHARDED-CAPLESS-OK")
    """, devices=4)
    assert "SHARDED-CAPLESS-OK" in out


def test_sharded_pipeline_parity_and_telemetry():
    """The sharded backend routes through the SAME staged pipeline: refine
    impl 'sharded', the shared patch/complement stages downstream, results
    equal to the host pipeline's on the mixed store (8 fake CPU devices)."""
    out = _run_py("""
        import numpy as np
        from repro.utils.compat import make_auto_mesh
        mesh = make_auto_mesh((4, 2), ("data", "model"))
        from repro.core.datasets import generate, make_query_windows
        from repro.core.geometry import mbrs_of_verts
        from repro.core.index import GLIN, GLINConfig
        from repro.core.engine import EngineConfig, SpatialIndex

        gs = generate("mixed", 4000, seed=2)
        gs.verts = gs.verts.astype(np.float32).astype(np.float64)
        gs.mbrs = mbrs_of_verts(gs.verts, gs.nverts)
        idx = SpatialIndex(
            GLIN.build(gs, GLINConfig(piece_limitation=300)),
            EngineConfig(mesh=mesh, device_min_batch=1,
                         stale_rebuild_min_batch=1, shard_min_records=1))
        wins = make_query_windows(gs, 0.02, 8, seed=5)
        wins = wins.astype(np.float32).astype(np.float64)
        for rel in ("intersects", "disjoint", "dwithin:0.002"):
            s = idx.query(wins, rel, backend="sharded")
            h = idx.query(wins, rel, backend="host")
            for a, b in zip(s, h):
                assert np.array_equal(a, b), rel
            stages = {st.stage: st for st in s.stages}
            assert stages["refine"].impl == "sharded"
            assert stages["refine"].covers == ("probe", "compact", "refine")
            last = [st for st in s.stages if not st.skipped][-1]
            assert last.survivors == s.total_hits
        agg = idx.stats()["stages"]["sharded"]["refine"]
        assert agg["calls"] == 3 and agg["wall_ms"] > 0.0
        print("EXEC-SHARDED-OK")
    """)
    assert "EXEC-SHARDED-OK" in out
