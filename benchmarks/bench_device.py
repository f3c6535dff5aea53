"""Beyond-paper benchmarks: the TPU-native batched query path and the
Pallas kernels (timed via their XLA reference semantics on CPU; interpret
mode executes kernel bodies in Python and is not a timing proxy).

``refine_pipeline`` is the perf-trajectory anchor: it times the OLD
refinement (legacy stable-argsort compaction over chained per-query MBR
gathers, ``compaction="sort"``), the staged pipeline (slot-aligned MBR
tables + cumsum/scatter compaction, ``compaction="scan"``) and the
whole-table fused path (``batch_query_fused``, one XLA program; on TPU the
``pallas`` column adds the compact kernel) per dataset and relation,
asserts exactness against ``query_bruteforce`` every time, and emits the
``BENCH {json}`` line committed as ``BENCH_device.json``.
``knn_pipeline`` adds the ``"knn"`` row: the device-complete knn batch
(CDF-seeded radii + device top-k) against the host-ranked rung ladder it
replaced, both asserted exact against the fp64 brute-force oracle. The
Pallas kernel columns are only *measured* on TPU; elsewhere they are
emitted as ``null`` and listed in each row's ``"unmeasured"`` marker so the
committed trajectory never silently conflates backends.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.datasets import generate, make_query_windows
from repro.core.device import (batch_query, batch_query_bounds,
                               batch_query_fused)
from repro.core.engine import EngineConfig, SpatialIndex
from repro.core.geometry import mbrs_of_verts
from repro.core.index import GLINConfig
from repro.core.relations import get_relation
from repro.kernels import ops

from .common import Csv, build_index, scale_n, timeit, windows

REFINE_CAP = 4096
REFINE_BUDGET = 256
REFINE_DATASETS = ("uniform", "cluster", "concave")
REFINE_RELATIONS = ("intersects", "contains")


def _fp32_dataset(name: str, n: int, seed: int = 0):
    """fp32-representable coordinates: fp64 ``query_bruteforce`` and fp32
    device refinement then decide identically, so exactness is assertable
    bit-for-bit. Generated fresh (the cast mutates the GeometrySet)."""
    gs = generate(name, n, seed=seed)
    gs.verts = gs.verts.astype(np.float32).astype(np.float64)
    gs.mbrs = mbrs_of_verts(gs.verts, gs.nverts)
    return gs


def refine_pipeline(csv: Csv, n: int, q: int = 128) -> dict:
    """Old-vs-new refinement per dataset × relation at the tracked config
    (cap=4096, budget=256). ``refine_us`` isolates the refinement stage:
    total batched query time minus the (shared) probe time. ``fused`` is the
    whole-table ``batch_query_fused`` XLA program; ``pallas`` (TPU only) is
    ``batch_query`` with the compact kernel (interpret mode is a
    correctness tool, not a timing proxy)."""
    on_tpu = jax.default_backend() == "tpu"
    impls = ["sort", "scan", "fused"]
    if on_tpu:
        impls.append("pallas")
    out: dict = {"bench": "device_refine", "n": n, "q": q, "cap": REFINE_CAP,
                 "budget": REFINE_BUDGET, "backend": jax.default_backend(),
                 "datasets": {}}
    for name in REFINE_DATASETS:
        gs = _fp32_dataset(name, n)
        idx = SpatialIndex.build(
            gs, GLINConfig(piece_limitation=10_000),
            EngineConfig(initial_cap=REFINE_CAP, exact_budget=REFINE_BUDGET))
        snap = idx.snapshot()
        pods, mb = idx._device_payload(idx._snapshot_recs)
        wins = make_query_windows(gs, 0.0001, q, seed=2)
        wins = wins.astype(np.float32).astype(np.float64)
        wj = jnp.asarray(wins.astype(np.float32))
        out["datasets"][name] = {}
        bounds_fn = jax.jit(batch_query_bounds, static_argnames=("relation",))
        for rel_name in REFINE_RELATIONS:
            base = get_relation(rel_name).base_name()

            def probe(wj=wj, base=base):
                s, e = bounds_fn(snap, wj, base)
                return e.block_until_ready()

            probe()
            probe_us = timeit(probe, repeats=5)
            # settle the candidate cap the way the facade's overflow ladder
            # does: the dense legacy path must cover the longest augmented
            # run (its core weakness — the (Q, cap) intermediate scales with
            # the run; the fused kernel path has no such intermediate)
            s0, e0 = bounds_fn(snap, wj, base)
            need = int(np.max(np.asarray(e0) - np.asarray(s0)))
            cap = max(REFINE_CAP, 1 << max(need - 1, 1).bit_length())
            row: dict = {"probe_us": probe_us, "settled_cap": cap,
                         "max_run": need}
            ref_hits = None
            for impl in impls:
                if impl == "fused":
                    def run(wj=wj):
                        # one dispatch end-to-end (probe included), so the
                        # probe_us subtraction below still isolates the
                        # refinement delta fairly vs the staged columns
                        h, c = batch_query_fused(
                            snap, wj, pods, relation=base,
                            exact_budget=REFINE_BUDGET)
                        return h.block_until_ready(), c.block_until_ready()
                else:
                    def run(impl=impl, wj=wj, cap=cap):
                        h, c = batch_query(
                            snap, wj, pods, mb, relation=base,
                            cap=cap, exact_budget=REFINE_BUDGET,
                            compaction=impl)
                        return h.block_until_ready(), c.block_until_ready()
                hits, counts = run()   # compile outside the timed region
                counts = np.asarray(counts)
                assert (counts >= 0).all(), \
                    f"{name}/{rel_name}/{impl}: overflow at settled cap"
                total_us = timeit(run, repeats=5)
                row[f"{impl}_us"] = total_us
                row[f"refine_{impl}_us"] = max(total_us - probe_us, 0.0)
                ids = [np.sort(r[r >= 0]) for r in np.asarray(hits)]
                if ref_hits is None:
                    ref_hits = ids
                    # exactness vs the brute-force oracle (fp32 grid: exact)
                    for qi in range(q):
                        bf = idx.glin.query_bruteforce(wins[qi], rel_name)
                        np.testing.assert_array_equal(ids[qi], bf)
                    row["hits"] = int(sum(r.shape[0] for r in ids))
                else:
                    for a, b in zip(ids, ref_hits):   # impls agree exactly
                        np.testing.assert_array_equal(a, b)
            if not on_tpu:
                # the Pallas kernel columns exist on every row of the
                # committed trajectory but are only measurable on TPU:
                # null + an explicit marker beats silent omission
                row["pallas_us"] = None
                row["refine_pallas_us"] = None
                row["unmeasured"] = ["pallas"]
            row["speedup_refine"] = (row["refine_sort_us"]
                                     / max(row["refine_scan_us"], 1e-9))
            row["speedup_fused"] = (row["refine_scan_us"]
                                    / max(row["refine_fused_us"], 1e-9))
            out["datasets"][name][rel_name] = row
            csv.emit(
                f"device/refine/{name}/{rel_name}_us", row["refine_fused_us"],
                f"scan={row['refine_scan_us']:.0f}us;"
                f"old_sort={row['refine_sort_us']:.0f}us;"
                f"probe={probe_us:.0f}us;"
                f"speedup=x{row['speedup_refine']:.2f};"
                f"fused=x{row['speedup_fused']:.2f};exact=True")
    out["speedup_cluster"] = (
        out["datasets"]["cluster"]["intersects"]["speedup_refine"])
    out["speedup_fused_cluster"] = (
        out["datasets"]["cluster"]["intersects"]["speedup_fused"])
    return out


def knn_pipeline(csv: Csv, n: int, q: int = 64, k: int = 10) -> dict:
    """Device-complete knn vs the host-ranked rung ladder it replaced.

    Baseline = the old shape: batched device ``dwithin`` probes at blindly
    doubling radii, then PER-POINT host ranking — gather every candidate's
    vertices to the host (``gs.padded``), exact fp64 distances, lexsort
    top-k. New = ``QueryBatch.knn`` on the device backend: CDF-seeded
    per-point radii, exact squared distances on the pooled VertexPods
    survivors and a device top-k; only the final ``(Q, k)`` comes home.
    Both run fresh on the same index; BOTH are asserted exact against the
    fp64 brute-force oracle every run, and the payload carries the median
    rung depth seeded vs blind (``check_bench`` gates seeded <= 2)."""
    import dataclasses

    from repro.core import geometry as geom
    from repro.core.engine import QueryBatch
    from repro.core.index import initial_knn_radius

    gs = _fp32_dataset("cluster", n)
    idx = SpatialIndex.build(
        gs, GLINConfig(piece_limitation=10_000),
        EngineConfig(initial_cap=REFINE_CAP, exact_budget=REFINE_BUDGET))
    idx.snapshot()
    rng = np.random.default_rng(7)
    lo, hi = gs.mbrs[:, :2].min(0), gs.mbrs[:, 2:].max(0)
    pts = lo + (hi - lo) * rng.uniform(0.1, 0.9, (q, 2))
    pts = pts.astype(np.float32).astype(np.float64)

    # fp64 brute-force oracle, ranked by the shared (distance, id) contract
    all_ids = np.arange(n, dtype=np.int64)
    pad, nv, kd = gs.padded(all_ids), gs.nverts, gs.kinds

    def exact_rank(p, ids, vv, nvv, kdd):
        rect = np.array([p[0], p[1], p[0], p[1]])
        d2 = geom.rect_geom_sqdist(rect, vv, nvv, kdd, xp=np)
        return geom.rank_knn(ids, np.sqrt(np.maximum(d2, 0.0)), k)[0]

    want = [exact_rank(p, all_ids, pad, nv, kd) for p in pts]

    # ---- baseline: blind doubling ladder, candidates ranked on the host
    r0 = initial_knn_radius(idx.glin, k)
    r0 = float(np.power(2.0, np.ceil(np.log2(max(r0, 1e-9)))))

    def host_ladder():
        done = np.zeros(q, bool)
        out = [None] * q
        r = r0
        while not done.all():
            sel = np.nonzero(~done)[0]
            w = np.concatenate([pts[sel], pts[sel]], axis=1)
            res = idx.query(QueryBatch.window(
                w, f"dwithin:{r:.17g}", backend="device"))
            for j, i in enumerate(sel):
                hits = np.asarray(res.ids[j])
                if len(hits) >= min(k, n):
                    out[i] = exact_rank(pts[i], hits, gs.padded(hits),
                                        nv[hits], kd[hits])
                    done[i] = True
            r *= 2.0
        return out

    base_ids = host_ladder()   # compile every rung's query bucket
    host_us = timeit(host_ladder, repeats=3)

    # ---- new: one device-complete knn batch
    batch = QueryBatch.knn(pts, k, backend="device")
    res = idx.query(batch)     # compile + walk the adaptive cap up
    assert res.plan.backend == "device"
    idx.query(batch)           # second warm: the first call grew the cap
    #                            mid-flight, so rung shapes recompile once
    #                            at the settled cap before the timed region
    dev_us = timeit(lambda: idx.query(batch), repeats=3)

    for qi in range(q):        # exactness of BOTH paths, every run
        np.testing.assert_array_equal(np.asarray(res.ids[qi]), want[qi])
        np.testing.assert_array_equal(np.asarray(base_ids[qi]), want[qi])

    def med_rungs(stage):
        probes = np.repeat(np.arange(1, stage.rungs + 1),
                           np.asarray(stage.rung_hist, np.int64))
        return float(np.median(probes)) if probes.size else 0.0

    seeded = res.stages[-1]
    cfg0 = idx.config
    try:                       # same batch, blind global seed radius
        idx.config = dataclasses.replace(cfg0, knn_seed="global")
        blind = idx.query(QueryBatch.knn(pts, k, backend="device")).stages[-1]
    finally:
        idx.config = cfg0
    row = {"n": n, "q": q, "k": k,
           "host_ladder_us": host_us, "device_us": dev_us,
           "speedup_knn": host_us / max(dev_us, 1e-9),
           "rungs_median_seeded": med_rungs(seeded),
           "rungs_median_blind": med_rungs(blind),
           "seed_hits": int(seeded.seed_hits), "exact": True}
    csv.emit("device/knn_us", dev_us,
             f"host_ladder={host_us:.0f}us;"
             f"speedup=x{row['speedup_knn']:.2f};"
             f"rungs_med={row['rungs_median_seeded']:.1f}"
             f"(blind={row['rungs_median_blind']:.1f});exact=True")
    return row


def device_batch_query(csv: Csv, n: int) -> None:
    name = "cluster"
    # Augmented Intersects runs are long: two-stage refinement (MBR masks over
    # the full run, exact checks on <=1024 survivors). The facade's adaptive
    # cap walks the overflow ladder once, so the timed region is exact AND
    # steady-state (the seed bench silently timed truncated results).
    idx = build_index(name, n, pl=10000,
                      engine=EngineConfig(initial_cap=4096, exact_budget=1024))
    idx.snapshot()  # materialize outside the timed region
    for q in (64, 512):
        wins = np.concatenate([windows(name, n, 0.0001, k=20)] * (q // 20 + 1))[:q]
        def fn(wins=wins):
            return idx.query(wins, "intersects", backend="device")
        fn()  # compile + settle the adaptive cap
        t = timeit(fn, repeats=3)
        # host loop comparison (same facade, forced host backend)
        t_host = timeit(lambda: idx.query(wins[:32], "intersects",
                                          backend="host"),
                        repeats=2) / 32 * q
        csv.emit(f"device/batch_query_us/Q={q}", t,
                 f"per_query={t/q:.1f}us;host_loop={t_host:.0f}us;"
                 f"speedup=x{t_host/t:.1f};cap={idx.device_cap}")
    # planner-chosen path + refine-kernel selectivity estimation
    wins = windows(name, n, 0.0001, k=20)
    plan = idx.plan(wins, "intersects")
    counts = idx.count_candidates(wins, "intersects")
    csv.emit("device/count_candidates_us",
             timeit(lambda: idx.count_candidates(wins, "intersects"), repeats=3),
             f"plan={plan.backend};mean_cand={float(counts.mean()):.0f}")


def kernels(csv: Csv) -> None:
    rng = np.random.default_rng(0)
    # morton (XLA path)
    qx = jnp.asarray(rng.integers(0, 2**30, 1 << 20), jnp.int32)
    qy = jnp.asarray(rng.integers(0, 2**30, 1 << 20), jnp.int32)
    def f():
        return ops.morton_encode(qx, qy,
                                 use_pallas=False)[0].block_until_ready()
    f()
    csv.emit("kernels/morton_1M_us", timeit(f), "XLA path; pallas=TPU target")
    # refine count
    wins = jnp.asarray(rng.uniform(0, 1, (64, 4)).astype(np.float32))
    mbrs = jnp.asarray(rng.uniform(0, 1, (1 << 17, 4)).astype(np.float32))
    bounds = jnp.zeros((64, 2), jnp.int32).at[:, 1].set(1 << 17)
    def f():
        return ops.refine_count(wins, bounds, mbrs,
                                use_pallas=False).block_until_ready()
    f()
    csv.emit("kernels/refine_64x131k_us", timeit(f), "XLA path")
    # fused compact (jnp reference semantics)
    def f():
        return ops.refine_compact(wins, bounds, mbrs, mbrs, budget=256,
                                  use_pallas=False)[0].block_until_ready()
    f()
    csv.emit("kernels/compact_64x131k_us", timeit(f),
             "XLA path; budget=256; pallas=TPU target")
    # flash attention vs reference (XLA timing)
    q = jnp.asarray(rng.normal(0, 1, (1, 8, 1024, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 2, 1024, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 2, 1024, 64)), jnp.float32)
    def f():
        return ops.flash_attention(q, k, v,
                                   use_pallas=False).block_until_ready()
    f()
    csv.emit("kernels/attention_1k_us", timeit(f), "XLA ref; pallas=TPU target")
    # ssd chunked
    x = jnp.asarray(rng.normal(0, 1, (1, 1024, 8, 64)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (1, 1024, 8)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.1, 1, 8), jnp.float32)
    bm = jnp.asarray(rng.normal(0, 1, (1, 1024, 64)), jnp.float32)
    cm = jnp.asarray(rng.normal(0, 1, (1, 1024, 64)), jnp.float32)
    from repro.models.ssm import ssd_chunked
    def f():
        return ssd_chunked(x, dt, a, bm, cm, 128)[0].block_until_ready()
    f()
    csv.emit("kernels/ssd_1k_us", timeit(f), "XLA chunked path")


def run(csv: Csv, large: bool = False, quick: bool = False) -> dict:
    if quick:
        bench = refine_pipeline(csv, n=30_000, q=64)
        bench["knn"] = knn_pipeline(csv, n=30_000, q=64)
        print("BENCH " + json.dumps(bench))
        return bench
    n = min(scale_n(large), 200_000)
    bench = refine_pipeline(csv, n=min(n, 120_000))
    bench["knn"] = knn_pipeline(csv, n=min(n, 60_000), q=64)
    print("BENCH " + json.dumps(bench))
    device_batch_query(csv, n)
    kernels(csv)
    return bench
