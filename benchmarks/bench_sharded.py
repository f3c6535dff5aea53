"""Sharded refinement benchmark: fused vs dense per-shard pipeline.

The ``sharded`` planner backend (``core.distributed``) runs the PR-4 fused
probe -> mask+compact -> exact-refine pipeline PER RECORD SHARD; before this
it materialized the dense ``(Q, cap)`` candidate window on every shard and
exact-checked all of it. This bench times both through the public facade —
``EngineConfig(exact_budget=256)`` (fused) vs ``exact_budget=0`` (the legacy
dense path, kept in ``build_glin_query_step`` as the baseline) — on a
host-device CPU mesh (``--xla_force_host_platform_device_count``), per
dataset x relation, asserting exactness against ``query_bruteforce`` every
run, and emits the ``BENCH {json}`` line committed as ``BENCH_sharded.json``.
Each mesh also times the device-complete knn tier (shard-local top-k + the
one-collective k-merge) on the cluster dataset, exact vs the fp64 host loop.

``run()`` measures the full matrix on a 4-way mesh and a cluster/intersects
confirmation on a 2-way mesh, and merges their BENCH payloads:

* on real devices (a TPU host) it runs every mesh in this process, over the
  devices present — one process holds the chips, and a child could not
  reach them;
* for a CPU rehearsal (``JAX_PLATFORMS=cpu``) from a process that has not
  imported JAX yet, it spawns one ``--inner`` child per mesh size with that
  many fake CPU devices (the device count is fixed per process).

    PYTHONPATH=src python -m benchmarks.bench_sharded [--n 30000]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
from typing import Optional

import numpy as np

from .common import Csv

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
SHARDED_BUDGET = 256
SHARDED_CAP = 4096
SHARDED_DATASETS = ("uniform", "cluster", "concave")
SHARDED_RELATIONS = ("intersects", "contains")


def _inner(csv: Csv, devices: int, n: int, q: int, full: bool) -> dict:
    """Runs inside the fake-device subprocess: one mesh, dense vs fused."""
    import jax

    from repro.core.datasets import generate, make_query_windows
    from repro.core.engine import EngineConfig, QueryBatch, SpatialIndex
    from repro.core.geometry import mbrs_of_verts
    from repro.core.index import GLIN, GLINConfig
    from repro.utils.compat import make_auto_mesh

    from .common import timeit

    assert jax.device_count() >= devices, (
        f"need {devices} devices, have {jax.device_count()} — a CPU run "
        "needs --xla_force_host_platform_device_count")
    mesh = make_auto_mesh((devices, 1), ("data", "model"),
                          devices=jax.devices()[:devices])

    def engine(budget: int) -> EngineConfig:
        return EngineConfig(mesh=mesh, shard_min_records=1,
                            initial_cap=SHARDED_CAP, exact_budget=budget)

    datasets = SHARDED_DATASETS if full else ("cluster",)
    relations = SHARDED_RELATIONS if full else ("intersects",)
    out: dict = {"devices": devices, "n": n, "q": q, "cap": SHARDED_CAP,
                 "budget": SHARDED_BUDGET, "backend": jax.default_backend(),
                 "datasets": {}}
    for name in datasets:
        # fp32-representable coordinates: fp64 query_bruteforce and fp32
        # sharded refinement then decide identically (exactness assertable)
        gs = generate(name, n, seed=0)
        gs.verts = gs.verts.astype(np.float32).astype(np.float64)
        gs.mbrs = mbrs_of_verts(gs.verts, gs.nverts)
        glin = GLIN.build(gs, GLINConfig(piece_limitation=10_000))
        fused = SpatialIndex(glin, engine(SHARDED_BUDGET))
        dense = SpatialIndex(glin, engine(0))
        wins = make_query_windows(gs, 0.0001, q, seed=2)
        wins = wins.astype(np.float32).astype(np.float64)
        out["datasets"][name] = {}
        for rel in relations:
            row: dict = {}
            ref_ids = None
            for impl, idx in (("dense", dense), ("fused", fused)):
                batch = QueryBatch.window(wins, rel, backend="sharded")

                def run(idx=idx, batch=batch):
                    return idx.query(batch)

                res = run()   # compile + settle the shared adaptive cap
                assert res.plan.backend == "sharded"
                row[f"{impl}_us"] = timeit(run, repeats=3)
                ids = list(res)
                if ref_ids is None:
                    ref_ids = ids
                    for qi in range(q):   # exactness vs the oracle, every run
                        bf = glin.query_bruteforce(wins[qi], rel)
                        np.testing.assert_array_equal(ids[qi], bf)
                    row["hits"] = int(sum(r.shape[0] for r in ids))
                else:
                    for a, b in zip(ids, ref_ids):   # impls agree exactly
                        np.testing.assert_array_equal(a, b)
                row[f"{impl}_cap"] = idx.device_cap
            row["speedup"] = row["dense_us"] / max(row["fused_us"], 1e-9)
            out["datasets"][name][rel] = row
            csv.emit(f"sharded/{devices}way/{name}/{rel}_us",
                     row["fused_us"],
                     f"dense={row['dense_us']:.0f}us;"
                     f"speedup=x{row['speedup']:.2f};exact=True")
        if name == "cluster":
            # device-complete knn over the mesh: shard-local top-k + the
            # one-collective k-merge, exact vs the fp64 host loop every run
            from repro.core.index import knn as host_knn
            kq = 10
            pts = (wins[:, :2] + wins[:, 2:]) / 2.0
            pts = pts.astype(np.float32).astype(np.float64)
            kb = QueryBatch.knn(pts, kq)

            def runk(idx=fused, kb=kb):
                return idx.query(kb)

            resk = runk()   # compile + settle
            assert resk.plan.backend == "sharded"
            knn_us = timeit(runk, repeats=3)
            for qi, p in enumerate(pts):
                hi, _ = host_knn(fused.glin, p, kq)
                np.testing.assert_array_equal(resk.ids[qi],
                                              np.asarray(hi, np.int64))
            stage = resk.stages[-1]
            out["knn"] = {"k": kq, "q": int(len(pts)), "knn_us": knn_us,
                          "merge_bytes": int(stage.merge_bytes),
                          "rungs": int(stage.rungs),
                          "seed_hits": int(stage.seed_hits), "exact": True}
            csv.emit(f"sharded/{devices}way/knn_us", knn_us,
                     f"k={kq};merge_bytes={stage.merge_bytes};"
                     f"rungs={stage.rungs};exact=True")
    return out


def _spawn_inner(csv: Csv, devices: int, n: int, q: int, full: bool) -> dict:
    """Run ``--inner`` in a subprocess with ``devices`` fake CPU devices and
    parse its CSV rows + BENCH payload off stdout. CPU rehearsal only: the
    child is pinned to the CPU backend and never needs an accelerator."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={devices}"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO_ROOT / "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, "-m", "benchmarks.bench_sharded", "--inner",
           "--devices", str(devices), "--n", str(n), "--q", str(q)]
    if full:
        cmd.append("--full")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=REPO_ROOT, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(
            f"bench_sharded inner ({devices} devices) failed:\n"
            f"STDOUT:\n{r.stdout[-4000:]}\nSTDERR:\n{r.stderr[-4000:]}")
    payload = None
    for line in r.stdout.splitlines():
        if line.startswith("BENCH "):
            payload = json.loads(line[len("BENCH "):])
        elif line.startswith("sharded/"):
            csv.rows.append(line)
            print(line, flush=True)
    if payload is None:
        raise RuntimeError("bench_sharded inner emitted no BENCH line")
    return payload


def run(csv: Csv, large: bool = False, n: int = 30_000,
        q: int = 64) -> Optional[dict]:
    """Fake-CPU-device children for a CPU rehearsal (``JAX_PLATFORMS=cpu``
    and JAX not yet imported here), else in-process over the real devices;
    ``None`` (skipped, with a printed reason) on fewer than two devices."""
    if large:
        n = max(n, 200_000)
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and "jax" not in sys.modules):
        meshes = {"4": _spawn_inner(csv, 4, n, q, full=True),
                  "2": _spawn_inner(csv, 2, n, q, full=False)}
    else:
        import jax

        from repro.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        if jax.device_count() < 2:
            print(f"# sharded bench skipped: it needs >= 2 devices and this "
                  f"process has {jax.device_count()} "
                  f"{jax.default_backend()} device(s) (for a CPU "
                  "rehearsal set JAX_PLATFORMS=cpu in a process that has "
                  "not imported JAX)", flush=True)
            return None
        meshes = {str(d): _inner(csv, d, n, q, full=(d == 4))
                  for d in (4, 2) if d <= jax.device_count()}
    main_mesh = max(meshes, key=int)
    speedups = [row["speedup"]
                for payload in meshes.values()
                for rels in payload["datasets"].values()
                for row in rels.values()]
    out = {
        "bench": "sharded_refine",
        "n": n,
        "q": q,
        "meshes": meshes,
        "speedup_cluster":
            meshes[main_mesh]["datasets"]["cluster"]["intersects"]["speedup"],
        "min_speedup": min(speedups),
    }
    csv.emit("sharded/min_fused_vs_dense_speedup", 0.0,
             f"x{out['min_speedup']:.2f}")
    print("BENCH " + json.dumps(out))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true",
                    help="run one mesh in-process (spawned by run())")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--n", type=int, default=30_000)
    ap.add_argument("--q", type=int, default=64)
    ap.add_argument("--full", action="store_true",
                    help="inner: full dataset x relation matrix")
    ap.add_argument("--large", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.inner:
        from repro.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        payload = _inner(Csv(), args.devices, args.n, args.q, args.full)
        print("BENCH " + json.dumps(payload))
    else:
        run(Csv(), large=args.large, n=args.n, q=args.q)


if __name__ == "__main__":
    main()
