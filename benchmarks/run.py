"""Benchmark orchestrator. One function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see common.Csv). GLIN benchmarks
mirror the paper's experiment suite (§IX); device/kernel benchmarks cover the
beyond-paper TPU-native path. Roofline artifacts are produced separately by
launch/dryrun.py and rendered by benchmarks/roofline_report.py.

``--quick`` is the CI bench-smoke mode: reduced scale, device + maintenance
+ sharded + serving + storage only, and the machine-readable ``BENCH`` dicts
are written to ``BENCH_device.json`` / ``BENCH_maintenance.json`` /
``BENCH_sharded.json`` / ``BENCH_serving.json`` / ``BENCH_storage.json``
in ``--bench-dir``
(default: the repo root — the committed perf trajectory;
``benchmarks.check_bench`` compares a fresh run against it).
"""
from __future__ import annotations

import argparse
import json
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--large", action="store_true",
                    help="paper-scale datasets (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma list: glin,device,maintenance,sharded,"
                         "serving,storage")
    ap.add_argument("--quick", action="store_true",
                    help="CI bench-smoke: reduced scale, write BENCH_*.json")
    ap.add_argument("--bench-dir", default=str(REPO_ROOT),
                    help="where --quick writes BENCH_*.json")
    args = ap.parse_args()

    from .common import Csv
    csv = Csv()
    default = ("device,maintenance,sharded,serving,storage" if args.quick
               else "glin,device,maintenance,sharded,serving,storage")
    which = set((args.only or default).split(","))
    bench_jsons = {}
    print("name,us_per_call,derived")
    if "sharded" in which:
        # first, while this process has not imported JAX: a CPU rehearsal
        # spawns fake-device children, which only a JAX-free parent may do;
        # on a TPU host the bench runs in this process
        from . import bench_sharded
        if args.quick:
            bench_jsons["sharded"] = bench_sharded.run(csv, n=20_000, q=48)
        else:
            bench_jsons["sharded"] = bench_sharded.run(csv, large=args.large)
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "glin" in which:
        from . import bench_glin
        bench_glin.run(csv, large=args.large)
    if "device" in which:
        from . import bench_device
        bench_jsons["device"] = bench_device.run(csv, large=args.large,
                                                 quick=args.quick)
    if "maintenance" in which:
        from . import bench_maintenance
        if args.quick:
            bench_jsons["maintenance"] = bench_maintenance.run(
                csv, n=20_000, rounds=8)
        else:
            bench_jsons["maintenance"] = bench_maintenance.run(
                csv, large=args.large)
    if "serving" in which:
        from . import bench_serving
        bench_jsons["serving"] = bench_serving.run(csv, large=args.large,
                                                   quick=args.quick)
    if "storage" in which:
        from . import bench_glin
        n_store = 20_000 if args.quick else (1_000_000 if args.large
                                             else 120_000)
        bench_jsons["storage"] = bench_glin.storage(csv, n_store)
    if args.quick:
        out_dir = pathlib.Path(args.bench_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        for name, payload in bench_jsons.items():
            if payload is None:             # skipped (printed why)
                continue
            path = out_dir / f"BENCH_{name}.json"
            path.write_text(json.dumps(payload, indent=1) + "\n")
            print(f"# wrote {path}")
    print(f"# {len(csv.rows)} measurements")


if __name__ == "__main__":
    main()
