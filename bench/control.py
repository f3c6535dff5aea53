#!/usr/bin/env python3
"""The control of a cell's correctness check, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it makes the cell's store and requests as a run does, puts
the plain reference computed in bfloat16 (the nearest precision below the
device's float32) in the program's place, and compares its answers with
the float64 reference exactly as a run compares the program's: the same
sample size, the same numbers and limits. Each seed prints its numbers
beside their limits and whether the check fails, which it must.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control(cell, seed, sample, cache_dir=None):
    """Numbers compared for ``seed`` with the bfloat16 reference answering."""
    import numpy as np

    from bench.reference import PlainReference

    ss = np.random.SeedSequence(int(seed))
    data_ss, req_ss, _, check_ss = ss.spawn(4)
    base = cell.generator.generate(cell.config, cache_dir)
    req = cell.requests.Requests(base, cell.traffic,
                                 np.random.default_rng(req_ss))
    store = base.permuted(np.random.default_rng(data_ss).permutation(
        len(base)))
    ref = PlainReference(store)
    low = PlainReference(store, "bfloat16")
    pick = np.random.default_rng(check_ss).choice(
        int(cell.traffic["max_requests"]) // 2, sample, replace=False)
    payloads = [req.payload(int(i)) for i in pick]
    found = req.check(ref, payloads, [req.answer(low, p) for p in payloads])
    return {k: (found[k], lim) for k, lim in cell.requests.LIMITS.items()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sample", type=int, default=1024)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.Cell(ROOT, args.workload)
    for seed in args.seeds.split(","):
        got = control(cell, int(seed), args.sample, os.path.join(
            ROOT, harness.CACHE_DIR, harness.DATA_DIR))
        fails = any(v > lim for v, lim in got.values())
        print(json.dumps({"workload": args.workload, "seed": int(seed),
                          "control_fails": fails,
                          "numbers": {k: {"value": v, "limit": lim}
                                      for k, (v, lim) in got.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
