#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``. The run takes its store (the
configuration's records, in an order drawn from ``--seed``) and its
requests from ``--seed``, builds and publishes the index, warms up every
shape the cell's traffic reaches, drives ``SpatialQueryServer`` for
``--seconds``, builds the plain reference and compares a sample of the
answers with it, and prints one JSON object as its last line: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of a slice of the window. The numbers compared, each with
its limit, are the last lines of standard error and the ``checks`` key of
the result. Without a TPU (or with fewer chips than the cell asks for) it
exits with code 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is missing: no package under {src}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from bench import harness

    def log(*a):
        print(*a, flush=True)

    cell = harness.Cell(ROOT, args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), log=log,
                                  t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
