#!/usr/bin/env python3
"""Record profiler traces of slices of one cell's window, and keep them.

    python3 bench/record.py --workload <cell> --seed <n> --seconds <s> \\
        --slices 3,1 --out <dir>

Set-up is a run's own (``harness.setup``) and the window is driven as a
``--trace 1`` run drives it. From 30% into the window the slices are traced
one after another, each for its seconds; each trace is written gzipped to
``<dir>/slice<i>.xplane.pb.gz`` and one JSON line per slice gives its
reductions (``bench/trace.py``, ``bench/spans.py``) and the deltas of the
server's counters over it. This is how a trace fixture of the tests and the
breakdown of PERF.md are made; it checks no answer.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("served_batches", "cache_misses", "queue_wait_ms",
            "queue_waited", "plans", "plan_ms")


def _deltas(s0, s1):
    out = {k: s1[k] - s0[k] for k in COUNTERS if k in s0}
    for backend, per in s1["engine_stages"].items():
        for stage, ent in per.items():
            was = s0["engine_stages"].get(backend, {}).get(stage, {})
            out[f"{backend}/{stage}"] = {
                k: v - was.get(k, 0) for k, v in ent.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
    return out


def record(cell, seed, seconds, slices, out, log=print, require_tpu=True):
    """Drive ``cell``'s window and trace ``slices`` of it into ``out``;
    returns one summary per slice."""
    import jax

    from bench import harness, spans, trace
    from bench.work import compact

    su = harness.setup(cell, seed, log, T_START, require_tpu)
    server, done = su.server, []

    def tracer():
        time.sleep(max(0.0, t0 + 0.3 * seconds - time.perf_counter()))
        for i, length in enumerate(slices):
            path = os.path.join(out, f"slice{i}")
            cap = trace.Capture(path)
            s0 = server.stats()
            cap.start()
            time.sleep(length)
            s1 = server.stats()
            cap.stop()
            done.append((i, cap, s0, s1))

    os.makedirs(out, exist_ok=True)
    t0 = time.perf_counter()
    th = threading.Thread(target=tracer, daemon=True)
    th.start()
    cell.loop.drive(server, harness._Offset(su.requests, 0, su.half),
                    cell.traffic, seconds, jax.profiler.TraceAnnotation,
                    harness.GRACE_S)
    th.join()
    server.stop()
    summaries = []
    for i, cap, s0, s1 in done:
        (pb,) = [os.path.join(d, f) for d, _, fs in os.walk(cap.path)
                 for f in fs if f.endswith(".xplane.pb")]
        with open(pb, "rb") as f, gzip.open(
                os.path.join(out, f"slice{i}.xplane.pb.gz"), "wb") as g:
            shutil.copyfileobj(f, g)
        summaries.append({
            "slice": i, "seconds": cap.seconds,
            "trace": trace.reduce(trace.load(cap.path), cap.seconds,
                                  kernels=[compact.TRACE_NAME]),
            "spans": spans.reduce(spans.load(cap.path)),
            "counters": _deltas(s0, s1)})
        shutil.rmtree(cap.path, ignore_errors=True)
    return summaries


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slices", default="3")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    slices = [float(s) for s in args.slices.split(",")]
    cell = harness.Cell(ROOT, args.workload)
    try:
        summaries = record(cell, args.seed, args.seconds, slices, args.out,
                           log=lambda *a: print(*a, flush=True))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for s in summaries:
        print(json.dumps(s), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
