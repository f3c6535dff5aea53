"""Render the roofline table from dry-run artifacts (EXPERIMENTS.md source),
plus the analytic roofline of the GLIN refinement kernels (``--kernels``).

Usage: PYTHONPATH=src python -m benchmarks.roofline_report [--mesh single]
       PYTHONPATH=src python -m benchmarks.roofline_report --kernels
"""
from __future__ import annotations

import argparse
import json
import pathlib

ART = pathlib.Path(__file__).resolve().parent / "artifacts" / "dryrun"

# Representative refinement shapes: (queries, slots, exact budget, gather
# width). The exact stage gathers per pow2 width-bucket from the vertex-pool
# pods, so the width term is the WIDEST SURVIVING bucket (pow2ceil of the
# ring width), not a store-wide dense padding.
KERNEL_SHAPES = [
    (512, 1 << 17, 256, 16),
    (4096, 1 << 20, 256, 16),
    (4096, 1 << 24, 512, 64),
]
# Mesh sizes for the sharded compact+refine variant (record shards)
KERNEL_SHARDS = (4, 16)


def kernel_rows(shapes=None, shards=KERNEL_SHARDS):
    """Roofline terms of the refinement pipeline from the analytic bytes/flops
    model in ``repro.kernels.refine.refine_cost`` — covering the compact
    kernel, the downstream exact-shape stage over the compacted survivors,
    the staged compact+refine pipeline sum, AND the sharded variant
    (``sharded_refine_cost``: per-shard compact+refine plus the cross-shard
    survivor all-gather bytes), matching what
    ``core.distributed.build_glin_query_step`` actually executes."""
    from repro.kernels.refine import refine_cost, sharded_refine_cost
    from repro.utils import roofline

    out = []
    for q, n, budget, verts in (shapes or KERNEL_SHAPES):
        shape = f"Q={q}/N={n}/budget={budget}"
        stages = {
            "count": refine_cost("count", q, n),
            "compact": refine_cost("compact", q, n, budget),
            "exact": refine_cost("exact", q, n, budget, verts=verts),
        }
        pipeline = {
            "flops": (stages["compact"]["flops"] + stages["exact"]["flops"]),
            "bytes_accessed": (stages["compact"]["bytes_accessed"]
                               + stages["exact"]["bytes_accessed"]),
        }
        stages["compact+refine"] = pipeline
        for s in shards:
            stages[f"sharded[{s}]"] = sharded_refine_cost(
                q, n, budget, shards=s, verts=verts)
        for stage, cost in stages.items():
            coll = cost.get("collective_bytes", 0.0)
            terms = roofline.roofline_terms(
                cost["flops"], cost["bytes_accessed"], coll, chips=1)
            detail = (
                f"flops={cost['flops']:.3g} bytes={cost['bytes_accessed']:.3g} "
                f"compute={terms['compute_s']*1e6:.3g}us "
                f"memory={terms['memory_s']*1e6:.3g}us ")
            if coll:
                detail += (f"allgather={coll:.3g}B "
                           f"coll={terms['collective_s']*1e6:.3g}us ")
            out.append((f"refine/{stage}/{shape}",
                        detail + f"dom={terms['dominant']}"))
    return out


def fmt(v, digits=3):
    return f"{v:.{digits}g}" if isinstance(v, (int, float)) else str(v)


def rows(mesh="single"):
    out = []
    for f in sorted(ART.glob(f"*__{mesh}.json")):
        r = json.loads(f.read_text())
        name = f"{r['arch']}/{r['shape']}"
        if r["status"] == "skip":
            out.append((name, "SKIP", r.get("reason", "")))
            continue
        if r["status"] != "ok":
            out.append((name, "FAIL", r.get("error", "")[:80]))
            continue
        ro = r["roofline"]
        mem = r["memory"].get("total_bytes_per_device", 0) / 2**30
        out.append((
            name, "ok",
            f"compute={ro['compute_s']:.4g}s memory={ro['memory_s']:.4g}s "
            f"coll={ro['collective_s']:.4g}s dom={ro['dominant']} "
            f"frac={ro['compute_fraction']:.3f} "
            f"useful={fmt(r.get('useful_flops_ratio'))} mem/dev={mem:.2f}GiB"))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--kernels", action="store_true",
                    help="analytic roofline of the GLIN refinement kernels "
                         "(count / compact / exact / compact+refine)")
    args = ap.parse_args()
    if args.kernels:
        for name, detail in kernel_rows():
            print(f"{name:44s} {detail}")
        return
    if args.markdown:
        print(markdown(args.mesh))
        return
    for name, status, detail in rows(args.mesh):
        print(f"{name:42s} {status:5s} {detail}")


def markdown(mesh="single"):
    """Markdown table for EXPERIMENTS.md §Roofline."""
    lines = [
        f"| arch/shape ({mesh}-pod) | compute_s | memory_s | coll_s | dominant "
        "| MODEL/HLO flops | mem/dev GiB | note |",
        "|---|---|---|---|---|---|---|",
    ]
    for f in sorted(ART.glob(f"*__{mesh}.json")):
        r = json.loads(f.read_text())
        name = f"{r['arch']}/{r['shape']}"
        if r["status"] == "skip":
            lines.append(f"| {name} | — | — | — | SKIP | — | — | "
                         f"{r.get('reason','')[:60]} |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {name} | — | — | — | FAIL | — | — | "
                         f"{r.get('error','')[:60]} |")
            continue
        ro = r["roofline"]
        mem = r["memory"].get("total_bytes_per_device", 0) / 2**30
        ur = r.get("useful_flops_ratio")
        lines.append(
            f"| {name} | {ro['compute_s']:.3g} | {ro['memory_s']:.3g} | "
            f"{ro['collective_s']:.3g} | {ro['dominant']} | "
            f"{ur:.3f} | {mem:.2f} | |" if ur is not None else
            f"| {name} | {ro['compute_s']:.3g} | {ro['memory_s']:.3g} | "
            f"{ro['collective_s']:.3g} | {ro['dominant']} | — | {mem:.2f} | |")
    return "\n".join(lines)


if __name__ == "__main__":
    main()
