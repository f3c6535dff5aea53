"""Distributed GLIN on a simulated 8-device mesh (4 data x 2 model).

Demonstrates the production layout from DESIGN.md §4: replicated learned
model, range-partitioned record table, query batch sharded over the model
axis — the same `glin_query_step` the 512-chip dry-run lowers.

    PYTHONPATH=src python examples/distributed_glin.py
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import time

import numpy as np
import jax

from repro.core import GLINConfig, SpatialIndex, generate, make_query_windows
from repro.core.distributed import build_glin_query_step, shard_glin_arrays


def main() -> None:
    from repro.utils.compat import make_auto_mesh
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    print(f"[dist] mesh {dict(mesh.shape)} over {mesh.devices.size} devices")

    gs = generate("cluster", 100_000, seed=0)
    index = SpatialIndex.build(gs, GLINConfig(piece_limitation=5_000))
    snap = index.snapshot()                  # current-epoch flattened index
    table_np = shard_glin_arrays(index.glin, 4)

    step, in_sh, out_sh = build_glin_query_step(mesh, "intersects", cap=32768)
    windows = make_query_windows(gs, 1e-4, 64, seed=1).astype(np.float32)

    with mesh:
        table = {k: jax.device_put(v, in_sh[2][k]) for k, v in table_np.items()}
        sd = jax.tree_util.tree_map(lambda x: jax.device_put(x, in_sh[0]), snap)
        w = jax.device_put(windows, in_sh[1])
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        hits, counts = fn(sd, w, table)          # compile
        t0 = time.time()
        for _ in range(5):
            hits, counts = fn(sd, w, table)
        jax.block_until_ready(counts)
        dt = (time.time() - t0) / 5

    counts = np.asarray(counts)
    assert (counts >= 0).all(), "cap overflow"
    per_shard = counts.sum(axis=0)
    print(f"[dist] {windows.shape[0]} queries in {dt*1e3:.1f} ms "
          f"({windows.shape[0]/dt:.0f} q/s)")
    print(f"[dist] hits per record-shard: {per_shard.tolist()} "
          f"(total {counts.sum()})")
    # cross-check one query against the host path of the facade
    q0 = np.sort(np.asarray(hits[0])[np.asarray(hits[0]) >= 0])
    host = index.query(windows[0].astype(np.float64), "intersects",
                       backend="host")
    print(f"[dist] query 0: {len(q0)} hits; host agrees: {len(host[0])} "
          f"(fp64 host may differ at window boundaries by design)")


if __name__ == "__main__":
    main()
