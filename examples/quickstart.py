"""GLIN quickstart: the ONE public API — build, query, maintain.

    PYTHONPATH=src python examples/quickstart.py

Everything goes through the ``SpatialIndex`` facade::

    from repro.core import SpatialIndex, QueryBatch, generate

    index = SpatialIndex.build(generate("cluster", 100_000, seed=0))
    res = index.query(windows, "intersects")     # 1 or 10k windows; host or
    ids0 = res[0]                                # device picked by the planner
    nn = index.query(QueryBatch.knn([[0.5, 0.5]], k=10))
    rec = index.insert(verts, nverts=8, kind=0)  # bumps the mutation epoch
    index.delete(rec)                            # snapshot rebuilt lazily

Relations: contains, intersects, within, covers, disjoint, touches, crosses
and the parametric ``dwithin:<d>`` (``repro.core.relations`` registry; exact
for concave polygons) — plus knn as a query kind.
"""
import numpy as np

from repro.core import (GLINConfig, QueryBatch, SpatialIndex, generate,
                        make_query_windows, relation_names)
from repro.core.relations import RELATIONS
from repro.utils.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. a synthetic "parks"-like dataset (100k convex polygons, metro clusters)
gs = generate("cluster", 100_000, seed=0)

# 2. build the learned index behind the facade (Zmin-sorted hierarchical model
#    + leaf MBRs + the piecewise augmentation function)
index = SpatialIndex.build(gs, GLINConfig(piece_limitation=10_000))
stats = index.stats()
print(f"index: {stats['nodes']} nodes, {stats['total_index_bytes']/1024:.0f} KiB "
      f"({stats['piecewise_pieces']} pieces), data {gs.nbytes()/2**20:.0f} MiB")

# 3. one entry point, every relation, batched: 5 windows x all relations
#    (parametric families like dwithin are bound by name: "dwithin:<d>")
windows = make_query_windows(gs, 0.001, 5, seed=1)
for relation in relation_names():
    if RELATIONS[relation].parametric:
        relation = f"{relation}:0.001"
    res = index.query(windows, relation, collect_stats=True)
    st = res.stats[0] if res.stats else None
    extra = (f", {st.checked} exact checks, {st.leaves_skipped} leaves "
             f"skipped by MBR pruning" if st else "")
    print(f"{relation:10s}: {res.total_hits} hits over {len(res)} windows "
          f"[{res.plan.backend}]{extra}")

# 4. big batches take the jitted device path automatically
big = np.repeat(windows, 64, axis=0)
res = index.query(big, "intersects")
print(f"batched   : {len(res)} windows -> {res.total_hits} hits "
      f"[{res.plan.backend}: {res.plan.reason}]")

# 5. knn is a query kind, not another API
nn = index.query(QueryBatch.knn([[0.5, 0.5]], k=10))
print(f"knn       : {len(nn.ids[0])} neighbours, "
      f"d_max={nn.distances[0].max():.4f}")

# 6. verify against brute force (the library's own oracle)
assert np.array_equal(index.query(windows[1], "intersects")[0],
                      np.sort(index.glin.query_bruteforce(windows[1],
                                                          "intersects")))

# 7. maintenance: insert a new polygon, delete an old record — the device
#    snapshot is epoch-invalidated and rebuilt lazily, never served stale
ang = np.sort(np.random.default_rng(7).uniform(0, 2 * np.pi, 8))
verts = np.stack([0.5 + 3e-4 * np.cos(ang), 0.5 + 3e-4 * np.sin(ang)], -1)
rec = index.insert(verts, 8, kind=0)
assert index.snapshot_is_stale()
hit = index.query(np.array([0.49, 0.49, 0.51, 0.51]), "intersects")
assert rec in hit[0]
assert index.delete(rec)
print(f"insert/delete ok (epoch {index.epoch}); quickstart done.")
