#!/usr/bin/env python3
"""Bring-up smoke run of the GLIN query path on a TPU.

    python chip_smoke.py              # one chip: the main serving path
    python chip_smoke.py --chips 4    # four chips: sharded backend + replicas

One chip, through the entry points a user calls (``SpatialIndex.build``,
``SpatialQueryServer.start/submit/submit_knn/result``):

a deployment-size store (4M heavy-tailed ``mixed`` records, ~38M vertices)
served window batches of 64 (intersects, contains and dwithin at
selectivity 1e-4, within on tiny windows inside polygons, touches on
flush-edge windows) and one 64-point kNN batch at k=10, then a write burst
of 1,000 inserts and 1,000 deletes re-queried on ``device+delta``, then a
republish re-queried on ``device``, and one ``count_candidates`` call.

Four chips (``--chips 4``): the ``sharded`` backend over a (4, 1)
data x model mesh on a 16M-record store with the same window and kNN
traffic (a selectivity-1e-4 window there holds ~1,600 records, so the
overflow ladder grows the compact kernel's budget past one 1024-column
chunk); and a ``SpatialQueryServer`` with four replicas on a 1M-record
store, whose placements must sit on four distinct devices, each of which
must serve traffic, before and after a write burst.

The server runs each round's relation groups on concurrent workers, so the
cold compiles of a round overlap.

Every answer is checked against a plain NumPy reference that shares no
index code: an MBR prefilter over every record MBR, then the relation's
exact predicate (``core.relations``) on the candidate rings gathered from a
private copy of the CSR pool; kNN ranks exact point-to-geometry distances
(``geometry.rect_geom_sqdist``) by (distance, id). Any phase that raises,
any answer that differs, any host plan or host fallback, any batch the
server resolved as ``Rejected``, and any TPU default that resolved to a jnp
stand-in or interpret mode exits non-zero. The last stdout line is the JSON
result; everything else comes before it. The numbers printed are bring-up
observations, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

RELATIONS = ("intersects", "contains", "within", "touches")
DWITHIN = "dwithin:0.0009765625"          # 2^-10: exact in fp32 and fp64
K = 10
# stage notes that name a path other than the compiled TPU kernels
FORBIDDEN_NOTES = ("host", "scan", "sort", "reference", "interpret", "dense")


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(*a):
    print(*a, flush=True)


def fp32(a):
    return np.asarray(a, np.float32).astype(np.float64)


# --------------------------------------------------------------- data
def make_store(n, seed):
    """``mixed`` records with fp32-representable coordinates, rounded in the
    CSR pool itself (so the fp64 reference and the fp32 device decide on the
    same numbers), and MBRs recomputed from the rounded rings."""
    from repro.core.datasets import generate

    gs = generate("mixed", n, seed=seed)
    pool = gs.pool
    pool[:] = fp32(pool)                    # in place: a view of the store
    off, nv = gs.offsets, gs.nverts
    order = np.argsort(off, kind="stable")  # reduceat needs ascending starts
    starts = off[order]
    mbrs = np.empty((len(gs), 4))
    for c, red in ((0, np.minimum), (1, np.minimum), (2, np.maximum),
                   (3, np.maximum)):
        col = pool[:, c % 2]
        mbrs[order, c] = red.reduceat(col, starts)
    check((nv >= 1).all() and (np.diff(starts) == nv[order][:-1]).all(),
          "mixed store pool is not contiguous CSR")
    gs.mbrs = mbrs
    return gs


class PlainReference:
    """Brute-force answers over a private copy of the records."""

    def __init__(self, gs):
        self.pool = gs.pool.copy()
        self.off = gs.offsets.astype(np.int64).copy()
        self.nv = gs.nverts.astype(np.int64).copy()
        self.kinds = gs.kinds.copy()
        self.mbrs = gs.mbrs.copy()
        self.alive = np.ones(len(self.nv), bool)
        self._new = []                      # inserted (ring, kind), pending
        self._by_x = None                   # records sorted by MBR xmin

    def insert(self, ring, kind):
        self._new.append((np.asarray(ring, np.float64), int(kind)))
        return len(self.nv) + len(self._new) - 1

    def delete(self, rec):
        self._merge()
        self.alive[rec] = False

    def _merge(self):
        """Fold pending inserts into the arrays (once per write burst, not
        once per insert: the pool is hundreds of MB)."""
        if not self._new:
            return
        rings = [r for r, _ in self._new]
        nv = np.array([len(r) for r in rings], np.int64)
        self.off = np.concatenate(
            [self.off, self.pool.shape[0] + np.cumsum(nv) - nv])
        self.pool = np.concatenate([self.pool] + rings)
        self.nv = np.concatenate([self.nv, nv])
        self.kinds = np.concatenate(
            [self.kinds, np.array([k for _, k in self._new], np.int8)])
        self.mbrs = np.concatenate([self.mbrs, [
            (r[:, 0].min(), r[:, 1].min(), r[:, 0].max(), r[:, 1].max())
            for r in rings]])
        self.alive = np.concatenate([self.alive, np.ones(len(rings), bool)])
        self._new = []
        self._by_x = None

    def rings(self, ids):
        nv = self.nv[ids]
        w = max(int(nv.max()) if len(ids) else 1, 1)
        j = np.minimum(np.arange(w)[None, :], nv[:, None] - 1)
        return self.pool[self.off[ids][:, None] + j]

    def _x_range(self, lo, hi):
        """Ids of every record whose MBR may meet the x-range [lo, hi]: a
        slice of the records sorted by MBR xmin (xmin in [lo - widest
        record, hi]), so a query reads a sliver of a large store."""
        if self._by_x is None:
            order = np.argsort(self.mbrs[:, 0], kind="stable")
            widest = float((self.mbrs[:, 2] - self.mbrs[:, 0]).max())
            self._by_x = (order, self.mbrs[order, 0], widest)
        order, xmin, widest = self._by_x
        a = np.searchsorted(xmin, lo - widest, side="left")
        b = np.searchsorted(xmin, hi, side="right")
        return order[a:b]

    def window(self, w, relation):
        """Live ids satisfying ``relation`` against window ``w``, ascending."""
        from repro.core.relations import get_relation

        self._merge()
        rel = get_relation(relation)
        pad = rel.probe_pad
        ids = self._x_range(w[0] - pad, w[2] + pad)
        m = self.mbrs[ids]
        cand = np.sort(ids[
            self.alive[ids]
            & (m[:, 0] <= w[2] + pad) & (w[0] - pad <= m[:, 2])
            & (m[:, 1] <= w[3] + pad) & (w[1] - pad <= m[:, 3])])
        if not len(cand):
            return cand
        ok = rel.predicate(np.asarray(w, np.float64), self.rings(cand),
                           self.nv[cand], self.kinds[cand], xp=np)
        return cand[np.asarray(ok, bool)]

    def mbr_count(self, w):
        self._merge()
        ids = self._x_range(w[0], w[2])
        m = self.mbrs[ids]
        return int(((m[:, 0] <= w[2]) & (w[0] <= m[:, 2])
                    & (m[:, 1] <= w[3]) & (w[1] <= m[:, 3])
                    & self.alive[ids]).sum())

    def knn(self, p, k):
        """(ids, distances) of the k nearest live records by (distance, id).
        Candidates: records whose MBR lies within the k-th smallest
        first-vertex distance, an upper bound of the k-th true distance."""
        from repro.core import geometry as geom

        self._merge()
        live = np.nonzero(self.alive)[0]
        kk = min(k, len(live))
        # grow a square around p until it holds k live first vertices:
        # their k-th distance bounds the k-th true distance
        half = 1e-3
        while True:
            ids = self._x_range(p[0] - half, p[0] + half)
            ids = ids[self.alive[ids]]
            v0 = self.pool[self.off[ids]]
            near = (np.abs(v0 - p) <= half).all(axis=1)
            if near.sum() >= kk or len(ids) == len(live):
                break
            half *= 4.0
        ub2 = ((v0[near] - p) ** 2).sum(axis=1) if near.sum() >= kk else \
            ((self.pool[self.off[live]] - p) ** 2).sum(axis=1)
        bound = np.partition(ub2, kk - 1)[kk - 1]
        r = np.sqrt(bound)
        ids = self._x_range(p[0] - r, p[0] + r)
        ids = ids[self.alive[ids]]
        m = self.mbrs[ids]
        dx = np.maximum(np.maximum(m[:, 0] - p[0], p[0] - m[:, 2]), 0.0)
        dy = np.maximum(np.maximum(m[:, 1] - p[1], p[1] - m[:, 3]), 0.0)
        cand = np.sort(ids[dx * dx + dy * dy <= bound])
        rect = np.array([p[0], p[1], p[0], p[1]], np.float64)
        d2 = geom.rect_geom_sqdist(rect, self.rings(cand), self.nv[cand],
                                   self.kinds[cand], xp=np)
        order = np.lexsort((cand, d2))[:k]
        return cand[order], np.sqrt(d2[order])


def flush_edge_windows(gs, q, seed):
    """Windows whose right edge lies on a record MBR's left edge: random
    windows almost never *touch* a geometry exactly, these do."""
    rng = np.random.default_rng(seed + 11)
    recs = rng.choice(np.nonzero(gs.nverts > 1)[0], size=q, replace=False)
    m = gs.mbrs[recs]
    return fp32(np.stack([m[:, 0] - 1e-3, m[:, 1], m[:, 0], m[:, 3]], 1))


def inner_windows(gs, q, seed):
    """Tiny windows at the MBR centres of random polygons: selectivity
    windows are wider than any record, so ``within`` (the record contains
    the window) needs these to see hits."""
    rng = np.random.default_rng(seed + 13)
    recs = rng.choice(np.nonzero(gs.nverts >= 6)[0], size=q, replace=False)
    c = (gs.mbrs[recs, :2] + gs.mbrs[recs, 2:]) / 2.0
    return fp32(np.concatenate([c - 1e-6, c + 1e-6], 1))


def traffic(gs, q, seed):
    """{relation: (Q, 4) windows} plus the (Q, 2) kNN points; selectivity
    windows cover 1e-4 of the store."""
    from repro.core.datasets import make_query_windows

    wins = fp32(make_query_windows(gs, 1e-4, q, seed=seed))
    per_rel = {rel: wins for rel in RELATIONS}
    per_rel["within"] = inner_windows(gs, q, seed)
    per_rel["touches"] = flush_edge_windows(gs, q, seed)
    per_rel[DWITHIN] = wins
    return per_rel, fp32((wins[:, :2] + wins[:, 2:]) / 2.0)


# ------------------------------------------------------------ checking
def compare(name, got_windows, got_knn, ref, per_rel, pts):
    """Every answer equal to the plain reference. Returns match counts."""
    t_all = time.perf_counter()
    out = {}
    for rel, wins in per_rel.items():
        hits = 0
        for i, w in enumerate(wins):
            want = ref.window(w, rel)
            got = np.asarray(got_windows[rel][i], np.int64)
            check(np.array_equal(got, want),
                  f"{name}: {rel} window {i}: {len(got)} ids vs "
                  f"{len(want)} in the reference "
                  f"(missing {np.setdiff1d(want, got)[:8]}, "
                  f"extra {np.setdiff1d(got, want)[:8]})")
            hits += len(want)
        out[rel] = hits
    knn_ids = 0
    t0 = time.perf_counter()
    for i, p in enumerate(pts):
        want_i, want_d = ref.knn(p, K)
        got_i, got_d = got_knn[i]
        check(np.array_equal(np.asarray(got_i, np.int64), want_i),
              f"{name}: knn point {i}: ids {list(got_i)} vs {list(want_i)}")
        check(np.allclose(got_d, want_d, rtol=1e-5, atol=1e-7),
              f"{name}: knn point {i}: distances {got_d} vs {want_d}")
        knn_ids += len(want_i)
    if len(pts):
        out[f"knn(k={K})"] = knn_ids
    log(f"[{name}] reference checks {time.perf_counter() - t_all:.2f} s "
        f"(knn {time.perf_counter() - t0:.2f} s)")
    return out


def check_serving(name, srv, allowed_backends):
    st = srv.stats()
    log(f"[{name}] backend_counts={st['backend_counts']} "
        f"failed_batches={st['failed_batches']} shed={st['shed']}")
    check(st["failed_batches"] == 0,
          f"{name}: {st['failed_batches']} serve batches failed")
    check(st["shed"] == 0, f"{name}: {st['shed']} submissions shed")
    bad = set(st["backend_counts"]) - set(allowed_backends) - {"cache"}
    check(not bad, f"{name}: served by {sorted(bad)}, "
                   f"expected only {sorted(allowed_backends)}")
    check_stages(name, st["engine_stages"])


def check_stages(name, stages):
    for backend, per in stages.items():
        for stage, ent in per.items():
            log(f"[{name}] stage {backend}/{stage}: impl={ent['impl']} "
                f"calls={ent['calls']} dispatches={ent['dispatches']} "
                f"escalations={ent['escalations']} notes={ent['notes']}")
            check(backend != "host" and ent["impl"] != "host",
                  f"{name}: {backend}/{stage} ran on the host")
            for note in ent["notes"]:
                check(not any(f in note for f in FORBIDDEN_NOTES),
                      f"{name}: {backend}/{stage} took a non-kernel path: "
                      f"{note!r}")


def serve_round(srv, per_rel, pts):
    """Enqueue every request of a round before the pump starts, so each
    relation reaches the engine as ONE batch, then collect."""
    from repro.serve import Rejected

    srv.stop()
    tickets = {rel: [srv.submit(w, rel) for w in wins]
               for rel, wins in per_rel.items()}
    kt = [srv.submit_knn(p, K) for p in pts]
    t0 = time.perf_counter()
    srv.start()
    got = {rel: [srv.result(t, timeout=1200) for t in ts]
           for rel, ts in tickets.items()}
    knn = [srv.result(t, timeout=1200) for t in kt]
    dt = time.perf_counter() - t0
    srv.stop()
    for rel, vals in got.items():
        for v in vals:
            check(not isinstance(v, Rejected), f"{rel}: Rejected: {v}")
    for v in knn:
        check(not isinstance(v, Rejected), f"knn: Rejected: {v}")
    return got, knn, dt


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def write_burst(srv, ref, gs, n_ins, n_del, seed):
    """``n_ins`` inserts (rings of a fresh fp32 store) and ``n_del`` deletes
    of original records, mirrored into the reference."""
    rng = np.random.default_rng(seed + 23)
    donor = make_store(n_ins, seed + 101)
    for r in range(n_ins):
        ring = donor.ring(r)
        kind = int(donor.kinds[r])
        rec = srv.insert(ring, len(ring), kind)
        check(rec == ref.insert(ring, kind),
              f"insert id {rec} differs from the reference's")
    victims = rng.choice(len(gs) - n_ins, size=n_del, replace=False)
    for rec in victims:
        check(srv.delete(int(rec)), f"delete of live record {rec} failed")
        ref.delete(int(rec))


# -------------------------------------------------------------- phases
def engine_config(**kw):
    from repro.core.engine import EngineConfig

    # every batch size plans onto the device: a host plan fails the run
    return EngineConfig(device_min_batch=1, knn_device_min_batch=1,
                        stale_rebuild_min_batch=1, **kw)


def server_config(replicas=1):
    from repro.serve import ServerConfig

    # one worker per relation group of a round: cold compiles overlap
    return ServerConfig(replicas=replicas, max_batch=4096, max_workers=8)


def phase_store(name, n, seed, q, dev, writes, eng_kw):
    from repro.core.engine import QueryBatch, SpatialIndex
    from repro.core.index import GLINConfig
    from repro.serve import SpatialQueryServer

    t0 = time.perf_counter()
    gs = make_store(n, seed)
    ref = PlainReference(gs)
    t_data = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx = SpatialIndex.build(gs, GLINConfig(piece_limitation=10_000),
                             engine_config(**eng_kw))
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx.snapshot()
    idx._device_payload(idx._snapshot_recs)
    t_pub = time.perf_counter() - t0
    log(f"[{name}] store: {n} mixed records, {gs.pool.shape[0]} vertices; "
        f"set-up s: data={t_data:.2f} build={t_build:.2f} "
        f"publish={t_pub:.2f}")
    per_rel, pts = traffic(gs, q, seed)
    log(idx.explain(per_rel["intersects"], "intersects"))
    srv = SpatialQueryServer(idx, config=server_config())
    rounds = [("published", ("device",))]
    if writes:
        rounds += [("after writes", ("device+delta",)),
                   ("republished", ("device",))]
    for label, backends in rounds:
        if label == "after writes":
            t0 = time.perf_counter()
            write_burst(srv, ref, gs, writes, writes, seed)
            log(f"[{name}] write burst: {writes} inserts + {writes} deletes "
                f"in {time.perf_counter() - t0:.2f} s; delta="
                f"{idx.delta_size()}")
        if label == "republished":
            t0 = time.perf_counter()
            idx.snapshot()
            log(f"[{name}] republish {time.perf_counter() - t0:.2f} s")
        srv.backend_counts.clear()
        got, knn, dt = serve_round(srv, per_rel, pts)
        tag = f"{name}/{label}"
        check_serving(tag, srv, backends)
        matches = compare(tag, got, knn, ref, per_rel, pts)
        log(f"[{tag}] served in {dt:.2f} s (first round includes compiles); "
            f"matches vs reference: {matches}")
        if label == "published":
            # same traffic straight through the facade: warm, no cache
            t0 = time.perf_counter()
            for rel, wins in per_rel.items():
                idx.query(wins, rel)
            if len(pts):
                idx.query(QueryBatch.knn(pts, K))
            warm = time.perf_counter() - t0
            log(f"[{tag}] warm facade re-run {warm:.2f} s -> compile and "
                f"first-serve overhead ~{dt - warm:.2f} s")
    t0 = time.perf_counter()
    got = idx.count_candidates(per_rel["intersects"], "intersects")
    want = [ref.mbr_count(w) for w in per_rel["intersects"]]
    check(np.array_equal(np.asarray(got), want),
          f"{name}: count_candidates {list(got[:8])} vs {want[:8]}")
    log(f"[{name}] count_candidates: {int(np.sum(got))} MBR candidates "
        f"over {len(want)} windows, equal to the reference "
        f"({time.perf_counter() - t0:.2f} s)")
    log(f"[{name}] peak_bytes_in_use={peak_bytes(dev)}")
    return idx


def run_one_chip(seed, n=4_000_000, q=64, writes=1000, eng_kw=None):
    import jax

    phase_store("store", n, seed, q, jax.devices()[0], writes,
                dict(eng_kw or {}))


def warm_sharded(idx, relations, q, k):
    """Compile the sharded window step of every relation of the round and
    the kNN step concurrently, at the cap and budget their first ladder
    attempt uses: the queries run one at a time under the facade lock, so
    their cold compiles would otherwise add up one after another. A guess
    that misses only costs the query its own compile."""
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.relations import get_relation

    snap_repl, table, _, maxw = idx._sharded_placement()
    cfg = idx.config
    qb = 1 << (q - 1).bit_length()
    wj = jnp.zeros((qb, 4), jnp.float32)
    jobs = []
    for rel in relations:
        base = get_relation(rel).base_name()
        step = idx._sharded_step(base, idx._cap, cfg.exact_budget,
                                 idx._compaction(base), maxw)
        jobs.append((step, (snap_repl, wj, table)))
    step = idx._sharded_knn_step(k, idx._cap, cfg.exact_budget,
                                 idx._compaction("intersects"), maxw)
    jobs.append((step, (snap_repl, wj, jnp.ones((qb,), jnp.float32),
                        table)))
    with ThreadPoolExecutor(len(jobs)) as pool:
        list(pool.map(lambda j: j[0].lower(*j[1]).compile(), jobs))


def run_four_chips(seed, n_sharded=16_000_000, n_replica=1_000_000, q=64,
                   eng_kw=None):
    import jax

    from repro.core.engine import QueryBatch, SpatialIndex
    from repro.core.index import GLINConfig
    from repro.serve import SpatialQueryServer
    from repro.utils.compat import make_auto_mesh

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    eng_kw = dict(eng_kw or {})

    # sharded backend over a (4, 1) data x model mesh
    mesh = make_auto_mesh((4, 1), ("data", "model"), devices=devs[:4])
    t0 = time.perf_counter()
    gs = make_store(n_sharded, seed)
    ref = PlainReference(gs)
    t_data = time.perf_counter() - t0
    t0 = time.perf_counter()
    # a selectivity-1e-4 window holds ~1,600 records here: the budget is
    # sized to that (the ladder would grow a smaller one to the same size,
    # one cold compile of the sharded step per rung)
    idx = SpatialIndex.build(gs, GLINConfig(piece_limitation=10_000),
                             engine_config(mesh=mesh, exact_budget=4096,
                                           **eng_kw))
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx.snapshot()                          # publish; queries go sharded
    idx._sharded_placement()
    log(f"[sharded] store: {n_sharded} mixed records, {gs.pool.shape[0]} "
        f"vertices; set-up s: data={t_data:.2f} build={t_build:.2f} "
        f"publish={time.perf_counter() - t0:.2f}")
    per_rel, pts = traffic(gs, q, seed)
    log(idx.explain(per_rel["intersects"], "intersects"))
    t0 = time.perf_counter()
    warm_sharded(idx, list(per_rel), q, K)
    log(f"[sharded] compiled the round's sharded steps concurrently in "
        f"{time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    got = {}
    for rel, wins in per_rel.items():
        res = idx.query(wins, rel)
        check(res.plan.backend == "sharded",
              f"sharded: {rel} planned {res.plan.backend}")
        got[rel] = res.ids
    res = idx.query(QueryBatch.knn(pts, K))
    check(res.plan.backend == "sharded",
          f"sharded: knn planned {res.plan.backend}")
    knn = list(zip(res.ids, res.distances))
    dt = time.perf_counter() - t0
    check_stages("sharded", idx.stats()["stages"])
    matches = compare("sharded", got, knn, ref, per_rel, pts)
    log(f"[sharded] served in {dt:.2f} s (includes compiles); matches vs "
        f"reference: {matches}")
    for i, d in enumerate(devs[:4]):
        log(f"[sharded] device {i} peak_bytes_in_use={peak_bytes(d)}")
    del idx, gs, ref

    # four replicas behind the server's least-loaded router
    gs = make_store(n_replica, seed + 2)
    ref = PlainReference(gs)
    idx = SpatialIndex.build(gs, GLINConfig(piece_limitation=10_000),
                             engine_config(**eng_kw))
    srv = SpatialQueryServer(idx, config=server_config(replicas=4))
    snap = idx.snapshot()
    payload = idx._device_payload(idx._snapshot_recs)
    homes = []
    for r in range(4):
        rs, rp = idx._replica_view(r, snap, payload)
        places = {d for a in jax.tree_util.tree_leaves((rs, rp))
                  for d in a.devices()}
        check(len(places) == 1, f"replica {r} spans devices {places}")
        homes.append(places.pop())
    log(f"[replicas] placements: {[str(h) for h in homes]}")
    check(len(set(homes)) == 4, f"replicas share devices: {homes}")
    per_rel, pts = traffic(gs, q, seed + 2)
    for label, backends in (("published", ("device",)),
                            ("after writes", ("device+delta",))):
        if label == "after writes":
            write_burst(srv, ref, gs, 1000, 1000, seed + 2)
        srv.backend_counts.clear()
        before = list(srv.replica_queries)
        got, knn, dt = serve_round(srv, per_rel, pts)
        check_serving(f"replicas/{label}", srv, backends)
        matches = compare(f"replicas/{label}", got, knn, ref, per_rel, pts)
        served = [a - b for a, b in zip(srv.replica_queries, before)]
        check(all(v > 0 for v in served),
              f"replicas/{label}: not every replica served: {served}")
        log(f"[replicas/{label}] served in {dt:.2f} s; queries per replica "
            f"{served}; matches vs reference: {matches}")
    for i, d in enumerate(devs[:4]):
        log(f"[replicas] device {i} peak_bytes_in_use={peak_bytes(d)}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    log(f"devices: {len(devs)} x {devs[0].device_kind}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
