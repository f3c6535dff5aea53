"""Device-resident GLIN: flattened snapshot + jitted batch query path.

This is the TPU-native half of the adaptation (DESIGN.md §2): the host tree is
flattened into struct-of-arrays form and thousands of query windows are probed
*simultaneously* with pure array ops:

* model traversal  — bounded ``fori_loop`` of gathers over the flattened node
  table (equal-width routing in re-centred fp32; exactness restored by a ±2
  leaf fix-up against integer leaf-domain boundaries);
* leaf search      — fp32 linear model prediction + fixed-trip binary search
  whose window is the *device-side* max model error (recomputed in fp32 at
  snapshot time so the fp64→fp32 drop can never shrink the window);
* refinement       — fixed-capacity candidate tiles: leaf-MBR skip, record-MBR
  mask and exact-shape checks as masked vector ops.

Z-addresses are (hi, lo) int32 limb pairs throughout — no 64-bit integers.
Every public function is shape-polymorphic in the query batch and jittable.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import geometry as geom
from .model import InternalNode, LeafNode
from .relations import get_relation
from .zorder import (LO_LIMB_SIZE, mbr_to_zinterval_hilo, split_hilo_np,
                     z_less_hilo)

__all__ = ["GLINSnapshot", "HostCapture", "VertexPods", "pack_pods",
           "pods_from_store", "snapshot_capture", "snapshot_from_capture",
           "snapshot_from_host", "batch_probe", "batch_query_bounds",
           "batch_query", "batch_query_fused", "DeltaTable",
           "delta_table_from_host",
           "batch_check_added", "knn_seed_radii", "batch_knn_rank",
           "input_specs_like"]

_I32 = jnp.int32
_INF_HI = np.int32(2**30)  # > any valid 30-bit limb


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GLINSnapshot:
    """Flattened GLIN index as device arrays."""

    # sorted record table
    keys_hi: jax.Array      # (N,) int32
    keys_lo: jax.Array      # (N,) int32
    recs: jax.Array         # (N,) int32 record ids
    rec_leaf: jax.Array     # (N,) int32 leaf id of each slot
    # slot-aligned fp32 MBR tables (built once per publish): the refinement
    # mask streams/loads these directly instead of chaining
    # leaf_mbr[rec_leaf[slot]] / mbrs[recs[slot]] gathers per query
    slot_lmbr: jax.Array    # (N, 4) float32 leaf MBR of each slot
    slot_rmbr: jax.Array    # (N, 4) float32 record MBR of each slot
    # leaf tables (L leaves; +1 sentinel on boundaries)
    leaf_start: jax.Array   # (L+1,) int32 slot offsets
    leaf_dlo_hi: jax.Array  # (L+1,) int32 leaf domain lower bounds
    leaf_dlo_lo: jax.Array  # (L+1,) int32
    leaf_mbr: jax.Array     # (L, 4) float32 aggregate MBRs
    leaf_k0_hi: jax.Array   # (L,) int32 model re-centring key
    leaf_k0_lo: jax.Array   # (L,) int32
    leaf_slope: jax.Array   # (L,) float32
    leaf_icpt: jax.Array    # (L,) float32
    # flattened internal nodes
    node_dlo_hi: jax.Array  # (M,) int32
    node_dlo_lo: jax.Array  # (M,) int32
    node_scale: jax.Array   # (M,) float32  fanout / domain-width
    node_fanout: jax.Array  # (M,) int32
    node_child_base: jax.Array  # (M,) int32 into child_codes
    child_codes: jax.Array  # (C,) int32  >=0: internal node id; <0: -(leaf+1)
    # piecewise augmentation (suffix-min form)
    pw_zmax_hi: jax.Array   # (P,) int32
    pw_zmax_lo: jax.Array   # (P,) int32
    pw_sufmin_hi: jax.Array  # (P,) int32
    pw_sufmin_lo: jax.Array  # (P,) int32
    # static meta
    search_steps: int = dataclasses.field(metadata=dict(static=True))
    depth: int = dataclasses.field(metadata=dict(static=True))
    grid_x0: float = dataclasses.field(metadata=dict(static=True))
    grid_y0: float = dataclasses.field(metadata=dict(static=True))
    grid_cell: float = dataclasses.field(metadata=dict(static=True))

    @property
    def num_slots(self) -> int:
        return self.keys_hi.shape[0]

    @property
    def num_leaves(self) -> int:
        return self.leaf_mbr.shape[0]


# ---------------------------------------------------------------------------
# Width-bucketed vertex pods (device half of the CSR vertex pool)
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VertexPods:
    """Device-resident ragged geometry: one flat fp32 vertex pod pool plus
    per-record ``(off, nv)`` CSR addressing.

    Records are grouped by pow2 vertex-count bucket and each record's ring
    is padded (with its last valid vertex) to its bucket width, so every
    bucket is a contiguous run of equal-width, slot-aligned pods. Pod memory
    is <= 2x the tight ring total — independent of the widest geometry in
    the store, unlike the dense ``(N, V, 2)`` block it replaces.

    The exact-refine stage gathers survivors at the widest bucket PRESENT in
    the batch (``lax.switch`` over the static width ladder ``1, 2, ...,
    max_width``), not at the global max width: a batch of point/polyline
    survivors never pays a 64-vertex gather because one wide ring exists
    somewhere in the store.
    """

    pool: jax.Array    # (P, 2) float32 bucket-grouped padded pods
    off: jax.Array     # (N,) int32 pod start of each record
    nv: jax.Array      # (N,) int32 valid vertices of each record
    kd: jax.Array      # (N,) int32 GeomKind of each record
    bucket: jax.Array  # (N,) int32 pow2 bucket index (width = 1 << bucket)
    # static pow2 width ceiling; the branch ladder is 1 << (0..log2(max))
    max_width: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_records(self) -> int:
        return self.off.shape[0]

    @property
    def num_buckets(self) -> int:
        return int(math.log2(self.max_width)) + 1


def _pow2ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def pack_pods(pool: np.ndarray, offsets: np.ndarray, nverts: np.ndarray,
              kinds: np.ndarray, *, pad_records_to: int = 0,
              pool_pad_to: int = 0, max_width: int = 0,
              dtype=np.float32) -> dict:
    """Pack host CSR rings into the bucket-grouped pod layout (numpy).

    Returns ``{"pool", "off", "nv", "kd", "bucket", "max_width"}``; arrays
    are numpy so callers control the upload (replicated payload, per-shard
    slices, tests). Records beyond ``len(nverts)`` (up to ``pad_records_to``)
    are inert: ``off=0, nv=1, bucket=0`` — in-bounds reads, masked upstream.
    ``max_width`` forces a wider static ladder than the data needs (sticky
    jit-signature floors); ``pool_pad_to`` likewise floors the pod count.
    """
    nverts = np.asarray(nverts, np.int64)
    n = nverts.shape[0]
    maxw = _pow2ceil(max(int(nverts.max()) if n else 1, 1))
    if max_width:
        if max_width != _pow2ceil(max_width):
            raise ValueError(f"max_width must be a power of 2, got {max_width}")
        maxw = max(maxw, int(max_width))
    ladder = 1 << np.arange(int(math.log2(maxw)) + 1, dtype=np.int64)
    bucket = np.searchsorted(ladder, nverts).astype(np.int32)
    widths = ladder[bucket]
    order = np.argsort(bucket, kind="stable")   # bucket-grouped, stable
    w_seq = widths[order]
    start_seq = np.zeros(n, np.int64)
    if n:
        np.cumsum(w_seq[:-1], out=start_seq[1:])
    total = int(w_seq.sum())
    p = max(total, int(pool_pad_to), 1)
    pod = np.zeros((p, 2), dtype)
    if total:
        lane = np.arange(total) - np.repeat(start_seq, w_seq)
        src_rec = np.repeat(order, w_seq)
        src = (np.asarray(offsets, np.int64)[src_rec]
               + np.minimum(lane, nverts[src_rec] - 1))
        pod[:total] = pool[src]
    m = max(n, int(pad_records_to))
    off = np.zeros(m, np.int32)
    nv = np.ones(m, np.int32)
    kd = np.zeros(m, np.int32)
    bk = np.zeros(m, np.int32)
    off[order] = start_seq.astype(np.int32)
    nv[:n] = nverts
    kd[:n] = np.asarray(kinds)
    bk[:n] = bucket
    return {"pool": pod, "off": off, "nv": nv, "kd": kd, "bucket": bk,
            "max_width": maxw}


def pods_from_store(gs, pad_records_to: int = 0, pool_pad_to: int = 0,
                    max_width: int = 0) -> VertexPods:
    """Pack a GeometrySet's pool into device-resident :class:`VertexPods`."""
    p = pack_pods(gs.pool, gs.offsets, gs.nverts, gs.kinds,
                  pad_records_to=pad_records_to, pool_pad_to=pool_pad_to,
                  max_width=max_width)
    return VertexPods(pool=jnp.asarray(p["pool"]), off=jnp.asarray(p["off"]),
                      nv=jnp.asarray(p["nv"]), kd=jnp.asarray(p["kd"]),
                      bucket=jnp.asarray(p["bucket"]),
                      max_width=p["max_width"])


# ---------------------------------------------------------------------------
# Host tree -> capture -> snapshot
#
# The flatten is split in two so a republish can run OFF the caller's thread
# (engine double-buffering): ``snapshot_capture`` touches the live, mutable
# host structure (leaf list, node tree, piecewise arrays) and must be called
# synchronously with respect to insert/delete; ``snapshot_from_capture`` does
# the heavy O(N) numpy work and the device uploads on plain numpy copies (or
# append-immutable store arrays) and is safe to run on a background thread
# while writes keep mutating the host index.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HostCapture:
    """A consistent host-side flattening of the index at one epoch.

    ``keys``/``recs``/``starts``/``leaf_mbrs`` are fresh copies; the geometry
    store fields alias the store's live views, which are immutable once
    captured (the CSR pool only ever appends past the captured length, and
    growth/compaction replace the buffer rather than mutating it) — so the
    capture stays valid while the live index keeps mutating."""

    keys: np.ndarray        # (N,) int64 Zmin keys in slot order
    recs: np.ndarray        # (N,) int64 record ids in slot order
    starts: np.ndarray      # (L+1,) int64 leaf slot offsets
    leaf_mbrs: np.ndarray   # (L, 4) f64 aggregate leaf MBRs
    dlo_hi: np.ndarray      # (L+1,) int32 leaf domain bounds
    dlo_lo: np.ndarray
    k0_hi: np.ndarray       # (L,) int32 leaf model re-centring keys
    k0_lo: np.ndarray
    slope: np.ndarray       # (L,) float32
    icpt: np.ndarray        # (L,) float32
    node_dlo_hi: np.ndarray
    node_dlo_lo: np.ndarray
    node_scale: np.ndarray
    node_fanout: np.ndarray
    node_child_base: np.ndarray
    child_codes: np.ndarray
    depth: int
    pw_zmax_hi: np.ndarray
    pw_zmax_lo: np.ndarray
    pw_sufmin_hi: np.ndarray
    pw_sufmin_lo: np.ndarray
    grid_x0: float
    grid_y0: float
    grid_cell: float
    # geometry store at capture time (aliases; see class docstring)
    gs_mbrs: np.ndarray
    gs_pool: np.ndarray     # (P, 2) f64 CSR vertex pool (live view)
    gs_offsets: np.ndarray  # (N,) i64 ring starts into the pool
    gs_nverts: np.ndarray
    gs_kinds: np.ndarray
    num_records: int        # store length at capture time

    @property
    def num_leaves(self) -> int:
        return self.leaf_mbrs.shape[0]


def snapshot_capture(glin) -> HostCapture:
    """Flatten the live host tree into plain numpy (synchronous part).

    Also runs the store's pool compaction pass: records tombstoned since the
    last publish give their ring storage back here, where it's safe — the
    new snapshot's tree no longer references them, previously captured pool
    views are untouched (compaction replaces buffers), and device payloads
    key on the store's ``pool_version`` so they re-upload the slimmer pool.
    """
    glin.gs.compact()
    keys, recs, starts, mbrs = glin.all_leaf_arrays()
    leaves = glin.leaves
    L = len(leaves)

    dlos = np.array([lf.dlo for lf in leaves] + [leaves[-1].dhi if L else 1],
                    dtype=object)
    dlo_hi = np.array([int(d) >> 30 for d in dlos], np.int64).astype(np.int32)
    dlo_lo = np.array([int(d) & (LO_LIMB_SIZE - 1) for d in dlos], np.int32)

    k0_hi, k0_lo = split_hilo_np(
        np.array([lf.key0 for lf in leaves], np.int64))
    slope = np.array([lf.slope for lf in leaves], np.float32)
    icpt = np.array([lf.intercept for lf in leaves], np.float32)

    # Flatten internal nodes (BFS). A leaf root is wrapped in a fanout-1 node.
    leaf_ids = {id(lf): i for i, lf in enumerate(leaves)}
    root = glin.root
    if isinstance(root, LeafNode):
        wrapper = InternalNode(root.dlo, root.dhi, 1)
        wrapper.children[0] = root
        root = wrapper
    order = [root]
    index_of = {id(root): 0}
    qi = 0
    while qi < len(order):
        node = order[qi]
        qi += 1
        for c in node.children:
            if isinstance(c, InternalNode):
                index_of[id(c)] = len(order)
                order.append(c)
    M = len(order)
    n_dlo_hi = np.empty(M, np.int32)
    n_dlo_lo = np.empty(M, np.int32)
    n_scale = np.empty(M, np.float32)
    n_fan = np.empty(M, np.int32)
    n_base = np.empty(M, np.int32)
    codes = []
    depth = 1
    for i, node in enumerate(order):
        n_dlo_hi[i] = node.dlo >> 30
        n_dlo_lo[i] = node.dlo & (LO_LIMB_SIZE - 1)
        n_scale[i] = np.float32(node.fanout / float(node.dhi - node.dlo))
        n_fan[i] = node.fanout
        n_base[i] = len(codes)
        for c in node.children:
            if isinstance(c, InternalNode):
                codes.append(index_of[id(c)])
            else:
                codes.append(-(leaf_ids[id(c)] + 1))
    # tree depth for the fixed traversal trip count
    def _depth(node, d):
        nonlocal depth
        depth = max(depth, d)
        if isinstance(node, InternalNode):
            for c in node.children:
                _depth(c, d + 1)
    _depth(root, 1)

    # Piecewise function in suffix-min form (copied: pw mutates in place).
    if glin.pw is not None and glin.pw.num_pieces:
        pw = glin.pw
        pz_hi, pz_lo = split_hilo_np(np.array(pw.zmax_end, np.int64))
        ps_hi, ps_lo = split_hilo_np(pw.suffix_min().astype(np.int64))
    else:
        pz_hi = pz_lo = ps_hi = ps_lo = np.empty(0, np.int32)

    gs = glin.gs
    grid = gs.grid
    return HostCapture(
        keys=keys, recs=recs, starts=starts, leaf_mbrs=mbrs,
        dlo_hi=dlo_hi, dlo_lo=dlo_lo, k0_hi=k0_hi, k0_lo=k0_lo,
        slope=slope, icpt=icpt,
        node_dlo_hi=n_dlo_hi, node_dlo_lo=n_dlo_lo, node_scale=n_scale,
        node_fanout=n_fan, node_child_base=n_base,
        child_codes=np.asarray(codes, np.int32), depth=depth,
        pw_zmax_hi=pz_hi, pw_zmax_lo=pz_lo,
        pw_sufmin_hi=ps_hi, pw_sufmin_lo=ps_lo,
        grid_x0=float(grid.x0), grid_y0=float(grid.y0),
        grid_cell=float(grid.cell_size),
        gs_mbrs=gs.mbrs, gs_pool=gs.pool, gs_offsets=gs.offsets,
        gs_nverts=gs.nverts, gs_kinds=gs.kinds, num_records=len(gs),
    )


def snapshot_from_capture(c: HostCapture) -> GLINSnapshot:
    """Heavy O(N) flattening + device upload over a capture (thread-safe)."""
    keys, recs, starts = c.keys, c.recs, c.starts
    L = c.num_leaves
    k_hi, k_lo = split_hilo_np(keys)
    rec_leaf = np.repeat(np.arange(L, dtype=np.int32),
                         np.diff(starts).astype(np.int64))

    # Device-side max error: re-evaluate the fp32 model on every key so the
    # binary-search window provably brackets the answer on device.
    max_err = 1
    key_f = ((k_hi - c.k0_hi[rec_leaf]).astype(np.float32)
             * np.float32(LO_LIMB_SIZE)
             + (k_lo - c.k0_lo[rec_leaf]).astype(np.float32))
    pred = np.rint(c.slope[rec_leaf] * key_f
                   + c.icpt[rec_leaf]).astype(np.int64)
    local = np.arange(keys.shape[0], dtype=np.int64) - starts[rec_leaf]
    if keys.shape[0]:
        max_err = max(1, int(np.max(np.abs(pred - local))))
    search_steps = max(1, math.ceil(math.log2(2 * max_err + 4)))

    mbrs32 = c.leaf_mbrs.astype(np.float32)
    return GLINSnapshot(
        keys_hi=jnp.asarray(k_hi), keys_lo=jnp.asarray(k_lo),
        recs=jnp.asarray(recs.astype(np.int32)),
        rec_leaf=jnp.asarray(rec_leaf),
        slot_lmbr=jnp.asarray(mbrs32[rec_leaf] if L else
                              np.empty((0, 4), np.float32)),
        slot_rmbr=jnp.asarray(c.gs_mbrs[recs].astype(np.float32)),
        leaf_start=jnp.asarray(starts.astype(np.int32)),
        leaf_dlo_hi=jnp.asarray(c.dlo_hi), leaf_dlo_lo=jnp.asarray(c.dlo_lo),
        leaf_mbr=jnp.asarray(mbrs32),
        leaf_k0_hi=jnp.asarray(c.k0_hi), leaf_k0_lo=jnp.asarray(c.k0_lo),
        leaf_slope=jnp.asarray(c.slope), leaf_icpt=jnp.asarray(c.icpt),
        node_dlo_hi=jnp.asarray(c.node_dlo_hi),
        node_dlo_lo=jnp.asarray(c.node_dlo_lo),
        node_scale=jnp.asarray(c.node_scale),
        node_fanout=jnp.asarray(c.node_fanout),
        node_child_base=jnp.asarray(c.node_child_base),
        child_codes=jnp.asarray(c.child_codes),
        pw_zmax_hi=jnp.asarray(c.pw_zmax_hi),
        pw_zmax_lo=jnp.asarray(c.pw_zmax_lo),
        pw_sufmin_hi=jnp.asarray(c.pw_sufmin_hi),
        pw_sufmin_lo=jnp.asarray(c.pw_sufmin_lo),
        search_steps=search_steps, depth=c.depth,
        grid_x0=c.grid_x0, grid_y0=c.grid_y0, grid_cell=c.grid_cell,
    )


def snapshot_from_host(glin) -> GLINSnapshot:
    return snapshot_from_capture(snapshot_capture(glin))


# ---------------------------------------------------------------------------
# Batched probing
# ---------------------------------------------------------------------------
def _find_leaf(s: GLINSnapshot, q_hi: jax.Array, q_lo: jax.Array) -> jax.Array:
    """Model traversal (Alg 1 model_traversal), batched: (Q,) -> leaf ids."""

    def body(_, state):
        node, leaf, done = state
        dh = (q_hi - s.node_dlo_hi[node]).astype(jnp.float32)
        dl = (q_lo - s.node_dlo_lo[node]).astype(jnp.float32)
        key_f = dh * jnp.float32(LO_LIMB_SIZE) + dl
        cell_f = jnp.clip(jnp.floor(key_f * s.node_scale[node]), 0.0,
                          (s.node_fanout[node] - 1).astype(jnp.float32))
        cell = cell_f.astype(_I32)
        code = s.child_codes[s.node_child_base[node] + cell]
        is_leaf = code < 0
        new_leaf = jnp.where(is_leaf & ~done, -code - 1, leaf)
        new_node = jnp.where(is_leaf | done, node, code)
        return new_node, new_leaf, done | is_leaf

    q = q_hi.shape[0]
    node0 = jnp.zeros((q,), _I32)
    leaf0 = jnp.zeros((q,), _I32)
    done0 = jnp.zeros((q,), bool)
    _, leaf, _ = jax.lax.fori_loop(0, s.depth, body, (node0, leaf0, done0))

    # fp32 routing fix-up against exact integer leaf-domain boundaries.
    for _ in range(2):
        too_low = z_less_hilo(q_hi, q_lo, s.leaf_dlo_hi[leaf], s.leaf_dlo_lo[leaf])
        leaf = jnp.maximum(leaf - too_low.astype(_I32), 0)
        too_high = ~z_less_hilo(q_hi, q_lo, s.leaf_dlo_hi[leaf + 1],
                                s.leaf_dlo_lo[leaf + 1])
        leaf = jnp.minimum(leaf + too_high.astype(_I32), s.num_leaves - 1)
    return leaf


def model_window(s: GLINSnapshot, q_hi: jax.Array, q_lo: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Model traversal + leaf prediction -> global slot window [lo, hi)
    guaranteed to bracket lower_bound(q). Uses only the small replicated
    model tables (no record-level arrays)."""
    leaf = _find_leaf(s, q_hi, q_lo)
    start = s.leaf_start[leaf]
    end = s.leaf_start[leaf + 1]
    size = end - start

    key_f = ((q_hi - s.leaf_k0_hi[leaf]).astype(jnp.float32) * LO_LIMB_SIZE
             + (q_lo - s.leaf_k0_lo[leaf]).astype(jnp.float32))
    pred = jnp.rint(s.leaf_slope[leaf] * key_f + s.leaf_icpt[leaf]).astype(_I32)
    pred = jnp.clip(pred, 0, jnp.maximum(size - 1, 0))
    err = (1 << s.search_steps) // 2 + 2
    lo = jnp.maximum(pred - err, 0) + start
    hi = jnp.minimum(pred + err, size) + start
    return lo, hi


def lower_bound_in_window(keys_hi: jax.Array, keys_lo: jax.Array,
                          q_hi: jax.Array, q_lo: jax.Array,
                          lo: jax.Array, hi: jax.Array, steps: int) -> jax.Array:
    """Bounded binary search for the first key >= q within [lo, hi)."""

    def step(_, st):
        lo_i, hi_i = st
        live = lo_i < hi_i  # converged lanes must not move (clamped gathers)
        mid = (lo_i + hi_i) >> 1
        less = z_less_hilo(keys_hi[mid], keys_lo[mid], q_hi, q_lo) & live
        return jnp.where(less, mid + 1, lo_i), jnp.where(less | ~live, hi_i, mid)

    lo, hi = jax.lax.fori_loop(0, steps, step, (lo, hi))
    return lo


def batch_probe(s: GLINSnapshot, q_hi: jax.Array, q_lo: jax.Array) -> jax.Array:
    """Batched lower_bound: global slot of the first key >= query key."""
    lo, hi = model_window(s, q_hi, q_lo)
    return lower_bound_in_window(s.keys_hi, s.keys_lo, q_hi, q_lo, lo, hi,
                                 s.search_steps + 2)


def _augment(s: GLINSnapshot, q_hi, q_lo):
    """Suffix-min piecewise augmentation, batched (Alg 2 equivalent)."""
    p = s.pw_zmax_hi.shape[0]
    if p == 0:
        return q_hi, q_lo
    # binary search: first piece with zmax_end >= q
    lo = jnp.zeros_like(q_hi)
    hi = jnp.full_like(q_hi, p)
    steps = max(1, math.ceil(math.log2(p + 1)))

    def step(_, st):
        lo_i, hi_i = st
        mid = (lo_i + hi_i) >> 1
        less = z_less_hilo(s.pw_zmax_hi[mid], s.pw_zmax_lo[mid], q_hi, q_lo)
        return jnp.where(less, mid + 1, lo_i), jnp.where(less, hi_i, mid)

    lo, _ = jax.lax.fori_loop(0, steps, step, (lo, hi))
    in_range = lo < p
    idx = jnp.minimum(lo, p - 1)
    m_hi = jnp.where(in_range, s.pw_sufmin_hi[idx], _INF_HI)
    m_lo = jnp.where(in_range, s.pw_sufmin_lo[idx], 0)
    take = z_less_hilo(m_hi, m_lo, q_hi, q_lo)
    return jnp.where(take, m_hi, q_hi), jnp.where(take, m_lo, q_lo)


def _raw_query_keys(s: GLINSnapshot, windows: jax.Array, rel
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Window quantization WITHOUT the augmentation rewrite: (zmin, ub=
    zmax+1) hi/lo limbs; ``query_keys`` layers ``_augment`` on top."""
    from .zorder import ZGrid

    grid = ZGrid(s.grid_x0, s.grid_y0, s.grid_cell)
    # probe with the relation's (possibly padded) window; conservative fp32
    # quantization on top (never lose a candidate)
    (zmin_hi, zmin_lo), (zmax_hi, zmax_lo) = mbr_to_zinterval_hilo(
        rel.probe_window(windows, xp=jnp), grid,
        guard=ZGrid.FP32_GUARD_CELLS)
    carry = (zmax_lo + 1) >= LO_LIMB_SIZE
    ub_hi = zmax_hi + carry.astype(_I32)
    ub_lo = jnp.where(carry, 0, zmax_lo + 1)
    return zmin_hi, zmin_lo, ub_hi, ub_lo


def query_keys(s: GLINSnapshot, windows: jax.Array, relation: str
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Windows (Q,4) -> ((zmin', ub) hi/lo limbs): the probe key (augmented
    per the relation's rule) and the exclusive upper key zmax+1."""
    rel = _device_relation(relation)
    zmin_hi, zmin_lo, ub_hi, ub_lo = _raw_query_keys(s, windows, rel)
    if rel.augment:
        zmin_hi, zmin_lo = _augment(s, zmin_hi, zmin_lo)
    return zmin_hi, zmin_lo, ub_hi, ub_lo


def _device_relation(relation: str):
    """Registry lookup restricted to relations the batched path can serve."""
    rel = get_relation(relation)
    if not rel.device_native:
        raise ValueError(
            f"relation {relation!r} is not device-native (evaluate its base "
            f"relation {rel.base_name()!r} and finish on host — the "
            f"SpatialIndex facade does this automatically)")
    return rel


def batch_query_bounds(s: GLINSnapshot, windows: jax.Array,
                       relation: str = "contains"
                       ) -> Tuple[jax.Array, jax.Array]:
    """Windows (Q,4) float32 -> (start_slot, end_slot) per query."""
    zmin_hi, zmin_lo, ub_hi, ub_lo = query_keys(s, windows, relation)
    start = batch_probe(s, zmin_hi, zmin_lo)
    end = batch_probe(s, ub_hi, ub_lo)
    return start, end


def _exact_over(rel, windows: jax.Array, pods: VertexPods, rec: jax.Array,
                sel: jax.Array) -> jax.Array:
    """Exact predicates over gathered records ``rec`` (Q, M) -> bool.

    Gathers vertex pods at the widest pow2 bucket among the ``sel`` lanes:
    ``lax.switch`` over the static width ladder executes exactly one
    branch, so a batch whose survivors are all points/polylines never pays
    the widest ring's gather. Unselected lanes read real (clamped,
    in-bounds) data and are masked by the caller. Shared by every exact
    stage — ``batch_query``'s compaction paths, the dense path and
    ``batch_query_fused``."""
    off = pods.off[rec]
    nv = pods.nv[rec]
    kd = pods.kd[rec]
    b = jnp.max(jnp.where(sel, pods.bucket[rec], 0))

    def exact_for(w, vv, nn, kk):
        return rel.predicate(w, vv, nn, kk, xp=jnp)

    def branch(width):
        def run(off, nv, kd):
            lane = jnp.minimum(jnp.arange(width, dtype=_I32),
                               nv[..., None] - 1)
            idx = jnp.clip(off[..., None] + lane, 0,
                           pods.pool.shape[0] - 1)
            return jax.vmap(exact_for)(windows, pods.pool[idx], nv, kd)
        return run

    return jax.lax.switch(
        b, [branch(1 << i) for i in range(pods.num_buckets)], off, nv, kd)


def _sqdist_over(windows: jax.Array, pods: VertexPods, rec: jax.Array,
                 sel: jax.Array) -> jax.Array:
    """Exact squared window-to-geometry distances over gathered records
    ``rec`` (Q, M) -> f32: the distance twin of ``_exact_over``. Same
    widest-surviving-bucket pod gather (one ``lax.switch`` branch executes),
    with ``geometry.rect_geom_sqdist`` in place of the boolean predicate.
    Unselected lanes read real (clamped, in-bounds) data and are masked by
    the caller."""
    off = pods.off[rec]
    nv = pods.nv[rec]
    kd = pods.kd[rec]
    b = jnp.max(jnp.where(sel, pods.bucket[rec], 0))

    def dist_for(w, vv, nn, kk):
        return geom.rect_geom_sqdist(w, vv, nn, kk, xp=jnp)

    def branch(width):
        def run(off, nv, kd):
            lane = jnp.minimum(jnp.arange(width, dtype=_I32),
                               nv[..., None] - 1)
            idx = jnp.clip(off[..., None] + lane, 0,
                           pods.pool.shape[0] - 1)
            return jax.vmap(dist_for)(windows, pods.pool[idx], nv, kd)
        return run

    return jax.lax.switch(
        b, [branch(1 << i) for i in range(pods.num_buckets)], off, nv, kd)


def _exact_refine_compacted(rel, windows: jax.Array, s: GLINSnapshot,
                            pods: VertexPods, slots: jax.Array
                            ) -> Tuple[jax.Array, jax.Array]:
    """Exact-shape stage over compacted survivor slots (Q, kb) -> (hits,
    counts). Shared by the two-stage ``batch_query`` paths and the fused
    reference composition."""
    taken = slots >= 0
    slotc = jnp.maximum(slots, 0)
    rec = jnp.where(taken, s.recs[slotc], 0)
    fmask = taken & _exact_over(rel, windows, pods, rec, taken)
    hits = jnp.where(fmask, rec, -1)
    counts = fmask.sum(axis=1).astype(_I32)
    return hits, counts


def _compact_kernel_refine(rel, windows: jax.Array, s: GLINSnapshot,
                           pods: VertexPods, start: jax.Array,
                           end: jax.Array, kb: int, interpret: bool
                           ) -> Tuple[jax.Array, jax.Array]:
    """Probe runs -> the Pallas ``refine_compact`` kernel (capless: it
    streams the whole slot table in wide record tiles, ``compact_tile``)
    -> the shared exact stage. Overflow encodes the
    TOTAL survivor count (``-(survivors) - 1``), so the caller can size its
    budget ladder in one step."""
    from repro.kernels import ops

    if rel.prefilter_kind == "custom":
        raise ValueError(
            f"relation {rel.name!r} has a custom MBR prefilter; the compact "
            "kernel cannot evaluate it — use compaction='scan'")
    bounds = jnp.stack([start, end], axis=1)
    slots, mbr_counts = ops.refine_compact(
        rel.probe_window(windows, xp=jnp), bounds, s.slot_lmbr, s.slot_rmbr,
        budget=kb, prefilter=rel.prefilter_kind, interpret=interpret)
    hits, counts = _exact_refine_compacted(rel, windows, s, pods, slots)
    return hits, jnp.where(mbr_counts > kb, -mbr_counts - 1, counts)


@partial(jax.jit, static_argnames=("relation", "cap", "exact_budget",
                                   "compaction"))
def batch_query(s: GLINSnapshot, windows: jax.Array, pods: VertexPods,
                mbrs: jax.Array,
                relation: str = "contains", cap: int = 4096,
                exact_budget: int = 0, compaction: str = "scan"
                ) -> Tuple[jax.Array, jax.Array]:
    """Full two-step batched query.

    Returns ``(hits, counts)`` where ``hits`` is (Q, K) int32 record ids
    (-1 padded). ``cap`` bounds candidates per query; overflow is reported
    via negative counts, never silently. On the two-stage paths a negative
    count carries the exact need: ``-(run length) - 1`` when the slot run
    outgrew ``cap`` (scan/sort window stage 1 to the cap; the magnitude
    being > cap disambiguates), else ``-(TOTAL MBR survivors) - 1`` so the
    caller can grow its ``exact_budget`` ladder straight to a sufficient
    budget (``core.exec.OverflowLadder`` — the ONE escalation policy every
    backend's refine stage shares — does). On the single-stage dense path it
    encodes the truncated hit count and only signals that the slot run
    outgrew ``cap``.

    ``exact_budget`` > 0 enables TWO-STAGE refinement (beyond-paper, §Perf):
    stage 1 evaluates only the cheap interval + leaf-MBR + record-MBR masks;
    stage 2 compacts the survivors per query and runs exact-shape checks +
    vertex gathers on at most ``exact_budget`` candidates — the expensive
    (Q·cap·W) gather shrinks to (Q·budget·W), where W is the widest pow2
    vertex bucket among the batch's survivors (``VertexPods``), not the
    store's global max width. Budget overflow is signalled like cap
    overflow. ``compaction`` picks the stage-1 implementation:

    * ``"pallas"`` — the fused ``refine_compact`` kernel: interval + leaf-MBR
      + record-MBR mask with in-VMEM prefix-sum compaction over the whole
      slot table; only (Q, budget) slot ids reach HBM, no ``cap``-sized
      intermediate exists at all (``cap`` only bounds the dense path, and
      a budget at or past the cap still runs two-stage). Any budget: the
      kernel fills its output in 1024-column chunks. Always the compiled
      kernel, on every backend.
    * ``"interpret"`` — the same kernel body through Pallas interpret mode
      (the CPU correctness path for the kernel).
    * ``"scan"``   — jnp reference semantics: (Q, cap) candidate window from
      the probe run, masked via the slot-aligned MBR tables, compacted with
      a stable cumsum + scatter (no sort). The CPU/interpret parity path.
    * ``"sort"``   — the legacy stable-argsort compaction over chained
      ``leaf_mbr[rec_leaf[slot]]`` / ``mbrs[recs[slot]]`` gathers (kept for
      the old-vs-new refinement benchmark).
    """
    if compaction not in ("pallas", "interpret", "scan", "sort"):
        raise ValueError(f"unknown compaction {compaction!r}")
    rel = _device_relation(relation)
    start, end = batch_query_bounds(s, windows, relation)
    q = windows.shape[0]

    def exact_over(rec, sel):
        return _exact_over(rel, windows, pods, rec, sel)

    def exact_refine_compacted(slots):
        return _exact_refine_compacted(rel, windows, s, pods, slots)

    capless = compaction in ("pallas", "interpret")
    if exact_budget > 0 and (capless or exact_budget < cap):
        kb = exact_budget
        probe_w = rel.probe_window(windows, xp=jnp)
        if capless:
            return _compact_kernel_refine(rel, windows, s, pods, start, end,
                                          kb, compaction == "interpret")

        pos = start[:, None] + jnp.arange(cap, dtype=_I32)[None, :]
        valid = pos < jnp.minimum(end, start + cap)[:, None]
        posc = jnp.minimum(pos, s.num_slots - 1)
        if compaction == "scan":
            # no leaf-MBR gather: every record MBR lies inside its leaf's
            # aggregate MBR (grow-only maintenance), so the record prefilter
            # implies the leaf test — the streaming kernel keeps the leaf
            # stage because there it prunes for free, but a second (Q, cap,
            # 4) gather here would only re-derive a weaker mask
            rmbr = s.slot_rmbr[posc]
            rec_ok = rel.mbr_prefilter(rmbr, windows[:, None, :], xp=jnp)
            mask = valid & rec_ok
            # stable cumsum + scatter compaction (no argsort): survivor j of
            # row q lands in column (exclusive prefix of mask)[q, j]
            m32 = mask.astype(_I32)
            excl = jnp.cumsum(m32, axis=1) - m32
            col = jnp.where(mask & (excl < kb), excl, kb)
            slots = jnp.full((q, kb), -1, _I32).at[
                jnp.arange(q, dtype=_I32)[:, None], col
            ].set(posc, mode="drop")
            hits, counts = exact_refine_compacted(slots)
            surv = m32.sum(axis=1)
            runlen = end - start
            run_over = runlen > cap
            overflow = run_over | (surv > kb)
            # run overflow reports the run length (> cap, so callers can
            # tell it from a survivor count <= cap and grow the right knob)
            enc = jnp.where(run_over, runlen, surv)
            return hits, jnp.where(overflow, -enc - 1, counts)

        # "sort": legacy argsort compaction over chained gathers
        leaf = s.rec_leaf[posc]
        lmbr = s.leaf_mbr[leaf]
        leaf_ok = geom.mbr_intersects(lmbr, probe_w[:, None, :], xp=jnp)
        rec = s.recs[posc]
        rmbr = mbrs[rec]
        rec_ok = rel.mbr_prefilter(rmbr, windows[:, None, :], xp=jnp)
        mask = valid & leaf_ok & rec_ok
        order = jnp.argsort(~mask, axis=1, stable=True)[:, :kb]  # (Q, kb)
        sub_rec = jnp.take_along_axis(rec, order, axis=1)
        sub_mask = jnp.take_along_axis(mask, order, axis=1)
        fmask = sub_mask & exact_over(sub_rec, sub_mask)
        hits = jnp.where(fmask, sub_rec, -1)
        counts = fmask.sum(axis=1).astype(_I32)
        surv = mask.sum(axis=1)
        runlen = end - start
        run_over = runlen > cap
        overflow = run_over | (surv > kb)
        enc = jnp.where(run_over, runlen, surv)
        return hits, jnp.where(overflow, -enc - 1, counts)

    # single-stage dense path (exact_budget disabled or >= cap)
    pos = start[:, None] + jnp.arange(cap, dtype=_I32)[None, :]  # (Q, cap)
    valid = pos < jnp.minimum(end, start + cap)[:, None]
    posc = jnp.minimum(pos, s.num_slots - 1)
    lmbr = s.slot_lmbr[posc]                     # (Q, cap, 4)
    wq = windows[:, None, :]                     # (Q, 1, 4)
    # leaf-MBR pruning against the padded probe window (a dwithin hit's leaf
    # may not overlap the raw window); the record prefilter pads internally
    leaf_ok = geom.mbr_intersects(
        lmbr, rel.probe_window(windows, xp=jnp)[:, None, :], xp=jnp)
    rec = s.recs[posc]
    rmbr = s.slot_rmbr[posc]
    rec_ok = rel.mbr_prefilter(rmbr, wq, xp=jnp)
    mask = valid & leaf_ok & rec_ok
    mask = mask & exact_over(rec, mask)          # (Q, cap) pod gathers
    hits = jnp.where(mask, rec, -1)
    counts = mask.sum(axis=1).astype(_I32)
    overflow = (end - start) > cap
    counts = jnp.where(overflow, -counts - 1, counts)  # signal truncation
    return hits, counts


@partial(jax.jit, static_argnames=("relation", "exact_budget"))
def batch_query_fused(s: GLINSnapshot, windows: jax.Array, pods: VertexPods,
                      relation: str = "contains", exact_budget: int = 256
                      ) -> Tuple[jax.Array, jax.Array]:
    """Whole-table batched query as ONE XLA program: learned-index probe,
    a capless mask over EVERY slot, cumsum + per-row searchsorted
    compaction to ``(Q, exact_budget)`` survivors, and the exact stage.
    Bit-identical to ``batch_query(..., compaction="scan")``; it holds a
    ``(Q, N)`` mask, so it suits small stores (and is what the CPU
    benchmarks time), while the TPU runs ``batch_query(...,
    compaction="pallas")``, which streams the slot table through the
    compact kernel in the same single program.

    Returns ``(hits (Q, budget) i32 [-1 padded], counts (Q,) i32)``. The
    path is CAPLESS, so a negative count always means budget overflow and
    encodes the total MBR-survivor count ``-(survivors) - 1``
    (``core.exec.OverflowLadder.on_capless_overflow`` sizes the retry
    budget from it in one step, no disambiguating bounds probe needed)."""
    rel = _device_relation(relation)
    if rel.prefilter_kind == "custom":
        raise ValueError(
            f"relation {relation!r} has a custom MBR prefilter; the fused "
            "path cannot evaluate it — use the staged batch_query")
    if exact_budget <= 0:
        raise ValueError("the fused path is two-stage only: exact_budget "
                         "must be > 0")
    kb = exact_budget
    start, end = batch_query_bounds(s, windows, relation)

    # compaction is cumsum + per-row searchsorted for the k-th survivor
    # position — no (Q, N) scatter, which is what makes this composition
    # beat the (Q, cap)-windowed scan path on CPU as well
    probe_w = rel.probe_window(windows, xp=jnp)
    n = s.num_slots
    slot = jnp.arange(n, dtype=_I32)[None, :]
    in_run = (slot >= start[:, None]) & (slot < end[:, None])
    leaf_ok = geom.mbr_intersects(s.slot_lmbr[None, :, :],
                                  probe_w[:, None, :], xp=jnp)
    if rel.prefilter_kind == "contains":
        rec_ok = geom.mbr_contains(s.slot_rmbr[None, :, :],
                                   probe_w[:, None, :], xp=jnp)
    else:
        rec_ok = geom.mbr_intersects(s.slot_rmbr[None, :, :],
                                     probe_w[:, None, :], xp=jnp)
    mask = in_run & leaf_ok & rec_ok
    m32 = mask.astype(_I32)
    cum = jnp.cumsum(m32, axis=1)
    mbr_counts = cum[:, -1]
    kth = jnp.arange(1, kb + 1, dtype=_I32)
    pos = jax.vmap(
        lambda c: jnp.searchsorted(c, kth, side="left"))(cum)
    slots = jnp.where(pos < n, pos.astype(_I32), -1)
    hits, counts = _exact_refine_compacted(rel, windows, s, pods, slots)
    return hits, jnp.where(mbr_counts > kb, -mbr_counts - 1, counts)


# ---------------------------------------------------------------------------
# Delta side table: device-resident secondary index over the added set
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeltaTable:
    """Small device-resident secondary index over the added-set delta (the
    records inserted since the last snapshot publish), sorted by Zmin key.

    ``SpatialIndex`` builds one lazily per mutation epoch so ``device+delta``
    queries stop round-tripping to the host per batch: the added-set check
    becomes one vectorized (Q × A) z-interval + MBR + exact-predicate pass on
    device (``batch_check_added``). Rows are padded to a size bucket with
    inert entries (``ids == -1``, +inf keys, far-away MBRs) so the jitted
    check compiles once per bucket, not once per insert."""

    ids: jax.Array       # (A,) int32 record ids (-1 = padding), Zmin-sorted
    zmin_hi: jax.Array   # (A,) int32 z-interval lower key
    zmin_lo: jax.Array   # (A,) int32
    zmax_hi: jax.Array   # (A,) int32 z-interval upper key
    zmax_lo: jax.Array   # (A,) int32
    mbrs: jax.Array      # (A, 4) float32
    pool: jax.Array      # (P, 2) float32 CSR vertex pool over the added set
    off: jax.Array       # (A,) int32 ring starts (inert rows -> sentinel)
    nverts: jax.Array    # (A,) int32
    kinds: jax.Array     # (A,) int32
    # static pow2 ceiling of the added set's widths (dense-gather width)
    max_width: int = dataclasses.field(metadata=dict(static=True))

    @property
    def size(self) -> int:
        return self.ids.shape[0]


def delta_table_from_host(glin, added_ids, pad_to: int = 0) -> DeltaTable:
    """Build the added-set side table from the host index (one upload per
    publish epoch). ``added_ids`` is any iterable of record ids; rows are
    sorted by Zmin and padded to ``pad_to`` with inert entries."""
    ids = np.asarray(sorted(added_ids), np.int64)
    zmin = glin.zmin[ids] if ids.shape[0] else np.empty(0, np.int64)
    zmax = glin.zmax[ids] if ids.shape[0] else np.empty(0, np.int64)
    order = np.argsort(zmin, kind="stable")
    ids, zmin, zmax = ids[order], zmin[order], zmax[order]
    gs = glin.gs
    a = ids.shape[0]
    m = max(a, int(pad_to))
    pad = m - a
    zmin_hi, zmin_lo = split_hilo_np(zmin)
    zmax_hi, zmax_lo = split_hilo_np(zmax)
    out_ids = np.full(m, -1, np.int32)
    out_ids[:a] = ids
    mbrs = np.full((m, 4), 2e30, np.float32)      # intersects nothing
    nverts = np.ones(m, np.int32)
    kinds = np.zeros(m, np.int32)
    # CSR ring pool over the added set, with one far-away sentinel vertex
    # that every inert pad row points at (intersects nothing, dwithin fails)
    counts = gs.nverts[ids].astype(np.int64) if a else np.empty(0, np.int64)
    off = np.zeros(m, np.int32)
    # pow2-bucket the pool axis (with the row padding, the table's whole
    # shape signature), so the jitted added-set check compiles once per
    # bucket — NOT once per insert as the pool creeps one ring at a time
    total = int(counts.sum())
    pool_cap = 1 << max(6, total.bit_length())
    pool = np.full((pool_cap, 2), 2e30, np.float32)
    if a:
        starts = np.zeros(a, np.int64)
        np.cumsum(counts[:-1], out=starts[1:])
        pos = np.arange(total) - np.repeat(starts, counts)
        src = gs.offsets[ids]
        pool[:total] = gs.pool[np.repeat(src, counts) + pos]
        off[:a] = starts
        off[a:] = pool.shape[0] - 1               # the sentinel row
        mbrs[:a] = gs.mbrs[ids]
        nverts[:a] = gs.nverts[ids]
        kinds[:a] = gs.kinds[ids]
    else:
        off[:] = pool.shape[0] - 1
    max_width = _pow2ceil(int(counts.max()) if a else 1)

    def _padk(x, fill):
        return jnp.asarray(np.concatenate([x, np.full(pad, fill, np.int32)]))

    return DeltaTable(
        ids=jnp.asarray(out_ids),
        zmin_hi=_padk(zmin_hi, _INF_HI), zmin_lo=_padk(zmin_lo, 0),
        zmax_hi=_padk(zmax_hi, _INF_HI), zmax_lo=_padk(zmax_lo, 0),
        mbrs=jnp.asarray(mbrs), pool=jnp.asarray(pool),
        off=jnp.asarray(off), nverts=jnp.asarray(nverts),
        kinds=jnp.asarray(kinds), max_width=max_width)


@partial(jax.jit, static_argnames=("relation", "grid_x0", "grid_y0",
                                   "grid_cell"))
def batch_check_added(t: DeltaTable, windows: jax.Array, relation: str,
                      grid_x0: float, grid_y0: float, grid_cell: float
                      ) -> jax.Array:
    """Windows (Q,4) f32 × added-set table -> (Q, A) bool hit matrix.

    The z-interval prune mirrors the index mechanism: a window and a record
    whose MBRs intersect always have overlapping z-intervals (any shared
    cell's code lies inside both corner-code intervals), so pruning on
    ``[zmin_g, zmax_g] ∩ [zmin_q, zmax_q] != ∅`` never loses a hit and needs
    no piecewise augmentation over the (unpublished) added set."""
    from .zorder import ZGrid

    rel = _device_relation(relation)
    grid = ZGrid(grid_x0, grid_y0, grid_cell)
    probe = rel.probe_window(windows, xp=jnp)
    (qmin_hi, qmin_lo), (qmax_hi, qmax_lo) = mbr_to_zinterval_hilo(
        probe, grid, guard=ZGrid.FP32_GUARD_CELLS)
    lo_ok = ~z_less_hilo(t.zmax_hi[None, :], t.zmax_lo[None, :],
                         qmin_hi[:, None], qmin_lo[:, None])
    hi_ok = ~z_less_hilo(qmax_hi[:, None], qmax_lo[:, None],
                         t.zmin_hi[None, :], t.zmin_lo[None, :])
    cand = lo_ok & hi_ok & (t.ids[None, :] >= 0)
    pre = rel.mbr_prefilter(t.mbrs[None, :, :], windows[:, None, :], xp=jnp)

    # one dense ragged-view materialization of the (small) added set, shared
    # by every query row; inert pads read the far-away sentinel vertex
    verts = geom.ragged_padded(t.pool, t.off, t.nverts, t.max_width, xp=jnp)

    def exact_for(w):
        return rel.predicate(w, verts, t.nverts, t.kinds, xp=jnp)

    exact = jax.vmap(exact_for)(windows)
    return cand & pre & exact


# ---------------------------------------------------------------------------
# Device-complete kNN: CDF-seeded radii + exact-distance top-k ranking
# ---------------------------------------------------------------------------
_ID_PAD = np.int32(2**31 - 1)     # sorts after every real record id


@jax.jit
def knn_seed_radii(s: GLINSnapshot, windows: jax.Array, k: jax.Array
                   ) -> jax.Array:
    """CDF-seeded initial kNN radii: degenerate windows (Q, 4) -> (Q,) f32.

    The published learned index doubles as a density estimate (cf. "Spatial
    Interpolation-based Learned Index", PAPERS.md 2102.06789): each query
    point routes through the model to its leaf (``_find_leaf``); the leaf's
    record count over its aggregate-MBR area is the local intensity rho, and
    the expected k-th-neighbour distance of a planar process of intensity
    rho is ``sqrt(k / (pi * rho))``, offset by the point's distance to the
    leaf's aggregate MBR — a point routed to a leaf it doesn't touch (empty
    space between clusters) must at least REACH the data before density
    matters, so the gap keeps it from crawling the doubling ladder across
    the void. This is an ESTIMATE only — the growth ladder above it is the
    correctness backstop (an under-estimate costs extra rungs, never hits);
    the settlement test is always the exact within-radius count from
    :func:`batch_knn_rank`."""
    from .zorder import ZGrid

    grid = ZGrid(s.grid_x0, s.grid_y0, s.grid_cell)
    (zmin_hi, zmin_lo), _ = mbr_to_zinterval_hilo(
        windows, grid, guard=ZGrid.FP32_GUARD_CELLS)
    leaf = _find_leaf(s, zmin_hi, zmin_lo)
    count = (s.leaf_start[leaf + 1] - s.leaf_start[leaf]).astype(jnp.float32)
    m = s.leaf_mbr[leaf]
    area = jnp.maximum((m[:, 2] - m[:, 0]) * (m[:, 3] - m[:, 1]),
                       jnp.float32(1e-12))
    rho = jnp.maximum(count, 1.0) / area
    gx = jnp.maximum(jnp.maximum(m[:, 0] - windows[:, 0],
                                 windows[:, 0] - m[:, 2]), 0.0)
    gy = jnp.maximum(jnp.maximum(m[:, 1] - windows[:, 1],
                                 windows[:, 1] - m[:, 3]), 0.0)
    gap = jnp.sqrt(gx * gx + gy * gy)
    return gap + jnp.sqrt(k.astype(jnp.float32) / (jnp.float32(math.pi) * rho))


@partial(jax.jit, static_argnames=("k", "impl"))
def batch_knn_rank(windows: jax.Array, pods: VertexPods, hits: jax.Array,
                   radius: jax.Array, k: int, impl: str = "sort",
                   tombstones=None, delta=None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Device top-k over dwithin survivors: (Q, B) hit ids -> ((Q, k) ids,
    (Q, k) distances, (Q,) within-radius candidate counts).

    ``hits`` is the refine stage's -1-padded id matrix; exact distances come
    from ONE widest-surviving-bucket pod gather (``_sqdist_over``), so the
    candidate set never leaves the device — only the (Q, k) result does.

    Ordering is the shared ``geometry.rank_knn`` (distance, id) contract.
    The selection sorts SQUARED distances (monotonic in the distance, and
    one rounding step more precise): ``impl="sort"`` is the XLA reference —
    a two-key ``lax.sort`` over ``[d2, ids]`` (plain ``lax.top_k`` cannot
    tie-break on ids); ``impl="pallas"`` routes the same selection through
    the compiled ``kernels.refine.knn_topk_pallas`` partial-sort kernel (TPU
    target; worthwhile once B is large), identical ordering, and
    ``impl="interpret"`` runs that kernel through interpret mode.

    ``radius`` ((Q,) f32) is each point's OWN probe radius this rung — the
    caller probes every still-undone point in one dispatch at per-point
    inflated square windows, so the radius is per-row, not per-batch. The
    returned count is |{candidates with d2 <= radius^2}| over snapshot AND
    delta rows — compared in squared form, exactly the dwithin predicate's
    test, so the ladder's settlement rule (done once count >= k: dwithin
    candidacy is exact, no closer record can be missing) never over-counts.

    ``tombstones`` (T,) i32 masks deleted-but-published ids out of the
    ranking; ``delta`` (a :class:`DeltaTable`) merges the unpublished added
    set by exact distance before the top-k, so ``device+delta`` kNN ranks
    inserted records without a republish (added ids postdate snapshot ids —
    the two id sets never collide)."""
    q = windows.shape[0]
    inf = jnp.float32(jnp.inf)
    valid = hits >= 0
    rec = jnp.maximum(hits, 0)
    d2 = _sqdist_over(windows, pods, rec, valid)
    d2 = jnp.where(valid, d2, inf)
    ids = jnp.where(valid, hits, _ID_PAD)
    if tombstones is not None and tombstones.shape[0]:
        dead = (hits[:, :, None] == tombstones[None, None, :]).any(axis=2)
        d2 = jnp.where(dead, inf, d2)
        ids = jnp.where(dead, _ID_PAD, ids)
    if delta is not None:
        verts = geom.ragged_padded(delta.pool, delta.off, delta.nverts,
                                   delta.max_width, xp=jnp)
        ad2 = jax.vmap(lambda w: geom.rect_geom_sqdist(
            w, verts, delta.nverts, delta.kinds, xp=jnp))(windows)
        live = delta.ids[None, :] >= 0
        ad2 = jnp.where(live, ad2, inf)
        aid = jnp.where(live, delta.ids[None, :], _ID_PAD)
        d2 = jnp.concatenate([d2, ad2], axis=1)
        ids = jnp.concatenate(
            [ids, jnp.broadcast_to(aid, (q, delta.size))], axis=1)
    counts = (d2 <= (radius * radius)[:, None]).sum(axis=1).astype(_I32)
    if d2.shape[1] < k:                    # k > budget(+delta): pad columns
        padw = k - d2.shape[1]
        d2 = jnp.concatenate([d2, jnp.full((q, padw), inf)], axis=1)
        ids = jnp.concatenate([ids, jnp.full((q, padw), _ID_PAD, _I32)],
                              axis=1)
    if impl in ("pallas", "interpret"):
        from repro.kernels import ops

        d2k, idk = ops.knn_topk(d2, ids, k=k, interpret=impl == "interpret")
    else:
        d2s, idss = jax.lax.sort([d2, ids], num_keys=2)
        d2k, idk = d2s[:, :k], idss[:, :k]
    dk = jnp.sqrt(jnp.maximum(d2k, 0.0))
    idk = jnp.where(jnp.isinf(d2k), -1, idk)
    return idk, dk, counts


def input_specs_like(num_queries: int):
    """ShapeDtypeStruct stand-ins for a query batch (dry-run use)."""
    return {
        "windows": jax.ShapeDtypeStruct((num_queries, 4), jnp.float32),
    }
