"""Pallas TPU kernels: tiled GLIN refinement (mask / count / compact) and
the kNN top-k selection.

The paper's profile (§IX-D) shows refinement dominates query time. These
kernels evaluate the (query-window × record) MBR tests in VMEM tiles, fused
with the Z-interval slot test (``start <= slot < end``), so a (BQ × BN) tile
of candidates is disposed of per grid step without materializing gathers in
HBM. MBR tables stream as lane-dense (4, BN) tiles.

Entry points (all pad internally — arbitrary Q and N just work):

* ``refine_mask_pallas``    — full (Q, N) int8 mask.
* ``refine_count_pallas``   — (Q,) int32 match counts via grid-axis
  accumulation (selectivity estimation at device speed).
* ``refine_compact_pallas`` — the refinement front-end: fused interval +
  leaf-MBR + record-MBR mask with in-VMEM prefix-sum compaction. Emits the
  per-query compacted candidate slots ``(Q, budget)`` plus survivor counts,
  replacing both the dense ``(Q, cap)`` mask materialization and the
  ``O(Q·cap·log cap)`` argsort compaction in ``core.device.batch_query``:
  only ``Q·budget`` slot ids ever reach HBM, and the expensive exact-shape
  vertex gathers downstream shrink from ``(Q·cap·V)`` to ``(Q·budget·V)``.
  The one-dispatch query (``core.device.batch_query_fused``) runs this
  kernel between the probe and the exact stage inside one jit: the probe's
  binary searches and the exact stage's pod gathers index tables with
  per-lane dynamic indices, which Mosaic cannot lower inside a kernel.
* ``knn_topk_pallas``       — k-round partial selection for the kNN rank.

The kernels use no scatter, no gather and no cumsum (Mosaic lowers none of
them): prefix sums are one MXU product with a triangular ones matrix, and
compaction is a one-hot reduction.

``refine_cost`` is the analytic bytes/flops model of each kernel (used both
as the ``pl.CostEstimate`` handed to the compiler and by
``benchmarks/roofline_report.py --kernels``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 8
DEFAULT_BN = 512
COMPACT_BN = 256      # smaller record tiles: the one-hot scatter block is
                      # (budget, BN) per query row in VMEM
COMPACT_CHUNK = 1024  # output columns one pass of the compact kernel
                      # fills: its (chunk, BN) int32 one-hot block (1 MiB)
                      # must fit the scoped VMEM next to the streamed tiles.
                      # Larger budgets take one pass per chunk
_NEVER = 2e30         # padding MBR coordinate: intersects nothing


def _mbr_rows(ref):
    """(4, BN) lane-dense MBR tile -> its four (1, BN) coordinate rows."""
    m = ref[...]
    return m[0:1, :], m[1:2, :], m[2:3, :], m[3:4, :]


def _win_cols(ref):
    """(BQ, 4) window tile -> its four (BQ, 1) coordinate columns."""
    w = ref[...]
    return w[:, 0:1], w[:, 1:2], w[:, 2:3], w[:, 3:4]


def _intersects(w, m):
    """(BQ,1) window columns x (1,BN) MBR rows -> (BQ,BN) bool overlap."""
    return ((w[0] <= m[2]) & (m[0] <= w[2])
            & (w[1] <= m[3]) & (m[1] <= w[3]))


def _in_run(bounds_ref, nb, bn, shape):
    """Slot-run test: tile column c is slot ``nb*bn + c``; the run is the
    row's ``[start, end)`` from the (BQ, 2) bounds block."""
    b = bounds_ref[...]
    slot = nb * bn + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (slot >= b[:, 0:1]) & (slot < b[:, 1:2])


def _tile_mask(win_ref, mbr_ref, bounds_ref, nb, bn):
    """(BQ,4) windows x (4,BN) record MBRs -> (BQ,BN) bool."""
    inter = _intersects(_win_cols(win_ref), _mbr_rows(mbr_ref))
    return inter & _in_run(bounds_ref, nb, bn, inter.shape)


def _mask_kernel(win_ref, bounds_ref, mbr_ref, out_ref, *, bn):
    nb = pl.program_id(1)
    out_ref[...] = _tile_mask(win_ref, mbr_ref, bounds_ref, nb, bn).astype(jnp.int8)


def _count_kernel(win_ref, bounds_ref, mbr_ref, out_ref, *, bn):
    nb = pl.program_id(1)
    mask = _tile_mask(win_ref, mbr_ref, bounds_ref, nb, bn)
    partial_counts = mask.sum(axis=1, keepdims=True).astype(jnp.int32)

    @pl.when(nb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial_counts


def _compact_tile_mask(win_ref, lmbr_ref, rmbr_ref, bounds_ref, nb, bn,
                       prefilter):
    """Fused interval + leaf-MBR + record-MBR tests -> (BQ, BN) bool.

    ``win_ref`` holds the PROBE window (already padded for dwithin-style
    relations); ``prefilter`` selects the record-MBR test shape:
    "intersects" (record MBR meets the probe window) or "contains" (record
    MBR covers the window — the ``within`` prefilter)."""
    w = _win_cols(win_ref)
    rm = _mbr_rows(rmbr_ref)
    leaf_ok = _intersects(w, _mbr_rows(lmbr_ref))
    if prefilter == "contains":
        rec_ok = ((rm[0] <= w[0]) & (rm[1] <= w[1])
                  & (w[2] <= rm[2]) & (w[3] <= rm[3]))
    else:
        rec_ok = _intersects(w, rm)
    return leaf_ok & rec_ok & _in_run(bounds_ref, nb, bn, leaf_ok.shape)


def _exclusive_prefix(m32, bn):
    """Exclusive prefix sum along the lanes of a (BQ, BN) 0/1 tile, as one
    MXU product with the upper-triangular ones matrix (Mosaic has no
    cumsum). Exact: the 0/1 operands are exact in bf16, and the partial
    sums (<= BN) are exact in the f32 accumulator."""
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    tri = (row <= col).astype(jnp.bfloat16)
    incl = jnp.dot(m32.astype(jnp.bfloat16), tri,
                   preferred_element_type=jnp.float32)
    return incl.astype(jnp.int32) - m32


def _compact_kernel(win_ref, bounds_ref, lmbr_ref, rmbr_ref,
                    slots_ref, count_ref, *, bn, chunk, prefilter):
    """Grid step (i, c, j): mask the (BQ, BN) record tile j, then prefix-sum
    compact the survivors whose output column falls in chunk c —
    ``[c * chunk, (c + 1) * chunk)`` — into the revisited (BQ, chunk)
    output block.

    ``count_ref`` ((BQ, 1): a rank-1 (BQ,) block is neither the whole array
    nor a multiple of 128 lanes, which Mosaic refuses) carries the running
    per-query survivor count across the record axis (restarted for every
    chunk, so the last chunk leaves the total); a survivor's output column
    is that running count plus its exclusive within-tile prefix sum. The
    scatter itself is a one-hot reduction (TPU vector units have no
    scatter), one query row at a time over a (chunk, BN) block so every
    operand stays 2D, and runs only on tiles that put a survivor into this
    chunk. Survivors past the budget only advance the count — overflow is
    ``count > budget``, signalled to the caller, never silent truncation."""
    c = pl.program_id(1)
    nb = pl.program_id(2)

    @pl.when(nb == 0)
    def _init():
        slots_ref[...] = jnp.full_like(slots_ref, -1)
        count_ref[...] = jnp.zeros_like(count_ref)

    mask = _compact_tile_mask(win_ref, lmbr_ref, rmbr_ref, bounds_ref, nb, bn,
                              prefilter)
    m32 = mask.astype(jnp.int32)
    base = count_ref[...]                            # (BQ, 1)
    tile = m32.sum(axis=1, keepdims=True)
    count_ref[...] = base + tile
    lo = c * chunk
    lands = (tile > 0) & (base < lo + chunk) & (base + tile > lo)

    @pl.when(jnp.max(lands.astype(jnp.int32)) > 0)
    def _scatter():
        pos = base + _exclusive_prefix(m32, bn) - lo  # column in this chunk
        sel = mask & (pos >= 0) & (pos < chunk)
        lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1) + 1
        out_col = jax.lax.broadcasted_iota(jnp.int32, (chunk, bn), 0)
        # written[:, r] = 1 + the tile lane that lands in each output
        # column of query row r (0 where none does)
        written = jnp.concatenate(
            [jnp.where((out_col == pos[r:r + 1]) & sel[r:r + 1], lane1, 0)
             .sum(axis=1, keepdims=True) for r in range(mask.shape[0])],
            axis=1).T                                # (BQ, chunk)
        slots_ref[...] = jnp.where(written > 0, nb * bn + written - 1,
                                   slots_ref[...])


def _grids(q, n, bq, bn):
    """Grid over internally padded operand shapes (no divisibility demands)."""
    return (pl.cdiv(q, bq), pl.cdiv(n, bn))


def _pad_inputs(windows, bounds, bq, bn, *mbr_tables):
    """Pad Q to a multiple of bq and N to a multiple of bn, and lay the
    (N, 4) MBR tables out lane-dense as (4, N) (a (BN, 4) block would fill
    4 of 128 lanes). Padded MBR columns sit at ``_NEVER`` (intersect
    nothing, contain nothing); padded query rows get empty [0, 0) runs.
    Callers slice outputs back to (q, n)."""
    q, n = windows.shape[0], mbr_tables[0].shape[0]
    qp, np_ = (-q) % bq, (-n) % bn
    if qp:
        windows = jnp.pad(windows, ((0, qp), (0, 0)))
        bounds = jnp.pad(bounds, ((0, qp), (0, 0)))
    padded = []
    for m in mbr_tables:
        if np_:
            m = jnp.pad(m, ((0, np_), (0, 0)), constant_values=_NEVER)
        padded.append(m.T)
    return windows, bounds, padded


def refine_mask_pallas(windows: jax.Array, bounds: jax.Array, mbrs: jax.Array,
                       bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                       interpret: bool = False) -> jax.Array:
    """windows (Q,4) f32, bounds (Q,2) i32, mbrs (N,4) f32 -> (Q,N) int8."""
    q, n = windows.shape[0], mbrs.shape[0]
    windows, bounds, (mbrs,) = _pad_inputs(windows, bounds, bq, bn, mbrs)
    qp, np_ = windows.shape[0], mbrs.shape[1]
    out = pl.pallas_call(
        partial(_mask_kernel, bn=bn),
        grid=_grids(qp, np_, bq, bn),
        in_specs=[
            pl.BlockSpec((bq, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((4, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, np_), jnp.int8),
        cost_estimate=_cost_estimate("mask", qp, np_),
        interpret=interpret,
    )(windows, bounds, mbrs)
    return out[:q, :n]


def refine_count_pallas(windows: jax.Array, bounds: jax.Array, mbrs: jax.Array,
                        bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                        interpret: bool = False) -> jax.Array:
    """Same inputs -> (Q,) int32 match counts (reduction over the N grid axis,
    accumulated in the revisited output block)."""
    q = windows.shape[0]
    windows, bounds, (mbrs,) = _pad_inputs(windows, bounds, bq, bn, mbrs)
    qp, np_ = windows.shape[0], mbrs.shape[1]
    out = pl.pallas_call(
        partial(_count_kernel, bn=bn),
        grid=_grids(qp, np_, bq, bn),
        in_specs=[
            pl.BlockSpec((bq, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((4, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        cost_estimate=_cost_estimate("count", qp, np_),
        interpret=interpret,
    )(windows, bounds, mbrs)
    return out[:q, 0]


def refine_compact_pallas(windows: jax.Array, bounds: jax.Array,
                          leaf_mbrs: jax.Array, rec_mbrs: jax.Array,
                          budget: int, prefilter: str = "intersects",
                          bq: int = DEFAULT_BQ, bn: int = COMPACT_BN,
                          interpret: bool = False):
    """Fused mask + in-VMEM compaction.

    windows (Q,4) f32 PROBE windows, bounds (Q,2) i32 slot runs,
    leaf_mbrs/rec_mbrs (N,4) f32 slot-aligned MBR tables ->
    (slots (Q, budget) int32 [-1 padded, ascending slot order],
     counts (Q,) int32 TOTAL mask survivors — ``counts > budget`` means the
     compacted list is truncated and the caller must re-issue).
    """
    if prefilter not in ("intersects", "contains"):
        raise ValueError(f"unsupported prefilter {prefilter!r}")
    if budget < 1:
        raise ValueError(f"budget must be positive, got {budget}")
    q = windows.shape[0]
    # lane-aligned output columns, filled one chunk per pass over the
    # record tiles: any budget fits, VMEM holds one (chunk, bn) block
    bud = max(128, -(-budget // 128) * 128)
    chunk = min(bud, COMPACT_CHUNK)
    bud = -(-bud // chunk) * chunk
    windows, bounds, (leaf_mbrs, rec_mbrs) = _pad_inputs(
        windows, bounds, bq, bn, leaf_mbrs, rec_mbrs)
    qp, np_ = windows.shape[0], leaf_mbrs.shape[1]
    slots, counts = pl.pallas_call(
        partial(_compact_kernel, bn=bn, chunk=chunk, prefilter=prefilter),
        grid=(pl.cdiv(qp, bq), bud // chunk, pl.cdiv(np_, bn)),
        in_specs=[
            pl.BlockSpec((bq, 4), lambda i, c, j: (i, 0)),
            pl.BlockSpec((bq, 2), lambda i, c, j: (i, 0)),
            pl.BlockSpec((4, bn), lambda i, c, j: (0, j)),
            pl.BlockSpec((4, bn), lambda i, c, j: (0, j)),
        ],
        out_specs=(
            pl.BlockSpec((bq, chunk), lambda i, c, j: (i, c)),
            pl.BlockSpec((bq, 1), lambda i, c, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((qp, bud), jnp.int32),
            jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        ),
        cost_estimate=_cost_estimate("compact", qp, np_, bud),
        interpret=interpret,
    )(windows, bounds, leaf_mbrs, rec_mbrs)
    return slots[:q, :budget], counts[:q, 0]


def _knn_topk_kernel(d_ref, id_ref, outd_ref, outi_ref, *, k):
    """Deterministic k-round partial selection sort of one (BQ, B) tile.

    Round j extracts the minimum (distance, id) pair — the minimum distance,
    then the minimum id among its ties, matching the ``geometry.rank_knn``
    ordering contract — and retires exactly one lane holding it (the lowest
    such lane, so a repeated pair is emitted as often as ``lax.sort`` would
    emit it). The pair lands in output column j through an iota select on
    the (BQ, KP) accumulators, and the whole block is written once after the
    loop: Mosaic cannot store one lane at a dynamic column. O(k·B) work per
    query row: cheaper than a full O(B log B) sort whenever k << B (the
    large-budget regime this kernel targets)."""
    big = jnp.int32(2**31 - 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, id_ref.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, outd_ref.shape, 1)

    def round_(j, st):
        dw, iw, outd, outi = st
        m = jnp.min(dw, axis=1, keepdims=True)               # (BQ, 1)
        tie = dw == m
        mid = jnp.min(jnp.where(tie, iw, big), axis=1, keepdims=True)
        first = jnp.min(jnp.where(tie & (iw == mid), lane, big),
                        axis=1, keepdims=True)
        gone = lane == first
        here = col == j
        # a retired lane becomes a dead one, (inf, INT32_MAX), so rounds
        # past the live candidates emit exactly the sort's padding pairs
        return (jnp.where(gone, jnp.float32(jnp.inf), dw),
                jnp.where(gone, big, iw),
                jnp.where(here, m, outd), jnp.where(here, mid, outi))

    _, _, outd, outi = jax.lax.fori_loop(
        0, k, round_,
        (d_ref[...],          # (BQ, B) f32 squared distances, +inf padded
         id_ref[...],         # (BQ, B) i32 record ids, INT32_MAX padded
         jnp.full(outd_ref.shape, jnp.inf, jnp.float32),
         jnp.full(outi_ref.shape, big, jnp.int32)))
    outd_ref[...] = outd
    outi_ref[...] = outi


def knn_topk_pallas(d: jax.Array, ids: jax.Array, k: int,
                    bq: int = DEFAULT_BQ, interpret: bool = False):
    """Partial-sort top-k for the kNN ranking stage.

    d (Q, B) f32 squared distances (+inf = dead lane), ids (Q, B) i32 ->
    ((Q, k) f32 ascending distances, (Q, k) i32 ids), ordered by the shared
    (distance, id) contract — identical to ``lax.sort([d, ids],
    num_keys=2)`` truncated to k columns (``core.device.batch_knn_rank``'s
    reference impl). Pads internally: any Q, B and k work."""
    q, b = d.shape
    qp = -(-q // bq) * bq
    bp = max(128, -(-b // 128) * 128)
    kp = max(128, -(-k // 128) * 128)   # lane-aligned output block
    dp = jnp.full((qp, bp), jnp.inf, jnp.float32).at[:q, :b].set(
        d.astype(jnp.float32))
    ip = jnp.full((qp, bp), 2**31 - 1, jnp.int32).at[:q, :b].set(ids)
    outd, outi = pl.pallas_call(
        partial(_knn_topk_kernel, k=k),
        grid=(qp // bq,),
        in_specs=[
            pl.BlockSpec((bq, bp), lambda i: (i, 0)),
            pl.BlockSpec((bq, bp), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bq, kp), lambda i: (i, 0)),
            pl.BlockSpec((bq, kp), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((qp, kp), jnp.float32),
            jax.ShapeDtypeStruct((qp, kp), jnp.int32),
        ),
        cost_estimate=_cost_estimate("knn", qp, bp, k),
        interpret=interpret,
    )(dp, ip)
    return outd[:q, :k], outi[:q, :k]


# ---------------------------------------------------------------------------
# Analytic cost model (compiler CostEstimate + roofline_report --kernels)
# ---------------------------------------------------------------------------
def refine_cost(kind: str, q: int, n: int, budget: int = 0,
                verts: int = 0, bq: int = DEFAULT_BQ,
                bn: int = DEFAULT_BN) -> dict:
    """Bytes/flops model of one kernel invocation.

    ``kind``: "mask" | "count" | "compact" | "exact" | "knn" —
    "exact" models the downstream exact-shape refinement stage over the
    compacted (Q, budget) survivors, so the roofline report covers the full
    compact+refine pipeline, not just candidate counting; "knn" models the
    device top-k ranking stage (``knn_topk_pallas`` /
    ``core.device.batch_knn_rank``): exact-distance evaluation over ``n``
    candidate columns at gather width ``verts`` plus the k-round partial
    selection, where ``budget`` is k. ``verts`` is
    the gather width of the batch's widest surviving pow2 width-bucket (the
    vertex-pool pods gather per-bucket, see ``core.device.VertexPods``),
    NOT the store-wide dense padding — callers should pass ``pow2ceil`` of
    the surviving ring width they expect.
    """
    tiles_q = -(-q // bq)
    if kind == "exact":
        # per-bucket pod gather + predicate over compacted survivors:
        # verts = widest surviving bucket width, (verts, 2) f32 rings plus
        # the (off, nverts, kind, bucket) record header; ~40 flops per
        # vertex (edge clip + ray cast)
        bytes_accessed = q * budget * (verts * 8 + 16) + q * budget * 4
        flops = q * budget * verts * 40
        return {"flops": float(flops), "bytes_accessed": float(bytes_accessed),
                "transcendentals": 0}
    if kind == "knn":
        # exact-distance gather over n candidate columns (same per-pair cost
        # as the "exact" predicate) + the k-round partial selection (budget
        # here is k): each round scans the n-wide tile twice (min + tie mask)
        k = max(budget, 1)
        bytes_accessed = (q * n * (verts * 8 + 16)   # pod gather
                          + q * n * 8                # (d2, ids) tile
                          + q * k * 8)               # (Q, k) result
        flops = q * n * verts * 40 + q * k * n * 3.0
        return {"flops": float(flops), "bytes_accessed": float(bytes_accessed),
                "transcendentals": float(q * k)}     # sqrt on the k winners
    # streaming kernels: each query row-tile streams the full MBR table(s),
    # the compact kernel once per COMPACT_CHUNK output columns
    streams = (2 * -(-max(budget, 1) // COMPACT_CHUNK) if kind == "compact"
               else 1)
    bytes_accessed = tiles_q * n * 16 * streams + q * 24
    flops = q * n * 10.0          # interval + MBR comparisons per pair
    if kind == "mask":
        bytes_accessed += q * n   # int8 mask writeback
    elif kind == "count":
        bytes_accessed += tiles_q * bq * 4
    elif kind == "compact":
        flops += q * n * 6.0      # prefix sums
        flops += q * n * float(max(budget, 1)) * 2.0   # one-hot scatter
        bytes_accessed += q * (max(budget, 1) + 1) * 4
    else:
        raise ValueError(f"unknown kernel kind {kind!r}")
    return {"flops": float(flops), "bytes_accessed": float(bytes_accessed),
            "transcendentals": 0}


def sharded_refine_cost(q: int, n: int, budget: int, shards: int,
                        verts: int = 0, bq: int = DEFAULT_BQ,
                        bn: int = DEFAULT_BN) -> dict:
    """Per-device cost of the SHARDED compact+refine pipeline
    (``core.distributed.build_glin_query_step`` with ``exact_budget``).

    Each of ``shards`` devices streams its N/shards slice of the slot-aligned
    MBR tables through the compact stage, exact-refines its own ``(Q,
    budget)`` survivor block, and contributes the block + its survivor count
    to the cross-shard result gather — ``collective_bytes`` models that
    all-gather of ``(Q, shards, budget+1)`` int32 (the only cross-shard
    traffic; the dense path moved ``(Q, shards, cap)``)."""
    n_local = -(-n // max(shards, 1))
    c = refine_cost("compact", q, n_local, budget, bq=bq, bn=bn)
    e = refine_cost("exact", q, n_local, budget, verts=verts)
    return {
        "flops": c["flops"] + e["flops"],
        "bytes_accessed": c["bytes_accessed"] + e["bytes_accessed"],
        "transcendentals": 0,
        # every device receives the other shards' survivor blocks + counts
        "collective_bytes": float(q * shards * (budget + 1) * 4),
    }


def sharded_knn_cost(q: int, n: int, budget: int, k: int, shards: int,
                     verts: int = 0, bq: int = DEFAULT_BQ,
                     bn: int = DEFAULT_BN) -> dict:
    """Per-device cost of the SHARDED device-complete kNN rung
    (``core.distributed.build_glin_knn_step``): the local compact+refine
    over the shard's N/shards slice, the local exact-distance top-k over its
    ``(Q, budget)`` survivors, and the cross-shard k-merge.
    ``collective_bytes`` models the all-gather of the per-shard ``(Q, k)``
    (distance, id) blocks plus the (Q,) within-radius counts — the ONLY
    cross-shard traffic; the host merge it replaces moved the full
    ``(Q, shards, budget)`` candidate lists through the host."""
    n_local = -(-n // max(shards, 1))
    c = refine_cost("compact", q, n_local, budget, bq=bq, bn=bn)
    r = refine_cost("knn", q, budget, k, verts=verts, bq=bq, bn=bn)
    # merge: every device re-sorts the gathered (Q, shards*k) block
    merge_flops = q * shards * k * math.log2(max(shards * k, 2)) * 4.0
    return {
        "flops": c["flops"] + r["flops"] + merge_flops,
        "bytes_accessed": (c["bytes_accessed"] + r["bytes_accessed"]
                           + q * shards * k * 8.0),
        "transcendentals": r["transcendentals"],
        "collective_bytes": float(q * shards * (k * 8 + 4)),
    }


def _cost_estimate(kind: str, q: int, n: int, budget: int = 0):
    c = refine_cost(kind, q, n, budget)
    return pl.CostEstimate(flops=int(c["flops"]),
                           bytes_accessed=int(c["bytes_accessed"]),
                           transcendentals=0)
