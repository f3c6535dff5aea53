"""Distributed GLIN — the paper's index scaled over a TPU pod mesh.

Layout (DESIGN.md §4):

* the **learned model** (flattened node table, leaf models, leaf MBRs,
  piecewise suffix-min) is tiny — KBs to a few MBs (paper Fig 8 / Tab V) — and
  is **replicated** on every device;
* the **record table** (sorted Zmin limbs, record MBRs, packed vertex rings)
  is **range-partitioned by slot** over the ``data`` (and ``pod``) mesh axes;
* **query batches are sharded over the ``model`` axis** — each model-column
  owns Q/16 windows, each data-row owns N/16 records, so a (16,16) pod
  evaluates 256 query×record tiles fully in parallel with zero collectives in
  the probe/refine path (results stay sharded; a count ``psum`` is optional).

``glin_query_step`` is built with ``shard_map`` so the per-device block logic
is explicit, and is what the multi-pod dry-run lowers (launch/dryrun.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import geometry as geom
from .device import (GLINSnapshot, HostCapture, lower_bound_in_window,
                     model_window, query_keys, snapshot_capture)
from .relations import get_relation
from .zorder import LO_LIMB_SIZE
from repro.utils.compat import shard_map as compat_shard_map

__all__ = ["shard_glin_arrays", "shard_arrays_from_capture",
           "build_glin_query_step", "build_glin_knn_step",
           "glin_input_specs", "GLIN_MODEL_SPEC", "TABLE_KEYS"]

_I32 = jnp.int32
_NEVER = 2e30          # padding MBR coordinate: intersects/contains nothing

# Replicated model pytree spec (everything in GLINSnapshot is replicated; the
# big sorted arrays travel separately, sharded).
GLIN_MODEL_SPEC = P()

# Slot-ordered record-table keys sharded over the data axes. ``lmbrs`` /
# ``mbrs`` are the slot-aligned leaf / record MBR tables the fused
# mask+compact stage streams (the sharded analogue of the snapshot's
# ``slot_lmbr`` / ``slot_rmbr``). Vertices travel as PER-SHARD POOL SLICES:
# ``vpool`` is each shard's local CSR vertex pool (equal length across
# shards), ``voff`` the slot-aligned offsets INTO THAT LOCAL SLICE, and
# ``vbucket`` each slot's pow2 width-bucket index — the exact-refine stage
# gathers only the widest surviving bucket's width, never a global ``V``.
TABLE_KEYS = ("keys_hi", "keys_lo", "recs", "rec_leaf", "lmbrs", "mbrs",
              "vpool", "voff", "vbucket", "nverts", "kinds")

# per-shard pool slices are padded to this slot quantum so append-driven
# growth between publishes rarely changes the sharded jit signature
_POOL_QUANTUM = 1024


def shard_arrays_from_capture(c: HostCapture, num_shards: int,
                              pool_pad_to: int = 0) -> Dict[str, np.ndarray]:
    """Slot-ordered record payloads from a host capture, padded to
    ``num_shards``. Padding slots carry +inf keys, ``recs == -1`` and
    ``_NEVER`` MBRs (they intersect and contain nothing), so neither
    prefilter shape can ever pick one up; their vertex pointers are inert
    ``(voff=0, nverts=1)``.

    Each shard's records' rings are gathered into a LOCAL vertex pool in
    slot order; every local pool is padded (zeros) to one common length —
    ``max(tightest shard, pool_pad_to)`` rounded up to ``_POOL_QUANTUM`` —
    so the concatenated ``vpool`` shards evenly. The caller can pass the
    previous publish's per-shard length as ``pool_pad_to`` to keep the
    sharded jit signature stable across (compacting) republishes."""
    keys, recs = c.keys, c.recs
    n = keys.shape[0]
    pad = (-n) % num_shards
    local_n = (n + pad) // num_shards if num_shards else 0
    rec_leaf = np.repeat(np.arange(c.num_leaves, dtype=np.int32),
                         np.diff(c.starts).astype(np.int64))
    lmbrs32 = c.leaf_mbrs.astype(np.float32)
    nvr = c.gs_nverts[recs].astype(np.int64)
    # local CSR offsets: exclusive cumsum of ring widths within each shard
    cnt = np.zeros(n + pad, np.int64)
    cnt[:n] = nvr
    cnt2 = cnt.reshape(num_shards, local_n)
    loc_off = np.zeros((num_shards, local_n), np.int64)
    if local_n > 1:
        np.cumsum(cnt2[:, :-1], axis=1, out=loc_off[:, 1:])
    tight = int(cnt2.sum(axis=1).max()) if num_shards else 0
    plocal = max(tight, pool_pad_to, 1)
    plocal += (-plocal) % _POOL_QUANTUM
    vpool = np.zeros((num_shards * plocal, 2), np.float32)
    total = int(nvr.sum())
    if total:
        pos = np.arange(total) - np.repeat(
            np.concatenate([[0], np.cumsum(nvr)[:-1]]), nvr)
        src = np.repeat(c.gs_offsets[recs], nvr) + pos
        loc_flat = loc_off.reshape(-1)
        dst_base = (np.arange(n) // local_n) * plocal + loc_flat[:n]
        vpool[np.repeat(dst_base, nvr) + pos] = \
            c.gs_pool[src].astype(np.float32)
    ladder = 1 << np.arange(31, dtype=np.int64)   # bucket b holds nv <= 2^b
    out = {
        "keys_hi": (keys >> 30).astype(np.int32),
        "keys_lo": (keys & (LO_LIMB_SIZE - 1)).astype(np.int32),
        "recs": recs.astype(np.int32),
        "rec_leaf": rec_leaf,
        "lmbrs": (lmbrs32[rec_leaf] if c.num_leaves
                  else np.empty((0, 4), np.float32)),
        "mbrs": c.gs_mbrs[recs].astype(np.float32),
        "vpool": vpool,
        "voff": loc_off.reshape(-1)[:n].astype(np.int32),
        "vbucket": np.searchsorted(ladder, nvr).astype(np.int32),
        "nverts": c.gs_nverts[recs].astype(np.int32),
        "kinds": c.gs_kinds[recs].astype(np.int32),
    }
    if pad:
        never = np.full((pad, 4), _NEVER, np.float32)
        # pad keys must be the MAXIMAL key in BOTH limbs: a real corner
        # record can carry hi == 2^30-1 with lo > 0, and a (hi, 0) pad
        # appended after it would break the shard-local sort order the
        # bounded binary search relies on
        out["keys_hi"] = np.concatenate(
            [out["keys_hi"], np.full(pad, 2**30 - 1, np.int32)])
        out["keys_lo"] = np.concatenate(
            [out["keys_lo"], np.full(pad, LO_LIMB_SIZE - 1, np.int32)])
        out["recs"] = np.concatenate([out["recs"], np.full(pad, -1, np.int32)])
        out["rec_leaf"] = np.concatenate(
            [out["rec_leaf"], np.zeros(pad, np.int32)])
        out["lmbrs"] = np.concatenate([out["lmbrs"], never])
        out["mbrs"] = np.concatenate([out["mbrs"], never])
        out["voff"] = np.concatenate([out["voff"], np.zeros(pad, np.int32)])
        out["vbucket"] = np.concatenate(
            [out["vbucket"], np.zeros(pad, np.int32)])
        out["nverts"] = np.concatenate([out["nverts"], np.ones(pad, np.int32)])
        out["kinds"] = np.concatenate([out["kinds"], np.zeros(pad, np.int32)])
    return out


def shard_glin_arrays(glin, num_shards: int) -> Dict[str, np.ndarray]:
    """``shard_arrays_from_capture`` over a fresh capture of the live index."""
    return shard_arrays_from_capture(snapshot_capture(glin), num_shards)


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def build_glin_query_step(mesh: Mesh, relation: str = "intersects",
                          cap: int = 512, exact_budget: int = 0,
                          compaction: str = "scan", max_width: int = 64):
    """Returns (step_fn, in_shardings, out_shardings) for the mesh.

    step(snapshot, windows, table) -> (hits, counts):
      hits  (Q, n_data_shards, K) int32  — -1 padded global record ids,
            K = ``exact_budget`` when two-stage refinement is on, else ``cap``
      counts(Q, n_data_shards)     int32 — per-shard hit counts

    ``exact_budget > 0`` runs the fused probe -> mask+compact -> exact-refine
    pipeline PER SHARD (the PR-4 device pipeline, sharded): stage 1 evaluates
    the interval + MBR masks over the shard-local slot-aligned MBR tables
    (``table["lmbrs"]`` / ``table["mbrs"]``) and compacts the survivors to
    ``(Q, exact_budget)`` local slots; stage 2 gathers vertices and runs the
    exact predicate only on those survivors. Each shard then contributes a
    ``(Q, exact_budget)`` survivor block plus its survivor count — the
    all-gathered per-shard counts replace the dense ``(Q, cap)`` candidate
    window as the only cross-shard signal, so HBM/ICI traffic scales with
    ``budget``, not ``cap``. Overflow is encoded per shard as a negative
    count carrying the exact LOCAL need — ``-(local run length) - 1`` when
    the shard's slot run outgrew ``cap`` (``compaction == "scan"`` windows
    stage 1 to ``(Q, cap)``; the Pallas kernel scans the full local run and
    has no cap), else ``-(survivors) - 1`` when only ``exact_budget``
    overflowed — so the caller can tell the two apart by comparing the
    magnitude against ``cap`` and size the right ladder in one step (the
    GLOBAL probe run is a useless overestimate here: a shard only ever sees
    its sub-run). ``core.exec.OverflowLadder.on_sharded_overflow`` consumes
    this encoding — the same ladder object that drives the single-device
    path, so escalation policy lives in exactly one place.

    ``compaction`` picks the stage-1 implementation: ``"scan"`` (the jnp
    cumsum+scatter reference — the CPU path), ``"pallas"`` (the compiled
    ``refine_compact`` kernel, TPU) or ``"interpret"`` (that kernel through
    Pallas interpret mode). ``exact_budget == 0`` is the legacy
    dense single-stage path (kept as the sharded benchmark baseline).

    ``max_width`` (a power of two) is the static top of the vertex-width
    bucket ladder and MUST cover the widest record in the table
    (``max_width >= pow2ceil(max nverts)``) — the exact stage switches on
    the widest SURVIVING bucket per call and gathers only that width from
    the shard-local ``vpool`` slice.
    """
    rel = get_relation(relation)
    if not rel.device_native:
        raise ValueError(f"relation {relation!r} is not device-native; shard "
                         f"its base relation {rel.base_name()!r} instead")
    if compaction not in ("scan", "pallas", "interpret"):
        raise ValueError(f"unsupported sharded compaction {compaction!r} "
                         "(use 'scan', 'pallas' or 'interpret')")
    if exact_budget and compaction in ("pallas", "interpret") \
            and rel.prefilter_kind == "custom":
        raise ValueError(
            f"relation {relation!r} has a custom MBR prefilter; the fused "
            "kernel cannot evaluate it — use compaction='scan'")
    if max_width < 1 or (max_width & (max_width - 1)):
        raise ValueError(f"max_width must be a power of two, got {max_width}")
    daxes = _data_axes(mesh)
    # the compact kernel scans every local slot whatever the cap, so its
    # budget is used even at or past the cap
    capless = compaction in ("pallas", "interpret")
    kb = (exact_budget if exact_budget > 0
          and (capless or exact_budget < cap) else 0)
    nbuckets = max_width.bit_length()      # widths 1, 2, ..., max_width

    table_spec = {k: P(daxes) for k in TABLE_KEYS}
    in_specs = (
        GLIN_MODEL_SPEC,           # snapshot: fully replicated (prefix spec)
        P("model"),                # windows sharded over query axis
        table_spec,
    )
    out_specs = (P("model", daxes), P("model", daxes))

    def local_step(snapshot: GLINSnapshot, windows, table):
        # Each device sees its local slice of the record table and a local
        # slice of the query batch; only the SMALL model tables (nodes, leaf
        # models, leaf MBRs, piecewise suffix-min) are replicated — the
        # global sorted key array is never materialized per device.
        shard_id = jax.lax.axis_index(daxes[0])
        if len(daxes) == 2:
            shard_id = (shard_id * jax.lax.axis_size(daxes[1])
                        + jax.lax.axis_index(daxes[1]))
        local_n = table["keys_hi"].shape[0]
        offset = shard_id.astype(_I32) * local_n

        zmin_hi, zmin_lo, ub_hi, ub_lo = query_keys(snapshot, windows, relation)

        def local_lb(q_hi, q_lo):
            # model predicts a global window; the final search runs on the
            # LOCAL key shard (clipping makes out-of-shard answers land on
            # the shard edge, which is exactly the local lower bound).
            lo_g, hi_g = model_window(snapshot, q_hi, q_lo)
            lo_l = jnp.clip(lo_g - offset, 0, local_n)
            hi_l = jnp.clip(hi_g - offset, 0, local_n)
            return lower_bound_in_window(table["keys_hi"], table["keys_lo"],
                                         q_hi, q_lo, lo_l, hi_l,
                                         snapshot.search_steps + 2)

        lstart = local_lb(zmin_hi, zmin_lo)
        lend = local_lb(ub_hi, ub_lo)
        qn = windows.shape[0]
        probe_w = rel.probe_window(windows, xp=jnp)

        def exact_for(w, vv, nn, kk):
            return rel.predicate(w, vv, nn, kk, xp=jnp)

        def exact_switch(sel, slotc):
            """Exact predicate over selected slots, gathering rings from the
            shard-local pool slice at the width of the WIDEST surviving
            bucket only: ``lax.switch`` executes exactly one width branch,
            so a batch of points never pays a 64-wide ring gather."""
            off = table["voff"][slotc]
            nvs = table["nverts"][slotc]
            kds = table["kinds"][slotc]
            b = jnp.max(jnp.where(sel, table["vbucket"][slotc], 0))

            def branch(width):
                def run(off, nvs, kds):
                    lane = jnp.minimum(
                        jnp.arange(width, dtype=_I32), nvs[..., None] - 1)
                    idx = jnp.clip(off[..., None] + lane, 0,
                                   table["vpool"].shape[0] - 1)
                    return jax.vmap(exact_for)(windows, table["vpool"][idx],
                                               nvs, kds)
                return run

            return jax.lax.switch(
                b, [branch(1 << i) for i in range(nbuckets)], off, nvs, kds)

        def exact_refine_compacted(slots):
            """Exact-shape stage over compacted local survivor slots."""
            taken = slots >= 0
            slotc = jnp.maximum(slots, 0)
            rec = jnp.where(taken, table["recs"][slotc], -1)
            exact = exact_switch(taken, slotc)
            fmask = taken & exact & (rec >= 0)
            hits = jnp.where(fmask, rec, -1)
            return hits, fmask.sum(axis=1).astype(_I32)

        if kb:
            if compaction in ("pallas", "interpret"):
                from repro.kernels import ops

                bounds = jnp.stack([lstart, lend], axis=1)
                slots, surv = ops.refine_compact(
                    probe_w, bounds, table["lmbrs"], table["mbrs"],
                    budget=kb, prefilter=rel.prefilter_kind,
                    interpret=compaction == "interpret")
                overflow = surv > kb
            else:
                pos = lstart[:, None] + jnp.arange(cap, dtype=_I32)[None, :]
                valid = pos < jnp.minimum(lend, lstart + cap)[:, None]
                posc = jnp.minimum(pos, local_n - 1)
                # no leaf-MBR gather: padded slots sit at _NEVER and every
                # record MBR lies inside its leaf's aggregate MBR (grow-only
                # maintenance), so the record prefilter implies the leaf test
                rmbr = table["mbrs"][posc]
                rec_ok = rel.mbr_prefilter(rmbr, windows[:, None, :], xp=jnp)
                mask = valid & rec_ok
                m32 = mask.astype(_I32)
                excl = jnp.cumsum(m32, axis=1) - m32
                col = jnp.where(mask & (excl < kb), excl, kb)
                slots = jnp.full((qn, kb), -1, _I32).at[
                    jnp.arange(qn, dtype=_I32)[:, None], col
                ].set(posc, mode="drop")
                surv = m32.sum(axis=1)
                runlen = lend - lstart
                run_over = runlen > cap
                overflow = run_over | (surv > kb)
                # run overflow reports the local run length (> cap, so the
                # caller can distinguish it from a survivor count <= cap)
                surv = jnp.where(run_over, runlen, surv)
            hits, counts = exact_refine_compacted(slots)
            counts = jnp.where(overflow, -surv - 1, counts)
            return hits[:, None, :], counts[:, None]

        # dense single-stage path (exact_budget == 0): the benchmark baseline
        pos = lstart[:, None] + jnp.arange(cap, dtype=_I32)[None, :]
        valid = pos < jnp.minimum(lend, lstart + cap)[:, None]
        posc = jnp.minimum(pos, local_n - 1)

        wq = windows[:, None, :]
        # leaf pruning uses the padded probe window (dwithin); the record
        # prefilter pads internally and the predicate sees the raw window
        lmbr = table["lmbrs"][posc]
        leaf_ok = geom.mbr_intersects(lmbr, probe_w[:, None, :], xp=jnp)
        rmbr = table["mbrs"][posc]
        rec_ok = rel.mbr_prefilter(rmbr, wq, xp=jnp)
        mask = valid & leaf_ok & rec_ok
        exact = exact_switch(mask, posc)
        mask = mask & exact & (table["recs"][posc] >= 0)
        hits = jnp.where(mask, table["recs"][posc], -1)
        counts = mask.sum(axis=1).astype(_I32)
        runlen = lend - lstart
        # truncation signal carries the local run length (the needed cap)
        counts = jnp.where(runlen > cap, -runlen - 1, counts)
        return hits[:, None, :], counts[:, None]

    step = compat_shard_map(local_step, mesh, in_specs, out_specs)

    in_shardings = (
        NamedSharding(mesh, GLIN_MODEL_SPEC),  # prefix: whole snapshot
        NamedSharding(mesh, P("model")),
        {k: NamedSharding(mesh, s) for k, s in table_spec.items()},
    )
    out_shardings = tuple(NamedSharding(mesh, s) for s in out_specs)
    return step, in_shardings, out_shardings


def build_glin_knn_step(mesh: Mesh, k: int, cap: int = 512,
                        exact_budget: int = 0, compaction: str = "scan",
                        max_width: int = 64):
    """Device-complete sharded kNN: shard-local top-k + cross-shard k-merge.

    Returns (step_fn, in_shardings, out_shardings) like
    :func:`build_glin_query_step`. ``windows`` are the degenerate point
    windows and ``radii`` (Q,) f32 each point's probe radius: every row
    probes ``intersects`` on its point inflated by its own radius (the
    ``dwithin:<r>`` probe geometry, per row), so points at different radii
    share one batch and one compiled program.

    step(snapshot, windows, radii, table) -> (ids, dists, counts):
      ids   (Q, k) int32   — merged global record ids, ascending
                             (distance, id), -1 past the candidate count
      dists (Q, k) float32 — matching exact point-to-geometry distances
      counts(Q, n_data_shards) int32 — per-shard within-radius candidate
                             counts; negative = the shard's overflow signal
                             (same encoding as the window step, consumed by
                             ``OverflowLadder.on_sharded_overflow``)

    Inside the shard_map each shard selects its dwithin candidates exactly
    like the window step (same compaction ladder, same overflow encoding),
    then gathers exact SQUARED distances from its local vertex pool at the
    widest surviving width bucket and partial-sorts its own block to a
    local ``(Q, k)`` top-k by ascending ``(d2, global id)`` — candidate
    sets never leave their shard. The cross-shard merge is ONE collective
    inside the shard_map: an all-gather of every shard's ``(Q, k)`` block
    over the data axes, then a replicated two-key sort takes the global
    k — ``q * shards * (k*8 + 4)`` bytes on the wire (the
    collective term of ``kernels.refine.sharded_knn_cost``), independent of
    the candidate counts.

    The within-radius counts compare squared distances with the squared
    radius — exactly the dwithin predicate's test for the power-of-two
    radii the caller uses — so the settlement rule (done once the summed
    counts reach k) never over-counts. Snapshot records only: no
    tombstone/delta merge here — the caller republishes a stale snapshot
    first (the sharded k-merge exactness contract)."""
    rel = get_relation("intersects")
    if compaction not in ("scan", "pallas", "interpret"):
        raise ValueError(f"unsupported sharded compaction {compaction!r} "
                         "(use 'scan', 'pallas' or 'interpret')")
    if max_width < 1 or (max_width & (max_width - 1)):
        raise ValueError(f"max_width must be a power of two, got {max_width}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    daxes = _data_axes(mesh)
    # the compact kernel scans every local slot whatever the cap, so its
    # budget is used even at or past the cap
    capless = compaction in ("pallas", "interpret")
    kb = (exact_budget if exact_budget > 0
          and (capless or exact_budget < cap) else 0)
    nbuckets = max_width.bit_length()
    id_pad = jnp.int32(2**31 - 1)     # sorts after every real record id

    table_spec = {kk: P(daxes) for kk in TABLE_KEYS}
    in_specs = (GLIN_MODEL_SPEC, P("model"), P("model"), table_spec)
    out_specs = (P("model"), P("model"), P("model", daxes))

    def local_step(snapshot: GLINSnapshot, windows, radii, table):
        # candidate selection: the window step's per-shard slot compaction,
        # verbatim (see build_glin_query_step for the commentary)
        shard_id = jax.lax.axis_index(daxes[0])
        if len(daxes) == 2:
            shard_id = (shard_id * jax.lax.axis_size(daxes[1])
                        + jax.lax.axis_index(daxes[1]))
        local_n = table["keys_hi"].shape[0]
        offset = shard_id.astype(_I32) * local_n

        # per-row probe square: the point inflated by its own radius
        probe_w = windows + jnp.stack([-radii, -radii, radii, radii], axis=1)
        zmin_hi, zmin_lo, ub_hi, ub_lo = query_keys(snapshot, probe_w,
                                                    "intersects")

        def local_lb(q_hi, q_lo):
            lo_g, hi_g = model_window(snapshot, q_hi, q_lo)
            lo_l = jnp.clip(lo_g - offset, 0, local_n)
            hi_l = jnp.clip(hi_g - offset, 0, local_n)
            return lower_bound_in_window(table["keys_hi"], table["keys_lo"],
                                         q_hi, q_lo, lo_l, hi_l,
                                         snapshot.search_steps + 2)

        lstart = local_lb(zmin_hi, zmin_lo)
        lend = local_lb(ub_hi, ub_lo)
        qn = windows.shape[0]

        if kb:
            if compaction in ("pallas", "interpret"):
                from repro.kernels import ops

                bounds = jnp.stack([lstart, lend], axis=1)
                slots, surv = ops.refine_compact(
                    probe_w, bounds, table["lmbrs"], table["mbrs"],
                    budget=kb, prefilter=rel.prefilter_kind,
                    interpret=compaction == "interpret")
                overflow = surv > kb
            else:
                pos = lstart[:, None] + jnp.arange(cap, dtype=_I32)[None, :]
                valid = pos < jnp.minimum(lend, lstart + cap)[:, None]
                posc = jnp.minimum(pos, local_n - 1)
                rmbr = table["mbrs"][posc]
                rec_ok = rel.mbr_prefilter(rmbr, probe_w[:, None, :], xp=jnp)
                mask = valid & rec_ok
                m32 = mask.astype(_I32)
                excl = jnp.cumsum(m32, axis=1) - m32
                col = jnp.where(mask & (excl < kb), excl, kb)
                slots = jnp.full((qn, kb), -1, _I32).at[
                    jnp.arange(qn, dtype=_I32)[:, None], col
                ].set(posc, mode="drop")
                surv = m32.sum(axis=1)
                runlen = lend - lstart
                run_over = runlen > cap
                overflow = run_over | (surv > kb)
                surv = jnp.where(run_over, runlen, surv)
        else:
            # dense single-stage selection: every in-run slot passing the
            # (radius-padded) record-MBR prefilter becomes a candidate
            pos = lstart[:, None] + jnp.arange(cap, dtype=_I32)[None, :]
            valid = pos < jnp.minimum(lend, lstart + cap)[:, None]
            posc = jnp.minimum(pos, local_n - 1)
            rmbr = table["mbrs"][posc]
            rec_ok = rel.mbr_prefilter(rmbr, probe_w[:, None, :], xp=jnp)
            slots = jnp.where(valid & rec_ok, posc, -1)
            runlen = lend - lstart
            overflow = runlen > cap
            surv = runlen

        # shard-local ranking: exact squared distances over the surviving
        # slots, rings gathered from the LOCAL pool slice at the width of
        # the widest surviving bucket only (the knn analogue of the window
        # step's exact_switch — sqdist instead of the predicate)
        taken = slots >= 0
        slotc = jnp.maximum(slots, 0)
        rec = jnp.where(taken, table["recs"][slotc], -1)
        off = table["voff"][slotc]
        nvs = table["nverts"][slotc]
        kds = table["kinds"][slotc]
        b = jnp.max(jnp.where(taken, table["vbucket"][slotc], 0))

        def branch(width):
            def run(off, nvs, kds):
                lane = jnp.minimum(
                    jnp.arange(width, dtype=_I32), nvs[..., None] - 1)
                idx = jnp.clip(off[..., None] + lane, 0,
                               table["vpool"].shape[0] - 1)
                return jax.vmap(
                    lambda w, vv, nn, kk: geom.rect_geom_sqdist(
                        w, vv, nn, kk, xp=jnp)
                )(windows, table["vpool"][idx], nvs, kds)
            return run

        d2 = jax.lax.switch(
            b, [branch(1 << i) for i in range(nbuckets)], off, nvs, kds)
        ok = taken & (rec >= 0)
        inf = jnp.float32(jnp.inf)
        d2 = jnp.where(ok, d2, inf)
        idv = jnp.where(ok, rec, id_pad)
        within = (d2 <= (radii * radii)[:, None]).sum(axis=1).astype(_I32)
        counts = jnp.where(overflow, -surv.astype(_I32) - 1, within)
        if d2.shape[1] < k:               # k > budget: pad the sort columns
            padw = k - d2.shape[1]
            d2 = jnp.concatenate([d2, jnp.full((qn, padw), inf)], axis=1)
            idv = jnp.concatenate(
                [idv, jnp.full((qn, padw), id_pad, _I32)], axis=1)
        d2s, idss = jax.lax.sort([d2, idv], num_keys=2)
        # cross-shard k-merge, inside the shard_map: ONE all-gather of the
        # (k) blocks over the data axes and a replicated two-key sort.
        # Left to the SPMD partitioner (a sort of the sharded block), the
        # TPU compiler overflowed its stack resharding the sort operand
        d2g = jax.lax.all_gather(d2s[:, :k], daxes, axis=1, tiled=True)
        idg = jax.lax.all_gather(idss[:, :k], daxes, axis=1, tiled=True)
        d2m, idm = jax.lax.sort([d2g, idg], num_keys=2)
        d2k, idk = d2m[:, :k], idm[:, :k]
        dists = jnp.sqrt(jnp.maximum(d2k, 0.0))
        return (jnp.where(jnp.isinf(d2k), -1, idk), dists, counts[:, None])

    step = compat_shard_map(local_step, mesh, in_specs, out_specs)

    in_shardings = (
        NamedSharding(mesh, GLIN_MODEL_SPEC),
        NamedSharding(mesh, P("model")),
        NamedSharding(mesh, P("model")),
        {kk: NamedSharding(mesh, s) for kk, s in table_spec.items()},
    )
    out_shardings = (NamedSharding(mesh, P("model")),
                     NamedSharding(mesh, P("model")),
                     NamedSharding(mesh, P("model", daxes)))
    return step, in_shardings, out_shardings


def _snapshot_spec_tree():
    """A GLINSnapshot-shaped pytree of placeholder leaves (for spec mapping)."""
    fields = [f.name for f in dataclasses.fields(GLINSnapshot)
              if not f.metadata.get("static")]
    dummy = {name: 0 for name in fields}
    return GLINSnapshot(**dummy, search_steps=1, depth=1, grid_x0=0.0,
                        grid_y0=0.0, grid_cell=1.0)


def glin_input_specs(num_records: int, num_queries: int, mesh: Mesh,
                     num_leaves: int = 1 << 20, num_nodes: int = 1 << 14,
                     num_pieces: int = 1 << 12, max_verts: int = 12,
                     fanout: int = 64, pool_slots: int = 0):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation).

    Sizes default to a 2^30-record production index: the model tables stay
    tiny (replicated), the record table shards over pod×data.
    ``pool_slots`` sizes the sharded CSR vertex pool (total slots across
    shards); it defaults to ``num_records * (max_verts + 1) // 2`` — the
    pooled layout stores the MEAN record width, not N x the max.
    """
    f32 = jnp.float32
    i32 = jnp.int32
    # Record-level arrays are NOT used by the distributed step (they travel
    # sharded in `table`); keep them 1-element so the replicated snapshot is
    # only the model tables (a few MB, matching the paper's index sizes).
    snap = GLINSnapshot(
        keys_hi=jax.ShapeDtypeStruct((1,), i32),
        keys_lo=jax.ShapeDtypeStruct((1,), i32),
        recs=jax.ShapeDtypeStruct((1,), i32),
        rec_leaf=jax.ShapeDtypeStruct((1,), i32),
        slot_lmbr=jax.ShapeDtypeStruct((1, 4), f32),
        slot_rmbr=jax.ShapeDtypeStruct((1, 4), f32),
        leaf_start=jax.ShapeDtypeStruct((num_leaves + 1,), i32),
        leaf_dlo_hi=jax.ShapeDtypeStruct((num_leaves + 1,), i32),
        leaf_dlo_lo=jax.ShapeDtypeStruct((num_leaves + 1,), i32),
        leaf_mbr=jax.ShapeDtypeStruct((num_leaves, 4), f32),
        leaf_k0_hi=jax.ShapeDtypeStruct((num_leaves,), i32),
        leaf_k0_lo=jax.ShapeDtypeStruct((num_leaves,), i32),
        leaf_slope=jax.ShapeDtypeStruct((num_leaves,), f32),
        leaf_icpt=jax.ShapeDtypeStruct((num_leaves,), f32),
        node_dlo_hi=jax.ShapeDtypeStruct((num_nodes,), i32),
        node_dlo_lo=jax.ShapeDtypeStruct((num_nodes,), i32),
        node_scale=jax.ShapeDtypeStruct((num_nodes,), f32),
        node_fanout=jax.ShapeDtypeStruct((num_nodes,), i32),
        node_child_base=jax.ShapeDtypeStruct((num_nodes,), i32),
        child_codes=jax.ShapeDtypeStruct((num_nodes * fanout,), i32),
        pw_zmax_hi=jax.ShapeDtypeStruct((num_pieces,), i32),
        pw_zmax_lo=jax.ShapeDtypeStruct((num_pieces,), i32),
        pw_sufmin_hi=jax.ShapeDtypeStruct((num_pieces,), i32),
        pw_sufmin_lo=jax.ShapeDtypeStruct((num_pieces,), i32),
        search_steps=8, depth=4, grid_x0=-180.0, grid_y0=-90.0,
        grid_cell=5e-7,
    )
    windows = jax.ShapeDtypeStruct((num_queries, 4), f32)
    if not pool_slots:
        pool_slots = num_records * (max_verts + 1) // 2
    table = {
        "keys_hi": jax.ShapeDtypeStruct((num_records,), i32),
        "keys_lo": jax.ShapeDtypeStruct((num_records,), i32),
        "recs": jax.ShapeDtypeStruct((num_records,), i32),
        "rec_leaf": jax.ShapeDtypeStruct((num_records,), i32),
        "lmbrs": jax.ShapeDtypeStruct((num_records, 4), f32),
        "mbrs": jax.ShapeDtypeStruct((num_records, 4), f32),
        "vpool": jax.ShapeDtypeStruct((pool_slots, 2), f32),
        "voff": jax.ShapeDtypeStruct((num_records,), i32),
        "vbucket": jax.ShapeDtypeStruct((num_records,), i32),
        "nverts": jax.ShapeDtypeStruct((num_records,), i32),
        "kinds": jax.ShapeDtypeStruct((num_records,), i32),
    }
    return snap, windows, table
