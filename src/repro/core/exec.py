"""The staged query-execution pipeline behind :meth:`SpatialIndex.query`.

GLIN's query path is ONE pipeline regardless of where it runs::

    probe -> compact -> refine -> delta-patch -> complement-finish
                                                       (knn: -> knn-rank)

What differs per backend is which *implementation* serves each stage:
the host loop walks the mutable tree one window at a time
(probe+compact+refine in one pass), the jitted device ``batch_query`` runs
the three stages as ONE program (probe, compaction — the Pallas compact
kernel on TPU — and the exact gather+check), ``batch_query_fused`` is the
whole-table XLA composition of the same stages
(:class:`FusedDeviceStage`, ``EngineConfig.fusion="reference"``), and the
sharded step runs them per record shard under a mesh. Delta patching and
complement finishing are backend-independent — they operate on id lists
against state frozen under the facade lock — so exactly ONE implementation
of each exists, here.

``SpatialIndex.plan()`` picks a backend; :func:`compile_plan` turns that
:class:`QueryPlan` into an :class:`ExecutionPlan` — an ordered stage tuple —
and :meth:`ExecutionPlan.execute` runs it, timing every stage into
:class:`StageStats` (wall time, survivor counts, overflow-ladder
escalations, delta sizes). The stats ride out on ``QueryResult.stages`` and
aggregate into ``SpatialIndex.stats()["stages"]``;
:meth:`SpatialIndex.explain` pretty-prints the compiled pipeline without
executing it.

**The overflow ladder** (:class:`OverflowLadder`) is the one shared
cap/budget escalation policy. Device-side refinement signals overflow with
negative counts: ``-(run length) - 1`` when a query's candidate run outgrew
``cap`` (magnitude > cap disambiguates), else ``-(survivors) - 1`` when the
MBR survivors outgrew ``exact_budget``. The ladder jumps the cap straight
to a sufficient power of two (a cheap bounds-only probe tells the two
overflows apart on the single-device path; the sharded step encodes the
exact local need) and grows the budget geometrically past the true
survivor count. CAPPED compaction (jnp ``scan``/``sort``, windowed to the
cap) escalates to the single-stage dense path once the needed budget
exceeds ``CAPPED_MAX_BUDGET`` or the cap. CAPLESS compaction (the Pallas
compact kernel and the whole-table fused path scan every slot) overflows
only on the budget — no bounds probe — and its budget grows to
``max_cap``: the dense path would need a cap as long as the probe run,
which on a deployment-size store is far past ``max_cap``.

**Locking contract** (unchanged from the monolithic backends, now stated
once): the host and sharded refine stages run under the facade lock — they
walk the mutable host tree or own every mesh device — and freeze the delta
/ live-id sets for the downstream stages in that same critical section; the
device refine stage freezes everything it needs under the lock, then runs
its device compute OUTSIDE it. Delta patching and complement finishing
always run lock-free on the frozen copies, so their answers are exact at
the frozen epoch no matter how writers interleave.

**Telemetry**: every stage counts the jitted programs it launched into
``StageStats.dispatches`` (one per ladder attempt — ``batch_query`` and the
sharded steps are single programs — plus one per disambiguating bounds
probe and one per kNN rank), and names in ``StageStats.note`` each
implementation its attempts ran (``compaction=pallas``, ``dense``, ...), so
a fallback in any attempt shows in ``stats()["stages"]`` and ``explain()``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .device import (batch_check_added, batch_knn_rank,
                     delta_table_from_host, knn_seed_radii)
from .index import QueryStats, initial_knn_radius
from .index import knn as _host_knn
from .relations import get_relation

__all__ = ["StageStats", "ExecContext", "Stage", "ExecutionPlan",
           "OverflowLadder", "compile_plan", "PIPELINE_STAGES"]

# canonical stage order (docs/api.md "Execution pipeline")
PIPELINE_STAGES = ("probe", "compact", "refine", "delta-patch",
                   "complement-finish", "knn-rank")


def _engine():
    """The engine module namespace, resolved at call time — tests monkeypatch
    ``repro.core.engine.batch_query`` and friends, and the stages must see
    the patched bindings (a ``from``-import here would freeze the originals).
    Deferred to avoid the circular import (engine imports this module)."""
    from . import engine
    return engine


# --------------------------------------------------------------- observability
@dataclasses.dataclass
class StageStats:
    """Per-stage telemetry for one executed query batch.

    ``survivors`` is the total id count LEAVING the stage (-1 when the stage
    does not produce ids, e.g. a skipped patch); ``escalations`` counts
    overflow-ladder retries; ``cap``/``budget`` are the settled ladder values
    a refine stage ended on (budget 0 = single-stage dense, -1 = n/a);
    ``dispatches`` counts jitted programs launched (one per ladder attempt,
    +1 per disambiguating bounds probe or kNN rank — 0 for host/shared
    stages that launch no device work)."""

    stage: str                       # primary canonical stage name
    impl: str                        # "host" | "device" | "fused" |
                                     # "sharded" | "shared"
    covers: Tuple[str, ...] = ()     # canonical stages this impl fuses
    wall_ms: float = 0.0
    queries: int = 0
    survivors: int = -1
    escalations: int = 0
    dispatches: int = 0
    cap: int = 0
    budget: int = -1
    delta_added: int = 0
    delta_tombstoned: int = 0
    skipped: bool = False            # compiled in, but a no-op this run
    note: str = ""
    # knn-rank telemetry (zero/empty on every other stage)
    rungs: int = 0                   # deepest per-point radius-ladder depth
    rung_hist: Tuple[int, ...] = ()  # points settled per rung; [0] = seeded
    seed_hits: int = 0               # points settled at their seeded radius
    seed_radius: float = 0.0         # median pow2-snapped seed radius
    merge_bytes: int = 0             # cross-shard k-merge collective bytes

    def add_note(self, text: str) -> None:
        """Append ``text`` to the note unless it is already there: a stage
        whose ladder attempts ran different implementations names each."""
        parts = self.note.split("; ") if self.note else []
        if text not in parts:
            self.note = "; ".join(parts + [text])

    def asdict(self) -> dict:
        d = dataclasses.asdict(self)
        d["covers"] = list(self.covers)
        d["rung_hist"] = list(self.rung_hist)
        return d


@dataclasses.dataclass
class ExecContext:
    """Mutable state threaded through the stages of one execution.

    The refine stage freezes everything downstream stages read (``epoch``,
    ``frozen_delta``, ``live``, ``snap``) under the facade lock; the stages
    after it touch only this context, never the live index fields."""

    index: Any                       # the SpatialIndex facade
    batch: Any                       # QueryBatch
    plan: Any                        # QueryPlan
    rel: Any                         # Relation (None for knn)
    base: Any                        # probed base Relation (None for knn)
    replica: int = 0
    # frozen under the facade lock by the refine stage
    epoch: int = -1
    frozen_delta: Optional[Tuple] = None
    live: Optional[np.ndarray] = None
    snap: Any = None                 # snapshot whose grid params patch uses
    # outputs
    ids: Optional[List[np.ndarray]] = None
    distances: Optional[List[np.ndarray]] = None
    host_stats: Optional[List[QueryStats]] = None
    stage_stats: List[StageStats] = dataclasses.field(default_factory=list)


def _total(ids: Optional[List[np.ndarray]]) -> int:
    return -1 if ids is None else int(sum(r.shape[0] for r in ids))


# -------------------------------------------------------------- overflow ladder
# budget ceiling of the CAPPED compactions (jnp "scan"/"sort", windowed to
# the cap) before the ladder drops to the dense path: there a dense retry
# only re-checks cheap predicates over the same (Q, cap) window
CAPPED_MAX_BUDGET = 1024


class OverflowLadder:
    """THE cap/budget escalation policy, shared by every refine
    implementation (single-device and sharded). See the module docstring for
    the negative-count encoding contract this consumes.

    Holds the adaptive state for one query's retries; the settled ``cap`` is
    max-merged back into the facade by the refine stage so the ladder is
    walked once per workload, not once per call.

    ``capless`` marks a compaction that scans every slot whatever the cap
    (the Pallas compact kernel, the whole-table reference): its budget is
    used even when it reaches the cap, and grows up to ``max_cap`` — the
    dense fallback would need a cap as long as the probe run, which on a
    deployment-size store is far past ``max_cap``."""

    def __init__(self, config, cap: int, max_budget: Optional[int] = None,
                 capless: bool = False):
        self.config = config
        self.cap = int(cap)
        self.budget = int(config.exact_budget)
        self.capless = bool(capless)
        # budget-growth ceiling before the ladder falls back to the dense
        # single-stage path: max_cap for capless compaction, the capped
        # ceiling otherwise. knn raises it to max_cap either way because
        # the rank's exact-distance work scales with the hit-matrix WIDTH —
        # scan compaction at a large budget is far cheaper than ranking a
        # dense (Q, cap) matrix every rung.
        if max_budget is None:
            max_budget = config.max_cap if capless else CAPPED_MAX_BUDGET
        self.max_budget = int(max_budget)
        self.escalations = 0

    @property
    def use_budget(self) -> int:
        """The budget the next call actually uses: capped two-stage
        refinement only pays for itself while the budget is positive AND
        below the cap; capless compaction takes any positive budget."""
        b = self.budget
        return b if b > 0 and (self.capless or b < self.cap) else 0

    def grow_cap(self, need: int) -> None:
        cfg = self.config
        if self.cap >= cfg.max_cap or need > cfg.max_cap:
            raise OverflowError(
                f"candidate run of {need} exceeded max_cap="
                f"{cfg.max_cap}; raise EngineConfig.max_cap or "
                f"narrow the windows")
        self.cap = min(max(self.cap * 2, 1 << (need - 1).bit_length()),
                       cfg.max_cap)

    def set_budget(self, target: int) -> None:
        """Take ``target`` as the next budget, or zero it (the dense path)
        once it passes ``max_budget`` — or, for capped compaction, the cap
        (two-stage would no longer shrink anything)."""
        self.budget = (0 if target > self.max_budget
                       or (not self.capless and target >= self.cap)
                       else int(target))

    def grow_budget(self, use_budget: int, survivors: int) -> None:
        """Budget overflow: the negative-count encoding carries the TRUE
        survivor count, so the budget grows geometrically straight past it
        (re-running compaction) up to ``max_budget``."""
        self.set_budget(max(use_budget * 2,
                            1 << max(survivors - 1, 0).bit_length()))

    def on_device_overflow(self, counts: np.ndarray, use_budget: int,
                           probe_bounds, batch_len: int) -> bool:
        """Single-device retry: the overflow signal of capped compaction
        conflates run-length > cap with survivors > budget; ``probe_bounds``
        (a cheap bounds-only probe) tells them apart, so the cap jumps
        straight to sufficiency — keeping the LOGICAL budget (one the old
        cap disabled because ``budget >= cap`` comes back into play once the
        cap outgrows it). Capless compaction with a budget active overflows
        ONLY on the budget, so no probe runs. Returns whether the bounds
        probe ran."""
        if use_budget and self.capless:
            self.on_capless_overflow(counts, use_budget)
            return False
        self.escalations += 1
        start, end = probe_bounds()
        need = int(np.max(np.asarray(end - start))) if batch_len else 0
        if need > self.cap:
            self.grow_cap(need)
            return True
        if not use_budget:
            raise AssertionError(
                "single-stage overflow with run <= cap")  # unreachable
        self.grow_budget(use_budget, int(-(counts.min()) - 1))
        return True

    def on_capless_overflow(self, counts: np.ndarray,
                            use_budget: int) -> None:
        """Capless retry: the mask spans the whole slot table, so a negative
        count is ALWAYS budget overflow carrying the total survivor count —
        the budget jumps straight past it with no disambiguating bounds
        probe."""
        self.escalations += 1
        if not use_budget:
            raise AssertionError(
                "capless overflow without an active budget")  # unreachable
        self.grow_budget(use_budget, int(-(counts.min()) - 1))

    def on_sharded_overflow(self, counts: np.ndarray,
                            use_budget: int) -> None:
        """Sharded retry: the step encodes the exact LOCAL need — no global
        bounds probe, whose run is a useless overestimate of any one
        shard's. Capless compaction with a budget active overflows ONLY on
        the budget."""
        self.escalations += 1
        need = int(-(counts.min()) - 1)
        if use_budget and self.capless:
            self.grow_budget(use_budget, need)
        elif need > self.cap:
            self.grow_cap(need)
        elif not use_budget:
            raise AssertionError(
                "single-stage overflow with run <= cap")  # unreachable
        else:
            self.grow_budget(use_budget, need)


def _capless(compaction: str) -> bool:
    return compaction in ("pallas", "interpret")


def _attempt_note(compaction: str, budget: int) -> str:
    """What one refine attempt ran: its compaction, or the dense path."""
    return f"compaction={compaction}" if budget else "dense"


# ------------------------------------------------------------------- stages
class Stage:
    """One pipeline stage: fill ``ctx`` (and its own ``StageStats``). A
    fused implementation covers several adjacent canonical stages —
    ``covers`` names them for ``explain()`` and the telemetry.
    ``dispatches`` is the static per-attempt device-dispatch count of the
    implementation (what ``explain()`` prints before execution; the
    executed count lands in ``StageStats.dispatches``)."""

    name: str = "?"
    covers: Tuple[str, ...] = ()
    impl: str = "?"
    dispatches: int = 0

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        raise NotImplementedError


class HostRefineStage(Stage):
    """fp64 probe+compact+refine: one ``GLIN.query`` walk per window over
    the mutable host tree, under the facade lock. Queries the BASE relation
    only — complement finishing is the shared downstream stage (the live-id
    set it needs is frozen here, in the same critical section)."""

    name = "refine"
    covers = ("probe", "compact", "refine")
    impl = "host"

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        idx, batch = ctx.index, ctx.batch
        stats = ([QueryStats() for _ in range(len(batch))]
                 if batch.collect_stats else None)
        ids: List[np.ndarray] = []
        with idx._lock:
            for i, w in enumerate(batch.windows):
                s = stats[i] if stats is not None else None
                ids.append(np.sort(idx.glin.query(w, ctx.base.name, s)))
            ctx.live = idx._freeze_live(ctx.rel)
            ctx.epoch = idx._epoch
        ctx.ids = ids
        ctx.host_stats = stats
        st.survivors = _total(ids)


class DeviceRefineStage(Stage):
    """The jitted fused probe+compact+refine dispatch (fp32). Freezes the
    served snapshot/payload (fanned to the requested replica), the delta
    and the live-id set under the facade lock, then runs the overflow-
    ladder retry loop OUTSIDE it — writers are never blocked by device
    compute, and the answer is exact at the frozen epoch."""

    name = "refine"
    covers = ("probe", "compact", "refine")
    impl = "device"
    dispatches = 1

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        eng = _engine()
        idx, batch = ctx.index, ctx.batch
        cfg = idx.config
        patch = ctx.plan.backend == "device+delta"
        with idx._lock:
            # freeze everything the unlocked compute below reads: the served
            # snapshot + payload (immutable device arrays), copies of the
            # delta sets and the live set — a writer landing after this
            # block changes none of them. device+delta serves the published
            # snapshot and patches the delta on top; plain device
            # republishes first — either way the answer reflects the frozen
            # epoch exactly.
            snap = idx._published_snapshot() if patch else idx.snapshot()
            payload = idx._device_payload(idx._snapshot_recs)
            snap, payload = idx._replica_view(ctx.replica, snap, payload)
            ctx.frozen_delta = idx._freeze_delta() if patch else None
            ctx.live = idx._freeze_live(ctx.rel)
            ctx.epoch = idx._epoch
            comp = idx._compaction(ctx.base.name)
            ladder = OverflowLadder(cfg, idx._cap, capless=_capless(comp))
        ctx.snap = snap
        pods, mb = payload
        q = len(batch.windows)
        wq = batch.windows.astype(np.float32)
        if cfg.pad_quantum > 0 and q:
            # bucket the query axis to a power of two: the jitted
            # batch_query compiles per windows shape, and a serving tier
            # draining adaptively-sized micro-batches would otherwise
            # compile once per distinct batch size. Padding rows repeat the
            # last window and are sliced off below.
            qb = 1 << (q - 1).bit_length()
            if qb > q:
                wq = np.concatenate([wq, np.repeat(wq[-1:], qb - q, 0)])
        wj = jnp.asarray(wq)
        base = ctx.base.name
        while True:
            ub = ladder.use_budget
            hits, counts = eng.batch_query(
                snap, wj, pods, mb, relation=base,
                cap=ladder.cap, exact_budget=ub, compaction=comp)
            st.dispatches += 1
            st.add_note(_attempt_note(comp, ub))
            counts = np.asarray(counts)
            if (counts >= 0).all():
                with idx._lock:
                    # max-merge: a concurrent query may have grown it further
                    idx._cap = max(idx._cap, ladder.cap)
                break
            if ladder.on_device_overflow(
                    counts, ub,
                    lambda: eng.batch_query_bounds(snap, wj, relation=base),
                    q):
                st.dispatches += 1            # disambiguating bounds probe
        hits = np.asarray(hits)[:q]
        ctx.ids = [np.sort(row[row >= 0]).astype(np.int64) for row in hits]
        st.survivors = _total(ctx.ids)
        st.escalations = ladder.escalations
        st.cap, st.budget = ladder.cap, ladder.use_budget


class FusedDeviceStage(Stage):
    """Whole-table probe+compact+refine (``core.device.batch_query_fused``,
    ``EngineConfig.fusion="reference"``): one jitted XLA program that masks
    EVERY slot against every window and compacts by cumsum + searchsorted —
    capless, O(Q·N) memory, so for small stores and CPU timing. Same
    freeze/retry/epilogue contract as :class:`DeviceRefineStage`; a dense
    escalation (budget past ``max_cap``) runs the staged dense path for
    that attempt."""

    name = "refine"
    covers = ("probe", "compact", "refine")
    impl = "fused"
    dispatches = 1

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        eng = _engine()
        idx, batch = ctx.index, ctx.batch
        cfg = idx.config
        patch = ctx.plan.backend == "device+delta"
        with idx._lock:
            snap = idx._published_snapshot() if patch else idx.snapshot()
            payload = idx._device_payload(idx._snapshot_recs)
            snap, payload = idx._replica_view(ctx.replica, snap, payload)
            ctx.frozen_delta = idx._freeze_delta() if patch else None
            ctx.live = idx._freeze_live(ctx.rel)
            ctx.epoch = idx._epoch
            ladder = OverflowLadder(cfg, idx._cap, capless=True)
        ctx.snap = snap
        pods, mb = payload
        q = len(batch.windows)
        wq = batch.windows.astype(np.float32)
        if cfg.pad_quantum > 0 and q:
            qb = 1 << (q - 1).bit_length()
            if qb > q:
                wq = np.concatenate([wq, np.repeat(wq[-1:], qb - q, 0)])
        wj = jnp.asarray(wq)
        base = ctx.base.name
        while True:
            ub = ladder.use_budget
            st.dispatches += 1
            if idx._fusion_mode(base, ub) is None:
                # dense escalation (survivors past max_cap): the staged
                # dense path serves this attempt
                hits, counts = eng.batch_query(
                    snap, wj, pods, mb, relation=base,
                    cap=ladder.cap, exact_budget=0, compaction="scan")
                st.add_note("dense")
                counts = np.asarray(counts)
                if (counts >= 0).all():
                    with idx._lock:
                        idx._cap = max(idx._cap, ladder.cap)
                    break
                if ladder.on_device_overflow(
                        counts, 0,
                        lambda: eng.batch_query_bounds(snap, wj,
                                                       relation=base), q):
                    st.dispatches += 1        # disambiguating bounds probe
                continue
            hits, counts = eng.batch_query_fused(
                snap, wj, pods, relation=base, exact_budget=ub)
            st.add_note("fusion=reference")
            counts = np.asarray(counts)
            if (counts >= 0).all():
                break
            ladder.on_capless_overflow(counts, ub)
        hits = np.asarray(hits)[:q]
        ctx.ids = [np.sort(row[row >= 0]).astype(np.int64) for row in hits]
        st.survivors = _total(ctx.ids)
        st.escalations = ladder.escalations
        st.cap, st.budget = ladder.cap, ladder.use_budget


class ShardedRefineStage(Stage):
    """Per-record-shard fused probe+compact+refine over the mesh
    (``core.distributed``), query windows sharded over the model axis. Runs
    entirely under the facade lock (the mesh owns every device — there is
    nothing to overlap with) and freezes the delta + live-id sets in that
    same critical section for the downstream shared stages."""

    name = "refine"
    covers = ("probe", "compact", "refine")
    impl = "sharded"
    dispatches = 1

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        idx, batch = ctx.index, ctx.batch
        cfg = idx.config
        with idx._lock:
            if ctx.plan.rebuild_snapshot:
                idx.snapshot()
            else:
                idx._published_snapshot()
            patch = idx.snapshot_is_stale()
            q = len(batch)
            # pad the batch to a model-axis multiple (shard_map divides Q
            # evenly); padded rows repeat the last window, sliced off after
            m = cfg.mesh.shape["model"]
            wins32 = batch.windows.astype(np.float32)
            qpad = (-q) % m
            if qpad:
                wins32 = np.concatenate(
                    [wins32, np.repeat(wins32[-1:], qpad, axis=0)])
            wj = jnp.asarray(wins32)
            snap_repl, table, _, maxw = idx._sharded_placement()
            base = ctx.base.name
            comp = idx._compaction(base)
            if comp == "sort":  # legacy argsort baseline: 1-device only
                comp = "scan"
            ladder = OverflowLadder(cfg, idx._cap, capless=_capless(comp))
            while True:
                ub = ladder.use_budget
                step = idx._sharded_step(base, ladder.cap, ub, comp, maxw)
                hits, counts = step(snap_repl, wj, table)
                st.dispatches += 1
                st.add_note(_attempt_note(comp, ub))
                counts = np.asarray(counts)
                if (counts >= 0).all():
                    idx._cap = max(idx._cap, ladder.cap)
                    break
                ladder.on_sharded_overflow(counts, ub)
            hits = np.asarray(hits)[:q]              # (Q, shards, K)
            ctx.ids = [np.sort(row[row >= 0]).astype(np.int64)
                       for row in hits.reshape(q, -1)]
            ctx.frozen_delta = idx._freeze_delta() if patch else None
            ctx.live = idx._freeze_live(ctx.rel)
            ctx.epoch = idx._epoch
            ctx.snap = idx._snapshot
        st.survivors = _total(ctx.ids)
        st.escalations = ladder.escalations
        st.cap, st.budget = ladder.cap, ladder.use_budget


class DeltaPatchStage(Stage):
    """Restore exactness of snapshot results at the frozen epoch: mask out
    tombstoned records and check the added set (fp32, matching the device
    precision contract) against the *base* relation — complement finishing
    happens after, on top of the patched ids.

    Operates only on the ``ExecContext`` freeze (the refine stage captured
    the delta under the lock), so it runs lock-free on every backend —
    THE one patch implementation. Small added sets are brute-force checked
    in a host loop; past ``EngineConfig.delta_device_min`` the check runs on
    device through the Zmin-sorted :class:`~repro.core.device.DeltaTable`
    (one vectorized (Q x A) pass, no per-batch host round-trip)."""

    name = "delta-patch"
    covers = ("delta-patch",)
    impl = "shared"

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        frozen = ctx.frozen_delta
        if frozen is None:
            st.skipped = True
            st.note = "no delta against the served snapshot"
            return
        tombs, added, table, av, an, ak = frozen
        st.delta_added = int(added.shape[0])
        st.delta_tombstoned = 0 if tombs is None else int(tombs.shape[0])
        batch, snap = ctx.batch, ctx.snap
        base = ctx.base.name
        added_hits: Optional[List[np.ndarray]] = None
        if table is not None:
            wj = jnp.asarray(batch.windows.astype(np.float32))
            st.dispatches += 1           # device DeltaTable added-set check
            st.note = "added set checked on device"
            ok = np.asarray(batch_check_added(
                table, wj, base, snap.grid_x0, snap.grid_y0, snap.grid_cell))
            tbl_ids = np.asarray(table.ids, np.int64)
            added_hits = [np.sort(tbl_ids[row]) for row in ok]
        elif added.shape[0]:
            st.note = "added set checked in a host loop"
            pred = get_relation(base).predicate
            added_hits = []
            for qi in range(len(ctx.ids)):
                w32 = batch.windows[qi].astype(np.float32)
                added_hits.append(added[np.asarray(pred(w32, av, an, ak))])
        out: List[np.ndarray] = []
        for qi, h in enumerate(ctx.ids):
            if tombs is not None:
                h = h[~np.isin(h, tombs)]
            if added_hits is not None:
                # added ids all postdate (exceed) every snapshot id, so the
                # concatenation stays ascending
                h = np.concatenate([h, added_hits[qi]])
            out.append(h)
        ctx.ids = out
        st.survivors = _total(out)


class ComplementFinishStage(Stage):
    """Complement relations (e.g. ``disjoint``): subtract the base hits from
    the live-id set the refine stage froze under the lock — THE one
    complement implementation, identical lock story on every backend."""

    name = "complement-finish"
    covers = ("complement-finish",)
    impl = "shared"

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        rel = ctx.rel
        if not rel.is_complement:
            st.skipped = True
            st.note = "relation is not a complement"
            return
        live = ctx.live
        if live is None:   # refine stages freeze it whenever rel needs it
            with ctx.index._lock:
                live = ctx.index._freeze_live(rel)
        ctx.ids = [np.setdiff1d(live, r) for r in ctx.ids]
        if ctx.host_stats is not None:
            # candidates/checked/leaves_* honestly describe the base
            # probe's work, but the hit count must be the complement's
            for s, r in zip(ctx.host_stats, ctx.ids):
                s.results = int(r.shape[0])
        st.survivors = _total(ctx.ids)


class KnnHostStage(Stage):
    """knn on the mutable host tree, one point at a time under the lock."""

    name = "knn-rank"
    covers = ("probe", "refine", "knn-rank")
    impl = "host"

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        idx, batch = ctx.index, ctx.batch
        ids, dists = [], []
        with idx._lock:      # the host knn walks the mutable tree
            for p in batch.points:
                i, d = _host_knn(idx.glin, p, batch.k)
                ids.append(np.asarray(i, np.int64))
                dists.append(np.asarray(d))
            ctx.epoch = idx._epoch
        ctx.ids, ctx.distances = ids, dists
        st.survivors = _total(ids)


def _pow2_radii(r: np.ndarray) -> np.ndarray:
    """Per-point power-of-two radius snap: each (bucket, radius) pair
    compiles once, not once per distinct estimate."""
    return np.power(2.0, np.ceil(np.log2(np.maximum(r, 1e-9))))


def _seed_radii(snap, wins, q, k, seed_mode, r_global, st,
                pow2: bool = True) -> np.ndarray:
    """Initial radii for ``q`` degenerate windows. CDF seeds route through
    the published model (``device.knn_seed_radii``); a seed that comes back
    non-finite or non-positive (a point routed to an empty leaf, whose
    aggregate-MBR sentinel has no area) falls back to the global density
    radius — the seed is a performance prior, never allowed to poison the
    probe relation. ``pow2`` snaps UP to powers of two (the sharded stage's
    doubling ladder); the device stage passes ``pow2=False`` because it
    grows radii by its count-informed estimate and an up-snap only doubles
    the probe area."""
    if seed_mode == "cdf":
        wq = wins.astype(np.float32)
        qb = 1 << max(q - 1, 0).bit_length()
        if qb > q:
            wq = np.concatenate([wq, np.repeat(wq[-1:], qb - q, 0)])
        seeds = np.asarray(knn_seed_radii(
            snap, jnp.asarray(wq), jnp.float32(k)))[:q].astype(np.float64)
        st.dispatches += 1
        bad = ~np.isfinite(seeds) | (seeds <= 0.0)
        if bad.any():
            seeds[bad] = r_global
    else:
        seeds = np.full(q, r_global)
    return _pow2_radii(seeds) if pow2 else seeds


def _knn_backstop(idx, cfg) -> tuple:
    """Resolve the knn config knobs under the caller's lock: (seed mode,
    top-k impl). ``knn_seed=None`` -> the CDF density seed (the planner only
    routes knn to a device backend when the learned model is published);
    ``knn_topk=None`` -> the Pallas partial-selection kernel on TPU, the
    two-key ``lax.sort`` reference elsewhere; ``"interpret"`` runs the
    kernel through interpret mode."""
    seed = cfg.knn_seed or "cdf"
    if seed not in ("cdf", "global"):
        raise ValueError(f"unknown knn_seed {cfg.knn_seed!r} "
                         "(use 'cdf' or 'global')")
    impl = cfg.knn_topk or (
        "pallas" if jax.default_backend() == "tpu" else "sort")
    if impl not in ("sort", "pallas", "interpret"):
        raise ValueError(f"unknown knn_topk {cfg.knn_topk!r} "
                         "(use 'sort', 'pallas' or 'interpret')")
    return seed, impl


class KnnDeviceStage(Stage):
    """Device-complete knn (cf. LISA): each point probes at its OWN seeded
    radius and the survivors are ranked ON DEVICE by exact squared distance
    (:func:`~repro.core.device.batch_knn_rank`) — only the final ``(Q, k)``
    ids + distances and the within-radius counts that drive the ladder ever
    cross back to the host. Candidate sets never do.

    Each rung probes EVERY still-undone point in ONE dispatch: the probe
    window is the per-point L-inf inflation of the query point by its own
    radius (``relations._pad_window``'s dwithin geometry, applied per row),
    run through the plain ``intersects`` pipeline — a square superset of
    the dwithin disc whose corner candidates the exact distance test in the
    rank discards, so settlement stays exact while rung cost never
    fragments across radius classes (and every rung reuses the one
    ``intersects`` compile instead of one ``dwithin:<r>`` compile per
    class). A point is DONE once its within-radius candidate count reaches
    k (the within set is exactly {distance <= r} — no closer geometry can
    be missing) or covers every live record (k > live).

    Radius selection: the published learned index doubles as a density
    estimate (``knn_seed_radii``), seeding each point near its expected
    k-th-neighbour distance (pow2-snapped). Between rungs an undone point
    grows by the 2D density scaling ``d_within * sqrt(k / within)`` of the
    exact distances it already holds, clamped to [2r, 4r] — at least the
    doubling backstop (a bad estimate costs rungs, never hits), at most one
    quadrupling, which is also what a still-empty point racing across empty
    space takes. Since the radius rides in the window COORDS (not a traced
    relation constant), per-point growth costs no extra compiles. The
    overflow ladder runs with ``max_budget=max_cap``: survivor compaction
    keeps paying for itself in rank width long past the Pallas VMEM bound
    (the scan reference has none), so a dense-width rank is the last
    resort, not the second rung. On ``device+delta`` the frozen tombstones
    are masked out of the ranking and the unpublished added set is
    distance-merged before the top-k — inserted-but-unpublished records are
    rankable with no republish. ``rung_hist`` / ``seed_hits`` /
    ``seed_radius`` report how well the seeding worked; ``escalations``
    counts overflow-ladder retries (NOT rungs — those are ``rungs``)."""

    name = "knn-rank"
    covers = ("probe", "compact", "refine", "knn-rank")
    impl = "device"
    dispatches = 2

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        eng = _engine()
        idx, batch = ctx.index, ctx.batch
        cfg = idx.config
        pts = np.asarray(batch.points, np.float64)
        q, k = len(batch), int(batch.k)
        wins = np.concatenate([pts, pts], axis=1)    # degenerate windows
        patch = ctx.plan.backend == "device+delta"
        with idx._lock:
            # same freeze contract as DeviceRefineStage: snapshot + payload
            # + delta copies captured under the lock, device compute outside
            # it — every rung serves the SAME frozen epoch
            snap = idx._published_snapshot() if patch else idx.snapshot()
            payload = idx._device_payload(idx._snapshot_recs)
            snap, payload = idx._replica_view(ctx.replica, snap, payload)
            ctx.frozen_delta = idx._freeze_delta() if patch else None
            ctx.epoch = idx._epoch
            comp = idx._compaction("intersects")
            ladder = OverflowLadder(cfg, idx._cap, max_budget=cfg.max_cap,
                                    capless=_capless(comp))
            n_live = idx.glin.num_records
            r_global = initial_knn_radius(idx.glin, k)
            seed_mode, impl = _knn_backstop(idx, cfg)
            # the rank needs the added set as a device DeltaTable regardless
            # of the host/device patching threshold (engine caches it per
            # mutation epoch)
            dtab = (idx._delta_table() if patch and idx._added else None)
        ctx.snap = snap
        pods, mb = payload
        ctx.ids = [np.empty(0, np.int64) for _ in range(q)]
        ctx.distances = [np.empty(0, np.float64) for _ in range(q)]
        if k <= 0 or n_live == 0 or q == 0:
            st.survivors = 0
            return
        tomb = None
        if ctx.frozen_delta is not None:
            tombs, added = ctx.frozen_delta[0], ctx.frozen_delta[1]
            st.delta_added = int(added.shape[0])
            st.delta_tombstoned = 0 if tombs is None else int(tombs.shape[0])
            if tombs is not None:
                tomb = jnp.asarray(tombs.astype(np.int32))
        radius = _seed_radii(snap, wins, q, k, seed_mode, r_global, st,
                             pow2=False)
        st.seed_radius = float(np.median(radius))
        st.add_note(f"seed={seed_mode} topk={impl}")
        # tier-1 budget: the CONFIGURED exact budget, pinned — the rank
        # stays narrow for the common case and only fat rows escalate
        # through `ladder` below. The tier-1 CAP tracks the ladder
        # (z-interval runs are a property of the data, every row pays them)
        b0 = int(cfg.exact_budget)
        done = np.zeros(q, bool)
        probes = np.zeros(q, np.int32)
        out_ids: List[Optional[np.ndarray]] = list(ctx.ids)
        out_d: List[Optional[np.ndarray]] = list(ctx.distances)
        for _ in range(64):
            todo = np.nonzero(~done)[0]
            if todo.size == 0:
                break
            ctr = wins[todo].astype(np.float32)
            rr = radius[todo].astype(np.float32)
            # per-point inflated square probe (the dwithin probe_pad
            # geometry, applied per row): ONE intersects dispatch covers
            # every undone point at its own radius — the exact d2 <= r^2
            # test in the rank discards the square's corner candidates
            sq = np.stack([ctr[:, 0] - rr, ctr[:, 1] - rr,
                           ctr[:, 2] + rr, ctr[:, 3] + rr], axis=1)
            # pow2 query bucket (repeating the last row): each bucket
            # compiles once and shares the intersects pipeline's cache
            bucket = 1 << max(len(todo) - 1, 0).bit_length()
            if bucket > len(todo):
                padq = bucket - len(todo)
                sq = np.concatenate([sq, np.repeat(sq[-1:], padq, 0)])
                ctr = np.concatenate([ctr, np.repeat(ctr[-1:], padq, 0)])
                rr = np.concatenate([rr, np.repeat(rr[-1:], padq, 0)])
            probes[todo] += 1
            # tier 1: ONE fixed-budget dispatch for every undone point.
            # Typical rows fit — a fat row (a square that swallowed a dense
            # core) signals a negative count and is re-dispatched below in
            # its own small batch, so one straggler never drags the whole
            # batch onto a wide budget.
            c1 = ladder.cap
            ub = b0 if b0 > 0 and (ladder.capless or b0 < c1) else 0
            hits, ch = eng.batch_query(
                snap, jnp.asarray(sq), pods, mb, relation="intersects",
                cap=c1, exact_budget=ub, compaction=comp)
            st.dispatches += 1
            st.add_note(_attempt_note(comp, ub))
            ch = np.asarray(ch)[: len(todo)]
            good = ch >= 0
            idk, dk, within = batch_knn_rank(
                jnp.asarray(ctr), pods, hits, jnp.asarray(rr), k, impl,
                tombstones=tomb, delta=dtab)
            st.dispatches += 1
            idk = np.array(idk[: len(todo)])     # writable: fat rows splice
            dk = np.array(dk[: len(todo)])
            within = np.array(within[: len(todo)])
            fat = np.nonzero(~good)[0]
            if fat.size:
                # tier 2: only the overflowed rows walk the escalating
                # ladder (cap/budget grow to fit THEM, nobody else pays).
                # The negative-count encoding already carries each fat
                # row's TRUE survivor count, so the budget is right-sized
                # for THIS rung's fat set up front — never the high-water
                # mark of an earlier, fatter rung
                need = int((-ch[fat] - 1).max())
                ladder.set_budget(1 << max(need - 1, b0 - 1, 1).bit_length())
                fb = 1 << max(fat.size - 1, 0).bit_length()
                fi = (np.concatenate([fat, np.repeat(fat[-1:],
                                                     fb - fat.size)])
                      if fb > fat.size else fat)
                try:
                    fhits = _knn_refine(eng, idx, snap, pods, mb,
                                        jnp.asarray(sq[fi]), comp, ladder,
                                        st)
                except OverflowError:
                    # a straggler's radius outgrew max_cap: the host loop
                    # has no cap — finish the stragglers there instead of
                    # failing the whole batch
                    st.add_note("straggler radius outgrew max_cap: "
                                "host fallback")
                    with idx._lock:
                        for i in todo[fat]:
                            hi, hd = _host_knn(idx.glin, pts[int(i)], k)
                            out_ids[int(i)] = np.asarray(hi, np.int64)
                            out_d[int(i)] = np.asarray(hd)
                    done[todo[fat]] = True
                else:
                    fidk, fdk, fwit = batch_knn_rank(
                        jnp.asarray(ctr[fi]), pods, fhits,
                        jnp.asarray(rr[fi]), k, impl,
                        tombstones=tomb, delta=dtab)
                    st.dispatches += 1
                    idk[fat] = np.asarray(fidk)[: fat.size]
                    dk[fat] = np.asarray(fdk)[: fat.size]
                    within[fat] = np.asarray(fwit)[: fat.size]
                    good[fat] = True
            settle = good & ((within >= k) | (within >= n_live))
            for j in np.nonzero(settle)[0]:
                i = int(todo[j])
                keep = idk[j] >= 0
                out_ids[i] = idk[j][keep].astype(np.int64)
                out_d[i] = dk[j][keep].astype(np.float64)
            done[todo[settle]] = True
            und = np.nonzero(~settle)[0]
            if und.size:
                # count-informed radius growth: an undone row already has
                # the exact distances of its `within` (< k) nearest, so the
                # 2D density scaling d_within * sqrt(k / within) estimates
                # the k-th-neighbour radius directly. Clamped to [2r, 4r]:
                # at least the doubling backstop, at most one quadrupling
                # (which is also what an empty row — a point still racing
                # across empty space toward the data — takes).
                ru = radius[todo[und]]
                cnt = within[und].astype(np.float64)
                dlast = dk[und, np.maximum(within[und] - 1, 0)]
                est = np.where(
                    cnt > 0,
                    dlast.astype(np.float64)
                    * np.sqrt(k / np.maximum(cnt, 1.0)),
                    np.inf)
                radius[todo[und]] = np.maximum(
                    2.0 * ru, np.minimum(est, 4.0 * ru))
        else:
            raise RuntimeError("knn did not converge")
        ctx.ids, ctx.distances = out_ids, out_d
        st.survivors = _total(out_ids)
        st.escalations = ladder.escalations
        st.cap, st.budget = ladder.cap, ladder.use_budget
        maxp = int(probes.max()) if q else 0
        st.rungs = maxp
        st.rung_hist = tuple(int((probes == i).sum())
                             for i in range(1, maxp + 1))
        st.seed_hits = int((probes == 1).sum())


def _knn_refine(eng, idx, snap, pods, mb, wj, comp, ladder, st):
    """One knn rung through the staged device refine — per-point inflated
    square windows over the plain ``intersects`` pipeline — under the
    shared overflow ladder (DeviceRefineStage's retry contract).
    The hit matrix STAYS ON DEVICE — the caller hands it straight to
    ``batch_knn_rank``; only the overflow-signal counts cross to the host."""
    while True:
        ub = ladder.use_budget
        hits, counts = eng.batch_query(
            snap, wj, pods, mb, relation="intersects",
            cap=ladder.cap, exact_budget=ub, compaction=comp)
        st.dispatches += 1
        st.add_note(_attempt_note(comp, ub))
        ch = np.asarray(counts)
        if (ch >= 0).all():
            with idx._lock:
                idx._cap = max(idx._cap, ladder.cap)
            return hits
        if ladder.on_device_overflow(
                ch, ub,
                lambda: eng.batch_query_bounds(snap, wj,
                                               relation="intersects"),
                wj.shape[0]):
            st.dispatches += 1                # disambiguating bounds probe


class KnnShardedStage(Stage):
    """Device-complete knn over the mesh: every record shard ranks its own
    dwithin survivors to a local ``(Q, k)`` block INSIDE the shard_map
    (exact squared distances gathered from the shard-local vertex pool at
    the widest surviving width bucket), then ONE collective all-gathers the
    ``(shards, Q, k)`` blocks for a replicated two-key k-merge — the host
    sees only the final ``(Q, k)`` ids + distances plus the per-shard
    within-radius counts driving the ladder. ``merge_bytes`` accounts the
    collective's payload (the ``roofline_terms`` collective term of
    ``kernels.refine.sharded_knn_cost``).

    Exactness contract: the sharded k-merge ranks SNAPSHOT records only, so
    a stale snapshot is always republished before probing — the fresh
    snapshot has no delta to merge, and results are exact at the published
    epoch. Same per-point seeding as :class:`KnnDeviceStage`; each rung
    probes every undone point in one dispatch at its own radius (a traced
    input, so all radii share one compile), doubling the radius of every
    point still short of k."""

    name = "knn-rank"
    covers = ("probe", "compact", "refine", "knn-rank")
    impl = "sharded"
    dispatches = 1

    def run(self, ctx: ExecContext, st: StageStats) -> None:
        idx, batch = ctx.index, ctx.batch
        cfg = idx.config
        pts = np.asarray(batch.points, np.float64)
        q, k = len(batch), int(batch.k)
        wins = np.concatenate([pts, pts], axis=1)
        with idx._lock:     # the mesh owns every device: run under the lock
            if idx.snapshot_is_stale():
                idx.snapshot()         # k-merge exactness: no delta on top
            else:
                idx._published_snapshot()
            snap_repl, table, shards, maxw = idx._sharded_placement()
            snap = idx._snapshot
            ctx.snap = snap
            ctx.epoch = idx._epoch
            n_live = idx.glin.num_records
            r_global = initial_knn_radius(idx.glin, k)
            seed_mode, _ = _knn_backstop(idx, cfg)
            comp = idx._compaction("intersects")
            if comp == "sort":   # legacy argsort baseline: 1-device only
                comp = "scan"
            ladder = OverflowLadder(cfg, idx._cap, max_budget=cfg.max_cap,
                                    capless=_capless(comp))
            m = cfg.mesh.shape["model"]
            ctx.ids = [np.empty(0, np.int64) for _ in range(q)]
            ctx.distances = [np.empty(0, np.float64) for _ in range(q)]
            if k <= 0 or n_live == 0 or q == 0:
                st.survivors = 0
                return
            radius = _seed_radii(snap, wins, q, k, seed_mode, r_global, st)
            st.seed_radius = float(np.median(radius))
            st.add_note(f"seed={seed_mode}")
            done = np.zeros(q, bool)
            probes = np.zeros(q, np.int32)
            out_ids: List[Optional[np.ndarray]] = list(ctx.ids)
            out_d: List[Optional[np.ndarray]] = list(ctx.distances)
            for _ in range(64):
                todo = np.nonzero(~done)[0]
                if todo.size == 0:
                    break
                # every undone point in ONE dispatch, each at its own
                # radius, padded to the WHOLE batch's pow2 bucket (a
                # model-axis multiple: shard_map divides Q evenly) so later
                # rungs reuse the first rung's compiled step
                sub = wins[todo].astype(np.float32)
                rr = radius[todo].astype(np.float32)
                b = 1 << max(q - 1, 0).bit_length()
                b += (-b) % m
                if b > len(todo):
                    sub = np.concatenate(
                        [sub, np.repeat(sub[-1:], b - len(todo), 0)])
                    rr = np.concatenate(
                        [rr, np.repeat(rr[-1:], b - len(todo))])
                probes[todo] += 1
                try:
                    idk, dk, within = self._rank(
                        idx, snap_repl, table, jnp.asarray(sub),
                        jnp.asarray(rr), k, maxw, comp, ladder, st, b,
                        shards)
                except OverflowError:
                    st.add_note("straggler radius outgrew max_cap: "
                                "host fallback")
                    for i in todo:
                        hi, hd = _host_knn(idx.glin, pts[int(i)], k)
                        out_ids[int(i)] = np.asarray(hi, np.int64)
                        out_d[int(i)] = np.asarray(hd)
                    done[todo] = True
                    continue
                idk = idk[: len(todo)]
                dk = dk[: len(todo)]
                within = within[: len(todo)]
                settle = (within >= k) | (within >= n_live)
                for j in np.nonzero(settle)[0]:
                    i = int(todo[j])
                    keep = idk[j] >= 0
                    out_ids[i] = idk[j][keep].astype(np.int64)
                    out_d[i] = dk[j][keep].astype(np.float64)
                done[todo[settle]] = True
                radius[~done] *= 2.0
            else:
                raise RuntimeError("knn did not converge")
        ctx.ids, ctx.distances = out_ids, out_d
        st.survivors = _total(out_ids)
        st.escalations = ladder.escalations
        st.cap, st.budget = ladder.cap, ladder.use_budget
        maxp = int(probes.max()) if q else 0
        st.rungs = maxp
        st.rung_hist = tuple(int((probes == i).sum())
                             for i in range(1, maxp + 1))
        st.seed_hits = int((probes == 1).sum())

    @staticmethod
    def _rank(idx, snap_repl, table, wj, rj, k, maxw, comp, ladder, st,
              qpad, shards):
        """One sharded probe+rank+k-merge dispatch under the ladder. Caller
        holds the facade lock (ShardedRefineStage's contract)."""
        while True:
            ub = ladder.use_budget
            step = idx._sharded_knn_step(k, ladder.cap, ub, comp, maxw)
            idk, dk, counts = step(snap_repl, wj, rj, table)
            st.dispatches += 1
            st.add_note(_attempt_note(comp, ub))
            # all-gathered (shards, Q, k) blocks — k f32 distances + k i32
            # ids per shard — plus the (Q, shards) i32 counts
            st.merge_bytes += qpad * shards * (k * 8 + 4)
            counts = np.asarray(counts)
            if (counts >= 0).all():
                idx._cap = max(idx._cap, ladder.cap)
                return (np.asarray(idk), np.asarray(dk),
                        counts.sum(axis=1))
            ladder.on_sharded_overflow(counts, ub)


# ------------------------------------------------------------- execution plan
@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """The compiled stage composition for one planned backend."""

    backend: str
    stages: Tuple[Stage, ...]

    def execute(self, ctx: ExecContext) -> ExecContext:
        for stage in self.stages:
            st = StageStats(stage=stage.name, impl=stage.impl,
                            covers=stage.covers, queries=len(ctx.batch))
            t0 = time.perf_counter()
            stage.run(ctx, st)
            st.wall_ms = 1e3 * (time.perf_counter() - t0)
            ctx.stage_stats.append(st)
        return ctx

    def describe(self) -> List[str]:
        return [f"{i}. {s.name:<18} impl={s.impl:<8} "
                f"covers={'+'.join(s.covers)}"
                + (f" dispatches={s.dispatches}" if s.dispatches else "")
                for i, s in enumerate(self.stages)]


def compile_plan(plan) -> ExecutionPlan:
    """``QueryPlan`` -> ordered stage tuple. Every backend ends in the SAME
    shared delta-patch / complement-finish implementations; conditional
    stages (an empty delta, a non-complement relation) stay compiled in and
    no-op with ``skipped=True`` so the pipeline shape is static per
    backend."""
    if plan.kind == "knn":
        if plan.backend == "sharded":
            stage: Stage = KnnShardedStage()
        elif plan.backend in ("device", "device+delta"):
            stage = KnnDeviceStage()
        else:
            stage = KnnHostStage()
        return ExecutionPlan(plan.backend, (stage,))
    if plan.backend == "host":
        return ExecutionPlan("host", (HostRefineStage(),
                                      ComplementFinishStage()))
    if plan.backend == "device":
        refine = (FusedDeviceStage() if getattr(plan, "fused", False)
                  else DeviceRefineStage())
        return ExecutionPlan("device", (refine, ComplementFinishStage()))
    if plan.backend == "device+delta":
        refine = (FusedDeviceStage() if getattr(plan, "fused", False)
                  else DeviceRefineStage())
        return ExecutionPlan("device+delta", (refine,
                                              DeltaPatchStage(),
                                              ComplementFinishStage()))
    if plan.backend == "sharded":
        return ExecutionPlan("sharded", (ShardedRefineStage(),
                                         DeltaPatchStage(),
                                         ComplementFinishStage()))
    raise ValueError(f"unknown backend {plan.backend!r}")
