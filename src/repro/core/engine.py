"""`SpatialIndex` — the one public way to build, mutate, snapshot and query.

The paper's mechanism (probe an interval, refine with a predicate) is the same
whether one window runs on the host or ten thousand run on a TPU; what differed
in this repo was plumbing: the mutable host ``GLIN`` answered one window at a
time while callers hand-stitched ``snapshot_from_host`` + ``batch_query`` for
the device path. This facade owns all of it:

* **relations** are first-class (``core.relations``): ``contains``,
  ``intersects``, ``within``, ``covers``, ``disjoint`` — plus ``knn`` as a
  query *kind* — all through one entry point, ``SpatialIndex.query``;
* **snapshots are epoch-invalidated**: every insert/delete bumps a mutation
  epoch; the flattened device snapshot is materialized lazily and republished
  automatically when stale, so a stale snapshot is never served;
* **writes are LSM-style deltas** (DESIGN.md §2): ALEX-style in-place mutation
  does not map onto immutable device arrays — per-record scatter into a sorted
  device array is O(N). Instead every insert/delete is applied to the host
  ``GLIN`` immediately (host queries are always exact) and recorded in a small
  delta against the last *published* snapshot: inserted record ids in an
  added-set, deleted published records in a tombstone-set. Device queries can
  then be served from the stale snapshot and *patched* — tombstones masked
  out, added records brute-force checked (the delta is tiny, a vectorized
  fp32 mask) — instead of paying a full republish per write. Once the delta
  grows past ``EngineConfig.refresh_threshold`` the snapshot is republished
  (bulk re-flatten, a few ms of vectorized work, amortized O(1)/update);
* **execution is planned, then staged**: ``plan(batch)`` picks a backend
  (host loop for small or stats-collecting batches and knn; jitted device
  ``batch_query`` for large batches against a fresh or republished
  snapshot; ``device+delta`` for a stale snapshot with a small delta;
  ``sharded`` when a mesh is active) and ``core.exec.compile_plan`` turns
  the choice into an :class:`~repro.core.exec.ExecutionPlan` — an ordered
  stage composition (refine -> delta-patch -> complement-finish) with ONE
  shared overflow-ladder/patch/complement implementation across backends
  and per-stage telemetry on every result (``QueryResult.stages``,
  ``stats()["stages"]``, :meth:`SpatialIndex.explain`); the adaptive
  candidate ``cap`` is shared by all device modes, and
  ``count_candidates`` routes through the Pallas refine kernel on TPU;
* **precision**: host execution refines in fp64; device execution refines in
  fp32 (results can differ at exact window boundaries, by design — the probe
  interval is quantized conservatively so hits are never missed, see
  ``core.device``).

Typical use::

    from repro.core import SpatialIndex, QueryBatch, generate, make_query_windows

    index = SpatialIndex.build(generate("cluster", 100_000))
    res = index.query(make_query_windows(index.gs, 1e-3, 256), "intersects")
    ids0 = res[0]                       # hits of window 0, ascending record id
    nn = index.query(QueryBatch.knn([[0.5, 0.5]], k=10))
    rec = index.insert(verts, nverts=8, kind=0)   # bumps the epoch
    res = index.query(windows, "contains")        # snapshot auto-rebuilt
"""
from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import exec as qexec
from .datasets import GeometrySet
# batch_query is re-exported for the exec stages (and tests), which resolve
# it through THIS module's namespace so a monkeypatched binding is honored
from .device import batch_query, batch_query_fused  # noqa: F401
from .device import (DeltaTable, GLINSnapshot, HostCapture, _pow2ceil,
                     batch_query_bounds, delta_table_from_host,
                     pods_from_store, snapshot_capture, snapshot_from_capture)
from .index import GLIN, GLINConfig, QueryStats
from .relations import get_relation

__all__ = ["EngineConfig", "QueryBatch", "QueryPlan", "QueryResult",
           "SpatialIndex"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Planner / execution knobs for :class:`SpatialIndex`."""

    device_min_batch: int = 16        # smaller window batches run on host
    stale_rebuild_min_batch: int = 64  # stale + unpatchable: republish only
                                       # for batches this big, else host
    initial_cap: int = 4096           # device candidate capacity per query
    max_cap: int = 1 << 20            # give up (OverflowError) past this
    exact_budget: int = 256           # two-stage refinement budget (0 = off):
                                      # stage 1 masks + compacts, stage 2
                                      # exact-checks at most this many
                                      # candidates per query
    compaction: Optional[str] = None  # stage-1 impl: "pallas" (compiled
                                      # compact kernel), "interpret" (that
                                      # kernel in Pallas interpret mode),
                                      # "scan" (jnp reference), "sort"
                                      # (legacy argsort); None = pallas on
                                      # TPU, scan elsewhere
    fusion: Optional[str] = None      # "reference": refine with the
                                      # capless whole-table XLA composition
                                      # (batch_query_fused, O(Q·N) memory —
                                      # small stores, CPU timing); "off" or
                                      # None: the staged batch_query, whose
                                      # "pallas" compaction already runs
                                      # probe + kernel + exact stage as one
                                      # jitted program. Custom-prefilter
                                      # relations and dense escalations run
                                      # staged either way
    delta_device_min: int = 64        # added-set size at which device+delta
                                      # patching moves from the host loop to
                                      # the device-resident DeltaTable
    knn_device_min_batch: int = 16    # knn point batches this big run
                                      # device-complete (seeded dwithin
                                      # probes + on-device top-k ranking);
                                      # smaller ones loop on the host
    knn_seed: Optional[str] = None    # initial knn radius selection: "cdf"
                                      # (per-point density seed read off the
                                      # published learned model — leaf count
                                      # over leaf-MBR area) or "global" (one
                                      # whole-store density estimate); None =
                                      # cdf. Either way the doubling ladder
                                      # is the correctness backstop: a bad
                                      # seed costs extra rungs, never hits
    knn_topk: Optional[str] = None    # device top-k impl: "sort" (two-key
                                      # lax.sort reference), "pallas" (the
                                      # k-round partial-selection kernel —
                                      # wins when k << candidate columns) or
                                      # "interpret" (that kernel in interpret
                                      # mode);
                                      # None = auto: pallas on TPU, sort
                                      # elsewhere. Both obey the (distance,
                                      # id) tie-break contract
    pad_quantum: int = 4096           # bucket-pad record/slot array lengths so
                                      # insert-driven growth does not change
                                      # jitted shapes (0 disables padding)
    delta_patch_max: int = 4096       # patch a stale snapshot instead of
                                      # republishing while the delta (added +
                                      # tombstoned records) is at most this
                                      # (0 disables delta patching)
    refresh_threshold: int = 4096     # delta size at which the planner prefers
                                      # a republish over patching (0 means
                                      # republish on every stale query)
    mesh: Optional[Any] = None        # jax Mesh with a "model" axis (query
                                      # sharding) and a "data"/"pod" axis
                                      # (record sharding): activates the
                                      # "sharded" planner backend
    shard_min_records: int = 1 << 16  # below this the single-device path
                                      # beats per-shard dispatch overhead;
                                      # the sharded backend is not chosen
    async_republish: bool = False     # double-buffered snapshots: a stale
                                      # delta past refresh_threshold builds
                                      # the NEXT snapshot on a background
                                      # thread while queries keep serving the
                                      # current snapshot + delta patch; the
                                      # finished build swaps in atomically
    replicas: int = 1                 # independent device placements of the
                                      # published snapshot + geometry payload
                                      # for serving fan-out: every publish
                                      # (sync or async swap) refreshes all of
                                      # them from the same HostCapture;
                                      # query(..., replica=r) serves placement
                                      # r (round-robin over jax.devices(); on
                                      # a single device the copies alias the
                                      # primary buffers, costing nothing but
                                      # enabling concurrent callers)


@dataclasses.dataclass(frozen=True)
class QueryBatch:
    """One or many queries of one kind against one relation.

    Build with :meth:`window` / :meth:`knn`; ``backend`` forces a specific
    execution path (benchmarks, tests), otherwise the planner decides.
    """

    kind: str = "window"                    # "window" | "knn"
    windows: Optional[np.ndarray] = None    # (Q, 4) fp64
    relation: str = "intersects"
    points: Optional[np.ndarray] = None     # (Q, 2) fp64, knn only
    k: int = 1
    backend: Optional[str] = None     # force "host"/"device"/"device+delta"/
                                      # "sharded"
    collect_stats: bool = False             # per-window QueryStats (host path)

    @classmethod
    def window(cls, windows, relation: str = "intersects",
               backend: Optional[str] = None,
               collect_stats: bool = False) -> "QueryBatch":
        w = np.atleast_2d(np.asarray(windows, np.float64))
        if w.ndim != 2 or w.shape[1] != 4:
            raise ValueError(f"windows must be (Q, 4); got {w.shape}")
        get_relation(relation)  # fail fast on unknown relations
        return cls(kind="window", windows=w, relation=relation,
                   backend=backend, collect_stats=collect_stats)

    @classmethod
    def knn(cls, points, k: int,
            backend: Optional[str] = None) -> "QueryBatch":
        p = np.atleast_2d(np.asarray(points, np.float64))
        if p.ndim != 2 or p.shape[1] != 2:
            raise ValueError(f"points must be (Q, 2); got {p.shape}")
        return cls(kind="knn", points=p, k=int(k), backend=backend)

    def __len__(self) -> int:
        arr = self.windows if self.kind == "window" else self.points
        return 0 if arr is None else int(arr.shape[0])


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """How a batch will execute (returned by ``plan``, recorded on results)."""

    backend: str                  # "host" | "device" | "device+delta" |
                                  # "sharded"
    kind: str                     # "window" | "knn"
    relation: Optional[str]       # None for knn
    base_relation: Optional[str]  # probed relation (complements differ)
    rebuild_snapshot: bool        # device path will republish the snapshot
    reason: str
    delta_size: int = 0           # added + tombstoned records vs the snapshot
    fused: bool = False           # device refine compiles to the one-dispatch
                                  # FusedDeviceStage (EngineConfig.fusion)


@dataclasses.dataclass
class QueryResult:
    """Per-query hit ids (ascending record id) plus execution metadata."""

    ids: List[np.ndarray]
    plan: QueryPlan
    epoch: int                                  # index epoch that was served
    stats: Optional[List[QueryStats]] = None    # host path, when requested
    distances: Optional[List[np.ndarray]] = None  # knn only
    stages: Optional[List["qexec.StageStats"]] = None  # per-stage telemetry
    # (wall time, survivors, ladder escalations, delta sizes) of the
    # executed ExecutionPlan, in stage order

    def __len__(self) -> int:
        return len(self.ids)

    def __getitem__(self, i: int) -> np.ndarray:
        return self.ids[i]

    def __iter__(self) -> Iterator[np.ndarray]:
        return iter(self.ids)

    @property
    def total_hits(self) -> int:
        return int(sum(r.shape[0] for r in self.ids))


@dataclasses.dataclass
class _InflightPublish:
    """A double-buffered snapshot build running on a background thread.

    ``capture`` is the synchronous host flattening at ``epoch``; the thread
    turns it into the padded snapshot (+ the sharded placement when a mesh is
    active) and sets ``done``. ``tombs_after`` collects records deleted while
    the build runs that the PENDING snapshot contains (``rec < recs``) — they
    become the tombstone set of the swapped-in snapshot."""

    capture: HostCapture
    epoch: int
    recs: int
    done: threading.Event
    tombs_after: Set[int]
    thread: Optional[threading.Thread] = None
    snapshot: Optional[GLINSnapshot] = None
    table_np: Optional[Dict[str, np.ndarray]] = None
    error: Optional[BaseException] = None


class SpatialIndex:
    """Facade over the host ``GLIN`` + lazily-materialized device snapshot.

    All mutations MUST go through :meth:`insert` / :meth:`delete` so the
    mutation epoch tracks the host structure; the device snapshot and device
    geometry payload are invalidated by epoch and rebuilt on demand.

    Thread-safe for concurrent callers (the serving tier drives it from many
    worker threads): writes and the query prologue (planning, snapshot
    install/swap, delta freezing) serialize on one internal lock, while the
    heavy device compute of the ``device``/``device+delta`` backends runs
    OUTSIDE it against frozen immutable state — a ``device`` query is exact
    at the epoch frozen under the lock, and concurrent writers are never
    blocked by device execution. The host, sharded and knn paths hold the
    lock for their whole run (they walk the mutable host tree, or own every
    mesh device anyway). The ``async_republish`` machinery runs the snapshot
    REBUILD on a background thread; all state transitions (start, swap)
    happen under the lock at query boundaries.
    """

    def __init__(self, glin: GLIN, config: Optional[EngineConfig] = None):
        self.glin = glin
        self.config = config or EngineConfig()
        # one reentrant lock guards every mutable facade field AND the host
        # tree (writers mutate leaves in place); device compute runs outside
        self._lock = threading.RLock()
        self._epoch = 0
        self._snapshot: Optional[GLINSnapshot] = None
        self._snapshot_epoch = -1
        self._snapshot_recs = 0         # store length at publish time
        self._publishes = 0             # snapshot (re)publish count
        # delta vs the last published snapshot (LSM-style patch-not-rebuild)
        self._added: Set[int] = set()   # record ids inserted since publish
        self._tombstones: Set[int] = set()  # published records deleted since
        self._dtable: Optional[DeltaTable] = None  # device added-set index
        self._dtable_epoch = -1
        self._payload = None
        self._payload_key: Optional[Tuple[int, int]] = None
        # (real records, store layout generation)
        # adaptive candidate capacity: remembered across queries so the
        # overflow ladder (cap doubling) is walked once, not per call
        self._cap = self.config.initial_cap
        # host capture backing the published snapshot (sharded placement src)
        self._capture: Optional[HostCapture] = None
        # double-buffered republish in flight (async_republish)
        self._inflight: Optional[_InflightPublish] = None
        # sticky floors for the snapshot's STATIC jit fields: search_steps /
        # depth may shrink after a refit, but serving the larger value is
        # still correct (extra bounded-search/traversal trips no-op) and
        # keeps the jit signature stable across republishes
        self._steps_floor = 0
        self._depth_floor = 0
        # sticky floors for the geometry payload's STATIC shapes: the pod
        # pool may SHRINK at a compacting republish and the width ladder
        # may shrink after wide records die — serving the larger padded
        # shape is still correct (pad slots are never gathered) and keeps
        # the jitted query signature stable across republishes
        self._pool_floor = 0
        self._width_floor = 1
        self._shard_pool_floor = 0
        # sharded backend caches: jitted steps per (relation, cap, budget,
        # compaction); device placement (replicated model snapshot + sharded
        # record table) per publish
        self._shard_steps: Dict[Tuple, Any] = {}
        self._shard_placement: Optional[Tuple] = None   # (publish_id, ...)
        self._staged_table: Optional[Dict[str, np.ndarray]] = None
        # replica placements (config.replicas > 1): per replica r >= 1 a
        # device_put copy of the published snapshot + payload, keyed on the
        # (publish, payload) generation it was fanned out from
        self._replica_places: Dict[int, Tuple] = {}
        # per-(backend, stage) telemetry aggregates (stats()["stages"]):
        # calls, wall_ms, queries, survivors, ladder escalations, delta sizes
        self._stage_totals: Dict[str, Dict[str, Dict[str, float]]] = {}

    # ------------------------------------------------------------------ build
    @classmethod
    def build(cls, gs: GeometrySet, glin_cfg: GLINConfig = GLINConfig(),
              config: Optional[EngineConfig] = None) -> "SpatialIndex":
        return cls(GLIN.build(gs, glin_cfg), config)

    @property
    def gs(self) -> GeometrySet:
        return self.glin.gs

    def __len__(self) -> int:
        return self.glin.num_records

    def stats(self) -> dict:
        with self._lock:
            st = self.glin.stats()
            st["epoch"] = self._epoch
            st["snapshot_epoch"] = self._snapshot_epoch
            st["snapshot_stale"] = self.snapshot_is_stale()
            st["delta_size"] = self.delta_size()
            st["snapshot_publishes"] = self._publishes
            st["republish_inflight"] = self._inflight is not None
            st["replicas"] = max(1, self.config.replicas)
            st["stages"] = {b: {s: dict(v) for s, v in per.items()}
                            for b, per in self._stage_totals.items()}
            return st

    def _record_stages(self, backend: str,
                       stage_stats: List["qexec.StageStats"]) -> None:
        """Fold one execution's per-stage telemetry into the aggregates
        surfaced by ``stats()["stages"]`` (keyed backend -> stage label)."""
        with self._lock:
            per = self._stage_totals.setdefault(backend, {})
            for ss in stage_stats:
                ent = per.setdefault(ss.stage, {
                    "impl": ss.impl, "calls": 0, "skipped": 0,
                    "wall_ms": 0.0, "queries": 0, "survivors": 0,
                    "escalations": 0, "dispatches": 0, "delta_added": 0,
                    "delta_tombstoned": 0, "rungs": 0, "seed_hits": 0,
                    "merge_bytes": 0, "rung_hist": [], "notes": []})
                ent["calls"] += 1
                ent["wall_ms"] += ss.wall_ms
                # the executing impl may differ per call (staged vs fused
                # refine share the "refine" label): report the latest
                ent["impl"] = ss.impl
                # every distinct note (implementation picked, fallbacks
                # taken): a fallback in one call must not hide behind a
                # later call's note
                if ss.note and ss.note not in ent["notes"]:
                    ent["notes"].append(ss.note)
                if ss.skipped:
                    ent["skipped"] += 1
                    continue
                ent["queries"] += ss.queries
                ent["survivors"] += max(ss.survivors, 0)
                ent["escalations"] += ss.escalations
                ent["dispatches"] += ss.dispatches
                ent["delta_added"] += ss.delta_added
                ent["delta_tombstoned"] += ss.delta_tombstoned
                # knn-rank seeding/merge telemetry (zero for window stages):
                # rung_hist sums element-wise — entry i is the points that
                # settled after i+1 probes, so hist[0]/queries is the seed
                # hit-rate across every call
                ent["rungs"] += ss.rungs
                ent["seed_hits"] += ss.seed_hits
                ent["merge_bytes"] += ss.merge_bytes
                hist = ent["rung_hist"]
                for i, v in enumerate(ss.rung_hist):
                    if i < len(hist):
                        hist[i] += v
                    else:
                        hist.append(v)

    # ------------------------------------------------------------ maintenance
    def insert(self, verts: np.ndarray, nverts: int, kind: int = 0) -> int:
        with self._lock:
            rec = self.glin.insert(verts, nverts, kind)
            self._epoch += 1
            self._added.add(rec)
            return rec

    def delete(self, rec: int) -> bool:
        with self._lock:
            ok = self.glin.delete(rec)
            if ok:
                self._epoch += 1
                if rec in self._added:
                    self._added.remove(rec)
                elif rec < self._snapshot_recs:
                    self._tombstones.add(rec)
                # else: the record was never published nor added since the
                # last publish — it cannot appear in snapshot results,
                # nothing to patch
                if self._inflight is not None and rec < self._inflight.recs:
                    # the PENDING double-buffered snapshot contains this
                    # record (it was live at capture time): remember it so
                    # the swap installs the correct tombstone set
                    self._inflight.tombs_after.add(rec)
            return ok

    def delta_size(self) -> int:
        """Records added plus published records tombstoned since the last
        snapshot publish (the work a ``device+delta`` query must patch)."""
        return len(self._added) + len(self._tombstones)

    # --------------------------------------------------------------- snapshot
    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def device_cap(self) -> int:
        """Current adaptive per-query candidate capacity of the device path."""
        return self._cap

    @property
    def snapshot_epoch(self) -> int:
        return self._snapshot_epoch

    def snapshot_is_stale(self) -> bool:
        return self._snapshot is None or self._snapshot_epoch != self._epoch

    def _padded(self, n: int) -> int:
        return self._bucket(n, self.config.pad_quantum)

    # bucket quanta for the small model tables (pad_quantum > 0): a republish
    # that grew the tree or the piecewise function keeps the SAME jitted-shape
    # signature as long as each table stays inside its bucket, so the first
    # query after an (async) snapshot swap hits the jit cache instead of
    # paying an XLA recompile
    _LEAF_QUANTUM = 256
    _NODE_QUANTUM = 64
    _CODE_QUANTUM = 256
    _PW_QUANTUM = 1024
    _INF_HI = np.int32(1 << 30)   # > any valid 30-bit limb

    @staticmethod
    def _bucket(n: int, q: int) -> int:
        return n if q <= 0 else max(q, -(-n // q) * q)

    def _pad_snapshot(self, snap: GLINSnapshot) -> GLINSnapshot:
        """Bucket-pad every snapshot table (``EngineConfig.pad_quantum``
        disables all of it when 0).

        * slot arrays — padding slots sit past the ``leaf_start`` sentinel,
          so no probe or candidate window ever reaches them;
        * leaf tables — padding leaves carry +inf domain bounds (the ±2
          routing fix-up can never step onto one), empty ``leaf_start`` runs
          and far-away MBRs;
        * node tables / child codes — only reachable through ``child_codes``
          entries of real nodes, so zero padding is inert;
        * piecewise pieces — +inf ``zmax_end`` (sorts after every real
          piece) with +inf suffix-min (an augmentation landing there is a
          no-op by the ``z_less`` take-test).
        """
        if self.config.pad_quantum <= 0:
            return snap
        reps: dict = {}
        # static jit fields: sticky-monotonic with generous floors (16 steps
        # cover a model-error window of 2^16 slots — clipped to the leaf size
        # anyway — at ~9 extra cheap binary-search gathers per probe).
        # Shrinking them would change the jit signature for no win; growing
        # them stays correct (extra bounded-search / traversal trips no-op),
        # and the floor keeps a republish whose refit grew the model error
        # from recompiling the query. Read-only here (this runs on the build
        # thread too); the floors are COMMITTED in _install_snapshot, on the
        # caller's thread only.
        steps = max(self._steps_floor, snap.search_steps, 16)
        depth = max(self._depth_floor, snap.depth, 8)
        if (steps, depth) != (snap.search_steps, snap.depth):
            reps.update(search_steps=steps, depth=depth)
        # slot arrays
        n = snap.keys_hi.shape[0]
        pad = self._padded(n) - n
        if pad:
            big = jnp.asarray(np.full(pad, (1 << 30) - 1, np.int32))
            far = jnp.full((pad, 4), 2e30, jnp.float32)  # hits nothing
            reps.update(
                keys_hi=jnp.concatenate([snap.keys_hi, big]),
                keys_lo=jnp.concatenate([snap.keys_lo, big]),
                recs=jnp.concatenate([snap.recs, jnp.zeros(pad, jnp.int32)]),
                rec_leaf=jnp.concatenate(
                    [snap.rec_leaf,
                     jnp.full(pad, snap.num_leaves - 1, jnp.int32)]),
                slot_lmbr=jnp.concatenate([snap.slot_lmbr, far]),
                slot_rmbr=jnp.concatenate([snap.slot_rmbr, far]),
            )
        # leaf tables ((L,) and (L+1,) shapes share one bucket). The domain
        # sentinel dlo[L] (the last leaf's nominal dhi) is REPLACED together
        # with the pads by a strictly-infinite bound: inserted keys may
        # legitimately exceed the nominal dhi (the host tree stores them in
        # the last leaf), and without padding it was the fix-up's clamp to
        # ``num_leaves - 1`` that kept such probes on the last REAL leaf —
        # the infinite sentinel reproduces exactly that, so the ±2 routing
        # fix-up can never step onto a (empty-windowed) pad leaf.
        L = snap.num_leaves
        lb = self._bucket(L, self._LEAF_QUANTUM)
        if lb > L:
            inf_lo = jnp.full(lb + 1 - L, 1 << 30, jnp.int32)
            reps.update(
                leaf_dlo_hi=jnp.concatenate(
                    [snap.leaf_dlo_hi[:L],
                     jnp.full(lb + 1 - L, self._INF_HI, jnp.int32)]),
                leaf_dlo_lo=jnp.concatenate(
                    [snap.leaf_dlo_lo[:L], inf_lo]),
                leaf_start=jnp.concatenate(
                    [snap.leaf_start,
                     jnp.full(lb - L, snap.leaf_start[-1], jnp.int32)]),
                leaf_mbr=jnp.concatenate(
                    [snap.leaf_mbr, jnp.full((lb - L, 4), 2e30,
                                             jnp.float32)]),
                leaf_k0_hi=jnp.concatenate(
                    [snap.leaf_k0_hi, jnp.zeros(lb - L, jnp.int32)]),
                leaf_k0_lo=jnp.concatenate(
                    [snap.leaf_k0_lo, jnp.zeros(lb - L, jnp.int32)]),
                leaf_slope=jnp.concatenate(
                    [snap.leaf_slope, jnp.zeros(lb - L, jnp.float32)]),
                leaf_icpt=jnp.concatenate(
                    [snap.leaf_icpt, jnp.zeros(lb - L, jnp.float32)]),
            )
        # node tables + child codes (reachable only via real child_codes)
        M = snap.node_scale.shape[0]
        mb = self._bucket(M, self._NODE_QUANTUM)
        if mb > M:
            k = mb - M
            reps.update(
                node_dlo_hi=jnp.concatenate(
                    [snap.node_dlo_hi, jnp.zeros(k, jnp.int32)]),
                node_dlo_lo=jnp.concatenate(
                    [snap.node_dlo_lo, jnp.zeros(k, jnp.int32)]),
                node_scale=jnp.concatenate(
                    [snap.node_scale, jnp.zeros(k, jnp.float32)]),
                node_fanout=jnp.concatenate(
                    [snap.node_fanout, jnp.ones(k, jnp.int32)]),
                node_child_base=jnp.concatenate(
                    [snap.node_child_base, jnp.zeros(k, jnp.int32)]),
            )
        C = snap.child_codes.shape[0]
        cb = self._bucket(C, self._CODE_QUANTUM)
        if cb > C:
            reps["child_codes"] = jnp.concatenate(
                [snap.child_codes, jnp.zeros(cb - C, jnp.int32)])
        # piecewise pieces (only when the function exists at all)
        Pn = snap.pw_zmax_hi.shape[0]
        pb = self._bucket(Pn, self._PW_QUANTUM) if Pn else 0
        if pb > Pn:
            k = pb - Pn
            inf = jnp.full(k, self._INF_HI, jnp.int32)
            zero = jnp.zeros(k, jnp.int32)
            reps.update(
                pw_zmax_hi=jnp.concatenate([snap.pw_zmax_hi, inf]),
                pw_zmax_lo=jnp.concatenate([snap.pw_zmax_lo, zero]),
                pw_sufmin_hi=jnp.concatenate([snap.pw_sufmin_hi, inf]),
                pw_sufmin_lo=jnp.concatenate([snap.pw_sufmin_lo, zero]),
            )
        return dataclasses.replace(snap, **reps) if reps else snap

    def snapshot(self) -> GLINSnapshot:
        """The flattened device snapshot at the CURRENT epoch (rebuilds when
        stale; a stale snapshot is never handed out).

        The slot arrays are bucket-padded (``EngineConfig.pad_quantum``) so an
        insert-only epoch bump usually republishes with UNCHANGED shapes and
        the jitted query does not recompile.
        """
        with self._lock:
            if self.snapshot_is_stale():
                # a finished double-buffered build may already BE the current
                # epoch — swap it in instead of rebuilding synchronously
                self._poll_republish()
            if self.snapshot_is_stale():
                cap = snapshot_capture(self.glin)
                self._install_snapshot(
                    self._pad_snapshot(snapshot_from_capture(cap)), cap,
                    self._epoch, added=set(), tombstones=set())
            return self._snapshot

    def _install_snapshot(self, snap: GLINSnapshot, capture: HostCapture,
                          epoch: int, added: Set[int],
                          tombstones: Set[int]) -> None:
        """Atomically publish ``snap`` as the served snapshot (single caller
        thread; every dependent field moves together)."""
        self._snapshot = snap
        self._snapshot_epoch = epoch
        self._snapshot_recs = capture.num_records
        # the capture is only consumed by the sharded placement; without a
        # mesh, retaining it would pin O(N) dead host copies per publish
        self._capture = capture if self.config.mesh is not None else None
        self._publishes += 1
        self._added = added
        self._tombstones = tombstones
        self._dtable = None
        self._dtable_epoch = -1
        # any sharded table staged by a (now superseded) async build belongs
        # to a different capture — serving it would drop post-capture writes
        self._staged_table = None
        # replica placements describe the previous snapshot: refresh lazily
        # (first query routed to each replica fans the new snapshot out)
        self._replica_places.clear()
        # commit the static-field floors on the caller's thread (see
        # _pad_snapshot — the build thread only reads them)
        self._steps_floor = max(self._steps_floor, snap.search_steps)
        self._depth_floor = max(self._depth_floor, snap.depth)

    # ------------------------------------------------- async double-buffering
    @property
    def serving_generation(self) -> Tuple[int, int]:
        """Identity of what a query at this instant would serve: the mutation
        epoch AND the published-snapshot generation. Result caches must key
        on this (not the epoch alone) so an async snapshot swap — which does
        not bump the epoch — can never serve a hit computed against the
        previous snapshot."""
        with self._lock:
            return (self._epoch, self._publishes)

    def republish_inflight(self) -> bool:
        return self._inflight is not None

    def _maintain_async(self) -> None:
        """Per-query async upkeep: swap in a finished double-buffered build,
        then kick off a new one when the delta has crossed the republish
        point. Runs on the caller's thread at the top of :meth:`query`."""
        self._poll_republish()
        cfg = self.config
        if (cfg.async_republish and self._inflight is None
                and self._snapshot is not None and self.snapshot_is_stale()
                and self.delta_size() >= max(cfg.refresh_threshold, 1)):
            self._start_republish()

    def _start_republish(self) -> None:
        """Capture the host tree NOW (synchronous, cheap) and build the next
        snapshot + sharded placement on a daemon thread. Queries keep serving
        the current snapshot + delta until :meth:`_poll_republish` swaps."""
        capture = snapshot_capture(self.glin)
        inf = _InflightPublish(capture=capture, epoch=self._epoch,
                               recs=capture.num_records,
                               done=threading.Event(), tombs_after=set())
        shards = self._shard_count() if self._sharded_available() else 0

        def build():
            try:
                # serve-first: schedule this thread SCHED_IDLE (it runs only
                # on cycles the query threads leave idle — Linux applies it
                # per native TID), falling back to plain niceness. A rebuild
                # stretching a little is fine; query latency spiking is not.
                # On a single-core host SCHED_IDLE is indefinite starvation
                # (a saturated serving thread leaves no idle cycles and the
                # swap never lands), so niceness — a weighted share, not an
                # absolute yield — is the serve-first policy there.
                tid = threading.get_native_id()
                if (os.cpu_count() or 1) > 1:
                    try:
                        os.sched_setscheduler(tid, os.SCHED_IDLE,
                                              os.sched_param(0))
                    except (AttributeError, OSError):
                        os.setpriority(os.PRIO_PROCESS, tid, 10)
                else:
                    os.setpriority(os.PRIO_PROCESS, tid, 10)
            except (AttributeError, OSError, PermissionError):
                pass
            try:
                snap = snapshot_from_capture(capture)
                inf.snapshot = self._pad_snapshot(snap)
                if shards:
                    from .distributed import shard_arrays_from_capture
                    # the sticky per-shard pool floor is read-only here
                    # (committed under the lock in _sharded_placement)
                    inf.table_np = shard_arrays_from_capture(
                        capture, shards,
                        pool_pad_to=self._shard_pool_floor)
            except BaseException as e:   # surfaced on the caller's thread
                inf.error = e
            finally:
                inf.done.set()

        inf.thread = threading.Thread(target=build, daemon=True,
                                      name="glin-republish")
        self._inflight = inf
        inf.thread.start()

    def _poll_republish(self) -> None:
        """Non-blocking: if the background build finished, swap it in. The
        swap is epoch-tagged — a synchronous publish that overtook the build
        (forced rebuild, ``count_candidates``) simply discards it."""
        inf = self._inflight
        if inf is None or not inf.done.is_set():
            return
        self._inflight = None
        inf.thread.join()
        if inf.epoch <= self._snapshot_epoch:
            return   # a newer (or identical) snapshot is already published —
            # the build (even a failed one) is superseded and irrelevant
        if inf.error is not None:
            raise RuntimeError(
                "async snapshot republish failed") from inf.error
        # Post-capture delta: record ids are append-only, so everything
        # inserted after the capture has id >= capture recs; deletes of
        # pending-snapshot records were collected in tombs_after.
        added = {r for r in self._added if r >= inf.recs}
        self._install_snapshot(inf.snapshot, inf.capture, inf.epoch,
                               added=added,
                               tombstones=set(inf.tombs_after))
        if inf.table_np is not None:
            self._staged_table = inf.table_np

    def _published_snapshot(self) -> GLINSnapshot:
        """The last *published* snapshot, possibly behind the current epoch —
        only the ``device+delta`` path may serve it, and only together with
        the tombstone/added patch that restores exactness. Publishes a fresh
        snapshot when none exists yet (the delta is then empty)."""
        if self._snapshot is None:
            return self.snapshot()
        return self._snapshot

    def _device_payload(self, needed_recs: Optional[int] = None):
        """fp32 device copy of the geometry store as width-bucketed
        :class:`~repro.core.device.VertexPods` plus the record-MBR table,
        bucket-padded like the snapshot (padding records are never gathered:
        snapshot ``recs`` only holds real record ids). Keyed on (records,
        store layout generation) rather than the epoch, and reused as long
        as it covers ``needed_recs`` (the store length the snapshot being
        served references): the pool is append-only between compactions, so
        neither deletes nor inserts past the snapshot force a multi-MB
        re-upload — only a compacting republish (layout generation bump)
        rebuilds the payload, which is exactly when the device pool should
        shrink."""
        gs = self.glin.gs
        need = len(gs) if needed_recs is None else needed_recs
        if (self._payload is None
                or self._payload_key[1] != gs.layout_version
                or self._payload_key[0] < need):
            n = len(gs)
            m = self._padded(n)
            # static pod shapes under sticky floors: the width ladder covers
            # the widest live record, the pool covers every record's pow2
            # bucket slots (quantum headroom absorbs insert-driven growth)
            maxw = max(self._width_floor, _pow2ceil(gs.max_nverts))
            nv = np.maximum(gs.nverts.astype(np.int64), 1)
            slots = int(np.sum(np.left_shift(
                1, np.ceil(np.log2(nv)).astype(np.int64))))
            pool_pad = max(self._pool_floor,
                           self._bucket(max(slots, 1),
                                        self.config.pad_quantum))
            pods = pods_from_store(gs, pad_records_to=m,
                                   pool_pad_to=pool_pad, max_width=maxw)
            mbrs = np.zeros((m, 4), np.float32)
            mbrs[:n] = gs.mbrs
            self._payload = (pods, jnp.asarray(mbrs))
            self._payload_key = (n, gs.layout_version)
            self._pool_floor = max(self._pool_floor, pool_pad)
            self._width_floor = max(self._width_floor, maxw)
        return self._payload

    def _replica_view(self, rep: int, snap: GLINSnapshot, payload):
        """Device placement of ``(snap, payload)`` for replica ``rep``.

        Replica 0 is the primary placement (the facade's own fields). Higher
        replicas are ``device_put`` copies fanned out round-robin over
        ``jax.devices()``, built once per (publish, payload) generation from
        the SAME HostCapture-derived snapshot the primary serves — the
        write/delta stream therefore republishes to every replica at the
        same swap. On a single-device host every replica serves the primary
        placement directly: there is no second device to fan out to, and a
        same-device ``device_put`` would commit the arrays, forking the jit
        cache into a recompile per (relation, batch bucket) for zero
        routing benefit. The serving tier's replica routing stays
        meaningful either way (per-replica inflight/telemetry); only the
        physical placement collapses. Call under ``self._lock``."""
        R = max(1, int(self.config.replicas))
        rep = rep % R if R else 0
        if rep <= 0 or jax.device_count() <= 1:
            return snap, payload
        key = (self._publishes, self._payload_key)
        ent = self._replica_places.get(rep)
        if ent is None or ent[0] != key:
            dev = jax.devices()[rep % jax.device_count()]
            ent = (key, jax.device_put(snap, dev),
                   tuple(jax.device_put(p, dev) for p in payload))
            self._replica_places[rep] = ent
        return ent[1], ent[2]

    def _compaction(self, base_relation: str) -> str:
        """Stage-1 refinement implementation for ``batch_query``: the Pallas
        compact kernel on TPU, the jnp reference elsewhere (interpret-mode
        Pallas is a correctness tool, taken only when configured by name),
        and the jnp reference whenever the relation's MBR prefilter has no
        static kernel shape (``prefilter_kind == "custom"``). The kernel
        takes any budget, so the choice does not change along the overflow
        ladder."""
        mode = self.config.compaction
        if mode is None:
            mode = "pallas" if jax.default_backend() == "tpu" else "scan"
        if (mode in ("pallas", "interpret")
                and get_relation(base_relation).prefilter_kind == "custom"):
            mode = "scan"   # no static kernel shape for a custom prefilter
        return mode

    def _fusion_mode(self, base_relation: str,
                     budget: Optional[int] = None) -> Optional[str]:
        """Resolve ``EngineConfig.fusion`` to a ``batch_query_fused`` mode,
        or ``None`` when the staged pipeline serves the call: fusion off, a
        custom-prefilter relation (no static mask shape), or a dense
        escalation (budget 0)."""
        mode = self.config.fusion
        if mode is None or mode == "off":
            return None
        if mode != "reference":
            raise ValueError(f"unknown fusion mode {mode!r} "
                             "(use 'reference' or 'off')")
        if get_relation(base_relation).prefilter_kind == "custom":
            return None
        b = self.config.exact_budget if budget is None else budget
        return mode if b > 0 else None

    # ---------------------------------------------------------------- sharded
    def _sharded_available(self) -> bool:
        """A mesh is configured and shaped for the sharded backend (a loud
        error on a malformed mesh beats silently planning around it)."""
        mesh = self.config.mesh
        if mesh is None:
            return False
        names = tuple(mesh.axis_names)
        if "model" not in names or not any(a in ("data", "pod")
                                           for a in names):
            raise ValueError(
                f"EngineConfig.mesh axes {names} unusable: the sharded "
                "backend needs a 'model' axis (query sharding) and a "
                "'data' and/or 'pod' axis (record sharding)")
        return True

    def _shard_count(self) -> int:
        """Number of record shards (product of the data/pod axis sizes)."""
        from .distributed import _data_axes

        mesh = self.config.mesh
        s = 1
        for a in _data_axes(mesh):
            s *= mesh.shape[a]
        return s

    def _sharded_placement(self):
        """Device placement of the PUBLISHED snapshot for the mesh, built
        once per publish: the record table range-partitioned over the data
        axes (slot order, slot-aligned MBR tables) and a model-only snapshot
        (record-level arrays stripped to 1-element stand-ins — the sharded
        step never touches them) replicated on every device."""
        if self._shard_placement is not None \
                and self._shard_placement[0] == self._publishes:
            return self._shard_placement[1:]
        from .distributed import _data_axes, shard_arrays_from_capture

        mesh = self.config.mesh
        shards = self._shard_count()
        if self._capture is None:
            # the mesh was configured AFTER the last publish (captures are
            # only retained while a mesh is active): re-derive it — from the
            # live tree when the snapshot is fresh (they are identical), via
            # a republish otherwise
            if self.snapshot_is_stale():
                self.snapshot()
            else:
                self._capture = snapshot_capture(self.glin)
        table_np = self._staged_table
        self._staged_table = None
        # a staged table (built by the async swap's background thread) must
        # describe exactly the published capture's slots — anything else is
        # rebuilt here (every publish clears stale stagings, so this is just
        # a belt-and-braces shape check)
        n = self._capture.keys.shape[0]
        if table_np is None or table_np["keys_hi"].shape[0] != n + (-n) % shards:
            table_np = shard_arrays_from_capture(
                self._capture, shards, pool_pad_to=self._shard_pool_floor)
        # sticky floors: a compacting republish may shrink the per-shard
        # pool or retire the widest records; serving the previous padded
        # shapes keeps the sharded jit signature stable
        self._shard_pool_floor = max(self._shard_pool_floor,
                                     table_np["vpool"].shape[0] // shards)
        maxw = max(self._width_floor,
                   _pow2ceil(int(table_np["nverts"].max())))
        self._width_floor = max(self._width_floor, maxw)
        tsh = NamedSharding(mesh, P(_data_axes(mesh)))
        table = {k: jax.device_put(v, tsh) for k, v in table_np.items()}
        tiny_i = jnp.zeros((1,), jnp.int32)
        tiny_f = jnp.zeros((1, 4), jnp.float32)
        model_only = dataclasses.replace(
            self._snapshot, keys_hi=tiny_i, keys_lo=tiny_i, recs=tiny_i,
            rec_leaf=tiny_i, slot_lmbr=tiny_f, slot_rmbr=tiny_f)
        repl = NamedSharding(mesh, P())
        snap_repl = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, repl), model_only)
        # key read AFTER the potential republish above bumped the count —
        # caching under the pre-publish key would force a rebuilt placement
        # (and its multi-MB device_put) on the very next query
        self._shard_placement = (self._publishes, snap_repl, table, shards,
                                 maxw)
        return self._shard_placement[1:]

    def _sharded_step(self, base: str, cap: int, budget: int,
                      compaction: str, max_width: int):
        key = (base, cap, budget, compaction, max_width)
        fn = self._shard_steps.get(key)
        if fn is None:
            from .distributed import build_glin_query_step

            step, in_sh, out_sh = build_glin_query_step(
                self.config.mesh, base, cap=cap, exact_budget=budget,
                compaction=compaction, max_width=max_width)
            fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            self._shard_steps[key] = fn
        return fn

    def _sharded_knn_step(self, k: int, cap: int, budget: int,
                          compaction: str, max_width: int):
        """Jit cache for the sharded knn probe+rank+k-merge step, keyed like
        ``_sharded_step`` with k in place of the relation: the radii are a
        traced input, so every radius shares one compilation."""
        key = ("knn", k, cap, budget, compaction, max_width)
        fn = self._shard_steps.get(key)
        if fn is None:
            from .distributed import build_glin_knn_step

            step, in_sh, out_sh = build_glin_knn_step(
                self.config.mesh, k, cap=cap, exact_budget=budget,
                compaction=compaction, max_width=max_width)
            fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            self._shard_steps[key] = fn
        return fn

    def _check_augmentable(self, relation: str, base) -> None:
        """Fail loudly when a relation needs the piecewise augmentation and
        the index was built without it — the device ``_augment()`` would
        silently no-op on an empty piecewise table and drop true hits."""
        if base.augment and self.glin.pw is None:
            raise ValueError(f"{relation} requires the piecewise function "
                             "(cfg.enable_piecewise=True)")

    # ------------------------------------------------------------------- plan
    def plan(self, batch, relation: Optional[str] = None) -> QueryPlan:
        """Planned execution for ``batch`` (same input forms as ``query``)."""
        if not isinstance(batch, QueryBatch):
            batch = QueryBatch.window(batch, relation or "intersects")
        cfg = self.config
        if batch.kind == "knn":
            q = len(batch)
            seed = cfg.knn_seed or "cdf"
            delta = self.delta_size()
            stale = self.snapshot_is_stale()

            def knn_plan(backend, reason, rebuild=False):
                return QueryPlan(backend, "knn", None, None, rebuild,
                                 reason, delta)

            if batch.backend == "host":
                return knn_plan("host", "forced by caller")
            if batch.backend == "sharded":
                if not self._sharded_available():
                    raise ValueError("backend='sharded' requires "
                                     "EngineConfig.mesh")
                return knn_plan("sharded", "forced by caller", rebuild=stale)
            if batch.backend in ("device", "device+delta"):
                return knn_plan(batch.backend, "forced by caller")
            if batch.backend is not None:
                raise ValueError(f"unknown backend {batch.backend!r}")
            if q < cfg.knn_device_min_batch or self.glin.pw is None:
                why = (f"batch of {q} < knn_device_min_batch="
                       f"{cfg.knn_device_min_batch}"
                       if q < cfg.knn_device_min_batch
                       else "no piecewise function published")
                return knn_plan("host",
                                f"knn executes on the host index ({why})")
            shard_ok = (self._sharded_available()
                        and self.glin.num_records >= cfg.shard_min_records)
            if shard_ok:
                nsh = self._shard_count()
                return knn_plan(
                    "sharded",
                    f"device-complete knn over {nsh} shards: {seed}-seeded "
                    f"radii, shard-local top-{batch.k}, one-collective "
                    f"k-merge ({q} points)", rebuild=stale)
            patchable = (self._snapshot is not None
                         and delta <= cfg.delta_patch_max
                         and delta < cfg.refresh_threshold)
            if stale and patchable:
                return knn_plan(
                    "device+delta",
                    f"device-complete knn with {seed}-seeded radii; "
                    f"snapshot stale, delta of {delta} ranked in-line "
                    f"(tombstones masked, added set distance-merged before "
                    f"the device top-{batch.k})")
            return knn_plan(
                "device",
                f"device-complete knn: {seed}-seeded dwithin ladder + "
                f"device top-{batch.k} ({q} points >= knn_device_min_batch="
                f"{cfg.knn_device_min_batch})")
        rel = get_relation(batch.relation)
        base = get_relation(rel.base_name())
        self._check_augmentable(batch.relation, base)
        stale = self.snapshot_is_stale()
        delta = self.delta_size()
        inflight = self._inflight is not None
        # patch viable: a snapshot has been published, the per-query patch
        # work is bounded (delta_patch_max), and the delta has not yet hit
        # the republish point (refresh_threshold)
        patchable = (self._snapshot is not None
                     and delta <= cfg.delta_patch_max
                     and delta < cfg.refresh_threshold)

        def host(reason):
            return QueryPlan("host", "window", rel.name, base.name, False,
                             reason, delta)

        fused = self._fusion_mode(base.name) is not None
        fnote = "; fused whole-table refine" if fused else ""

        def device(reason):
            return QueryPlan("device", "window", rel.name, base.name, stale,
                             reason + fnote, delta, fused=fused)

        def patched(reason):
            return QueryPlan("device+delta", "window", rel.name, base.name,
                             self._snapshot is None, reason + fnote, delta,
                             fused=fused)

        def sharded(reason, rebuild=False):
            return QueryPlan("sharded", "window", rel.name, base.name,
                             rebuild, reason, delta)

        if batch.collect_stats and batch.backend in ("device", "device+delta",
                                                     "sharded"):
            raise ValueError("collect_stats is host-only; drop it or force "
                             "backend='host'")
        if batch.backend == "host":
            return host("forced by caller")
        if batch.backend == "device":
            return device("forced by caller")
        if batch.backend == "device+delta":
            return patched("forced by caller")
        if batch.backend == "sharded":
            if not self._sharded_available():
                raise ValueError("backend='sharded' requires "
                                 "EngineConfig.mesh")
            return sharded("forced by caller",
                           rebuild=stale and not (patchable or inflight))
        if batch.backend is not None:
            raise ValueError(f"unknown backend {batch.backend!r}")
        if batch.collect_stats:
            return host("QueryStats instrumentation is host-only")
        if not base.device_native:
            return host(f"relation {base.name!r} is not device-native")
        q = len(batch)
        if q < cfg.device_min_batch:
            return host(f"batch of {q} < device_min_batch={cfg.device_min_batch}")
        shard_ok = (self._sharded_available()
                    and self.glin.num_records >= cfg.shard_min_records)
        nsh = self._shard_count() if shard_ok else 0
        if not stale:
            if shard_ok:
                return sharded(f"sharded over {nsh} shards: batch of {q} "
                               f"windows on {jax.default_backend()} mesh")
            return device(f"batch of {q} windows on {jax.default_backend()}")
        if inflight and self._snapshot is not None:
            # double-buffering: the next snapshot is building on the side;
            # keep serving the published one + delta patch (the patch bound
            # is waived — the delta stays bounded by write rate x build time)
            if shard_ok:
                return sharded(f"sharded over {nsh} shards; async republish "
                               f"in flight, delta of {delta} patched on top")
            return patched(f"async republish in flight; serving published "
                           f"snapshot + delta of {delta}")
        if patchable:
            if shard_ok:
                return sharded(f"sharded over {nsh} shards; snapshot stale, "
                               f"delta of {delta} patched on top")
            return patched(f"snapshot stale; delta of {delta} <= "
                           f"delta_patch_max={cfg.delta_patch_max}: patching "
                           "instead of republishing")
        if q < cfg.stale_rebuild_min_batch:
            return host(f"snapshot stale and batch of {q} < "
                        f"stale_rebuild_min_batch={cfg.stale_rebuild_min_batch}")
        if shard_ok:
            verb = ("publishing" if self._snapshot is None
                    else "republishing")
            return sharded(f"sharded over {nsh} shards; {verb} for "
                           f"batch of {q}", rebuild=True)
        if self._snapshot is None:
            return device(f"no published snapshot yet: publishing for "
                          f"batch of {q}")
        return device(f"snapshot stale; delta of {delta} not patchable "
                      f"(delta_patch_max={cfg.delta_patch_max}, "
                      f"refresh_threshold={cfg.refresh_threshold}): "
                      f"republishing for batch of {q}")

    # ------------------------------------------------------------------ query
    def query(self, batch, relation: Optional[str] = None,
              replica: Optional[int] = None, **kw) -> QueryResult:
        """THE entry point: one or thousands of queries, any relation or knn.

        ``batch`` is a :class:`QueryBatch`, or a bare (4,) / (Q, 4) window
        array (``relation`` then applies, default ``intersects``).
        ``replica`` routes a device-backend batch to placement ``replica %
        EngineConfig.replicas`` (the serving tier's least-loaded dispatcher
        sets it; default: the primary placement).

        Concurrency contract: safe to call from many threads, interleaved
        with :meth:`insert`/:meth:`delete`. A window batch on the
        ``device``/``device+delta`` backends is exact at the epoch frozen in
        its prologue (``result.epoch``) and runs its device compute without
        blocking writers; host/sharded batches serialize with writers and
        are exact at the epoch they hold the lock. A device knn batch
        freezes its snapshot + delta ONCE up front — every radius rung of
        every point serves that same frozen epoch.
        """
        if not isinstance(batch, QueryBatch):
            batch = QueryBatch.window(batch, relation or "intersects", **kw)
        else:
            if relation is not None and relation != batch.relation:
                raise ValueError("pass the relation inside the QueryBatch")
            if kw:
                raise ValueError(f"{sorted(kw)} must be set on the QueryBatch "
                                 "itself")
        with self._lock:
            self._maintain_async()
            plan = self.plan(batch)
        rel = base = None
        if batch.kind == "window":
            rel = get_relation(batch.relation)
            base = get_relation(rel.base_name())
        ctx = qexec.ExecContext(index=self, batch=batch, plan=plan,
                                rel=rel, base=base, replica=replica or 0)
        qexec.compile_plan(plan).execute(ctx)
        self._record_stages(plan.backend, ctx.stage_stats)
        return QueryResult(ids=ctx.ids, plan=plan, epoch=ctx.epoch,
                           stats=ctx.host_stats, distances=ctx.distances,
                           stages=ctx.stage_stats)

    def explain(self, batch, relation: Optional[str] = None) -> str:
        """Pretty-print how ``batch`` WOULD execute (same input forms as
        :meth:`query`, nothing runs): the planner's decision plus the
        compiled stage composition — one line per stage with its
        implementation and the canonical pipeline stages it fuses."""
        if not isinstance(batch, QueryBatch):
            batch = QueryBatch.window(batch, relation or "intersects")
        with self._lock:
            plan = self.plan(batch)
        eplan = qexec.compile_plan(plan)
        head = (f"QueryPlan backend={plan.backend} kind={plan.kind} "
                f"relation={plan.relation} delta={plan.delta_size}"
                + (" rebuild" if plan.rebuild_snapshot else ""))
        lines = [head, f"  reason: {plan.reason}", "  stages:"]
        lines += [f"    {row}" for row in eplan.describe()]
        return "\n".join(lines)

    # ------------------------------------------------------------- estimation
    def count_candidates(self, windows, relation: str = "intersects"
                         ) -> np.ndarray:
        """MBR-level candidate counts per window (selectivity estimation)
        through the tiled refine kernel — Pallas on TPU, its XLA reference
        semantics elsewhere."""
        from repro.kernels import ops

        base = get_relation(relation).base_name()
        base_rel = get_relation(base)
        self._check_augmentable(relation, base_rel)
        snap = self.snapshot()
        wj = jnp.asarray(np.atleast_2d(np.asarray(windows)).astype(np.float32))
        start, end = batch_query_bounds(snap, wj, base)
        bounds = jnp.stack([start, end], axis=1).astype(jnp.int32)
        # MBR-level counting uses the padded probe window so dwithin-style
        # relations count the candidates their refine step will actually see;
        # the slot-aligned record-MBR table lives on the snapshot (no per-call
        # host gather + upload)
        counts = ops.refine_count(base_rel.probe_window(wj, xp=jnp), bounds,
                                  snap.slot_rmbr,
                                  use_pallas=jax.default_backend() == "tpu")
        return np.asarray(counts)

    # ----------------------------------------------------- execution support
    # The execution bodies themselves live in ``core.exec`` as stage
    # compositions (compile_plan); what remains here are the freeze helpers
    # the stages call under ``self._lock`` to capture consistent state.
    def _freeze_live(self, rel) -> Optional[np.ndarray]:
        """Live record ids for complement finishing, frozen under the lock
        (the live mask walks the mutable host leaves)."""
        if not rel.is_complement:
            return None
        return np.nonzero(self.glin._live_mask())[0].astype(np.int64)

    def _delta_table(self) -> DeltaTable:
        """The device-resident added-set side table at the current epoch,
        rebuilt lazily after a write burst (one upload per epoch served, not
        one host round-trip per query batch). Rows are padded to a power-of-
        two bucket so the jitted check compiles per bucket, not per insert."""
        if self._dtable is None or self._dtable_epoch != self._epoch:
            a = len(self._added)
            pad = max(self.config.delta_device_min,
                      1 << max(a - 1, 0).bit_length())
            self._dtable = delta_table_from_host(self.glin, self._added,
                                                 pad_to=pad)
            self._dtable_epoch = self._epoch
        return self._dtable

    def _freeze_delta(self) -> Optional[Tuple]:
        """Copies of the tombstone/added delta plus the geometry slices (or
        the device :class:`DeltaTable`) the patch step needs, frozen under
        ``self._lock`` so the shared delta-patch stage can run outside it while
        writers keep mutating the live sets."""
        if not (self._tombstones or self._added):
            return None
        gs = self.glin.gs
        tombs = (np.fromiter(self._tombstones, np.int64,
                             len(self._tombstones))
                 if self._tombstones else None)
        added = np.asarray(sorted(self._added), np.int64)
        table = av = an = ak = None
        if added.shape[0] >= self.config.delta_device_min:
            table = self._delta_table()
        elif added.shape[0]:
            av = gs.padded(added).astype(np.float32)
            an, ak = gs.nverts[added], gs.kinds[added]
        return (tombs, added, table, av, an, ak)

