"""Program spans and counters of the served query path (``utils/trace.py``):
span names and nesting in a profiler trace of a started server, the
overflowing attempt's escalation, the queue-wait, plan and per-stage compile
counters."""
import glob
import os
import pathlib
import re
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import exec as qexec
from repro.core.datasets import generate, make_query_windows
from repro.core.engine import EngineConfig, SpatialIndex
from repro.core.geometry import mbrs_of_verts
from repro.core.index import GLINConfig
from repro.serve import ServerConfig, SpatialQueryServer
from repro.utils import trace

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


def _index(n=3000, seed=0, **eng):
    gs = generate("cluster", n, seed=seed)
    gs.verts = gs.verts.astype(np.float32).astype(np.float64)
    gs.mbrs = mbrs_of_verts(gs.verts, gs.nverts)
    cfg = EngineConfig(device_min_batch=1, stale_rebuild_min_batch=1, **eng)
    return SpatialIndex.build(gs, GLINConfig(piece_limitation=200), config=cfg)


def _windows(idx, k, seed=1, sel=1e-2):
    return make_query_windows(idx.gs, sel, k, seed=seed).astype(
        np.float32).astype(np.float64)


def _glin_events(path):
    """``{thread line: [(name, start_ns, end_ns, args), ...]}`` of the
    ``glin.`` spans in the trace under ``path``."""
    from jax.profiler import ProfileData

    (f,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(f).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("glin.")]
            if evs:
                out[(plane.name, i)] = evs
    return out


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def _parents(ev, line):
    """The spans of ``ev``'s line that enclose it, innermost last."""
    return sorted((o for o in line if o is not ev and _inside(ev, o)),
                  key=lambda o: (o[1], -o[2]))


def _traced(path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def test_every_span_name_is_listed():
    """``SPANS`` names every span the program opens: each literal
    ``glin.`` name in the source, and one ``glin.stage.<name>`` per
    stage."""
    found = set()
    for f in SRC.rglob("*.py"):
        found |= set(re.findall(r'"(glin\.[a-z.-]+)"', f.read_text()))
    assert found and found <= set(trace.SPANS)
    stages = {c.name for c in vars(qexec).values()
              if isinstance(c, type) and issubclass(c, qexec.Stage)
              and c is not qexec.Stage}
    assert {f"glin.stage.{s}" for s in stages} <= set(trace.SPANS)
    assert len(set(trace.SPANS)) == len(trace.SPANS)


def test_served_batch_spans_nest_and_overflow_escalates(tmp_path):
    """A small index behind a started server, traced: the worker's spans
    nest under ``glin.serve.batch``, each ladder attempt carries its budget,
    and the first attempt at the configured budget overflows, so it holds
    a ``glin.refine.escalate``."""
    idx = _index(exact_budget=4)           # far below the survivors: overflow
    srv = SpatialQueryServer(idx, config=ServerConfig(max_workers=1)).start()
    w = _windows(idx, 16)
    try:
        ticket = srv.submit(w[0])          # compiles outside the trace
        srv.result(ticket, timeout=300)
        idx._cap = idx.config.initial_cap  # walk the ladder again
        _traced(tmp_path, lambda: [srv.result(t, timeout=300) for t in
                                   [srv.submit(x) for x in w]])
    finally:
        srv.stop()
    lines = _glin_events(str(tmp_path))
    names = {e[0] for line in lines.values() for e in line}
    assert names <= set(trace.SPANS)
    assert {"glin.serve.slot_wait", "glin.serve.batch", "glin.serve.resolve",
            "glin.engine.plan", "glin.stage.refine",
            "glin.stage.complement-finish", "glin.refine.freeze",
            "glin.refine.attempt", "glin.refine.wait",
            "glin.refine.escalate", "glin.refine.assemble"} <= names
    worker = [line for line in lines.values()
              if any(e[0] == "glin.serve.batch" for e in line)]
    assert len(worker) == 1                # one worker thread serves
    (line,) = worker
    batches = [e for e in line if e[0] == "glin.serve.batch"]
    assert sum(b[3]["q"] for b in batches) == len(w)
    assert all(b[3]["rel"] == "intersects" for b in batches)
    seqs = [b[3]["batch"] for b in batches]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    want = {"glin.engine.plan": "glin.serve.batch",
            "glin.stage.refine": "glin.serve.batch",
            "glin.serve.resolve": "glin.serve.batch",
            "glin.refine.freeze": "glin.stage.refine",
            "glin.refine.attempt": "glin.stage.refine",
            "glin.refine.escalate": "glin.refine.attempt",
            "glin.refine.assemble": "glin.stage.refine"}
    for ev in line:
        par = [p[0] for p in _parents(ev, line)]
        if ev[0] != "glin.serve.batch":
            assert par and par[0] == "glin.serve.batch", (ev[0], par)
        if ev[0] in want:
            assert par[-1] == want[ev[0]], (ev[0], par)
        if ev[0] == "glin.refine.wait":
            assert par[-1] in ("glin.refine.attempt", "glin.stage.refine")
    attempts = [e for e in line if e[0] == "glin.refine.attempt"]
    assert all({"budget", "cap"} <= set(a[3]) for a in attempts)
    lost = [a for a in attempts
            if any(e[0] == "glin.refine.escalate" and _inside(e, a)
                   for e in line)]
    assert lost and lost[0][3]["budget"] == 4
    kept = [a for a in attempts if a not in lost]
    assert len(kept) == len(batches)       # one settled attempt per batch


def test_publish_span_inside_the_refine_freeze(tmp_path):
    """The first device query publishes the snapshot under the facade lock:
    ``glin.index.publish`` inside ``glin.refine.freeze``."""
    idx = _index(n=1500, seed=7)
    w = _windows(idx, 4)
    _traced(tmp_path, lambda: idx.query(w, "intersects"))
    (line,) = [ln for ln in _glin_events(str(tmp_path)).values()
               if any(e[0] == "glin.index.publish" for e in ln)]
    (pub,) = [e for e in line if e[0] == "glin.index.publish"]
    assert [p[0] for p in _parents(pub, line)] == [
        "glin.stage.refine", "glin.refine.freeze"]
    (plan,) = [e for e in line if e[0] == "glin.engine.plan"]
    assert plan[3]["backend"] == "device"


def test_queue_wait_grows_by_the_hold():
    """A request queued behind a held worker waits at least the hold, and
    the wait is charged when its batch starts being served."""
    idx = _index()
    srv = SpatialQueryServer(idx, config=ServerConfig(max_workers=1))
    w = _windows(idx, 2)
    release, entered = threading.Event(), threading.Event()
    query = idx.query

    def held(*a, **kw):
        if not entered.is_set():
            entered.set()
            release.wait(60)
        return query(*a, **kw)

    idx.query = held
    srv.start()
    try:
        first = srv.submit(w[0])
        assert entered.wait(60)
        second = srv.submit(w[1])
        hold = 0.3
        time.sleep(hold)
        assert srv.stats()["queue_waited"] == 1     # only the first started
        release.set()
        srv.result(first, timeout=300)
        srv.result(second, timeout=300)
    finally:
        release.set()
        srv.stop()
    st = srv.stats()
    assert st["queue_waited"] == 2
    assert st["queue_wait_ms"] >= 1e3 * hold


def test_flush_charges_queue_wait_once_at_commit():
    """``flush()`` charges every drained request, and a failed flush,
    which requeues its requests, charges nothing."""
    idx = _index()
    srv = SpatialQueryServer(idx)
    w = _windows(idx, 4)
    for x in w:
        srv.submit(x)
    query = idx.query

    def boom(*a, **kw):
        raise RuntimeError("injected")

    idx.query = boom
    with pytest.raises(RuntimeError):
        srv.flush()
    assert srv.stats()["queue_waited"] == 0
    idx.query = query
    time.sleep(0.05)
    srv.flush()
    st = srv.stats()
    assert st["queue_waited"] == len(w)
    assert st["queue_wait_ms"] >= len(w) * 50.0


def test_fresh_shape_compiles_on_its_stage():
    """A batch shape not seen before compiles on the refine stage that runs
    it; the same shape again compiles nothing."""
    idx = _index(n=2000, seed=5)
    w = _windows(idx, 8)
    jax.clear_caches()
    other = trace.OUTSIDE["compiles"]
    res = idx.query(w, "intersects")
    (refine,) = [s for s in res.stages if s.stage == "refine"]
    assert refine.impl == "device" and refine.compiles > 0
    assert refine.compile_ms > 0
    assert all(s.compiles == 0 for s in res.stages if s is not refine)
    assert trace.OUTSIDE["compiles"] == other
    again = idx.query(w, "intersects")
    assert all(s.compiles == 0 for s in again.stages)
    agg = idx.stats()["stages"]["device"]["refine"]
    assert agg["compiles"] == refine.compiles
    assert agg["compile_ms"] == pytest.approx(refine.compile_ms)
    assert 0 < agg["wait_ms"] <= agg["wall_ms"]


def test_plans_advance_once_per_query():
    idx = _index(n=1500, seed=7)
    srv = SpatialQueryServer(idx)
    w = _windows(idx, 4)
    assert idx.stats()["plans"] == 0 and idx.stats()["plan_ms"] == 0.0
    for i in range(3):
        idx.query(w, "intersects")
        st = idx.stats()
        assert st["plans"] == i + 1 and st["plan_ms"] > 0
    idx.explain(w, "intersects")           # plans, but runs no query
    assert idx.stats()["plans"] == 3
    srv.query(w)
    st = srv.stats()
    assert st["plans"] == 4
    assert st["plan_ms"] == pytest.approx(idx.stats()["plan_ms"])
