"""Program spans in a trace (``bench/spans.py``) and the per-layer metrics
read from the program's counters: device time charged to the span that
launched it, overflowed attempts, idle gaps labelled with the program's
spans, and metric files that read nothing from a program without the
counters."""
import gzip
import os
import re

import pytest

from bench import harness, spans, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _unpack(tmp_path, name):
    blob = open(os.path.join(DATA, name), "rb").read()
    (tmp_path / "slice.xplane.pb").write_bytes(gzip.decompress(blob))
    return str(tmp_path)


def _metric(name):
    return harness.load_module(ROOT, "metrics", name)


# ------------------------------------------------------------ the knn slice
def test_knn_slice_reduction_unchanged(tmp_path):
    """``bench/trace.py`` reads the recorded kNN slice as it always has:
    pinned busy and kernel time, top op and labelled gaps."""
    rec = trace.load(_unpack(tmp_path, "knn_slice.xplane.pb.gz"))
    red = trace.reduce(rec, 315522826e-9, ["refine_compact"])
    assert red["busy_s"] == pytest.approx(0.092947633, rel=1e-9)
    assert red["kernel_s"]["refine_compact"] == pytest.approx(0.087056934,
                                                             rel=1e-9)
    assert red["device_ops"][0][0] == (
        "refine_compact.1 custom-call (s32[16,256]{1,0:T(8,128)S(1)}, "
        "s32[16,1]{1,0:T(8,128)S(1)})")
    assert red["device_ops"][0][1] == pytest.approx(0.026969928, rel=1e-9)
    assert [g[0] for g in red["idle_gaps"][:6]] == [
        "no benchmark span", "bench.send", "no benchmark span",
        "no benchmark span", "bench.collect", "bench.collect"]
    assert red["idle_gaps"][0][1] == pytest.approx(0.041956756, rel=1e-9)
    assert len(rec["spans"]) == 123


def test_knn_slice_links_every_module(tmp_path):
    """Each of the slice's 47 program executions leads, through the flow
    records, to the Python thread that launched it (two worker threads)."""
    prog = spans.load(_unpack(tmp_path, "knn_slice.xplane.pb.gz"))
    mods = prog["modules"]
    assert len(mods) == 47 and all(m[3] is not None for m in mods)
    per_line = {}
    for _, _, _, (line, _) in mods:
        per_line[line] = per_line.get(line, 0) + 1
    assert sorted(per_line.values()) == [20, 27]
    assert prog["spans"] == {}            # recorded before the program's spans
    red = spans.reduce(prog)
    assert red["unlinked_s"] == 0.0
    assert red["program_s"] == {spans.NO_SPAN: pytest.approx(red["module_s"])}
    assert red["long_idle_labelled_s"] == 0


# ---------------------------------------------------------- the mixed slice
def _budget(ops, s, d):
    """The budget of the compact kernel a program ran, from the width of
    its ``refine_compact`` output in the op text, or ``None``."""
    widths = {int(w) for nm, a, _ in ops if s <= a <= s + d
              and trace.op_name(nm).split(".")[0] == "refine_compact"
              for w in re.findall(r"= \(s32\[\d+,(\d+)\]", nm)}
    assert len(widths) <= 1
    return widths.pop() if widths else None


def test_mixed_slice_charges_the_lost_first_rung(tmp_path):
    """A second of ``mixed10m-tiles-closed96`` recorded on a TPU v5e with the
    program's spans: every batch overflows budget 256 and settles at 1,024.
    ``discarded_s`` is the device time of the 256-wide programs the trace
    saw launched, read from the device's own op shapes."""
    prog = spans.load(_unpack(tmp_path, "mixed_slice.xplane.pb.gz"))
    lo, hi = prog["recorded"]
    assert 1.0e9 < hi - lo < 1.1e9
    attempts = [e for evs in prog["spans"].values() for e in evs
                if e[0] == spans.ATTEMPT]
    lost = {id(a) for per in spans._lost_attempts(prog["spans"]).values()
            for a in per}
    assert attempts and lost
    for a in attempts:
        assert a[3]["budget"] == (256 if id(a) in lost else 1024)
    red = spans.reduce(prog)
    clipped = {}
    for nm, s, d, launch in prog["modules"]:
        b = _budget(prog["ops"], s, d)
        if b is not None and launch is not None:
            t = max(0.0, min(s + d, hi) - max(s, lo)) * 1e-9
            clipped[b] = clipped.get(b, 0.0) + t
    assert set(clipped) == {256, 1024}
    assert red["discarded_s"] == pytest.approx(clipped[256], rel=1e-9)
    assert 0.2 < red["discarded_s"] / red["busy_s"] < 0.45
    assert red["program_s"] == {
        spans.ATTEMPT: pytest.approx(0.806454122, rel=1e-6),
        spans.EDGE: pytest.approx(0.138565554, rel=1e-6)}
    # the two programs with no flow were launched before the trace began
    first_launch = min(m[3][1] for m in prog["modules"] if m[3])
    unlinked = [m for m in prog["modules"] if m[3] is None]
    assert len(unlinked) == 2 and all(m[1] < first_launch for m in unlinked)
    assert red["unlinked_s"] == pytest.approx(
        sum(min(s + d, hi) - max(s, lo) for _, s, d, _ in unlinked) * 1e-9)


def test_mixed_slice_gaps_carry_program_spans(tmp_path):
    """The chip is busy through the recorded interval; its idle gaps, each
    a few microseconds between programs, fall while a worker waits on the
    device (``glin.refine.wait``)."""
    red = spans.reduce(spans.load(_unpack(tmp_path,
                                          "mixed_slice.xplane.pb.gz")))
    assert red["busy_s"] / red["recorded_s"] > 0.999
    assert red["long_idle_s"] == 0
    labels = [lb for lb, _ in red["idle_gaps"]]
    assert labels.count("glin.refine.wait") >= 5
    assert all(lb == spans.NO_SPAN or lb.startswith("glin.")
               for lb in labels)


# ------------------------------------------------------------ a made-up flow
def _chain(line, t, launch_flow, native_flow, queue_flow):
    """The events of one launch: the Python linkage on ``line``, the
    native execute on its own thread, the enqueue on the runtime's queue."""
    return {
        line: [(spans.LAUNCH, t, t + 1, {"_p": launch_flow})],
        "native": [("PJRT_LoadedExecutable_Execute", t + 1, t + 30,
                    {"_c": launch_flow}),
                   ("tpu::System::Execute", t + 10, t + 25,
                    {"_p": native_flow})],
        "queue": [("tpu::System::Execute=>IssueSequencedEvent", t + 26,
                   t + 40, {"_c": native_flow}),
                  ("DoEnqueueProgram", t + 27, t + 39, {"_p": queue_flow})],
    }


def _made_up():
    worker = [("glin.serve.batch", 0, 1000, {"batch": 3}),
              ("glin.stage.refine", 10, 900, {}),
              ("glin.refine.attempt", 20, 400, {"budget": 256}),
              ("glin.refine.wait", 200, 290, {}),
              ("glin.refine.escalate", 300, 390, {}),
              ("glin.refine.attempt", 400, 800, {"budget": 1024}),
              ("glin.refine.wait", 500, 790, {}),
              ("glin.refine.assemble", 800, 880, {})]
    other = [("glin.serve.batch", 0, 100, {"batch": 2}),
             ("glin.serve.batch", 600, 700, {"batch": 4})]
    lines = {"worker": worker, "other": other, "native": [], "queue": [],
             "pump": [("glin.serve.gather", 820, 990, {})]}
    for line, t, flows in (("worker", 30, (1, 2, 3)),
                           ("worker", 410, (4, 5, 6)),
                           ("worker", 950, (7, 8, 9)),
                           ("other", 300, (10, 11, 12)),
                           ("other", 800, (13, 14, 15))):
        for key, evs in _chain(line, t, *flows).items():
            lines[key] = lines[key] + evs
    ops = [(f"%{c} = f32[8] fusion()", s, d) for c, s, d in
           (("a", 0, 200), ("b", 450, 50), ("c", 505, 200), ("d", 960, 20),
            ("e", 990, 10))]
    modules = [("jit_batch_query(1)", 100, 100, 3),   # first rung: lost
               ("jit_batch_query(1)", 450, 255, 6),   # second rung: kept
               ("jit_other(2)", 960, 20, 9),          # outside every attempt
               ("jit_lost(3)", 990, 5, 99),           # no flow
               ("jit_between(4)", 300, 30, 12),       # between two batches
               ("jit_late(5)", 800, 40, 15)]          # after the last span
    return spans.link(ops, modules, list(lines.items()))


def test_made_up_flow_charges_launching_span():
    prog = _made_up()
    assert prog["recorded"] == (0, 1000)
    launches = [m[3] for m in prog["modules"]]
    assert launches == [("worker", 30), ("worker", 410), ("worker", 950),
                        None, ("other", 300), ("other", 800)]
    red = spans.reduce(prog)
    assert red["recorded_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(480e-9)
    assert red["module_s"] == pytest.approx(450e-9)
    assert red["program_s"] == {
        "glin.refine.attempt": pytest.approx(355e-9),
        spans.EDGE: pytest.approx(40e-9),
        spans.NO_SPAN: pytest.approx(30e-9),
        "glin.serve.batch": pytest.approx(20e-9)}
    assert red["discarded_s"] == pytest.approx(100e-9)   # the first rung
    assert red["unlinked_s"] == pytest.approx(5e-9)
    # the gaps between the made-up ops, longest first, by the spans open at
    # their midpoints on each line
    assert red["idle_gaps"] == [
        ["glin.refine.assemble+glin.serve.gather", pytest.approx(255e-9)],
        ["glin.refine.escalate", pytest.approx(250e-9)],
        ["glin.serve.batch+glin.serve.gather", pytest.approx(10e-9)],
        ["glin.refine.wait", pytest.approx(5e-9)]]
    assert red["long_idle_s"] == 0.0       # no gap of a millisecond


def test_gap_label_names_every_line_or_none():
    prog = _made_up()
    assert spans.gap_label(prog["spans"], 850) == (
        "glin.refine.assemble+glin.serve.gather")
    assert spans.gap_label(prog["spans"], 650) == (
        "glin.refine.wait+glin.serve.batch")
    assert spans.gap_label(prog["spans"], 5000) == spans.NO_SPAN


def test_launch_inside_a_stage_the_trace_cut():
    """A stage still open when the trace stopped is dropped, its recorded
    attempt and wait left without it: a launch between them was made in
    the cut stage. A launch between two whole batches was made outside
    every span."""
    line = sorted([("glin.serve.batch", 0, 100, {}),
                   ("glin.stage.refine", 5, 95, {}),
                   ("glin.refine.attempt", 10, 90, {}),
                   ("glin.serve.batch", 120, 300, {}),
                   ("glin.stage.refine", 125, 295, {}),
                   ("glin.refine.attempt", 130, 290, {}),
                   ("glin.refine.attempt", 310, 400, {}),
                   ("glin.refine.escalate", 395, 400, {}),
                   ("glin.refine.wait", 410, 600, {})],
                  key=lambda e: (e[1], -e[2]))
    lines = {"w": line}
    assert spans._charged(lines, "w", 50) == "glin.refine.attempt"
    assert spans._charged(lines, "w", 110) == spans.NO_SPAN
    assert spans._charged(lines, "w", 405) == spans.EDGE
    assert spans._charged(lines, "w", 700) == spans.EDGE
    assert spans._cut_stages(line) == [(310, 600)]


def test_only_the_recorded_interval_counts():
    """Device time outside the interval both host and device recorded is
    cut: a program running past its end counts up to the end."""
    prog = _made_up()
    prog["recorded"] = (0, 500)
    red = spans.reduce(prog)
    assert red["module_s"] == pytest.approx(180e-9)      # 100 + 50 + 30
    assert red["discarded_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(250e-9)


# -------------------------------------------------- metrics from counters
def _stats(queue=(0.0, 0), plans=(0, 0.0), stage=None):
    s = {"queue_wait_ms": queue[0], "queue_waited": queue[1],
         "plans": plans[0], "plan_ms": plans[1],
         "engine_stages": {"device": {}}}
    if stage is not None:
        s["engine_stages"]["device"]["refine"] = dict(stage)
    return s


def _stage(calls, wall, wait, compiles):
    return {"calls": calls, "wall_ms": wall, "wait_ms": wait,
            "compiles": compiles}


def _older(stats):
    """The same counters as a program without this telemetry reports
    them."""
    old = {k: v for k, v in stats.items()
           if k not in ("queue_wait_ms", "queue_waited", "plans", "plan_ms")}
    old["engine_stages"] = {b: {s: {k: v for k, v in e.items()
                                    if k not in ("wait_ms", "compiles")}
                                for s, e in per.items()}
                            for b, per in stats["engine_stages"].items()}
    return old


def _rec(s0, s1):
    return {"stats0": s0, "stats1": s1, "stage": "refine"}


def test_queue_ms_reads_mean_wait():
    rec = _rec(_stats(queue=(100.0, 10)), _stats(queue=(700.0, 40)))
    assert _metric("queue_ms").read(rec) == pytest.approx(20.0)
    assert _metric("queue_ms").read(_rec(_stats(), _stats())) is None


def test_plan_ms_reads_mean_prologue():
    rec = _rec(_stats(plans=(5, 1.0)), _stats(plans=(9, 3.0)))
    assert _metric("plan_ms").read(rec) == pytest.approx(0.5)
    assert _metric("plan_ms").read(_rec(_stats(), _stats())) is None


def test_refine_host_ms_reads_self_time():
    rec = _rec(_stats(stage=_stage(2, 1000.0, 900.0, 0)),
               _stats(stage=_stage(6, 4000.0, 3700.0, 0)))
    assert _metric("refine_host_ms").read(rec) == pytest.approx(50.0)
    same = _stats(stage=_stage(2, 1.0, 1.0, 0))
    assert _metric("refine_host_ms").read(_rec(same, same)) is None


def test_compiles_in_window_sums_every_stage():
    s0 = _stats(stage=_stage(1, 1.0, 0.0, 21))
    s1 = _stats(stage=_stage(5, 9.0, 0.0, 23))
    s1["engine_stages"]["device"]["complement-finish"] = _stage(5, 1.0, 0.0,
                                                                1)
    assert _metric("compiles_in_window").read(_rec(s0, s1)) == 3
    assert _metric("compiles_in_window").read(_rec(s0, s0)) == 0
    empty = _stats()
    assert _metric("compiles_in_window").read(_rec(empty, empty)) is None


@pytest.mark.parametrize("name", ["queue_ms", "plan_ms", "refine_host_ms",
                                  "compiles_in_window"])
def test_metric_reads_nothing_from_a_program_without_it(name):
    s0 = _stats((1.0, 1), (1, 1.0), _stage(1, 2.0, 1.0, 1))
    s1 = _stats((9.0, 3), (3, 2.0), _stage(3, 8.0, 2.0, 2))
    assert _metric(name).read(_rec(s0, s1)) is not None
    assert _metric(name).read(_rec(_older(s0), _older(s1))) is None


# --------------------------------------------------------- the record tool
def test_record_keeps_a_slice_with_the_program_spans(monkeypatch, cells_root,
                                                     tmp_path):
    """``bench/record.py`` on the CPU, at a tiny size: the slice is kept
    gzipped, holds the served batches' spans, and its summary carries the
    program's counters over the slice."""
    from bench import record

    monkeypatch.setattr(harness, "enable_cache", lambda root: None)
    cell = harness.Cell(cells_root, "mixed10m-tiles-closed96")
    cell.config["records"] = 2000
    cell.config["server"]["max_batch"] = 2
    cell.config["layers"] = [lay for lay in cell.config["layers"]
                             if lay.get("verts", 0) <= 16]
    cell.traffic.update(max_requests=1 << 17, warm_seconds=0.2, clients=3,
                        pool=64)
    out = tmp_path / "out"
    (summary,) = record.record(cell, 987654321987, 2.0, [0.5], str(out),
                               log=lambda *a: None, require_tpu=False)
    assert summary["slice"] == 0 and summary["seconds"] >= 0.5
    got = summary["counters"]
    assert got["queue_waited"] > 0 and got["plans"] > 0
    assert got["device/refine"]["calls"] == got["plans"]
    assert summary["trace"]["busy_s"] == 0.0      # no TPU plane on the CPU
    blob = (out / "slice0.xplane.pb.gz").read_bytes()
    (tmp_path / "t").mkdir()
    (tmp_path / "t" / "slice.xplane.pb").write_bytes(gzip.decompress(blob))
    names = {e[0] for evs in spans.load(str(tmp_path / "t"))["spans"].values()
             for e in evs}
    assert {"glin.serve.batch", "glin.stage.refine",
            "glin.refine.attempt"} <= names
