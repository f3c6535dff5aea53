"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` names its generator,
  ``bench/generators/<generator>.py``;
* ``bench/traffic/<traffic>.json`` names its loop,
  ``bench/loops/<loop>.py``, and its request kind,
  ``bench/requests/<requests>.py``;
* a per-layer metric ``<name>`` is ``bench/metrics/<name>.py``, whose
  ``read(rec)`` returns a number or ``None``;
* device peaks are rows of ``bench/peaks.json`` keyed by ``device_kind``.

Adding a cell, a configuration, a mix or a metric adds files and edits
none of these.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import threading
import time

import numpy as np

CHECK_SAMPLE = 1024        # answers compared with the reference per run
GRACE_S = 60.0             # how long after the window an answer may come
TRACE_DIR = ".bench_trace"
CACHE_DIR = ".jax_cache"
DATA_DIR = "bench_data"    # generated stores, under CACHE_DIR


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- discovery
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(root, kind, name):
    """``bench/<kind>/<name>.py`` under ``root``, by file path."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, root, name):
        self.root = root
        man = load_json(os.path.join(root, "BENCHMARK.json"))
        work = {w["name"]: w for w in man["workloads"]}
        if name not in work:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(work)})")
        self.entry = work[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in man["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(root, conf["file"]))
        self.traffic = load_json(os.path.join(
            root, "bench", "traffic", f"{self.entry['traffic']}.json"))
        self.end_to_end = [m for m in man["end_to_end"]
                           if name in m.get("workloads", [name])]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in man["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in moved]
        self.generator = load_module(root, "generators",
                                     self.config["generator"])
        self.loop = load_module(root, "loops", self.traffic["loop"])
        self.requests = load_module(root, "requests",
                                    self.traffic["requests"])
        self.metrics = {m["name"]: load_module(root, "metrics", m["name"])
                        for m in self.per_layer}
        self.peaks = load_json(os.path.join(root, "bench", "peaks.json"))


# ------------------------------------------------------------ statistics
def nearest_rank(values, q):
    """The ``q`` quantile (0..1) by nearest rank: an element of the data,
    inf included."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.inf
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def stage_delta(s0, s1, stage, key):
    a = s0["engine_stages"].get("device", {}).get(stage, {})
    b = s1["engine_stages"].get("device", {}).get(stage, {})
    return b.get(key, 0) - a.get(key, 0)


class CompileCounter:
    """Counts backend compiles (persistent-cache hits included) and cache
    hits/misses through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        self.names = []

        def on_duration(event, secs, fun_name=None, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.names.append(fun_name)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return (self.compiles, self.hits, self.misses)


# --------------------------------------------------------------- the run
def _spans(tracing):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def enable_cache(root):
    import jax

    path = os.path.join(root, CACHE_DIR)
    os.makedirs(path, exist_ok=True)      # JAX writes into it, never makes it
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_row(peaks, kind):
    """The row of ``bench/peaks.json`` for ``device_kind``; a device the
    table does not know is an error, never a default."""
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def check_devices(chips, require_tpu=True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


class Setup:
    """What set-up leaves for the window: the store, the request stream,
    the index behind a started server, and timings."""


def setup(cell: Cell, seed: int, log=print, t_start=None, require_tpu=True):
    """Generate the store and requests from ``seed``, build and publish the
    index, warm up every shape the cell's traffic reaches, start the server
    and serve a short warm-up stream through it."""
    su = Setup()
    su.t_start = time.perf_counter() if t_start is None else t_start
    su.devs = check_devices(cell.chips, require_tpu)
    enable_cache(cell.root)
    su.counter = CompileCounter()
    from repro.core.datasets import GeometrySet
    from repro.core.engine import EngineConfig, SpatialIndex
    from repro.core.index import GLINConfig
    from repro.core.zorder import UNIT
    from repro.serve import ServerConfig, SpatialQueryServer

    cfg, traffic = cell.config, cell.traffic
    ss = np.random.SeedSequence(int(seed))
    data_ss, req_ss, _, check_ss = ss.spawn(4)
    su.check_rng = np.random.default_rng(check_ss)
    parts = su.parts = {"imports": time.perf_counter() - su.t_start}

    t = time.perf_counter()
    base = cell.generator.generate(
        cfg, os.path.join(cell.root, CACHE_DIR, DATA_DIR))
    parts["data"] = time.perf_counter() - t
    t = time.perf_counter()
    su.requests = requests = cell.requests.Requests(
        base, traffic, np.random.default_rng(req_ss))
    parts["requests"] = time.perf_counter() - t
    t = time.perf_counter()
    # the seed's ids: the same records in an order of its own
    su.store = store = base.permuted(
        np.random.default_rng(data_ss).permutation(len(base)))
    del base
    parts["data"] += time.perf_counter() - t
    t = time.perf_counter()
    gs = GeometrySet(pool=store.pool.copy(), offsets=store.offsets.copy(),
                     nverts=store.nverts.copy(), kinds=store.kinds.copy(),
                     mbrs=store.mbrs.copy(), grid=UNIT, name=cfg["name"])
    su.index = SpatialIndex.build(gs, GLINConfig(**cfg.get("glin", {})),
                                  EngineConfig(**cfg.get("engine", {})))
    parts["build"] = time.perf_counter() - t
    t = time.perf_counter()
    su.index.snapshot()
    parts["publish"] = time.perf_counter() - t
    t = time.perf_counter()
    c0 = su.counter.snapshot()
    scfg = ServerConfig(**cfg.get("server", {}))
    requests.warm(su.index, scfg.max_batch, log)
    su.server = SpatialQueryServer(su.index, config=scfg).start()
    su.half = int(traffic["max_requests"]) // 2
    cell.loop.drive(su.server, _Offset(requests, su.half, su.half), traffic,
                    float(traffic.get("warm_seconds", 2.0)),
                    _spans(False), GRACE_S)
    c1 = su.counter.snapshot()
    parts["warm"] = time.perf_counter() - t
    log(f"store: {len(store)} records, {store.pool.shape[0]} vertices; "
        f"compiles in set-up {c1[0] - c0[0]} (persistent-cache hits "
        f"{c1[1] - c0[1]}, misses {c1[2] - c0[2]})")
    return su


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             log=print, t_start=None, require_tpu=True):
    """One run; returns the object the last output line prints."""
    from repro.serve import Rejected

    from bench.reference import PlainReference

    su = setup(cell, seed, log, t_start, require_tpu)
    devs, counter, requests = su.devs, su.counter, su.requests
    server, half = su.server, su.half
    check_rng = su.check_rng
    c1 = counter.snapshot()
    t_start, parts, traffic = su.t_start, su.parts, cell.traffic

    # ---------------------------------------------------------- window
    tracing = bool(trace)
    slice_box = {}
    stats0 = server.stats()
    if tracing:
        tracer = threading.Thread(target=_trace_slice, daemon=True,
                                  args=(cell, server, seconds, slice_box))
    setup_s = time.perf_counter() - t_start
    log("set-up s: " + " ".join(f"{k}={v:.3f}" for k, v in parts.items())
        + f" total={setup_s:.3f}")
    if tracing:
        slice_box["t0"] = time.perf_counter()
        tracer.start()
    t0, t1, records = cell.loop.drive(server, _Offset(requests, 0, half),
                                      traffic, seconds,
                                      _spans(tracing), GRACE_S)
    c2 = counter.snapshot()
    if tracing:
        tracer.join()
    stats1 = server.stats()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    server.stop()
    del server, su.server, su.index
    gc.collect()
    log(f"compiles in window {c2[0] - c1[0]} (persistent-cache hits "
        f"{c2[1] - c1[1]}, misses {c2[2] - c1[2]}): "
        f"{counter.names[c1[0]:c2[0]][:20]}")
    if tracing:
        _reduce_slice(cell, slice_box)

    # ------------------------------------------------- end-to-end numbers
    lat, ok_in_window, failed = [], 0, 0
    answered = []
    for rec in records:
        i, t_sched, t_sent, _ticket, val, t_res = rec
        bad = val is None or isinstance(val, Rejected)
        failed += bad
        lat.append(math.inf if bad else t_res - t_sched)
        if not bad:
            answered.append(rec)
            ok_in_window += t_res <= t1
    late = [r[2] - r[1] for r in records]
    log(f"requests sent {len(records)}, answered {len(answered)}, "
        f"in window {ok_in_window}; generator lateness p95 "
        f"{nearest_rank(late, 0.95) * 1e3:.3f} ms")
    e2e = {
        "qps": (ok_in_window / seconds, "queries/s"),
        "p50_ms": (nearest_rank(lat, 0.50) * 1e3, "ms"),
        "p95_ms": (nearest_rank(lat, 0.95) * 1e3, "ms"),
        "setup_s": (setup_s, "s"),
    }
    d = {k: stats1[k] - stats0[k] for k in
         ("served_batches", "cache_misses", "cache_hits", "coalesced",
          "failed_batches", "shed")}
    backends = {b: n - stats0["backend_counts"].get(b, 0)
                for b, n in stats1["backend_counts"].items()}
    log(f"server deltas {d}; batches by backend {backends}; batch sizes "
        f"{stats1['batch_size_hist']}")
    st = requests.stage
    counts = {k: stage_delta(stats0, stats1, st, k)
              for k in ("calls", "dispatches", "escalations", "rungs")}
    notes = stats1["engine_stages"].get("device", {}).get(st, {}).get("notes")
    log(f"stage device/{st}: {counts} notes {notes}")

    # ------------------------------------------------------------- check
    # the reference is built only now: its time is no part of set-up, and
    # the window's peak memory has been read
    t = time.perf_counter()
    ref = PlainReference(su.store)
    t_ref = time.perf_counter() - t
    sample = _sample(answered, check_rng)
    t = time.perf_counter()
    found = requests.check(ref, [requests.payload(r[0]) for r in sample],
                           [r[4] for r in sample])
    log(f"reference built in {t_ref:.2f} s, check of {len(sample)} answers "
        f"in {time.perf_counter() - t:.2f} s: {found}")
    host = sum(n for b, n in backends.items() if b not in ("device", "cache"))
    compared = {"failed": (failed, 0), "host_batches": (host, 0),
                "failed_batches": (d["failed_batches"], 0),
                "shed": (d["shed"], 0)}
    for name, limit in cell.requests.LIMITS.items():
        compared[name] = (found[name], limit)
    correct = bool(len(sample)) and all(v <= lim
                                        for v, lim in compared.values())

    # -------------------------------------------------------- per layer
    result = {"correct": correct, "attempted": len(records),
              "failed": failed}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    if tracing:
        peaks = (peak_row(cell.peaks, devs[0].device_kind) if require_tpu
                 else None)
        rec = {"stats0": stats0, "stats1": stats1, "records": records,
               "requests": requests, "ref": ref, "stage": requests.stage,
               "peaks": peaks, **slice_box}
        metrics = {}
        for m in cell.per_layer:
            v = cell.metrics[m["name"]].read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        red = slice_box.get("reduced") or {}
        device["busy_s"] = red.get("busy_s", 0.0)
        device["window_s"] = red.get("window_s", 0.0)
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": red.get("device_ops", []),
                               "idle_gaps": red.get("idle_gaps", [])}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]][0],
                                         "unit": e2e[m["name"]][1]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in compared.items()}
    return result


class _Offset:
    """``count`` requests of a stream read from index ``offset`` on: the
    window sends the first half of ``max_requests`` and the served warm-up
    the second, so no window answer was ever cached."""

    def __init__(self, requests, offset, count):
        self._r, self._off, self._n = requests, offset, count

    def payload(self, i):
        if not 0 <= i < self._n:
            raise IndexError(f"request {i} is past the {self._n} this "
                             "traffic allows; raise max_requests")
        return self._r.payload(self._off + i)

    def submit(self, server, payload):
        return self._r.submit(server, payload)


def _sample(answered, rng):
    """Up to ``CHECK_SAMPLE`` answered requests drawn from the seed, the
    eight with the longest answers among them."""
    if len(answered) <= CHECK_SAMPLE:
        return list(answered)
    size = np.array([len(r[4][0] if isinstance(r[4], tuple) else r[4])
                     for r in answered])
    longest = set(np.argsort(size)[-8:].tolist())
    rest = [i for i in range(len(answered)) if i not in longest]
    pick = rng.choice(rest, CHECK_SAMPLE - len(longest), replace=False)
    return [answered[i] for i in sorted(longest | set(pick.tolist()))]


def _trace_slice(cell, server, seconds, box):
    """Trace a steady slice of the window: from 30% of it, for up to three
    seconds; the server's counters are read at both ends."""
    from bench import trace

    start = box["t0"] + 0.3 * seconds
    length = min(3.0, 0.4 * seconds)
    time.sleep(max(0.0, start - time.perf_counter()))
    cap = trace.Capture(os.path.join(cell.root, TRACE_DIR))
    box["slice_stats0"] = server.stats()
    cap.start()
    time.sleep(length)
    box["slice_stats1"] = server.stats()
    cap.stop()
    box["slice"] = (cap.t_start, cap.t_stop)
    box["capture"] = cap


def _reduce_slice(cell, box):
    """Reduce the slice's trace (after the window: the reduction must not
    compete with the window for the host), then delete it."""
    from bench import trace

    cap = box["capture"]
    kern = [cell.metrics[m].KERNEL for m in cell.metrics
            if hasattr(cell.metrics[m], "KERNEL")]
    box["reduced"] = trace.reduce(trace.load(cap.path), cap.seconds,
                                  kernels=sorted(set(kern)))
    shutil.rmtree(cap.path, ignore_errors=True)
