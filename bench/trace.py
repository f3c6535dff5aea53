"""Profiler trace of a steady slice of the window, and its reduction.

``capture`` starts JAX's profiler, ``load`` reads the ``.xplane.pb`` it
wrote into plain tuples, and the functions below reduce those tuples to
the numbers the per-layer metrics read: the union of device op intervals
(busy time), the idle gaps between them, the device time of one kernel's
events, and the ops that took most time. The benchmark's own host spans
(``bench.send``, ``bench.collect``, ``bench.warm``) label each idle gap
with what the benchmark's threads were doing in it.
"""
from __future__ import annotations

import glob
import os
import shutil
import time
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)          # one event per device op
SPAN_PREFIX = "bench."


class Capture:
    """One profiler session into ``path`` (emptied first)."""

    def __init__(self, path: str):
        self.path = path
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        shutil.rmtree(self.path, ignore_errors=True)
        os.makedirs(self.path, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # a Python call tracer starves the host
        opts.host_tracer_level = 1       # keeps TraceAnnotation spans
        opts.enable_hlo_proto = False    # op names suffice; keeps it small
        jax.profiler.start_trace(self.path, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t_stop - self.t_start


def load(path: str) -> dict:
    """The trace under ``path`` as ``{"devices": {plane: [(name, start_ns,
    dur_ns), ...]}, "spans": [(name, start_ns, dur_ns), ...]}``: the op
    events of each device plane and the benchmark's host spans."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(files[-1])
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs += [(e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def merged(events):
    """Union of the events' [start, end) intervals, sorted."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> float:
    return sum(e - s for s, e in merged(events))


def idle_gaps(events, lo: float, hi: float):
    """Idle [start, end) intervals of the device inside [lo, hi]."""
    gaps, at = [], lo
    for s, e in merged(events):
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(s, e) for s, e in gaps if e > s]


def op_name(event_name: str) -> str:
    """The op's own name from an HLO event text such as ``%refine_compact.1
    = (s32[8,256]...) custom-call(...)``: ``refine_compact.1``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def short_name(event_name: str) -> str:
    """Op name, opcode and the start of the result type: short enough for
    a breakdown line, distinct enough to tell shapes apart."""
    head, _, rest = event_name.partition(" = ")
    if not rest:
        return event_name[:120]
    depth, i = 0, 0
    for i, ch in enumerate(rest):           # skip the (possibly tuple) type
        depth += ch == "(" or ch == "["
        depth -= ch == ")" or ch == "]"
        if depth == 0 and ch == " ":
            break
    opcode = rest[i + 1:].split("(", 1)[0]
    return f"{head.lstrip('%')} {opcode} {rest[:i][:60]}"


def kernel_ns(events, kernel: str) -> float:
    """Device time of the events of the op named ``kernel`` (``kernel`` or
    ``kernel.<n>``)."""
    return sum(d for n, _, d in events
               if op_name(n).split(".")[0] == kernel)


def top_ops(events, n: int = 10):
    tot = defaultdict(float)
    for name, _, d in events:
        tot[short_name(name)] += d
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def label_gaps(gaps, spans, n: int = 10):
    """The ``n`` longest gaps as ``(label, seconds)``: the benchmark spans
    that cover the gap's midpoint, or ``"no benchmark span"``."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        names = sorted({nm for nm, a, d in spans if a <= mid <= a + d})
        out.append(("+".join(names) or "no benchmark span", (e - s) * 1e-9))
    return out


def reduce(trace: dict, window_s: float, kernels=()) -> dict:
    """Busy and window seconds averaged over the device planes, each
    kernel's device seconds, the top ops and the longest idle gaps."""
    planes = list(trace["devices"].values())
    if not planes or not any(planes):
        return {"busy_s": 0.0, "window_s": window_s, "kernel_s": {},
                "device_ops": [], "idle_gaps": []}
    hi = window_s * 1e9
    # an op recorded after the host asked the profiler to stop is cut off
    planes = [[(nm, s, max(0.0, min(s + d, hi) - s)) for nm, s, d in p
               if s < hi] for p in planes]
    busy = sum(busy_ns(p) for p in planes) / len(planes) * 1e-9
    ops = [e for p in planes for e in p]
    gaps = idle_gaps(planes[0], 0.0, hi)
    return {
        "busy_s": busy,
        "window_s": window_s,
        "kernel_s": {k: kernel_ns(ops, k) * 1e-9 / len(planes)
                     for k in kernels},
        "device_ops": [[nm, d * 1e-9] for nm, d in top_ops(ops)],
        "idle_gaps": [list(g) for g in label_gaps(gaps, trace["spans"])],
    }
