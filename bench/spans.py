"""The program's own spans in a profiler trace: device time charged to the
span that launched it, and idle gaps labelled with what the program did.

The program opens ``glin.`` spans (``src/repro/utils/trace.py``) on the
thread that serves a batch. TPU op events carry no HLO metadata, so a
device program is tied to its span through the flow records the runtime
writes instead: an ``XLA Modules`` event's ``_c`` is the ``_p`` of the host
``DoEnqueueProgram`` that queued it; from there each event's cause is the
producer (``_p``) of its own ``_c``, or of the ``_c`` of the innermost event
around it on its line, until the ``PJRT_LoadedExecutable_Execute linkage``
event on the Python thread that made the call. The innermost ``glin.``
span open on that thread at that moment is the span that launched the
program.

The profiler records a span only if it both starts and ends inside the
trace, and its time zero precedes the first recorded event by tens of
milliseconds. So the reduction reads only the interval in which both the
host and the device recorded, and a launch with no recorded span around it,
before the first or after the last recorded span of its thread, is charged
to a span that crossed the trace's edge rather than to no span.

``load`` reads a trace into plain tuples, ``reduce`` charges device time
to spans and labels idle gaps. Run as a script on a trace directory it
prints the reduction as one JSON line:

    python3 bench/spans.py <trace dir>
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from collections import defaultdict

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

SPAN_PREFIX = "glin."
MODULE_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute linkage"
ATTEMPT = "glin.refine.attempt"
ESCALATE = "glin.refine.escalate"
NO_SPAN = "no program span"
EDGE = "span crossing the trace edge"
MAX_HOPS = 16            # flow hops from an enqueue back to its launch


def load(path: str) -> dict:
    """The trace under ``path`` as ``{"ops": [(name, start_ns, dur_ns)],
    "modules": [(name, start_ns, dur_ns, launch)], "spans": {line:
    [(name, start_ns, end_ns, args)]}, "recorded": (lo_ns, hi_ns)}``: the
    first device plane's op events, its program executions with the
    ``(line, launch_ns)`` of the Python call that launched each (``None``
    when no flow leads there), the ``glin.`` spans of each host line sorted
    by start, and the interval in which host and device both recorded."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(files[-1])
    raw_modules, ops, lines = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            if raw_modules or ops:
                continue                      # the first device plane only
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    raw_modules += [(e.name, float(e.start_ns),
                                     float(e.duration_ns),
                                     dict(e.stats).get("_c"))
                                    for e in line.events]
                elif line.name in trace.OP_LINES:
                    ops += [(e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                lines.append(((plane.name, i), [
                    (e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns), dict(e.stats))
                    for e in line.events]))
    return link(ops, raw_modules, lines)


def link(ops, modules, lines) -> dict:
    """What :func:`load` returns, from the device's op events, its module
    events ``(name, start_ns, dur_ns, _c)`` and the host lines ``[(line,
    [(name, start_ns, end_ns, stats), ...]), ...]``."""
    launch_of = _launches(lines)
    spans = {}
    for key, evs in lines:
        mine = sorted((e for e in evs if e[0].startswith(SPAN_PREFIX)),
                      key=lambda e: (e[1], -e[2]))
        if mine:
            spans[key] = mine
    host = [e for _, evs in lines for e in evs]
    recorded = (0.0, 0.0)
    if ops and host:
        recorded = (max(min(s for _, s, _ in ops), min(e[1] for e in host)),
                    min(max(s + d for _, s, d in ops),
                        max(e[2] for e in host)))
    return {"ops": ops, "spans": spans, "recorded": recorded,
            "modules": [(nm, s, d, launch_of(c)) for nm, s, d, c in modules]}


def _launches(lines):
    """A function from a module's ``_c`` to the ``(line, launch_ns)`` of
    the Python call that launched it, or ``None``."""
    producer = {}                # flow id -> (line, event index)
    cause = {}                   # (line, index) -> flow id it consumes
    for key, evs in lines:
        order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
        stack = []               # open events: (end_ns, nearest _c)
        for i in order:
            _, start, end, st = evs[i]
            while stack and stack[-1][0] <= start:
                stack.pop()
            outer = stack[-1][1] if stack else None
            own = st.get("_c")
            cause[(key, i)] = own if own is not None else outer
            stack.append((end, own if own is not None else outer))
            if "_p" in st:
                producer.setdefault(st["_p"], (key, i))
    events = dict(lines)

    def launch_of(flow):
        for _ in range(MAX_HOPS):
            at = producer.get(flow)
            if at is None:
                return None
            key, i = at
            name, start = events[key][i][:2]
            if name == LAUNCH:
                return key, start
            flow = cause[at]
            if flow is None:
                return None
        return None

    return launch_of


def _innermost(spans, t):
    """The innermost span of one line open at ``t`` (spans nest on a
    thread, so the latest-started of those open), or ``None``."""
    starts = [s[1] for s in spans]
    for j in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[j][2] > t:
            return spans[j]
    return None


def _lost_attempts(spans):
    """Per line, the attempts that hold an escalation: overflowed, their
    result thrown away."""
    out = {}
    for key, evs in spans.items():
        esc = [e for e in evs if e[0] == ESCALATE]
        out[key] = [a for a in evs if a[0] == ATTEMPT and any(
            a[1] <= e[1] and e[2] <= a[2] for e in esc)]
    return out


def gap_label(spans, t) -> str:
    """The innermost ``glin.`` span open at ``t`` on each line, joined with
    ``+``, or ``"no program span"``."""
    names = set()
    for evs in spans.values():
        sp = _innermost(evs, t)
        if sp is not None:
            names.add(sp[0])
    return "+".join(sorted(names)) or NO_SPAN


def _cut_stages(evs):
    """The intervals of a line's stage spans that the trace cut.
    ``glin.refine.*`` spans run only inside a ``glin.stage.*`` span, so
    recorded ones with no recorded stage around them belong to a stage
    opened before the trace began (those before the line's first recorded
    stage) or still open when it stopped (those after its last)."""
    stages = [e for e in evs if e[0].startswith("glin.stage.")]
    orphans = [e for e in evs if e[0].startswith("glin.refine.")
               and not any(st[1] <= e[1] and e[2] <= st[2] for st in stages)]
    first = min((st[1] for st in stages), default=float("inf"))
    last = max((st[2] for st in stages), default=float("-inf"))
    cut = []
    for group in ([e for e in orphans if e[2] <= first],
                  [e for e in orphans if e[1] >= last]):
        if group:
            cut.append((min(e[1] for e in group), max(e[2] for e in group)))
    return cut


def _charged(spans, key, t) -> str:
    """The name of the span a launch at ``t`` on line ``key`` is charged
    to: the innermost recorded one, else a span the trace cut when ``t``
    lies before the line's first span, after its last, or inside a cut
    stage, else none."""
    evs = spans.get(key, [])
    sp = _innermost(evs, t)
    if sp is not None:
        return sp[0]
    if evs and (t < evs[0][1] or t > max(e[2] for e in evs)
                or any(a <= t <= b for a, b in _cut_stages(evs))):
        return EDGE
    return NO_SPAN


def reduce(prog: dict, n: int = 10, min_gap_s: float = 1e-3) -> dict:
    """Over the recorded interval (``recorded_s``): the device's busy
    seconds, and the device seconds of program executions in all
    (``module_s``), by the span that launched them (``program_s``, with
    ``"no program span"`` for a launch outside every span and ``"span
    crossing the trace edge"`` for one in a span the trace cut), launched
    inside an attempt that overflowed (``discarded_s``), and with no flow
    to a launch (``unlinked_s``); the ``n`` longest idle gaps of the device
    labelled by :func:`gap_label`, and the idle seconds in gaps of
    ``min_gap_s`` or more, in all and under a ``glin.`` label."""
    lo, hi = prog["recorded"]
    spans = prog["spans"]
    lost = _lost_attempts(spans)
    by_span = defaultdict(float)
    module = discarded = unlinked = 0.0
    for _, s, d, launch in prog["modules"]:
        d = max(0.0, min(s + d, hi) - max(s, lo)) * 1e-9
        if d <= 0:
            continue
        module += d
        if launch is None:
            unlinked += d
            continue
        key, t = launch
        by_span[_charged(spans, key, t)] += d
        if any(a[1] <= t <= a[2] for a in lost.get(key, ())):
            discarded += d
    ops = [(nm, max(s, lo), min(s + d, hi) - max(s, lo))
           for nm, s, d in prog["ops"] if s < hi and s + d > lo]
    gaps = sorted(trace.idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])
    labelled = [(gap_label(spans, (a + b) / 2), (b - a) * 1e-9)
                for i, (a, b) in enumerate(gaps)
                if i < n or (b - a) * 1e-9 >= min_gap_s]
    long_ = [(lb, s) for lb, s in labelled if s >= min_gap_s]
    return {
        "recorded_s": (hi - lo) * 1e-9,
        "busy_s": trace.busy_ns(ops) * 1e-9,
        "module_s": module,
        "program_s": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "discarded_s": discarded,
        "unlinked_s": unlinked,
        "idle_gaps": [list(g) for g in labelled[:n]],
        "long_idle_s": sum(s for _, s in long_),
        "long_idle_labelled_s": sum(s for lb, s in long_ if lb != NO_SPAN),
    }


def main(argv):
    print(json.dumps(reduce(load(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
