"""Program spans and compile counts of the served query path.

Spans are ``jax.profiler.TraceAnnotation`` host events: they cost well under
a microsecond when no profiler runs and are always compiled in. They sit at
batch, stage and ladder-attempt granularity only, never per request or row.
A profiler trace taken with ``host_tracer_level >= 1`` records them on the
thread that opened them, on the profiler's clock, so a device program can
be charged to the innermost span open on the thread that launched it.
``SPANS`` names every span the program opens.

:func:`timed` is a span that also measures its own duration on the host
clock: the counter it feeds (``StageStats.wall_ms``, ``wait_ms``,
``plan_ms``) and the span in a trace are one clock pair.

Importing the module registers one ``jax.monitoring`` listener that counts
backend compiles (persistent-cache hits included) and their milliseconds
into the ``StageStats`` current on the compiling thread
(:func:`in_stage`), or into :data:`OUTSIDE` when no stage is running.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

__all__ = ["SPANS", "OUTSIDE", "span", "timed", "in_stage"]

SPANS = (
    "glin.serve.gather",          # pump waiting for a batch to fill
    "glin.serve.slot_wait",       # pump blocked on a free worker slot
    "glin.serve.batch",           # one worker serving one relation group
    "glin.serve.resolve",         # ticket resolution and cache stores
    "glin.engine.plan",           # query prologue: lock, upkeep, plan
    "glin.stage.refine",          # ExecutionPlan.execute, one per stage
    "glin.stage.delta-patch",
    "glin.stage.complement-finish",
    "glin.stage.knn-rank",
    "glin.refine.freeze",         # state frozen under the facade lock
    "glin.refine.attempt",        # one overflow-ladder attempt
    "glin.refine.wait",           # host blocked reading device results
    "glin.refine.escalate",       # overflow handling: the attempt is lost
    "glin.refine.assemble",       # hits to sorted per-row id lists
    "glin.index.publish",         # a synchronous snapshot rebuild
)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

span = jax.profiler.TraceAnnotation

# compiles on a thread that runs no stage (set-up, background republish)
OUTSIDE = {"compiles": 0, "compile_ms": 0.0}

_local = threading.local()
_outside_lock = threading.Lock()


class timed:
    """``with timed(name, **args) as t:`` opens the span ``name`` and leaves
    the block's host-clock milliseconds in ``t.ms``, timed inside the
    span."""

    __slots__ = ("_span", "_t0", "ms")

    def __init__(self, name: str, **args):
        self._span = span(name, **args)
        self.ms = 0.0

    def __enter__(self) -> "timed":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms = 1e3 * (time.perf_counter() - self._t0)
        return self._span.__exit__(*exc)

    def set_args(self, **args) -> None:
        """Attach arguments known only once the span is open."""
        self._span.set_metadata(**args)


@contextlib.contextmanager
def in_stage(stats):
    """Charge compiles on this thread to ``stats`` (a ``StageStats``) while
    the block runs."""
    prev = getattr(_local, "stage", None)
    _local.stage = stats
    try:
        yield stats
    finally:
        _local.stage = prev


def _on_duration(event: str, secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    st = getattr(_local, "stage", None)
    if st is not None:
        st.compiles += 1
        st.compile_ms += 1e3 * secs
        return
    with _outside_lock:
        OUTSIDE["compiles"] += 1
        OUTSIDE["compile_ms"] += 1e3 * secs


jax.monitoring.register_event_duration_secs_listener(_on_duration)
