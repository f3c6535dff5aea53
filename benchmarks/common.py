"""Shared benchmark infrastructure.

Real TIGER/OSM corpora are unavailable offline; datasets are the synthetic
stand-ins from core.datasets (documented in DESIGN.md §6). Default scale is
CPU-friendly (--large raises it). Output format: ``name,us_per_call,derived``
CSV rows, one per measured quantity, mirroring a paper table/figure each.

Importing this module does not import JAX (the repro imports are deferred
into the helpers), so an orchestrator can still start CPU-rehearsal children
after importing it.
"""
from __future__ import annotations

import functools
import time
from typing import TYPE_CHECKING, Callable, List

import numpy as np

if TYPE_CHECKING:
    from repro.core.datasets import GeometrySet
    from repro.core.engine import EngineConfig, SpatialIndex
    from repro.core.index import GLIN

SELECTIVITIES = [0.01, 0.001, 0.0001, 0.00001]  # 1% .. 0.001% of N
DATASETS = ["cluster", "uniform", "roads", "concave", "mixed"]


@functools.lru_cache(maxsize=16)
def dataset(name: str, n: int, seed: int = 0) -> GeometrySet:
    from repro.core.datasets import generate

    return generate(name, n, seed=seed)


@functools.lru_cache(maxsize=32)
def windows(name: str, n: int, sel: float, k: int = 20, seed: int = 0):
    from repro.core.datasets import make_query_windows

    return make_query_windows(dataset(name, n), sel, k, seed=seed)


def build_index(name: str, n: int, pl: int = 10000,
                engine: "EngineConfig | None" = None, **kw) -> SpatialIndex:
    """The one public way to build an index (facade over the host GLIN)."""
    from repro.core.engine import SpatialIndex
    from repro.core.index import GLINConfig

    return SpatialIndex.build(dataset(name, n),
                              GLINConfig(piece_limitation=pl, **kw),
                              config=engine)


def build_glin(name: str, n: int, pl: int = 10000, **kw) -> GLIN:
    """Host-structure handle for model-internal measurements (probe timing,
    piecewise internals); querying goes through ``build_index``."""
    return build_index(name, n, pl, **kw).glin


def timeit(fn: Callable, repeats: int = 3, number: int = 1) -> float:
    """Median wall time per call, in microseconds."""
    best = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(number):
            fn()
        best.append((time.perf_counter() - t0) / number)
    return float(np.median(best) * 1e6)


class Csv:
    def __init__(self):
        self.rows: List[str] = []

    def emit(self, name: str, us_per_call: float, derived: str = "") -> None:
        line = f"{name},{us_per_call:.2f},{derived}"
        self.rows.append(line)
        print(line, flush=True)


def scale_n(large: bool) -> int:
    return 1_000_000 if large else 120_000
