"""Compile the query path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: unaligned blocks,
vector gathers inside a kernel, primitives Mosaic cannot lower, kernels past
the scoped VMEM. These tests lower the Pallas kernels and the jitted query
programs at deployment widths for one chip of a described ``v5e:2x2``
topology (and the sharded step for all four) and assert that a compiled
kernel (``tpu_custom_call``) is in the program, and that the compact kernel
keeps the op name the benchmark's trace reduction reads it by. Nothing
runs: a compile that passes is not a chip run.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import trace as bench_trace
from bench.work.compact import TRACE_NAME
from repro.core.device import VertexPods, batch_query
from repro.core.distributed import (build_glin_knn_step,
                                    build_glin_query_step, glin_input_specs)
from repro.kernels.refine import (COMPACT_CHUNK, knn_topk_pallas,
                                  refine_compact_pallas, refine_count_pallas)
from repro.utils.compat import make_auto_mesh

Q = 64                  # windows per batch
N_CHIP = 1 << 22        # slots of a 4M-record single-chip store
N_MESH = 1 << 24        # records of the 16M-record four-chip store
N_CELL = 10_002_432     # slots of the benchmark's 10M-record store (padded
                        # to the snapshot's 4,096-slot quantum)
MAX_WIDTH = 64          # widest ring of the mixed store


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **kw):
    return jax.jit(fn, **kw).lower(*args).compile().as_text()


def _kernel_calls(text, kernel):
    """Custom calls of a compiled program that the benchmark's trace
    reduction counts as ``kernel`` (device op events carry the
    instruction's text)."""
    calls = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if " custom-call(" in ln]
    return bench_trace.kernel_ns([(c, 0.0, 1.0) for c in calls], kernel)


@pytest.mark.parametrize("budget", [256, COMPACT_CHUNK, 4 * COMPACT_CHUNK])
def test_compact_kernel_compiles(one_chip, budget):
    """One output chunk, and a budget filled in four chunks."""
    f32, i32 = jnp.float32, jnp.int32
    text = _compiled_text(
        lambda w, b, lm, rm: refine_compact_pallas(w, b, lm, rm, budget),
        _spec((Q, 4), f32, one_chip), _spec((Q, 2), i32, one_chip),
        _spec((N_CHIP, 4), f32, one_chip), _spec((N_CHIP, 4), f32, one_chip))
    assert "tpu_custom_call" in text
    assert _kernel_calls(text, TRACE_NAME) == 1


@pytest.mark.parametrize("budget", [256, COMPACT_CHUNK])
def test_compact_kernel_compiles_at_cell_width(one_chip, budget):
    """The benchmark cell's two ladder budgets at its padded slot count:
    the wide record tile and its scatter fit the scoped VMEM."""
    f32, i32 = jnp.float32, jnp.int32
    text = _compiled_text(
        lambda w, b, lm, rm: refine_compact_pallas(w, b, lm, rm, budget),
        _spec((Q, 4), f32, one_chip), _spec((Q, 2), i32, one_chip),
        _spec((N_CELL, 4), f32, one_chip), _spec((N_CELL, 4), f32, one_chip))
    assert "tpu_custom_call" in text
    assert _kernel_calls(text, TRACE_NAME) == 1


def test_count_kernel_compiles(one_chip):
    f32, i32 = jnp.float32, jnp.int32
    text = _compiled_text(
        refine_count_pallas, _spec((Q, 4), f32, one_chip),
        _spec((Q, 2), i32, one_chip), _spec((N_CHIP, 4), f32, one_chip))
    assert "tpu_custom_call" in text


def test_knn_topk_kernel_compiles(one_chip):
    text = _compiled_text(
        lambda d, i: knn_topk_pallas(d, i, 10),
        _spec((Q, 4096), jnp.float32, one_chip),
        _spec((Q, 4096), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def _chip_store_specs(sharding):
    """Snapshot + vertex-pod shapes of a 4M-record single-chip store (slot
    tables at ``N_CHIP``, model tables as the dry-run sizes them)."""
    snap, _, table = glin_input_specs(N_CHIP, Q, mesh=None,
                                      num_leaves=1 << 16,
                                      max_verts=MAX_WIDTH)
    per_slot = {"keys_hi": table["keys_hi"], "keys_lo": table["keys_lo"],
                "recs": table["recs"], "rec_leaf": table["rec_leaf"],
                "slot_lmbr": table["lmbrs"], "slot_rmbr": table["mbrs"]}
    snap = dataclasses.replace(snap, **per_slot)
    pods = VertexPods(pool=table["vpool"], off=table["voff"],
                      nv=table["nverts"], kd=table["kinds"],
                      bucket=table["vbucket"], max_width=MAX_WIDTH)

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree_util.tree_map(place, (snap, pods))


@pytest.mark.parametrize("budget", [256, 4 * COMPACT_CHUNK])
def test_query_program_compiles(one_chip, budget):
    """The whole jitted window query at 4M slots: probe, the compact kernel
    and the exact stage in one program, at a budget past the cap too."""
    snap, pods = _chip_store_specs(one_chip)
    wins = _spec((Q, 4), jnp.float32, one_chip)
    mbrs = _spec((N_CHIP, 4), jnp.float32, one_chip)
    text = _compiled_text(
        lambda s, w, p, m: batch_query(s, w, p, m, relation="intersects",
                                       cap=4096, exact_budget=budget,
                                       compaction="pallas"),
        snap, wins, pods, mbrs)
    assert "tpu_custom_call" in text
    assert _kernel_calls(text, TRACE_NAME) == 1


@pytest.mark.parametrize("kind", ["query", "knn"])
def test_sharded_step_compiles(topo, kind):
    """One sharded step over the four described chips at 16M records: the
    compact kernel per shard; the kNN step adds its one cross-shard
    all-gather (the window step leaves its results sharded)."""
    mesh = make_auto_mesh((4, 1), ("data", "model"), devices=topo.devices)
    if kind == "query":
        step, in_sh, out_sh = build_glin_query_step(
            mesh, "intersects", cap=4096, exact_budget=256,
            compaction="pallas", max_width=MAX_WIDTH)
    else:
        step, in_sh, out_sh = build_glin_knn_step(
            mesh, 10, cap=4096, exact_budget=256, compaction="pallas",
            max_width=MAX_WIDTH)
    snap, wins, table = glin_input_specs(N_MESH, Q, mesh,
                                         num_leaves=1 << 16,
                                         max_verts=MAX_WIDTH)
    args = (snap, wins, table)
    if kind == "knn":      # per-point radii ride as a query-sharded input
        args = (snap, wins, _spec((Q,), jnp.float32, in_sh[2]), table)
    text = _compiled_text(step, *args, in_shardings=in_sh,
                          out_shardings=out_sh)
    assert "tpu_custom_call" in text
    assert ("all-gather" in text) == (kind == "knn")
