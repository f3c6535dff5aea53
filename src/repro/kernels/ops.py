"""Jit'd public wrappers around the Pallas kernels.

Each wrapper (a) pads/reshapes arbitrary inputs to the kernels' tile-aligned
layouts, (b) runs the COMPILED kernel unless the caller passes
``interpret=True`` (the kernels target TPU; interpret mode executes the same
kernel body on CPU for validation and is never chosen behind the caller's
back), and (c) exposes a ``use_pallas=False`` escape hatch that routes to the
pure-jnp reference (used by the XLA baselines in the perf comparisons).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .morton import LANES, morton_encode_pallas
from .refine import (knn_topk_pallas, refine_compact_pallas,
                     refine_count_pallas, refine_mask_pallas)
from .ssd_scan import ssd_scan_pallas


# ---------------------------------------------------------------- morton ----
@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def morton_encode(qx: jax.Array, qy: jax.Array, use_pallas: bool = True,
                  interpret: bool = False):
    """(N,) int32 coords -> (hi, lo) int32 limbs."""
    if not use_pallas:
        return ref.morton_ref(qx, qy)
    n = qx.shape[0]
    block = 8 * LANES
    pad = (-n) % block
    qxp = jnp.pad(qx, (0, pad)).reshape(-1, LANES)
    qyp = jnp.pad(qy, (0, pad)).reshape(-1, LANES)
    hi, lo = morton_encode_pallas(qxp, qyp, interpret=interpret)
    return hi.reshape(-1)[:n], lo.reshape(-1)[:n]


# ---------------------------------------------------------------- refine ----
@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def refine_mask(windows: jax.Array, bounds: jax.Array, mbrs: jax.Array,
                use_pallas: bool = True, interpret: bool = False):
    """(Q,4) f32, (Q,2) i32, (N,4) f32 -> (Q,N) int8 candidate mask.
    The kernels pad internally — any Q and N work."""
    if not use_pallas:
        return ref.refine_mask_ref(windows, bounds, mbrs)
    return refine_mask_pallas(windows, bounds, mbrs, interpret=interpret)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def refine_count(windows: jax.Array, bounds: jax.Array, mbrs: jax.Array,
                 use_pallas: bool = True, interpret: bool = False):
    if not use_pallas:
        return ref.refine_count_ref(windows, bounds, mbrs)
    return refine_count_pallas(windows, bounds, mbrs, interpret=interpret)


@partial(jax.jit, static_argnames=("budget", "prefilter", "use_pallas",
                                   "interpret"))
def refine_compact(windows: jax.Array, bounds: jax.Array,
                   leaf_mbrs: jax.Array, rec_mbrs: jax.Array, *,
                   budget: int, prefilter: str = "intersects",
                   use_pallas: bool = True, interpret: bool = False):
    """Fused mask + compaction: (Q,4) probe windows, (Q,2) i32 slot runs,
    slot-aligned (N,4) leaf/record MBR tables -> (slots (Q, budget) i32
    [-1 padded], counts (Q,) i32 total survivors; ``counts > budget``
    signals truncation)."""
    if not use_pallas:
        return ref.refine_compact_ref(windows, bounds, leaf_mbrs, rec_mbrs,
                                      budget, prefilter)
    return refine_compact_pallas(windows, bounds, leaf_mbrs, rec_mbrs,
                                 budget, prefilter, interpret=interpret)


@partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def knn_topk(d: jax.Array, ids: jax.Array, *, k: int,
             use_pallas: bool = True, interpret: bool = False):
    """Deterministic top-k by ascending (distance, id): d (Q, B) f32
    [+inf = dead lane], ids (Q, B) i32 -> ((Q, k) f32, (Q, k) i32).
    The jnp reference is the two-key ``lax.sort`` truncated to k columns;
    the kernel is a k-round partial selection sort (wins when k << B)."""
    if not use_pallas:
        ds, isrt = jax.lax.sort([d, ids], num_keys=2)
        return ds[:, :k], isrt[:, :k]
    return knn_topk_pallas(d, ids, k, interpret=interpret)


# ------------------------------------------------------------- attention ----
@partial(jax.jit, static_argnames=("window", "use_pallas", "bq", "bk",
                                   "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int = 0, use_pallas: bool = True,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """Causal (optionally sliding-window) GQA attention.
    q (B,Hq,S,D); k,v (B,Hkv,S,D)."""
    if not use_pallas:
        return ref.attention_ref(q, k, v, window=window)
    s = q.shape[2]
    bq_ = min(bq, s) if s % min(bq, s) == 0 else s
    bk_ = min(bk, s) if s % min(bk, s) == 0 else s
    return flash_attention_pallas(q, k, v, window=window, bq=bq_, bk=bk_,
                                  interpret=interpret)


# ------------------------------------------------------------------- ssd ----
@partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 128, use_pallas: bool = True,
             interpret: bool = False):
    """Mamba-2 SSD scan. x (B,S,H,P), dt (B,S,H), a (H,), b/c (B,S,N)."""
    if not use_pallas:
        return ref.ssd_ref(x, dt, a, b, c)
    s = x.shape[1]
    ch = min(chunk, s) if s % min(chunk, s) == 0 else s
    return ssd_scan_pallas(x, dt, a, b, c, chunk=ch, interpret=interpret)


# ------------------------------------------------------------ decode attn ---
@partial(jax.jit, static_argnames=("window", "use_pallas", "bk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     abs_pos: jax.Array, pos: jax.Array, *,
                     window: int = 0, use_pallas: bool = True, bk: int = 256,
                     interpret: bool = False):
    """One-token decode attention over a ring KV cache.
    q (B,Hq,D); k/v (B,Hkv,W,D); abs_pos (B,W); pos (B,)."""
    from .decode_attention import decode_attention_pallas
    if not use_pallas:
        return ref.decode_attention_ref(q, k, v, abs_pos, pos, window=window)
    w = k.shape[2]
    bk_ = min(bk, w) if w % min(bk, w) == 0 else w
    return decode_attention_pallas(q, k, v, abs_pos, pos, window=window,
                                   bk=bk_, interpret=interpret)
