"""Per-kernel shape/dtype sweeps, interpret-mode Pallas vs pure-jnp refs."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.refine import COMPACT_CHUNK

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------- morton ----
@pytest.mark.parametrize("n", [7, 128, 1000, 4096])
def test_morton_sweep(n):
    qx = jnp.asarray(RNG.integers(0, 2**30, n), jnp.int32)
    qy = jnp.asarray(RNG.integers(0, 2**30, n), jnp.int32)
    hi, lo = ops.morton_encode(qx, qy, interpret=True)
    rhi, rlo = ref.morton_ref(qx, qy)
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))


# ---------------------------------------------------------------- refine ----
@pytest.mark.parametrize("q,n", [(3, 100), (8, 512), (13, 1000), (32, 2048)])
def test_refine_sweep(q, n):
    wins = RNG.uniform(0, 1, (q, 4)).astype(np.float32)
    wins[:, 2:] = wins[:, :2] + RNG.uniform(0.01, 0.3, (q, 2)).astype(np.float32)
    mbrs = RNG.uniform(0, 1, (n, 4)).astype(np.float32)
    mbrs[:, 2:] = mbrs[:, :2] + 0.01
    lo = RNG.integers(0, n // 2, q).astype(np.int32)
    hi = RNG.integers(n // 2, n, q).astype(np.int32)
    bounds = jnp.asarray(np.stack([lo, hi], 1))
    wins_j, mbrs_j = jnp.asarray(wins), jnp.asarray(mbrs)
    m = ops.refine_mask(wins_j, bounds, mbrs_j, interpret=True)
    mr = ref.refine_mask_ref(wins_j, bounds, mbrs_j)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    c = ops.refine_count(wins_j, bounds, mbrs_j, interpret=True)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(m).sum(1))


def _compact_case(q, n, seed=0):
    rng = np.random.default_rng(seed)
    wins = rng.uniform(0, 1, (q, 4)).astype(np.float32)
    wins[:, 2:] = wins[:, :2] + rng.uniform(0.01, 0.3, (q, 2)).astype(np.float32)
    rmbrs = rng.uniform(0, 1, (n, 4)).astype(np.float32)
    rmbrs[:, 2:] = rmbrs[:, :2] + 0.01
    lmbrs = rmbrs + np.array([-0.02, -0.02, 0.02, 0.02], np.float32)
    lo = rng.integers(0, max(n // 2, 1), q).astype(np.int32)
    hi = rng.integers(n // 2, n + 1, q).astype(np.int32)
    bounds = jnp.asarray(np.stack([lo, hi], 1))
    return jnp.asarray(wins), bounds, jnp.asarray(lmbrs), jnp.asarray(rmbrs)


# odd shapes: Q and N not multiples of the tile sizes (internal padding)
@pytest.mark.parametrize("q,n", [(1, 37), (5, 256), (13, 1000), (32, 2049)])
@pytest.mark.parametrize("prefilter", ["intersects", "contains"])
def test_refine_compact_sweep(q, n, prefilter):
    wins, bounds, lmbrs, rmbrs = _compact_case(q, n)
    for budget in (8, 64):
        s, c = ops.refine_compact(wins, bounds, lmbrs, rmbrs, budget=budget,
                                  prefilter=prefilter, interpret=True)
        sr, cr = ref.refine_compact_ref(wins, bounds, lmbrs, rmbrs, budget,
                                        prefilter)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))


def test_refine_compact_empty_runs_and_extremes():
    """Empty probe runs, zero-survivor and all-survivor rows."""
    q, n = 9, 700
    wins, bounds, lmbrs, rmbrs = _compact_case(q, n, seed=3)
    b = np.asarray(bounds).copy()
    b[0] = (50, 50)                      # empty run
    b[1] = (60, 40)                      # inverted (empty) run
    wins = np.asarray(wins).copy()
    wins[2] = (2.0, 2.0, 3.0, 3.0)       # intersects nothing: zero survivors
    wins[3] = (-1.0, -1.0, 2.0, 2.0)     # covers everything: all survive
    b[3] = (0, n)
    wins_j, b_j = jnp.asarray(wins), jnp.asarray(b)
    budget = 1024                        # >= n: nothing truncated
    s, c = ops.refine_compact(wins_j, b_j, lmbrs, rmbrs, budget=budget,
                              interpret=True)
    sr, cr = ref.refine_compact_ref(wins_j, b_j, lmbrs, rmbrs, budget)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    c = np.asarray(c)
    s = np.asarray(s)
    assert c[0] == 0 and c[1] == 0 and c[2] == 0
    assert c[3] == n and (s[3] >= 0).sum() == n
    np.testing.assert_array_equal(s[3][:n], np.arange(n))


def test_refine_compact_budget_overflow_signalling():
    """counts carries TOTAL survivors even past the budget: the caller's
    overflow test (counts > budget) must fire, and the kept slots must be
    the first `budget` survivors in slot order."""
    q, n = 4, 300
    wins = np.tile(np.array([[-1, -1, 2, 2]], np.float32), (q, 1))
    rng = np.random.default_rng(5)
    rmbrs = rng.uniform(0, 1, (n, 4)).astype(np.float32)
    rmbrs[:, 2:] = rmbrs[:, :2] + 0.01
    lmbrs = rmbrs
    bounds = jnp.asarray(np.tile([0, n], (q, 1)).astype(np.int32))
    budget = 16
    s, c = ops.refine_compact(jnp.asarray(wins), bounds, jnp.asarray(lmbrs),
                              jnp.asarray(rmbrs), budget=budget,
                              interpret=True)
    s, c = np.asarray(s), np.asarray(c)
    assert (c == n).all() and (c > budget).all()
    for row in s:
        np.testing.assert_array_equal(row, np.arange(budget))


# budgets past one output chunk: the kernel fills them one chunk per pass
# over the record tiles (truncated past the budget, exact below it)
@pytest.mark.parametrize("budget", [COMPACT_CHUNK + 1, 2 * COMPACT_CHUNK,
                                    3000])
def test_refine_compact_multi_chunk_budgets(budget):
    q, n = 4, 4100
    rng = np.random.default_rng(11)
    rmbrs = rng.uniform(0, 0.95, (n, 4)).astype(np.float32)
    rmbrs[:, 2:] = rmbrs[:, :2] + 0.01
    lmbrs = rmbrs + np.array([-0.02, -0.02, 0.02, 0.02], np.float32)
    wins = np.array([[-1, -1, 2, 2],           # every record survives
                     [0.1, 0.1, 0.8, 0.8],     # about half survive
                     [-1, -1, 2, 2],           # a run starting mid-table
                     [0.5, 0.5, 0.52, 0.52]],  # a handful
                    np.float32)
    bounds = np.tile([0, n], (q, 1)).astype(np.int32)
    bounds[2] = (1500, n - 3)
    args = (jnp.asarray(wins), jnp.asarray(bounds), jnp.asarray(lmbrs),
            jnp.asarray(rmbrs))
    s, c = ops.refine_compact(*args, budget=budget, interpret=True)
    sr, cr = ref.refine_compact_ref(*args, budget)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    c = np.asarray(c)
    assert c[0] == n and c[1] > COMPACT_CHUNK    # several chunks filled
    kept = (np.asarray(s) >= 0).sum(axis=1)
    np.testing.assert_array_equal(kept, np.minimum(c, budget))


@pytest.mark.parametrize("q,n", [(3, 100), (13, 999), (30, 2047)])
def test_refine_mask_count_internal_padding(q, n):
    """mask/count accept shapes that are NOT tile multiples (the kernels pad
    internally; callers stopped pre-padding)."""
    wins, bounds, _, mbrs = _compact_case(q, n, seed=7)
    m = ops.refine_mask(wins, bounds, mbrs, interpret=True)
    mr = ref.refine_mask_ref(wins, bounds, mbrs)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mr))
    c = ops.refine_count(wins, bounds, mbrs, interpret=True)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(m).sum(1))


# ------------------------------------------------------------- attention ----
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("s,d,hq,hkv,window,bq",
                         [(256, 64, 4, 2, 0, 128),
                          (256, 32, 4, 1, 64, 128),
                          (128, 64, 2, 2, 0, 64),
                          (512, 64, 8, 4, 128, 128)])
def test_flash_attention_sweep(dtype, tol, s, d, hq, hkv, window, bq):
    b = 2
    q = jnp.asarray(RNG.normal(0, 1, (b, hq, s, d)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, s, d)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, s, d)), dtype)
    o = ops.flash_attention(q, k, v, window=window, bq=bq, bk=bq,
                            interpret=True)
    r = ref.attention_ref(q, k, v, window=window)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    assert err < tol, err


# ------------------------------------------------------------------- ssd ----
@pytest.mark.parametrize("s,h,p,n,chunk", [(128, 2, 16, 8, 32),
                                           (256, 3, 32, 16, 64),
                                           (256, 1, 64, 32, 128)])
def test_ssd_sweep(s, h, p, n, chunk):
    b = 2
    x = jnp.asarray(RNG.normal(0, 1, (b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.1, 1.0, h), jnp.float32)
    bm = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    cm = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    y = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    r = ref.ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), atol=2e-4, rtol=1e-3)


def test_ssd_chunk_invariance():
    """The chunked algorithm must be exact for ANY chunk size."""
    b, s, h, p, n = 1, 192, 2, 8, 4
    x = jnp.asarray(RNG.normal(0, 1, (b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.2, (b, s, h)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.1, 1.0, h), jnp.float32)
    bm = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    cm = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    outs = [np.asarray(ops.ssd_scan(x, dt, a, bm, cm, chunk=c,
                                       interpret=True))
            for c in (32, 64, 96, 192)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-4, rtol=1e-3)


def test_xla_path_matches_kernel():
    """models/ssm.ssd_chunked (the XLA lowering path) == Pallas kernel."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 2, 128, 3, 16, 8
    x = jnp.asarray(RNG.normal(0, 1, (b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.1, 1.0, h), jnp.float32)
    bm = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    cm = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    y_xla, _ = ssd_chunked(x, dt, a, bm, cm, chunk=32)
    y_pl = ops.ssd_scan(x, dt, a, bm, cm, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_pl),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("w,d,hq,hkv,window", [(256, 64, 4, 2, 0),
                                               (512, 32, 4, 1, 128),
                                               (256, 64, 2, 2, 64)])
def test_decode_attention_sweep(dtype, tol, w, d, hq, hkv, window):
    """Ring-cache decode kernel vs dense oracle, incl. empty + SWA slots."""
    b = 2
    q = jnp.asarray(RNG.normal(0, 1, (b, hq, d)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, hkv, w, d)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, hkv, w, d)), dtype)
    pos = jnp.asarray(RNG.integers(w // 2, w, b), jnp.int32)
    # ring semantics: slot s holds abs position p = s + w*floor((pos-s)/w)
    slots = np.arange(w)[None, :]
    p = np.asarray(pos)[:, None]
    ap = slots + w * ((p - slots) // w)
    ap = np.where(ap <= p, ap, -1)  # future/unwritten slots empty
    ap = jnp.asarray(ap, jnp.int32)
    o = ops.decode_attention(q, k, v, ap, pos, window=window,
                             interpret=True)
    r = ref.decode_attention_ref(q, k, v, ap, pos, window=window)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    assert err < tol, err


def test_decode_attention_matches_model_path():
    """Kernel == models/attention.attention_decode numerics (fp32)."""
    from repro.configs import get_arch
    from repro.models import transformer as tf
    from repro.sharding import constrain
    cfg = get_arch("phi4_mini_3p8b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 2, 48
    toks = rng.integers(0, cfg.vocab, (B, S + 1))
    _, cache = tf.prefill(params, cfg, {"tokens": jnp.asarray(toks[:, :S])},
                          constrain, seq_len_cache=S + 4)
    # run one decode step through the model, then replicate layer-0 attention
    # with the kernel on the PRE-update cache
    from repro.models.attention import _project_qkv
    import repro.models.attention as A
    pl0 = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    lc = jax.tree_util.tree_map(lambda x: x[0], cache)["attn"]
    x = params["embed"][toks[:, S]][:, None, :]
    from repro.models.layers import rms_norm
    h = rms_norm(x, pl0["ln1"])
    y_model, _ = A.attention_decode(h, pl0["attn"], cfg, dict(lc), constrain)
    # kernel path: project, write slot, then decode_attention
    q, k_new, v_new = _project_qkv(h, pl0["attn"], cfg, lc["pos"][:, None])
    w = lc["k"].shape[1]
    slot = lc["pos"] % w
    bidx = jnp.arange(B)
    k = lc["k"].at[bidx, slot].set(k_new[:, 0])
    v = lc["v"].at[bidx, slot].set(v_new[:, 0])
    ap = lc["abs_pos"].at[bidx, slot].set(lc["pos"])
    out = ops.decode_attention(
        q[:, 0], jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), ap, lc["pos"],
        window=cfg.window, interpret=True)
    from repro.models.layers import dense
    y_kernel = dense(out.reshape(B, 1, -1), pl0["attn"]["wo"])
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kernel),
                               atol=2e-5, rtol=1e-4)
