"""Window requests: one relation over rectangle windows (GLIN §IX-A).

A pool of windows follows the paper's recipe: pick a random record, take
the ``K = selectivity * N`` records nearest to it (Chebyshev distance
between MBR centres) and use the MBR of that set, with the selectivity
log-uniform over the traffic's ``[low, high]``. The pool is drawn from the
traffic's ``pool_seed`` over the configuration's records in their drawn
order, so every seed sends the same windows, and so the same work, in an
order of its own. Request ``i`` is pool window ``i % pool`` shifted by a
jitter of its own (the same shift on both corners), rounded to float32:
every request is a distinct window, so the server's result cache never
answers one.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.spatial import cKDTree

STAGE = "refine"          # the engine stage these requests run through
# numbers compared with the reference, and their limits
LIMITS = {"wrong_ids": 0, "wrong_rows": 0}


def _fp32(a):
    return np.asarray(a, np.float32).astype(np.float64)


def pool_sizes(n: int, selectivity, count: int, rng) -> np.ndarray:
    """``count`` values of K, log-uniform over the selectivity range at
    ``n`` records: evenly spaced quantiles, in an order drawn from
    ``rng``."""
    lo, hi = (selectivity if isinstance(selectivity, (list, tuple))
              else (selectivity, selectivity))
    q = (np.arange(count) + 0.5) / count
    k = np.rint(n * np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo))))
    return rng.permutation(np.maximum(k, 1).astype(np.int64))


class MbrIndex:
    """Records' MBRs with a ``cKDTree`` over their centres: the §IX-A
    neighbours, and how many MBRs meet a window (the compact kernel's
    survivors, which size the warm-up)."""

    def __init__(self, mbrs: np.ndarray):
        self.mbrs = mbrs
        self.centres = (mbrs[:, :2] + mbrs[:, 2:]) * 0.5
        self.half = (mbrs[:, 2:] - mbrs[:, :2]).max(axis=0) * 0.5
        self.tree = cKDTree(self.centres)

    def count(self, windows: np.ndarray) -> np.ndarray:
        """MBRs that meet each closed window of ``windows`` (W, 4)."""
        w = np.atleast_2d(windows)
        c = (w[:, :2] + w[:, 2:]) * 0.5
        r = ((w[:, 2:] - w[:, :2]) * 0.5 + self.half).max(axis=1)
        out = np.empty(len(w), np.int64)
        for i, ids in enumerate(self.tree.query_ball_point(c, r, p=np.inf)):
            m = self.mbrs[ids]
            out[i] = int(((m[:, 0] <= w[i, 2]) & (w[i, 0] <= m[:, 2])
                          & (m[:, 1] <= w[i, 3])
                          & (w[i, 1] <= m[:, 3])).sum())
        return out


def make_pool(mi: MbrIndex, selectivity, count: int, rng) -> np.ndarray:
    """``count`` windows by the §IX-A recipe."""
    n = len(mi.mbrs)
    ks = pool_sizes(n, selectivity, count, rng)
    anchors = rng.integers(0, n, count)
    _, near = mi.tree.query(mi.centres[anchors], k=int(ks.max()), p=np.inf)
    near = near.reshape(count, -1)
    sel = mi.mbrs[near]
    out = np.arange(near.shape[1])[None, :] >= ks[:, None]
    lo = np.where(out[..., None], np.inf, sel[..., :2]).min(axis=1)
    hi = np.where(out[..., None], -np.inf, sel[..., 2:]).max(axis=1)
    return _fp32(np.concatenate([lo, hi], axis=1))


class Requests:
    """The request stream of one run."""

    stage = STAGE

    def __init__(self, store, traffic: dict, rng):
        """The stream over ``store``'s records in their drawn order (the
        windows do not depend on ids), ordered and jittered by ``rng``."""
        self.relation = traffic["relation"]
        self.mbr_index = MbrIndex(store.mbrs)
        pool = make_pool(self.mbr_index, traffic["selectivity"],
                         int(traffic["pool"]),
                         np.random.default_rng(int(traffic["pool_seed"])))
        self.pool = pool[rng.permutation(len(pool))]
        self.jitter = float(traffic["jitter"])
        n = int(traffic["max_requests"])
        base = self.pool[np.arange(n) % len(self.pool)]
        self.windows = np.empty((n, 4))
        todo = np.arange(n)
        while len(todo):          # redraw the rare shift that repeats a window
            d = rng.uniform(-self.jitter, self.jitter, (len(todo), 2))
            self.windows[todo] = _fp32(base[todo] + np.concatenate([d, d], 1))
            _, first = np.unique(self.windows, axis=0, return_index=True)
            todo = np.setdiff1d(np.arange(n), first)

    def payload(self, i: int) -> np.ndarray:
        return self.windows[i]

    def submit(self, server, payload) -> int:
        return server.submit(payload, self.relation)

    # ------------------------------------------------------------ warm-up
    def warm(self, index, max_batch: int, log) -> None:
        """Compile every (pow2 batch bucket, budget rung) the window can
        reach, through the facade. A batch's budget is the power of two
        above its largest MBR survivor count (the compact kernel counts the
        records whose MBR meets the window), starting from the engine's
        first rung; the pool's largest count bounds it."""
        from repro.core.engine import QueryBatch

        first = int(index.config.exact_budget)
        counts = self.mbr_index.count(self.pool)
        top = int(self.mbr_index.count(np.stack([
            self._scaled(w, 1.0, pad=self.jitter)
            for w in self.pool[np.argsort(counts)[-8:]]])).max())
        budgets = [first]
        while budgets[-1] < top:
            budgets.append(budgets[-1] * 2)
        small = self._scaled(self.pool[0], 1e-6)
        buckets = [1 << i for i in range(max_batch.bit_length())
                   if 1 << i <= max_batch]
        rows = {first: small}
        for b in budgets[1:]:
            rows[b] = self._with_count(counts, b // 2 + 1, b)
        jobs = [np.stack([rows[b]] + [small] * (q - 1))
                for q in buckets for b in budgets]
        log(f"warm-up: MBR survivors per pool window min {counts.min()} "
            f"median {int(np.median(counts))} max {counts.max()} "
            f"(with jitter {top}); budgets {budgets}; buckets {buckets}; "
            f"{len(jobs)} batches")
        with ThreadPoolExecutor(8) as pool:
            list(pool.map(lambda w: index.query(
                QueryBatch.window(w, self.relation)), jobs))

    @staticmethod
    def _scaled(w, f, pad=0.0):
        c = (w[:2] + w[2:]) * 0.5
        h = (w[2:] - w[:2]) * 0.5 * f + pad
        return _fp32(np.concatenate([c - h, c + h]))

    def _with_count(self, counts, lo, hi):
        """A window whose MBR survivor count lies in [lo, hi]: a pool window
        if one does, else a pool window scaled about its centre."""
        ok = np.nonzero((counts >= lo) & (counts <= hi))[0]
        if len(ok):
            return self.pool[ok[0]]
        w = self.pool[int(np.argmin(np.abs(counts - (lo + hi) // 2)))]
        a, b = 0.0, 64.0
        for _ in range(60):
            f = (a + b) / 2
            c = int(self.mbr_index.count(self._scaled(w, f))[0])
            if lo <= c <= hi:
                return self._scaled(w, f)
            a, b = (f, b) if c < lo else (a, f)
        raise RuntimeError(f"no window with {lo}..{hi} MBR survivors")

    # ----------------------------------------------------------- checking
    def check(self, ref, payloads, answers) -> dict:
        """Compare each answer with the reference: ids missing or extra."""
        wrong_ids = wrong_rows = hits = 0
        for w, got in zip(payloads, answers):
            want = ref.intersects(w)
            got = np.asarray(got, np.int64)
            hits += len(want)
            if not np.array_equal(np.sort(got), want):
                wrong_rows += 1
                wrong_ids += len(np.setxor1d(got, want))
        return {"wrong_ids": wrong_ids, "wrong_rows": wrong_rows,
                "reference_hits": hits}

    def answer(self, ref, payload):
        """``ref``'s answer in the form the server returns it."""
        return ref.intersects(payload)

    def work_bytes(self, ref, payload, answer) -> int:
        """Least bytes any refine kernel must move for this request: the
        MBR of every record that meets the window, the window, and the
        returned ids."""
        from bench.work import compact

        return compact.bytes_needed(ref.mbr_count(payload), len(answer))
