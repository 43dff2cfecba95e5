"""The batched P-square quantile estimator, in NumPy, one group at a time.

The fabric's streaming collector summarises latencies with the P-square
algorithm (Jain and Chlamtac, 1985) fed one cycle's completions at a time:
the marker positions advance by the count of observations below each inner
marker, the inner markers then take up to three unit parabolic or linear
steps, and below five observations the heights are a sorted sample buffer
that seeds the markers on the call that crosses five.  The estimate is a
function of the multiset of each cycle's observations, so this plain
version fed the same multisets must give the same markers, up to the
rounding of the arithmetic type ``dtype``.
"""
from __future__ import annotations

import numpy as np

PCTS = (50.0, 95.0, 99.0)
PASSES = 3
FILL = 3.0e38


class P2Group:
    """Markers of one (view, class, direction) group for every tracked
    percentile: ``h`` heights and ``n`` positions, each ``[len(PCTS), 5]``."""

    def __init__(self, dtype=np.float32):
        self.t = dtype
        q = np.asarray(PCTS, np.float32) / np.float32(100.0)
        self.frac = np.stack([np.zeros_like(q), q / np.float32(2), q,
                              (np.float32(1) + q) / np.float32(2),
                              np.ones_like(q)], axis=-1).astype(dtype)
        self.h = np.full((len(PCTS), 5), FILL, dtype)
        self.n = np.tile(np.arange(1, 6), (len(PCTS), 1)).astype(dtype)
        self.count = 0

    def add(self, values) -> None:
        """Ingest one cycle's observations (a non-empty sequence)."""
        t = self.t
        vals = np.asarray(values, np.float64).astype(t)
        k = len(vals)
        total = self.count + k
        if self.count < 5:
            buf = np.sort(np.concatenate([self.h[0, :self.count], vals]))
            if total < 5:
                self.h[:] = np.concatenate(
                    [buf, np.full(5 - total, FILL)]).astype(t)
            else:
                idx = np.clip(np.round(self.frac * t(total - 1)),
                              0, total - 1).astype(np.int64)
                self.h = buf[idx].astype(t)
                self.n = (idx + 1).astype(t)
            self.count = total
            return
        h, n = self.h, self.n
        h[:, 0] = np.minimum(h[:, 0], vals.min())
        h[:, 4] = np.maximum(h[:, 4], vals.max())
        for i in (1, 2, 3):
            n[:, i] += (vals[None, :] < h[:, i, None]).sum(axis=1).astype(t)
        n[:, 4] += t(k)
        desired = t(1) + self.frac * t(total - 1)
        for _ in range(PASSES):
            for i in (1, 2, 3):
                self._step(i, desired)
        self.count = total

    def _step(self, i: int, desired) -> None:
        t = self.t
        h, n = self.h, self.n
        for q in range(h.shape[0]):
            d = desired[q, i] - n[q, i]
            nl, ni, nr = n[q, i - 1], n[q, i], n[q, i + 1]
            hl, hi, hr = h[q, i - 1], h[q, i], h[q, i + 1]
            if d >= 1 and nr - ni > 1:
                s = t(1)
            elif d <= -1 and nl - ni < -1:
                s = t(-1)
            else:
                continue

            def nz(x):
                return x if x != 0 else t(1)

            par = hi + s / nz(nr - nl) * (
                (ni - nl + s) * (hr - hi) / nz(nr - ni)
                + (nr - ni - s) * (hi - hl) / nz(ni - nl))
            if hl < par < hr:
                h[q, i] = par
            else:
                ln, lh = (nr, hr) if s > 0 else (nl, hl)
                h[q, i] = hi + s * (lh - hi) / nz(ln - ni)
            n[q, i] = ni + s

    def quantiles(self) -> np.ndarray:
        """One estimate per tracked percentile (NaN with no observations):
        below five observations the exact percentile of the buffer, else
        the central marker."""
        if self.count == 0:
            return np.full(len(PCTS), np.nan)
        if self.count < 5:
            buf = np.sort(self.h[0].astype(np.float64))[:self.count]
            return np.array([np.percentile(buf, q) for q in PCTS])
        return self.h[:, 2].astype(np.float64)
